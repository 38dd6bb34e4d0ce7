"""Model type ``qwen3``: all that the benchmark knows of this model type.
The harness finds this file by a configuration file's
``hf_config.model_type`` (``bench/arch/<model_type>.py``). The Qwen2
decoder is the same with ``architecture.qk_norm`` off and ``qkv_bias`` on.

* ``program_config(conf, base)`` — the program's ``ModelConfig``: the
  registry entry ``base`` (of ``conf["arch"]``) with every size taken
  from the file.
* The plain reference, the comparison behind ``correct``:
  ``Dims.from_config``, ``init_weights``, ``gaps(..., control=...)``, and
  ``loader_weights``, the program loader's tree in ``init_weights``'s
  layout, for the test that the two draw the same weights.
* The useful work (the section at the end): ``token_flops(conf,
  positions)`` and ``span_flops(conf, start, stop)``.

Imports nothing of the program. The reference reads the configuration
file's published numbers (the Hugging Face ``config.json`` keys) and
computes, in float32 with every matmul at ``Precision.HIGHEST``:

* ``init_weights`` — the seeded random weights that the program's loader
  draws for a dense GQA decoder (``repro.models.init_params``): the same
  key derivation, shapes and scales, in bfloat16. It is written here
  again so that the reference takes no weight from the program; a CPU
  test checks that the two agree bit for bit.
* ``hidden`` — the decoder of Qwen2/Qwen3 as published: token embedding;
  per layer pre-RMSNorm attention (Q/K/V projections with optional bias,
  per-head RMSNorm of Q and K when ``qk_norm``, rotary embedding with the
  half-split ("rotate half") convention, causal grouped-query softmax
  attention scaled by ``head_dim ** -0.5``, output projection) and a
  pre-RMSNorm SwiGLU MLP, both residual; final RMSNorm. Tied LM head.
* ``gaps`` — for each served token, by how much its reference logit lies
  below the reference's best logit at that position. A greedy server that
  computes the reference's function serves the argmax, so its gaps are 0
  up to the rounding of its own (bfloat16) arithmetic at near-ties.

A *control* is this same reference with every matmul weight (the tied
embedding included) rounded to int8, or to float8 e4m3, with one scale
per output channel: the lower-precision step a faster server might take.
At each position it serves its own argmax, and its gap is read against
the float32 reference.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_CHUNK = 512          # LM-head rows per block (bounds the logits buffer)
PAD_MULTIPLE = 512       # sequences pad to this, so lengths share compiles


@dataclasses.dataclass(frozen=True)
class Dims:
    """The published sizes the reference needs (HF config.json keys)."""
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    eps: float
    theta: float
    qk_norm: bool
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        hf = c["hf_config"]
        return cls(vocab=hf["vocab_size"], d=hf["hidden_size"],
                   layers=hf["num_hidden_layers"],
                   heads=hf["num_attention_heads"],
                   kv_heads=hf["num_key_value_heads"],
                   head_dim=hf.get("head_dim",
                                   hf["hidden_size"]
                                   // hf["num_attention_heads"]),
                   d_ff=hf["intermediate_size"], eps=hf["rms_norm_eps"],
                   theta=float(hf["rope_theta"]),
                   qk_norm=bool(c["architecture"]["qk_norm"]),
                   qkv_bias=bool(c["architecture"]["qkv_bias"]))


def program_config(conf: dict, base):
    """The program's ``ModelConfig``: ``base``, the registry entry of
    ``conf["arch"]``, with every size taken from the file."""
    hf, arch = conf["hf_config"], conf["architecture"]
    return dataclasses.replace(
        base, n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], qk_norm=arch["qk_norm"],
        qkv_bias=arch["qkv_bias"])


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def _layer(key, m: Dims) -> dict:
    """One decoder layer, keyed as the program's loader keys it: the
    layer key splits into (attention, mlp, ...); attention splits into
    (q, k, v, o) and the MLP into (up, down, gate)."""
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    ku, kd, kg = jax.random.split(k_mlp, 3)
    hq, hkv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    resid = (2 * m.layers) ** 0.5
    w = {"wq": _normal(kq, (m.d, hq), m.d ** -0.5),
         "wk": _normal(kk, (m.d, hkv), m.d ** -0.5),
         "wv": _normal(kv, (m.d, hkv), m.d ** -0.5),
         "wo": _normal(ko, (hq, m.d), hq ** -0.5 / resid),
         "w_up": _normal(ku, (m.d, m.d_ff), m.d ** -0.5),
         "w_down": _normal(kd, (m.d_ff, m.d), m.d_ff ** -0.5 / resid),
         "w_gate": _normal(kg, (m.d, m.d_ff), m.d ** -0.5),
         "ln1": jnp.ones((m.d,), jnp.float32),
         "ln2": jnp.ones((m.d,), jnp.float32)}
    if m.qkv_bias:
        w["bq"] = jnp.zeros((hq,), jnp.bfloat16)
        w["bk"] = jnp.zeros((hkv,), jnp.bfloat16)
        w["bv"] = jnp.zeros((hkv,), jnp.bfloat16)
    if m.qk_norm:
        w["q_norm"] = jnp.ones((m.head_dim,), jnp.float32)
        w["k_norm"] = jnp.ones((m.head_dim,), jnp.float32)
    return w


@partial(jax.jit, static_argnums=0)
def init_weights(m: Dims, key) -> dict:
    """Seeded weights, one program on the device, bfloat16 matmuls."""
    k_emb, k_dec, _, _, _ = jax.random.split(key, 5)
    _, sub = jax.random.split(k_dec)
    keys = jax.random.split(sub, m.layers)
    lkeys = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
    return {"embed": _normal(k_emb, (m.vocab, m.d), 0.02),
            "final_norm": jnp.ones((m.d,), jnp.float32),
            "layers": jax.vmap(lambda k: _layer(k, m))(lkeys)}


def loader_weights(params: dict) -> dict:
    """The tree of the program's loader (``repro.models.init_params``) in
    :func:`init_weights`'s layout, leaf for leaf."""
    layer = params["dec"][0]["e0"]
    attn, mlp = layer["attn"], layer["ffn"]
    w = {name: attn[name]["w"] for name in ("wq", "wk", "wv", "wo")}
    w.update({name: mlp[name]["w"] for name in ("w_up", "w_down", "w_gate")})
    w.update(ln1=layer["norm1"]["scale"], ln2=layer["norm2"]["scale"])
    for n in "qkv":
        if "b" in attn[f"w{n}"]:
            w[f"b{n}"] = attn[f"w{n}"]["b"]
    for n in ("q_norm", "k_norm"):
        if n in attn:
            w[n] = attn[n]["scale"]
    return {"embed": params["embed"]["table"],
            "final_norm": params["final_norm"]["scale"], "layers": w}


def quantize_int8(w: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 with one scale per output channel, dequantized back
    to float32; ``axis`` is the input (contracted) axis."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(wf / scale), -127, 127) * scale


def quantize_fp8(w: jax.Array, axis: int) -> jax.Array:
    """float8 e4m3 with one scale per output channel (its largest value
    maps to 448), dequantized back to float32."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (wf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


QUANTIZERS = {"int8": quantize_int8, "fp8": quantize_fp8}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, H, D); half-split rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _block(m: Dims, control: str | None, x, w):
    def weight(name):
        return QUANTIZERS[control](w[name], 0) if control else w[name]

    s = x.shape[0]
    h = _rms(x, w["ln1"], m.eps)
    q, k, v = _mm(h, weight("wq")), _mm(h, weight("wk")), _mm(h, weight("wv"))
    if m.qkv_bias:
        q, k, v = (q + w["bq"].astype(jnp.float32),
                   k + w["bk"].astype(jnp.float32),
                   v + w["bv"].astype(jnp.float32))
    q = q.reshape(s, m.heads, m.head_dim)
    k = k.reshape(s, m.kv_heads, m.head_dim)
    v = v.reshape(s, m.kv_heads, m.head_dim)
    if m.qk_norm:
        q, k = _rms(q, w["q_norm"], m.eps), _rms(k, w["k_norm"], m.eps)
    q, k = _rope(q, m.theta), _rope(k, m.theta)
    g = m.heads // m.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores * m.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(s, m.heads * m.head_dim), weight("wo"))
    h = _rms(x, w["ln2"], m.eps)
    a = jax.nn.silu(_mm(h, weight("w_gate"))) * _mm(h, weight("w_up"))
    return x + _mm(a, weight("w_down")), None


@partial(jax.jit, static_argnums=(0, 1))
def hidden(m: Dims, control: str | None, weights: dict,
           tokens: jax.Array):
    """(S,) tokens -> (S, d) float32 final hidden states."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_block, m, control), x, weights["layers"])
    return _rms(x, weights["final_norm"], m.eps)


@partial(jax.jit, static_argnums=(0,))
def _head_rows(control: str | None, embed, h_ref, h_ctl, served):
    """Per row: the reference's best logit, minus its logit of the served
    token (or, with ``control``, of the control's own argmax)."""
    z = jnp.dot(h_ref, embed.astype(jnp.float32).T, precision=HIGHEST)
    if control:
        zc = jnp.dot(h_ctl, QUANTIZERS[control](embed, 1).T,
                     precision=HIGHEST)
        served = zc.argmax(-1)
    return z.max(-1) - jnp.take_along_axis(z, served[:, None], -1)[:, 0]


def gaps(m: Dims, weights: dict, prompt: np.ndarray, served: np.ndarray,
         control: str | None = None) -> np.ndarray:
    """Gap of each served token (or, with ``control``, of the control's
    argmax at the same position) below the reference's best logit."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    n = len(seq)
    padded = -(-n // PAD_MULTIPLE) * PAD_MULTIPLE
    tokens = np.zeros(padded, np.int32)
    tokens[:n] = seq
    h_ref = hidden(m, None, weights, jnp.asarray(tokens))
    h_ctl = hidden(m, control, weights, jnp.asarray(tokens)) if control \
        else h_ref
    # row p predicts token p + 1: served token i comes from row
    # len(prompt) - 1 + i (the prefill's last row for i = 0)
    rows = np.arange(len(prompt) - 1, n - 1)
    out = []
    for lo in range(0, len(rows), ROW_CHUNK):
        r = np.zeros(ROW_CHUNK, np.int64)
        k = min(ROW_CHUNK, len(rows) - lo)
        r[:k] = rows[lo:lo + k]
        tgt = np.zeros(ROW_CHUNK, np.int32)
        tgt[:k] = served[lo:lo + k]
        gap = _head_rows(control, weights["embed"], h_ref[r], h_ctl[r],
                         jnp.asarray(tgt))
        out.append(np.asarray(jax.device_get(gap))[:k])
    return np.concatenate(out) if out else np.zeros(0)


# ---------------------------------------------------------------------------
# Useful work
# ---------------------------------------------------------------------------
#
# Only the bfloat16 target model's *useful* work is counted: what a plain
# autoregressive server of the same model must do for the same tokens. A
# speculative server does more (γ draft passes, rejected positions), so a
# utilization built on this count cannot pass what the device really did.
#
# Per token at context position ``c`` (the token attends to ``c + 1`` keys,
# itself included), with ``d`` hidden size, ``H``/``Hkv`` query/KV heads of
# size ``hd``, ``F`` the MLP width, ``V`` the vocabulary and ``L`` layers:
#
#     matmul_params = L * (d*H*hd + 2*d*Hkv*hd + H*hd*d + 3*d*F) + d*V
#     flops(c)      = 2 * matmul_params + L * 4 * H * hd * (c + 1)
#
# The first term is a multiply-add per weight (Q, K, V, O projections, the
# SwiGLU gate, up and down projections, and the LM head; the embedding is a
# row lookup and costs none). The second is QK^T and PV: 2 FLOPs per
# multiply-add, two products of ``H * hd`` per key. Norms, rotary
# embedding, softmax and biases are left out (under 0.1% here).

def matmul_params(conf: dict) -> int:
    hf = conf["hf_config"]
    d = hf["hidden_size"]
    h, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim", d // h)
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d \
        + 3 * d * hf["intermediate_size"]
    return hf["num_hidden_layers"] * per_layer + d * hf["vocab_size"]


def attention_flops_per_key(conf: dict) -> int:
    """FLOPs of one token against one key, over all layers."""
    hf = conf["hf_config"]
    h = hf["num_attention_heads"]
    hd = hf.get("head_dim", hf["hidden_size"] // h)
    return hf["num_hidden_layers"] * 4 * h * hd


def token_flops(conf: dict, positions) -> float:
    """Useful FLOPs of the tokens at the given context positions."""
    pos = np.asarray(positions, np.float64)
    return float(pos.size * 2 * matmul_params(conf)
                 + attention_flops_per_key(conf) * (pos + 1).sum())


def span_flops(conf: dict, start: int, stop: int) -> float:
    """Useful FLOPs of the tokens at positions ``start .. stop - 1``."""
    return token_flops(conf, np.arange(start, stop))
