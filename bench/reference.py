"""Plain reference of the served models, and the comparison behind ``correct``.

Imports nothing of the program. It reads the configuration file's
published numbers (the Hugging Face ``config.json`` keys) and computes,
in float32 with every matmul at ``Precision.HIGHEST``:

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
