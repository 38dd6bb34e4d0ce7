"""Model type ``deepseek_v2``: all that the benchmark knows of this model
type. The harness finds this file by a configuration file's
``hf_config.model_type`` (``bench/arch/<model_type>.py``).

* ``program_config(conf, base)`` — the program's ``ModelConfig``: the
  registry entry ``base`` (of ``conf["arch"]``) with every size taken
  from the file, the chip's expert share included.
* The plain reference, the comparison behind ``correct``:
  ``Dims.from_config``, ``init_weights``, ``gaps(..., control=...)``, and
  ``loader_weights``, the program loader's tree in ``init_weights``'s
  layout, for the test that the two draw the same weights.
* The useful work (the section at the end): ``token_flops(conf,
  positions)`` and ``span_flops(conf, start, stop)``.

Imports nothing of the program. The reference reads the configuration
file's published numbers (the Hugging Face ``config.json`` keys, and the
share of the experts this chip holds, ``expert_parallel``) and computes,
in float32 with every matmul at ``Precision.HIGHEST``, the DeepSeek-V2
decoder as published (``modeling_deepseek.py``), in its naive form:

* attention is MLA: q = W_q·x per head, split into q_nope and q_pe (no
  q-LoRA when ``q_lora_rank`` is null); [c, k_pe] = W_kva·x, the latent
  c RMS-normed; [k_nope, v] = W_kvb·c per head; YaRN rotary embedding on
  q_pe and on the one k_pe all heads share; scores (q_nope·k_nope +
  q_pe·k_pe) × (nope + rope)^-1/2 × mscale², causal softmax, then W_o.
  Per-head keys and values are materialised, and the scores computed in
  blocks of queries so that 8k contexts fit;
* YaRN: inverse frequencies f_e·m + (f_e / factor)·(1 − m) with f_e =
  θ^(−2i/D) and m = 1 − clip((i − low)/(high − low), 0, 1), low and high
  the correction dimensions of β_fast and β_slow over the original
  context; cos and sin scaled by mscale(factor, mscale) /
  mscale(factor, mscale_all_dim);
* the first ``first_k_dense_replace`` layers have a dense SwiGLU, the rest
  an expert layer: float32 router logits over all ``router_experts``,
  softmax, the top ``num_experts_per_tok`` probabilities (renormalised
  only when ``norm_topk_prob``) times ``routed_scaling_factor``, each
  weighting its expert's SwiGLU, plus the shared experts' SwiGLU (width
  ``n_shared_experts`` × ``moe_intermediate_size``);
* pre-RMSNorm residual blocks, final RMSNorm, an untied LM head.

Departures, each forced by the benchmark's set-up:

* the held experts' share: this chip holds experts ``first_expert ..
  first_expert + n_routed_experts - 1`` of ``router_experts``
  (``expert_parallel``). The router keeps every output and its top-k;
  the part of the result that the absent experts give is left out, here
  as in the program, and that partial result goes on to the next layer.
  Each held expert runs over every token, weighted 0 where the token did
  not pick it (the same sum as over the routed tokens alone);
* rotary layout: the checkpoint stores q_pe and k_pe interleaved, and
  the Hugging Face code de-interleaves them before its rotate-half. With
  seeded random weights that is a fixed permutation of weight columns, so
  both sides apply rotate-half to the loader's layout.

``init_weights`` draws the seeded random weights that the program's
loader draws (``repro.models.init_params``): the same key derivation,
shapes and scales, in bfloat16, the held experts only, expert ``e`` from
its id alone. A CPU test checks that the two agree bit for bit.

``gaps`` — for each served token, by how much its reference logit lies
below the reference's best logit at that position. A *control* is this
same reference with every matmul weight (attention, MLPs, experts and the
LM head) rounded to int8, or to float8 e4m3, with one scale per output
channel, and the router in float32 as the program keeps it.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_CHUNK = 512          # LM-head rows per block (bounds the logits buffer)
PAD_MULTIPLE = 1024      # sequences pad to this, so lengths share compiles
QUERY_BLOCK = 512        # attention queries per block (bounds the scores)


def published(conf: dict) -> dict:
    """The file's ``hf_config``, the numbers everything here reads. A file
    may also give them at its top level, in the form of the catalog's
    entry: every key given in both places has to agree."""
    hf = conf["hf_config"]
    differ = sorted(k for k in hf if k in conf and conf[k] != hf[k])
    if differ:
        raise ValueError(f"the top level and hf_config disagree on {differ}")
    return hf


@dataclasses.dataclass(frozen=True)
class Dims:
    """The published sizes the reference needs (HF config.json keys), and
    this chip's share of the experts."""
    vocab: int
    d: int
    layers: int
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_lora: int
    q_lora: int                  # 0: a direct q projection
    d_ff: int                    # the leading dense layers' SwiGLU
    moe_ff: int
    n_shared: int
    first_dense: int
    router_experts: int
    first_expert: int
    held: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    eps: float
    theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        hf, ep = published(c), c["expert_parallel"]
        ys = hf.get("rope_scaling") or {}
        return cls(vocab=hf["vocab_size"], d=hf["hidden_size"],
                   layers=hf["num_hidden_layers"],
                   heads=hf["num_attention_heads"],
                   nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                   v_dim=hf["v_head_dim"], kv_lora=hf["kv_lora_rank"],
                   q_lora=hf["q_lora_rank"] or 0,
                   d_ff=hf["intermediate_size"],
                   moe_ff=hf["moe_intermediate_size"],
                   n_shared=hf["n_shared_experts"],
                   first_dense=hf["first_k_dense_replace"],
                   router_experts=ep["router_experts"],
                   first_expert=ep["first_expert"],
                   held=hf["n_routed_experts"],
                   top_k=hf["num_experts_per_tok"],
                   norm_topk=bool(hf["norm_topk_prob"]),
                   routed_scale=float(hf["routed_scaling_factor"]),
                   eps=hf["rms_norm_eps"], theta=float(hf["rope_theta"]),
                   yarn_factor=float(ys.get("factor", 0.0)),
                   yarn_original=int(ys.get(
                       "original_max_position_embeddings", 0)),
                   beta_fast=float(ys.get("beta_fast", 32)),
                   beta_slow=float(ys.get("beta_slow", 1)),
                   mscale=float(ys.get("mscale", 1)),
                   mscale_all_dim=float(ys.get("mscale_all_dim", 0)))


def program_config(conf: dict, base):
    """The program's ``ModelConfig``: ``base``, the registry entry of
    ``conf["arch"]``, with every size taken from the file: the router
    over ``router_experts``, the held share ``first_expert`` +
    ``n_routed_experts``, and YaRN as the file states it. The program
    has no routed scale: a file whose ``routed_scaling_factor`` is not 1
    is refused."""
    hf, ep = published(conf), conf["expert_parallel"]
    if float(hf["routed_scaling_factor"]) != 1.0:
        raise ValueError("the program serves routed_scaling_factor 1 only, "
                         f"not {hf['routed_scaling_factor']}")
    ys = hf["rope_scaling"]
    yarn = dataclasses.replace(
        base.yarn, factor=float(ys["factor"]),
        original_max_position=int(ys["original_max_position_embeddings"]),
        beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
        mscale=float(ys["mscale"]),
        mscale_all_dim=float(ys["mscale_all_dim"]))
    return dataclasses.replace(
        base, n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), yarn=yarn,
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        n_experts=ep["router_experts"], expert_first=ep["first_expert"],
        experts_held=hf["n_routed_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        n_shared_experts=hf["n_shared_experts"],
        first_dense_layers=hf["first_k_dense_replace"],
        moe_d_ff=hf["moe_intermediate_size"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        q_lora_rank=hf["q_lora_rank"] or 0, kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_dim=hf["qk_nope_head_dim"],
        qk_rope_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"])


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def _mlp(key, m: Dims, ff: int) -> dict:
    """A SwiGLU, keyed as the loader keys one: (up, down, gate)."""
    ku, kd, kg = jax.random.split(key, 3)
    return {"w_up": _normal(ku, (m.d, ff), m.d ** -0.5),
            "w_down": _normal(kd, (ff, m.d),
                              ff ** -0.5 / (2 * m.layers) ** 0.5),
            "w_gate": _normal(kg, (m.d, ff), m.d ** -0.5)}


def _layer(key, m: Dims, moe: bool) -> dict:
    """One decoder layer, keyed as the program's loader keys it: the layer
    key splits into (attention, ffn, ...); attention into (q or q_a, q_b,
    kv_a, kv_b, o); an expert layer into (router, shared, experts), and
    expert ``e`` takes ``fold_in(experts, e)``."""
    k_attn, k_ffn, _, _ = jax.random.split(key, 4)
    ks = jax.random.split(k_attn, 5)
    qk = m.nope + m.rope
    if m.q_lora:
        raise NotImplementedError("the reference covers q_lora_rank null")
    w = {"wq": _normal(ks[0], (m.d, m.heads * qk), m.d ** -0.5),
         "kv_a": _normal(ks[2], (m.d, m.kv_lora + m.rope), m.d ** -0.5),
         "kv_a_norm": jnp.ones((m.kv_lora,), jnp.float32),
         "kv_b": _normal(ks[3], (m.kv_lora, m.heads * (m.nope + m.v_dim)),
                         m.kv_lora ** -0.5),
         "wo": _normal(ks[4], (m.heads * m.v_dim, m.d),
                       (m.heads * m.v_dim) ** -0.5 / (2 * m.layers) ** 0.5),
         "ln1": jnp.ones((m.d,), jnp.float32),
         "ln2": jnp.ones((m.d,), jnp.float32)}
    if not moe:
        w["mlp"] = _mlp(k_ffn, m, m.d_ff)
        return w
    k_router, k_shared, k_experts = jax.random.split(k_ffn, 3)
    ids = m.first_expert + jnp.arange(m.held)
    w["router"] = jax.random.normal(k_router, (m.d, m.router_experts),
                                    jnp.float32) * 0.02
    w["experts"] = jax.vmap(
        lambda e: _mlp(jax.random.fold_in(k_experts, e), m, m.moe_ff))(ids)
    w["shared"] = _mlp(k_shared, m, m.moe_ff * m.n_shared)
    return w


def _stack(key, m: Dims, repeats: int, moe: bool) -> dict:
    keys = jax.random.split(key, repeats)
    lkeys = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
    return jax.vmap(lambda k: _layer(k, m, moe))(lkeys)


@partial(jax.jit, static_argnums=0)
def init_weights(m: Dims, key) -> dict:
    """Seeded weights, one program on the device, bfloat16 matmuls. The
    loader's layer groups: the leading dense layers, then the expert
    layers, each group drawn from the next split of the decoder key."""
    k_emb, k_dec, _, k_head, _ = jax.random.split(key, 5)
    k_dec, k_dense = jax.random.split(k_dec)
    _, k_moe = jax.random.split(k_dec)
    return {"embed": _normal(k_emb, (m.vocab, m.d), 0.02),
            "lm_head": _normal(k_head, (m.d, m.vocab), 0.02),
            "final_norm": jnp.ones((m.d,), jnp.float32),
            "dense": _stack(k_dense, m, m.first_dense, False),
            "moe": _stack(k_moe, m, m.layers - m.first_dense, True)}


def loader_weights(params: dict) -> dict:
    """The tree of the program's loader (``repro.models.init_params``) in
    :func:`init_weights`'s layout, leaf for leaf."""
    def mlp(p):
        return {n: p[n]["w"] for n in ("w_up", "w_down", "w_gate")}

    def layer(e, moe):
        attn = e["attn"]
        w = {n: attn[n]["w"] for n in ("wq", "kv_a", "kv_b", "wo")}
        w.update(kv_a_norm=attn["kv_a_norm"]["scale"],
                 ln1=e["norm1"]["scale"], ln2=e["norm2"]["scale"])
        if moe:
            w.update(router=e["moe"]["router"]["w"],
                     experts=mlp(e["moe"]["experts"]),
                     shared=mlp(e["moe"]["shared"]))
        else:
            w["mlp"] = mlp(e["ffn"])
        return w

    return {"embed": params["embed"]["table"],
            "lm_head": params["lm_head"]["w"],
            "final_norm": params["final_norm"]["scale"],
            "dense": layer(params["dec"][0]["e0"], False),
            "moe": layer(params["dec"][1]["e0"], True)}


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

def _mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(m: Dims) -> jax.Array:
    """The rotary inverse frequencies (rope/2,), YaRN's when the file
    gives a factor (module doc)."""
    dim = m.rope
    expo = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    f_e = 1.0 / m.theta ** expo
    if not m.yarn_factor:
        return f_e

    def corr(beta):
        return (dim * math.log(m.yarn_original / (beta * 2 * math.pi))
                / (2 * math.log(m.theta)))

    low = max(math.floor(corr(m.beta_fast)), 0)
    high = min(math.ceil(corr(m.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return f_e * keep + f_e / m.yarn_factor * (1.0 - keep)


def softmax_scale(m: Dims) -> float:
    scale = (m.nope + m.rope) ** -0.5
    if m.yarn_factor and m.mscale_all_dim:
        scale *= _mscale(m.yarn_factor, m.mscale_all_dim) ** 2
    return scale


def _rope(x, m: Dims):
    """x (S, H, R); rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(m)[None, :]
    factor = (_mscale(m.yarn_factor, m.mscale)
              / _mscale(m.yarn_factor, m.mscale_all_dim)
              if m.yarn_factor else 1.0)
    cos = jnp.cos(ang)[:, None, :] * factor
    sin = jnp.sin(ang)[:, None, :] * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _swiglu(h, w, weight):
    a = jax.nn.silu(_mm(h, weight(w["w_gate"]))) * _mm(h, weight(w["w_up"]))
    return _mm(a, weight(w["w_down"]))


def _attention(m: Dims, h, w, weight):
    s = h.shape[0]
    q = _mm(h, weight(w["wq"])).reshape(s, m.heads, m.nope + m.rope)
    q_nope, q_pe = q[..., :m.nope], _rope(q[..., m.nope:], m)
    kv = _mm(h, weight(w["kv_a"]))
    c = _rms(kv[:, :m.kv_lora], w["kv_a_norm"], m.eps)
    k_pe = _rope(kv[:, None, m.kv_lora:], m)[:, 0]             # (S, R)
    kvb = _mm(c, weight(w["kv_b"])).reshape(s, m.heads, m.nope + m.v_dim)
    k_nope, v = kvb[..., :m.nope], kvb[..., m.nope:]
    scale = softmax_scale(m)

    def block(i):
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * QUERY_BLOCK,
                                          QUERY_BLOCK)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * QUERY_BLOCK, QUERY_BLOCK)
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HIGHEST)
                  + jnp.einsum("qhr,kr->hqk", qp, k_pe, precision=HIGHEST)
                  ) * scale
        causal = rows[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // QUERY_BLOCK))
    o = o.reshape(s, m.heads * m.v_dim)
    return _mm(o, weight(w["wo"]))


def _experts(m: Dims, h, w, weight):
    """The held experts' part of the expert layer, plus the shared
    experts (module doc)."""
    probs = jax.nn.softmax(jnp.dot(h, w["router"], precision=HIGHEST), -1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * m.routed_scale
    ids = m.first_expert + jnp.arange(m.held)
    gate = jnp.sum(jnp.where(top_e[:, :, None] == ids[None, None, :],
                             top_p[..., None], 0.0), axis=1)      # (S, held)

    def one(acc, xs):
        we, g = xs
        return acc + _swiglu(h, we, weight) * g[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (w["experts"], gate.T))
    return out + _swiglu(h, w["shared"], weight)


def _block(m: Dims, control: str | None, moe: bool, x, w):
    def weight(a):
        return QUANTIZERS[control](a, -2) if control else a

    x = x + _attention(m, _rms(x, w["ln1"], m.eps), w, weight)
    h = _rms(x, w["ln2"], m.eps)
    x = x + (_experts(m, h, w, weight) if moe
             else _swiglu(h, w["mlp"], weight))
    return x, None


@partial(jax.jit, static_argnums=(0, 1))
def hidden(m: Dims, control: str | None, weights: dict,
           tokens: jax.Array):
    """(S,) tokens -> (S, d) float32 final hidden states."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_block, m, control, False), x,
                        weights["dense"])
    x, _ = jax.lax.scan(partial(_block, m, control, True), x,
                        weights["moe"])
    return _rms(x, weights["final_norm"], m.eps)


@partial(jax.jit, static_argnums=(0,))
def _head_rows(control: str | None, head, h_ref, h_ctl, served):
    """Per row: the reference's best logit, minus its logit of the served
    token (or, with ``control``, of the control's own argmax)."""
    z = jnp.dot(h_ref, head.astype(jnp.float32), precision=HIGHEST)
    if control:
        zc = jnp.dot(h_ctl, QUANTIZERS[control](head, 0), precision=HIGHEST)
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
        gap = _head_rows(control, weights["lm_head"], h_ref[r], h_ctl[r],
                         jnp.asarray(tgt))
        out.append(np.asarray(jax.device_get(gap))[:k])
    return np.concatenate(out) if out else np.zeros(0)


# ---------------------------------------------------------------------------
# Useful work
# ---------------------------------------------------------------------------
#
# Only the bfloat16 target model's *useful* work is counted, in the
# published (naive) formulation: what a plain autoregressive server of the
# same model, holding the same expert share, must do for the same tokens.
# The program runs the absorbed form and every held expert over every
# token, and a speculative server does more (γ draft passes, rejected
# positions), so a utilization built on this count cannot pass what the
# device really did.
#
# Per token at context position ``c`` (it attends to ``c + 1`` keys), with
# ``d`` hidden size, ``H`` heads, ``n``/``r``/``v`` the nope, rope and value
# head sizes, ``L`` the latent, ``F`` the dense SwiGLU, ``f`` the expert
# width, ``E`` the router's experts, ``k`` experts per token, ``h`` held
# here, ``s`` shared experts, ``V`` the vocabulary, ``D`` dense and ``M``
# expert layers:
#
#     attention      = d*H*(n+r) + d*(L+r) + L*H*(n+v) + H*v*d
#     dense layer    = attention + 3*d*F
#     expert layer   = attention + d*E + 3*d*f*s + (k*h/E) * 3*d*f
#     matmul_params  = D*dense + M*expert + d*V
#     flops(c)       = 2 * matmul_params + (D+M) * 2*H*(n+r+v) * (c + 1)
#
# The first term is a multiply-add per weight (the router counted, the
# embedding a row lookup that costs none); an expert layer's routed part
# is the k of E experts a token picks, of which a share h/E is held here.
# The second is QK^T over n+r and PV over v: 2 FLOPs per multiply-add.
# Norms, rotary embedding, softmax and the combine are left out.

def matmul_params(conf: dict) -> float:
    hf, ep = published(conf), conf["expert_parallel"]
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    n, r, v = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
               hf["v_head_dim"])
    lat = hf["kv_lora_rank"]
    e, f = ep["router_experts"], hf["moe_intermediate_size"]
    attention = d * h * (n + r) + d * (lat + r) + lat * h * (n + v) \
        + h * v * d
    dense = attention + 3 * d * hf["intermediate_size"]
    expert = attention + d * e + 3 * d * f * hf["n_shared_experts"] \
        + hf["num_experts_per_tok"] * hf["n_routed_experts"] / e * 3 * d * f
    n_dense = hf["first_k_dense_replace"]
    n_expert = hf["num_hidden_layers"] - n_dense
    return n_dense * dense + n_expert * expert + d * hf["vocab_size"]


def attention_flops_per_key(conf: dict) -> int:
    """FLOPs of one token against one key, over all layers."""
    hf = published(conf)
    return hf["num_hidden_layers"] * 2 * hf["num_attention_heads"] * (
        hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"])


def token_flops(conf: dict, positions) -> float:
    """Useful FLOPs of the tokens at the given context positions."""
    pos = np.asarray(positions, np.float64)
    return float(pos.size * 2 * matmul_params(conf)
                 + attention_flops_per_key(conf) * (pos + 1).sum())


def span_flops(conf: dict, start: int, stop: int) -> float:
    """Useful FLOPs of the tokens at positions ``start .. stop - 1``."""
    return token_flops(conf, np.arange(start, stop))
