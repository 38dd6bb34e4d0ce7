"""Attention: GQA (qk-norm, bias, cross-attn) and MLA (deepseek).

Two compute paths, chosen statically by query length:

* ``_attend_dense`` — one einsum per score/value contraction. Used for
  decode (q_len = 1 or γ+1) and small sequences. When the KV cache is
  sequence-sharded (SP decode), XLA partitions the softmax reductions over
  the ``model`` axis with a pair of small all-reduces — the MagicDec-style
  distributed decode attention of DESIGN.md §4.
* ``_attend_flash`` — chunked online-softmax (flash) attention as a scan
  over query/key chunks, fp32 accumulators. Keeps 32k-prefill / 4k-train
  peak memory at chunk² instead of S².

MLA runs *naive* (materialised per-head K/V) for full sequences and
*absorbed* (latent-space scores, MQA-like) for cached decode — the standard
deployment split; the absorbed path is what makes the 512-d latent cache
(and Cassandra packing of it) pay off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as PA
from repro.models import layers as L
from repro.models.layers import Runtime

NEG_INF = -1e30

# Default flash chunk sizes. Threaded through ``Runtime.attn_chunk_q/k``
# (serve.py ``--attn-chunk-q/k`` pins them per arch; kernel_bench sweeps
# them) — these constants are only the fallback when no runtime is in
# play. No behaviour change at default.
DEFAULT_CHUNK_Q = 1024
DEFAULT_CHUNK_K = 1024


# ---------------------------------------------------------------------------
# Core attend primitives
# ---------------------------------------------------------------------------

def _attend_dense(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array | None, scale: float) -> jax.Array:
    """q (B,Sq,H,D), k/v (B,Sk,Hkv,Dk/Dv), mask (B,1,Sq,Sk) or None."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _attend_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                  q_offset: int, chunk_q: int = DEFAULT_CHUNK_Q,
                  chunk_k: int = DEFAULT_CHUNK_K) -> jax.Array:
    """Chunked online-softmax attention (pure-jnp flash).

    q (B,Sq,H,D), k/v (B,Sk,Hkv,D*). Sq % chunk_q == 0, Sk % chunk_k == 0.
    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    """
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // hkv
    cq, ck = min(chunk_q, sq), min(chunk_k, sk)
    while sq % cq:                     # largest divisors <= chunk
        cq -= 1
    while sk % ck:
        ck -= 1
    nq, nk = sq // cq, sk // ck
    scale = 1.0 / (d ** 0.5)

    qc = jnp.moveaxis(q.reshape(b, nq, cq, hkv, g, d), 1, 0)   # (nq,B,cq,hkv,g,d)
    kc = jnp.moveaxis(k.reshape(b, nk, ck, hkv, d), 1, 0)      # (nk,B,ck,hkv,d)
    vc = jnp.moveaxis(v.reshape(b, nk, ck, hkv, dv), 1, 0)

    q_pos_base = jnp.arange(nq) * cq + q_offset

    def q_step(_, xs):
        qi, qbase = xs                                         # (B,cq,hkv,g,d)
        qpos = qbase + jnp.arange(cq)

        def kv_step(carry, ys):
            m, l, acc = carry
            kj, vj, kbase = ys
            kpos = kbase + jnp.arange(ck)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            if causal:
                cm = qpos[:, None] >= kpos[None, :]            # (cq,ck)
                s = jnp.where(cm[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dv), jnp.float32)
        kbases = jnp.arange(nk) * ck
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kbases))
        out = acc / jnp.maximum(l[..., None], 1e-30)           # (b,hkv,g,cq,dv)
        return None, jnp.moveaxis(out, 3, 1)                   # (b,cq,hkv,g,dv)

    _, outs = jax.lax.scan(q_step, None, (qc, q_pos_base))     # (nq,b,cq,...)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def _attend_flash_latent(q_eff: jax.Array, q_rope: jax.Array, c: jax.Array,
                         kr: jax.Array, *, causal: bool, scale: float,
                         chunk_q: int = DEFAULT_CHUNK_Q,
                         chunk_k: int = DEFAULT_CHUNK_K) -> jax.Array:
    """Chunked online-softmax MLA attention *in latent space*.

    q_eff (B,Sq,H,L) f32 (q_nope absorbed through w_uk), q_rope
    (B,Sq,H,R), c (B,Sk,L), kr (B,Sk,R). Scores and context both live in
    the latent dim, so per-head K/V are never materialised — the same
    association order as the absorbed decode path, chunked so the
    (Sq, Sk) score matrix never exists. Returns latent context
    (B,Sq,H,L) f32; the caller applies w_uv.
    """
    b, sq, h, latent = q_eff.shape
    sk = c.shape[1]
    r_dim = kr.shape[-1]
    cq, ck = min(chunk_q, sq), min(chunk_k, sk)
    while sq % cq:
        cq -= 1
    while sk % ck:
        ck -= 1
    nq, nk = sq // cq, sk // ck

    qec = jnp.moveaxis(q_eff.reshape(b, nq, cq, h, latent), 1, 0)
    qrc = jnp.moveaxis(
        q_rope.astype(jnp.float32).reshape(b, nq, cq, h, r_dim), 1, 0)
    cc = jnp.moveaxis(c.astype(jnp.float32).reshape(b, nk, ck, latent), 1, 0)
    krc = jnp.moveaxis(
        kr.astype(jnp.float32).reshape(b, nk, ck, r_dim), 1, 0)
    q_pos_base = jnp.arange(nq) * cq

    def q_step(_, xs):
        qei, qri, qbase = xs
        qpos = qbase + jnp.arange(cq)

        def kv_step(carry, ys):
            m, l, acc = carry
            cj, krj, kbase = ys
            kpos = kbase + jnp.arange(ck)
            s = (jnp.einsum("bqhl,bkl->bhqk", qei, cj)
                 + jnp.einsum("bqhr,bkr->bhqk", qri, krj)) * scale
            if causal:
                cm = qpos[:, None] >= kpos[None, :]
                s = jnp.where(cm[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkl->bhql", p, cj)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, latent), jnp.float32)
        kbases = jnp.arange(nk) * ck
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (cc, krc, kbases))
        ctx = acc / jnp.maximum(l[..., None], 1e-30)           # (b,h,cq,L)
        return None, jnp.moveaxis(ctx, 2, 1)                   # (b,cq,h,L)

    _, outs = jax.lax.scan(q_step, None, (qec, qrc, q_pos_base))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, latent)


def causal_mask(sq: int, sk: int, q_offset) -> jax.Array:
    """(1,1,Sq,Sk) bool: query at abs pos q_offset+i sees keys 0..pos."""
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(sk)
    return (qpos[:, None] >= kpos[None, :])[None, None]


def full_mask(prefix_valid: jax.Array, sq: int) -> jax.Array:
    """(B|1,1,Sq,P+Sq): prefix keys per validity mask + causal among new.

    ``prefix_valid`` is (P,) or (B,P) — per-batch cache lengths arise in
    batched speculative decoding where sequences accept different counts,
    and in fused mixed-role serving where rows of one batch sit at
    different phases entirely (prefill chunk / draft+verify / idle). A
    row's garbage tail (chunk padding past its real tokens) needs no
    extra masking: real queries never attend it causally, and its own
    outputs are dropped at commit.
    """
    p = prefix_valid.shape[-1]
    b = prefix_valid.shape[0] if prefix_valid.ndim == 2 else 1
    pm = jnp.broadcast_to(
        prefix_valid.reshape(b, 1, 1, p), (b, 1, sq, p))
    tri = jnp.broadcast_to(causal_mask(sq, sq, 0), (b, 1, sq, sq))
    return jnp.concatenate([pm, tri], axis=-1)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_project_kv(rt: Runtime, p: dict, x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """K/V projections (+qk-norm on K, +rope). Returns (k, v) (B,S,Hkv,hd)."""
    cfg = rt.cfg
    b, s, _ = x.shape
    hd = cfg.hd
    k = L.dense(rt, p["wk"], x, "attn.wk").reshape(b, s, cfg.n_kv_heads, hd)
    v = L.dense(rt, p["wv"], x, "attn.wv").reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if positions is not None:
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_project_q(rt: Runtime, p: dict, x: jax.Array,
                  positions: jax.Array) -> jax.Array:
    cfg = rt.cfg
    b, s, _ = x.shape
    q = L.dense(rt, p["wq"], x, "attn.wq").reshape(b, s, cfg.n_heads, cfg.hd)
    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
    if positions is not None:
        q = L.apply_rope(q, positions, cfg.rope_theta)
    return q


def gqa_attention(rt: Runtime, p: dict, x: jax.Array, positions: jax.Array,
                  *, causal: bool = True,
                  prefix_kv: tuple[jax.Array, jax.Array] | None = None,
                  prefix_valid: jax.Array | None = None,
                  cross_kv: tuple[jax.Array, jax.Array] | None = None,
                  ) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """Full GQA layer. Returns (out, new_kv) — new_kv is None for cross-attn.

    * full-seq (train/prefill): ``prefix_kv`` and ``cross_kv`` None.
    * cached decode: ``prefix_kv`` = materialised (k, v) (B,P,Hkv,hd) prefix
      keys (packed-cache view ++ draft scratch, assembled by the caller)
      with ``prefix_valid`` (P,) bool; new tokens' K/V are computed here,
      attended as extra trailing keys, and returned for the caller to
      append/commit.
    * cross-attention: ``cross_kv`` = encoder-derived (k, v); not causal.
    """
    cfg = rt.cfg
    b, sq, _ = x.shape
    scale = 1.0 / (cfg.hd ** 0.5)
    q = gqa_project_q(rt, p, x, positions)

    if cross_kv is not None:
        q = rt.shard_act(q, ("batch", None, "heads", None))
        k, v = cross_kv
        out = _attend_dense(q, k, v, None, scale)
        new_kv = None
    elif prefix_kv is not None:
        # sequence-parallel decode: q replicated over `model`, prefix keys
        # token-sharded; XLA partitions the softmax with small psums.
        # (Head-sharding q here forces an all-gather of the whole KV view
        # per layer per step — §Perf iteration A1/A2.)
        q = rt.shard_act(q, ("batch", None, None, None))
        new_k, new_v = gqa_project_kv(rt, p, x, positions)
        pk, pv = prefix_kv
        pk = rt.shard_act(pk, ("batch", "seq_kv", None, None))
        pv = rt.shard_act(pv, ("batch", "seq_kv", None, None))
        k = jnp.concatenate([pk, new_k.astype(pk.dtype)], axis=1)
        v = jnp.concatenate([pv, new_v.astype(pv.dtype)], axis=1)
        mask = full_mask(prefix_valid, sq)
        out = _attend_dense(q, k, v, mask, scale)
        new_kv = (new_k, new_v)
    else:
        k, v = gqa_project_kv(rt, p, x, positions)
        k = rt.shard_act(k, ("batch", None, "kv_heads", None))
        v = rt.shard_act(v, ("batch", None, "kv_heads", None))
        if sq > 2048:
            out = _attend_flash(q, k, v, causal=causal, q_offset=0,
                                chunk_q=rt.attn_chunk_q,
                                chunk_k=rt.attn_chunk_k)
        else:
            mask = causal_mask(sq, k.shape[1], 0) if causal else None
            out = _attend_dense(q, k, v, mask, scale)
        new_kv = (k, v)

    out = out.reshape(b, sq, cfg.n_heads * out.shape[-1])
    return L.dense(rt, p["wo"], out, "attn.wo"), new_kv


# ---------------------------------------------------------------------------
# MLA block (deepseek-v2/v3)
# ---------------------------------------------------------------------------

def mla_latent(rt: Runtime, p: dict, x: jax.Array, positions: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """The cached quantities: latent c (B,S,kv_lora) + k_rope (B,S,rope)."""
    cfg = rt.cfg
    kv_full = L.dense(rt, p["kv_a"], x, "mla.kv_a")
    c = L.rmsnorm(p["kv_a_norm"], kv_full[..., :cfg.kv_lora_rank], cfg.norm_eps)
    k_rope = kv_full[..., cfg.kv_lora_rank:]
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions,
                          cfg.rope_theta, cfg.yarn)[:, :, 0]
    return c, k_rope


def _mla_q(rt: Runtime, p: dict, x: jax.Array, positions: jax.Array
           ) -> tuple[jax.Array, jax.Array]:
    """Per-head (q_nope, q_rope): through the q-LoRA (``q_a``, its norm,
    ``q_b``), or one direct projection ``wq`` when ``q_lora_rank`` is 0."""
    cfg = rt.cfg
    b, s, _ = x.shape
    if cfg.q_lora_rank:
        ql = L.rmsnorm(p["q_a_norm"], L.dense(rt, p["q_a"], x, "mla.q_a"),
                       cfg.norm_eps)
        q = L.dense(rt, p["q_b"], ql, "mla.q_b")
    else:
        q = L.dense(rt, p["wq"], x, "mla.wq")
    q = q.reshape(b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope = q[..., :cfg.qk_nope_dim]
    q_rope = L.apply_rope(q[..., cfg.qk_nope_dim:], positions,
                          cfg.rope_theta, cfg.yarn)
    return q_nope, q_rope


def mla_scale(cfg) -> float:
    """Softmax scale: (nope + rope)^-1/2, times mscale² under YaRN."""
    scale = 1.0 / ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5)
    return scale * cfg.yarn.attn_factor if cfg.yarn else scale


def _kv_b_split(rt: Runtime, p: dict) -> tuple[jax.Array, jax.Array]:
    cfg = rt.cfg
    w = L.resolve_weight(rt, p["kv_b"]["w"], "mla.kv_b")
    w = w.reshape(cfg.kv_lora_rank, cfg.n_heads,
                  cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]   # w_uk, w_uv


def mla_attention(rt: Runtime, p: dict, x: jax.Array, positions: jax.Array,
                  *, causal: bool = True,
                  prefix_latent: tuple[jax.Array, jax.Array] | None = None,
                  prefix_valid: jax.Array | None = None,
                  ) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """MLA layer. Cache = (c, k_rope) latents, NOT per-head K/V.

    Cached decode (``prefix_latent`` = (c, kr) prefix from the cache view
    ++ scratch) runs *absorbed* (scores and context in latent space).
    Full-seq runs the SAME absorbed math up to 2048 tokens so prefill,
    train and incremental decode share one association order and no bf16
    k_nope/v round-trip — the naive path (materialised per-head K/V +
    flash) used to sit ~1e-2 off the absorbed path, which deepseek's MoE
    router amplified into expert flips (the prefill-vs-decode drift).
    Beyond 2048 tokens the latent score matrix is the quadratic-memory
    killer, so long prefill runs ``_attend_flash_latent`` — chunked
    flash with absorbed-order scores/context, so per-head K/V are never
    materialised and the only prefill-vs-decode difference left is the
    online-softmax association order (tolerance documented in
    tests/test_models.py).
    """
    cfg = rt.cfg
    b, sq, _ = x.shape
    scale = mla_scale(cfg)
    q_nope, q_rope = _mla_q(rt, p, x, positions)
    new_c, new_kr = mla_latent(rt, p, x, positions)

    if prefix_latent is None and sq > 2048:
        # latent flash: absorbed math, chunked — the PR 2 leftover
        # (per-head K/V materialisation off the absorbed path) is gone.
        w_uk, w_uv = _kv_b_split(rt, p)
        q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        q_eff = rt.shard_act(q_eff, ("batch", None, "heads", None))
        ctx = _attend_flash_latent(q_eff, q_rope, new_c, new_kr,
                                   causal=causal, scale=scale,
                                   chunk_q=rt.attn_chunk_q,
                                   chunk_k=rt.attn_chunk_k)
        out = jnp.einsum("bqhl,lhn->bqhn", ctx,
                         w_uv.astype(jnp.float32)).astype(x.dtype)
    else:
        # absorbed path over the latents (sequence-parallel decode:
        # latents token-sharded, q replicated — mirrors GQA decode)
        w_uk, w_uv = _kv_b_split(rt, p)
        if prefix_latent is None:
            c_all, kr_all = new_c, new_kr
            mask = causal_mask(sq, sq, 0) if causal else None
        else:
            pc, pkr = prefix_latent
            pc = rt.shard_act(pc, ("batch", "seq_kv", None))
            pkr = rt.shard_act(pkr, ("batch", "seq_kv", None))
            c_all = jnp.concatenate([pc, new_c.astype(pc.dtype)], axis=1)
            kr_all = jnp.concatenate([pkr, new_kr.astype(pkr.dtype)],
                                     axis=1)
            mask = full_mask(prefix_valid, sq)
        q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))         # (B,sq,H,lora)
        if prefix_latent is None:
            # full-seq: heads-sharded like the naive path; decode keeps q
            # replicated against token-sharded latents (MagicDec layout)
            q_eff = rt.shard_act(q_eff, ("batch", None, "heads", None))
        s_nope = jnp.einsum("bqhl,bkl->bhqk", q_eff,
                            c_all.astype(jnp.float32))
        s_rope = jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(jnp.float32),
                            kr_all.astype(jnp.float32))
        scores = (s_nope + s_rope) * scale
        if mask is not None:
            scores = jnp.where(mask, scores, NEG_INF)
        pattn = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkl->bqhl", pattn,
                         c_all.astype(jnp.float32))          # (B,sq,H,lora)
        out = jnp.einsum("bqhl,lhn->bqhn", ctx, w_uv.astype(jnp.float32))
        out = out.astype(x.dtype)

    out = out.reshape(b, sq, cfg.n_heads * cfg.v_head_dim)
    return L.dense(rt, p["wo"], out, "mla.wo"), (new_c, new_kr)


# ---------------------------------------------------------------------------
# Paged-kernel decode entry points (attn_kernel knob). The block-table
# walk + pool flash run in kernels/paged_attention; the scratch/new-token
# suffix (which lives outside the pool) is folded in with one more flash
# step, then wo as usual.
# ---------------------------------------------------------------------------


def _suffix_valid(b: int, sq: int, g_scratch: int, scratch_len) -> jax.Array:
    """(B, Sq, g_scratch + Sq) bool: scratch validity + causal triangle."""
    parts = []
    if g_scratch:
        gv = jnp.arange(g_scratch) < scratch_len
        parts.append(jnp.broadcast_to(gv[None, None], (b, sq, g_scratch)))
    tri = causal_mask(sq, sq, 0)[0]                        # (1, Sq, Sq)
    parts.append(jnp.broadcast_to(tri, (b, sq, sq)))
    return jnp.concatenate(parts, axis=-1)


def gqa_attention_paged(rt: Runtime, p: dict, x: jax.Array,
                        positions: jax.Array, *, kv_pools: tuple,
                        table: jax.Array, length: jax.Array,
                        scratch: dict | None, scratch_len
                        ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """GQA cached decode through the paged-attention kernel.

    ``kv_pools`` is ``("plain", k_pool, v_pool)`` (bf16 (NB,BS,Hkv,hd)
    pool blocks) or ``("packed", k_spec, v_spec, book, keep)`` (the
    Cassandra spec leaf dicts — decode runs inside the kernel). The
    per-request dense prefix is never gathered.
    """
    cfg = rt.cfg
    b, sq, _ = x.shape
    hkv = cfg.n_kv_heads
    g = cfg.n_heads // hkv
    scale = 1.0 / (cfg.hd ** 0.5)
    q = gqa_project_q(rt, p, x, positions)
    q = rt.shard_act(q, ("batch", None, None, None))
    new_k, new_v = gqa_project_kv(rt, p, x, positions)
    qg = q.reshape(b, sq, hkv, g, cfg.hd)
    length = jnp.broadcast_to(jnp.atleast_1d(length), (b,))
    impl = rt.attn_kernel

    if kv_pools[0] == "packed":
        _, k_spec, v_spec, book, keep = kv_pools
        acc, m, l = PA.paged_gqa_packed(
            qg, k_spec, v_spec, table, length, book, d=cfg.hd, keep=keep,
            trunc=rt.cass.kv_trunc, exp_bits=rt.cass.exp_bits,
            scale=scale, impl=impl)
    else:
        _, k_pool, v_pool = kv_pools
        acc, m, l = PA.paged_gqa(qg, k_pool, v_pool, table, length,
                                 scale=scale, impl=impl)

    if scratch is not None:
        g_s = scratch["k"].shape[1]
        suf_k = jnp.concatenate(
            [scratch["k"], new_k.astype(scratch["k"].dtype)], axis=1)
        suf_v = jnp.concatenate(
            [scratch["v"], new_v.astype(scratch["v"].dtype)], axis=1)
    else:
        g_s = 0
        suf_k, suf_v = new_k, new_v
    suf_valid = _suffix_valid(b, sq, g_s, scratch_len)
    out = PA.merge_gqa_suffix(acc, m, l, qg, suf_k, suf_v, suf_valid,
                              scale=scale)                 # (B,Sq,hkv,g,hd)
    out = out.reshape(b, sq, cfg.n_heads * cfg.hd).astype(x.dtype)
    return L.dense(rt, p["wo"], out, "attn.wo"), (new_k, new_v)


def mla_attention_paged(rt: Runtime, p: dict, x: jax.Array,
                        positions: jax.Array, *, c_pool: jax.Array,
                        kr_pool: jax.Array, table: jax.Array,
                        length: jax.Array, scratch: dict | None,
                        scratch_len
                        ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """MLA cached decode through the paged latent-flash kernel.

    The kernel consumes the (c, k_rope) latent pools directly with
    absorbed-order math — the same latent flash that serves >2048
    prefill, walking the block table instead of a contiguous sequence.
    A packed latent pool reaches it decoded (``ventry`` or
    ``read_store``): the kernel reads dense latents only.
    """
    cfg = rt.cfg
    b, sq, _ = x.shape
    scale = mla_scale(cfg)
    q_nope, q_rope = _mla_q(rt, p, x, positions)
    new_c, new_kr = mla_latent(rt, p, x, positions)
    w_uk, w_uv = _kv_b_split(rt, p)
    q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    length = jnp.broadcast_to(jnp.atleast_1d(length), (b,))

    acc, m, l = PA.paged_mla(q_eff, q_rope.astype(jnp.float32), c_pool,
                             kr_pool, table, length, scale=scale,
                             impl=rt.attn_kernel)

    if scratch is not None:
        g_s = scratch["c"].shape[1]
        suf_c = jnp.concatenate(
            [scratch["c"], new_c.astype(scratch["c"].dtype)], axis=1)
        suf_kr = jnp.concatenate(
            [scratch["kr"], new_kr.astype(scratch["kr"].dtype)], axis=1)
    else:
        g_s = 0
        suf_c, suf_kr = new_c, new_kr
    suf_valid = _suffix_valid(b, sq, g_s, scratch_len)
    ctx = PA.merge_mla_suffix(acc, m, l, q_eff, q_rope, suf_c, suf_kr,
                              suf_valid, scale=scale)      # (B,Sq,H,L)
    out = jnp.einsum("bqhl,lhn->bqhn", ctx,
                     w_uv.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(b, sq, cfg.n_heads * cfg.v_head_dim)
    return L.dense(rt, p["wo"], out, "mla.wo"), (new_c, new_kr)
