"""Pallas paged-attention decode kernel with in-kernel Cassandra decode.

The serving hot path today assembles each request's KV prefix with
``kvcache.gather_block_leaf`` (an XLA gather that materialises a dense
``(B, MB*BS, ...)`` copy of the pool in HBM) before attention starts.
For the packed draft store it *also* materialises the Cassandra-decoded
bf16 KV densely — forfeiting exactly the bandwidth win the paper's
DRAM→L2 decoder module exists to capture.

This module walks the ``(B, MB)`` block table *in-kernel* instead: the
grid iterates (row, kv-block), each step streams one ``(BS, ...)`` pool
block HBM→VMEM via a scalar-prefetched table index map and folds it into
an online-softmax (flash) accumulator under the row's ``length`` mask.
The dense per-request prefix never exists.

Two variants behind one family of entry points:

* **plain** — bf16 pool blocks (verify pass, and any materialised view).
  ``paged_gqa`` / ``paged_mla``.
* **packed** — the pool blocks are the Cassandra C-1 spec leaves
  (bitmap / signmant / exp words / mode / emax); the rank-codebook
  reconstruction (``unary_decode``-style compare-sum ranks + 3-bit delta
  exponents, unpacked with ``kernels.unpack``) runs inside the kernel
  between the VMEM load and the QK dot. Draft-pass KV never exists
  densely in HBM. ``paged_gqa_packed``. (MLA caches cannot be packed
  repo-wide — ``qk_rope_dim=16`` fails the 32-lane pack — so the packed
  variant is GQA-only.)

Each entry point takes ``impl`` ∈ {"jnp", "interpret", "pallas"}:
``jnp`` is the gather-then-scan reference built from the *same* per-block
step helpers (this is both the CPU serving path and the parity oracle);
``interpret`` runs the Pallas kernel in interpreter mode (CPU CI);
``pallas`` compiles for the accelerator. The contract is bitwise:
``interpret``/``pallas`` must equal ``jnp`` at the (acc, m, l) level.

The kernels return *unnormalised* flash state ``(acc, m, l)`` so the
caller can merge the scratch/new-token suffix (which lives outside the
pool) with one more flash step — see ``merge_gqa_suffix`` /
``merge_mla_suffix`` — before the final ``acc / l`` division.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import unpack as U

NEG_INF = -1e30
# Unused table slots point at block 0 by convention (the trash block,
# same contract as serving.kvcache.TRASH_BLOCK / append_paged_batched).
# Kept as a local constant so kernels/ does not import serving/.
TRASH_BLOCK = 0


def sanitize_table(table: jax.Array, num_blocks: int) -> jax.Array:
    """Route out-of-range table entries through the trash block.

    The gather path and the kernel path must agree on what a garbage
    table slot reads: block 0 (whose contents are masked by ``length``
    anyway). ``jnp.take(..., mode="clip")`` alone would silently alias
    out-of-range entries to the *last* pool block.
    """
    ok = (table >= 0) & (table < num_blocks)
    return jnp.where(ok, table, TRASH_BLOCK).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Cassandra C-1 spec decode (bit-exact replica of the kvcache.read_store
# draft view: coding.decode_exponents + format._join_kept_draft +
# pruning.desparsify), written in the 2-D style Mosaic lowers.
# ---------------------------------------------------------------------------


def _unary_ranks(bits: jax.Array, keep: int) -> jax.Array:
    """Compare-sum unary rank decode (kernels/unary_decode.py Alg. 1).

    ``bits`` is the (R, n) 0/1 stream; returns (R, keep) int32 ranks in
    [0, 31]. Rank j is the gap between the j-th and (j+1)-th set bits:
    ``pos[j] = #{p : idx[p] < j+1}`` (strict compare — ``<=`` lands on
    the next bit) is the 0-indexed position of the (j+1)-th set bit,
    with ``idx`` the inclusive prefix count. One lane reduction per rank.
    """
    r = bits.shape[0]
    idx = U.prefix_count(bits)
    lane = U.iota((r, keep), 1)

    def body(j, carry):
        pos, prev = carry
        cnt = jnp.sum((idx < j + 1).astype(jnp.int32), axis=1, keepdims=True)
        return (jnp.where(lane == j, cnt, pos),
                jnp.where(lane == j + 1, cnt, prev))

    pos, prev = jax.lax.fori_loop(
        0, keep, body, (jnp.zeros((r, keep), jnp.int32),
                        jnp.full((r, keep), -1, jnp.int32)))
    return jnp.clip(pos - prev - 1, 0, 31)


def _decode_kv_rows(bitmap: jax.Array, signmant: jax.Array,
                    exp_words: jax.Array, mode: jax.Array, emax: jax.Array,
                    book32, *, d: int, keep: int, trunc: int,
                    exp_bits: int) -> jax.Array:
    """Decode (R,) Cassandra C-1 spec rows -> (R, d) bf16.

    Bit-exact vs the host draft view (``read_store`` with
    ``view="draft"``): unary/delta exponent reconstruction without the
    verif correction, truncated mantissas, desparsified against the
    bitmap. ``mode``/``emax`` are (R, 1) int32 columns; ``book32`` is
    ``exp_of_rank[:32]`` as int32 — an array, or the kernel's SMEM ref.
    """
    t_keep = 7 - trunc
    esc = (1 << exp_bits) - 1

    code = U.unpack_fixed(signmant, 1 + t_keep, keep)    # (R, keep)
    sign = (code >> t_keep) & 1
    mant = (code & ((1 << t_keep) - 1)) << trunc

    # exponents: unary ranks through the codebook, or 3-bit deltas. The
    # unary stream may run into the region's word-padding past
    # keep*exp_bits bits (encode_exponents sizes the region in whole
    # uint32 words), so rank-decode over the FULL region width.
    ebits = U.unpack_fixed(exp_words, 1, exp_words.shape[1] * 32)
    uranks = _unary_ranks(ebits, keep)
    uexp = jnp.zeros_like(uranks)
    for rk in range(32):
        uexp = uexp + jnp.where(uranks == rk, book32[rk], 0)

    dcodes = U.unpack_fixed(exp_words, exp_bits, keep)
    dexp = jnp.clip(emax - dcodes, 0, 255)
    dexp = jnp.where(dcodes == esc, 0, dexp)

    exp = jnp.where(mode == 0, uexp, dexp)
    kept16 = (sign << 15) | (exp << 7) | mant
    dense16 = U.desparsify(kept16, U.unpack_fixed(bitmap, 1, d))
    return jax.lax.bitcast_convert_type(dense16.astype(jnp.uint16),
                                        jnp.bfloat16)


def _spec_rows(spec: dict, lead: tuple[int, ...], rows: int) -> tuple:
    """Spec leaves (..., 1, W) / (..., 1) -> (*lead, rows, W) word planes
    and (*lead, rows, 1) int32 mode/emax columns."""
    return (
        spec["bitmap"].reshape(*lead, rows, -1),
        spec["signmant"].reshape(*lead, rows, -1),
        spec["exp_words"].reshape(*lead, rows, -1),
        spec["exp_mode"].reshape(*lead, rows, 1).astype(jnp.int32),
        spec["exp_emax"].reshape(*lead, rows, 1).astype(jnp.int32),
    )


@functools.partial(jax.jit,
                   static_argnames=("d", "keep", "trunc", "exp_bits"))
def decode_spec_pool(spec: dict, book: jax.Array, *, d: int, keep: int,
                     trunc: int, exp_bits: int) -> jax.Array:
    """Decode a whole packed pool: spec leaves (NB, BS, Hkv, 1, W) ->
    bf16 (NB, BS, Hkv, d).

    This is the same ``_decode_kv_rows`` the packed kernel runs per
    block — exposed so tests and the kernel-bench gate can assert the
    in-kernel Cassandra decode is bit-exact against the host draft view
    (``kvcache.read_store`` with ``view="draft"``) without going through
    flash state, whose float association order is compile-dependent.
    """
    nb, bs, hkv = spec["bitmap"].shape[:3]
    out = _decode_kv_rows(*_spec_rows(spec, (), nb * bs * hkv),
                          book[:32].astype(jnp.int32), d=d, keep=keep,
                          trunc=trunc, exp_bits=exp_bits)
    return out.reshape(nb, bs, hkv, d)


# ---------------------------------------------------------------------------
# Shared per-block flash step helpers. The Pallas kernel bodies and the
# jnp gather reference call the *same* functions on identically-shaped
# operands, which is what makes the parity contract bitwise.
#
# Everything is 2-D. A GQA block holds all Hkv heads of BS tokens as
# (BS*Hkv, D) rows ordered (token, head) — the pool's own memory order —
# and the queries of a row are (Hkv*G*T, D) rows ordered (head, group,
# token). One matmul scores every query row against every key row; the
# pairs whose heads differ are masked out, which costs Hkv× the QK/PV
# MACs of a per-head loop but needs no in-kernel transpose.
# ---------------------------------------------------------------------------


def _dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M, K) x (N, K) -> (M, N) f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_update(s: jax.Array, valid: jax.Array, v: jax.Array,
                  m: jax.Array, l: jax.Array, acc: jax.Array):
    """Fold (M, N) scores against (N, Dv) f32 values into (m, l, acc)."""
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _gqa_block(q: jax.Array, kb: jax.Array, vb: jax.Array, start, length,
               m: jax.Array, l: jax.Array, acc: jax.Array, *, scale: float,
               hkv: int, rows_per_head: int):
    """One flash step over a KV block of BS tokens x Hkv heads.

    q: (Hkv*G*T, D) f32 · kb/vb: (BS*Hkv, Dk)/(BS*Hkv, Dv) · start: the
    block's first token position · length: the row's prefix length ·
    m/l: (Hkv*G*T, 1) f32 · acc: (Hkv*G*T, Dv) f32.
    Invalid rows are zeroed on the *value* operand too: a masked packed
    lane can decode to NaN and 0·NaN would poison the accumulator.
    """
    vb = jnp.where(start + U.iota(vb.shape, 0) // hkv < length, vb, 0)
    s = _dot_nt(q, kb.astype(jnp.float32)) * scale
    col = U.iota(s.shape, 1)
    valid = ((U.iota(s.shape, 0) // rows_per_head == col % hkv)
             & (start + col // hkv < length))
    return _flash_update(s, valid, vb.astype(jnp.float32), m, l, acc)


def _mla_block(q_eff: jax.Array, q_rope: jax.Array, cb: jax.Array,
               krb: jax.Array, start, length, m: jax.Array, l: jax.Array,
               acc: jax.Array, *, scale: float):
    """One flash step in latent space over a (S, L)+(S, R) block.

    q_eff: (H*T, L) f32 (q_nope absorbed through w_uk) · q_rope:
    (H*T, R) f32 · cb: (S, L) · krb: (S, R) · m/l: (H*T, 1) f32 ·
    acc: (H*T, L) f32. The latent block ``cb`` is both the score and
    the value operand (absorbed MLA math), so one zeroed copy serves
    both and keeps masked-lane NaNs out of the accumulator.
    """
    cz = jnp.where(start + U.iota(cb.shape, 0) < length, cb,
                   0).astype(jnp.float32)
    krz = jnp.where(start + U.iota(krb.shape, 0) < length, krb,
                    0).astype(jnp.float32)
    s = (_dot_nt(q_eff, cz) + _dot_nt(q_rope, krz)) * scale
    valid = start + U.iota(s.shape, 1) < length
    return _flash_update(s, valid, cz, m, l, acc)


def _init_state(rows: int, dv: int):
    return (jnp.full((rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, dv), jnp.float32))


# ---------------------------------------------------------------------------
# Pallas kernel bodies. Grid = (B rows, MB table columns); the pool
# operands use scalar-prefetched index maps so grid step (b, j) streams
# pool block table[b, j] HBM->VMEM. Outputs are revisited across j with
# @pl.when(j == 0) init — flash state accumulates in program order.
# ---------------------------------------------------------------------------


def _flash_step(j, acc_ref, m_ref, l_ref, step):
    @pl.when(j == 0)
    def _init():
        m0, l0, a0 = _init_state(*acc_ref.shape[1:])
        m_ref[0], l_ref[0], acc_ref[0] = m0, l0, a0

    m_ref[0], l_ref[0], acc_ref[0] = step(m_ref[0], l_ref[0], acc_ref[0])


def _gqa_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                acc_ref, m_ref, l_ref, *, scale: float, block_size: int,
                hkv: int, rows_per_head: int):
    b, j = pl.program_id(0), pl.program_id(1)
    _flash_step(j, acc_ref, m_ref, l_ref, functools.partial(
        _gqa_block, q_ref[0], k_ref[0], v_ref[0], j * block_size,
        len_ref[b], scale=scale, hkv=hkv, rows_per_head=rows_per_head))


def _gqa_packed_kernel(tbl_ref, len_ref, book_ref, q_ref,
                       kbm_ref, ksm_ref, kew_ref, kmo_ref, kem_ref,
                       vbm_ref, vsm_ref, vew_ref, vmo_ref, vem_ref,
                       acc_ref, m_ref, l_ref, *, scale: float,
                       block_size: int, hkv: int, rows_per_head: int,
                       d: int, keep: int, trunc: int, exp_bits: int):
    b, j = pl.program_id(0), pl.program_id(1)
    dec = functools.partial(_decode_kv_rows, d=d, keep=keep, trunc=trunc,
                            exp_bits=exp_bits)
    kb = dec(kbm_ref[0], ksm_ref[0], kew_ref[0], kmo_ref[0], kem_ref[0],
             book_ref)
    vb = dec(vbm_ref[0], vsm_ref[0], vew_ref[0], vmo_ref[0], vem_ref[0],
             book_ref)
    _flash_step(j, acc_ref, m_ref, l_ref, functools.partial(
        _gqa_block, q_ref[0], kb, vb, j * block_size, len_ref[b],
        scale=scale, hkv=hkv, rows_per_head=rows_per_head))


def _mla_kernel(tbl_ref, len_ref, qe_ref, qr_ref, c_ref, kr_ref,
                acc_ref, m_ref, l_ref, *, scale: float, block_size: int):
    b, j = pl.program_id(0), pl.program_id(1)
    _flash_step(j, acc_ref, m_ref, l_ref, functools.partial(
        _mla_block, qe_ref[0], qr_ref[0], c_ref[0], kr_ref[0],
        j * block_size, len_ref[b], scale=scale))


def _scan_rows(step, rows: int, dv: int, mb: int, *per_row):
    """The jnp reference walk: per batch row, scan ``step(j, m, l, acc,
    *row_operands)`` over the MB table columns. Returns (acc, m, l)."""
    def row(*ops):
        def body(carry, j):
            return step(j, *carry, *ops), None

        (m, l, acc), _ = jax.lax.scan(body, _init_state(rows, dv),
                                      jnp.arange(mb, dtype=jnp.int32))
        return acc, m, l

    return jax.vmap(row)(*per_row)


def _pool_call(kernel, *, impl: str, b: int, mb: int, rows: int, dv: int,
               prefetch: list, row_operands: list, pool_operands: list):
    """pallas_call over grid (B, MB): ``row_operands`` are (B, ...) and
    blocked per batch row; ``pool_operands`` are (NB, ...) and blocked at
    pool block ``table[b, j]`` (``prefetch[0]``). Returns flash state
    acc (B, rows, dv), m/l (B, rows, 1)."""
    def row_spec(x):
        zeros = (0,) * (len(x.shape) - 1)
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda bi, j, *_: (bi,) + zeros)

    def pool_spec(x):
        zeros = (0,) * (len(x.shape) - 1)
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda bi, j, tbl, *_: (tbl[bi, j],) + zeros)

    out_shape = [jax.ShapeDtypeStruct((b, rows, dv), jnp.float32),
                 jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, rows, 1), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, mb),
        in_specs=([row_spec(x) for x in row_operands]
                  + [pool_spec(x) for x in pool_operands]),
        out_specs=[row_spec(o) for o in out_shape],
    )
    if impl == "interpret":
        mode = {"interpret": True}
    else:
        mode = {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))}
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          **mode)(*prefetch, *row_operands, *pool_operands)


def _gqa_rows(q: jax.Array) -> jax.Array:
    """(B, T, Hkv, G, D) -> (B, Hkv*G*T, D) f32, rows ordered (h, g, t)."""
    b, t, hkv, g, d = q.shape
    return jnp.transpose(q.astype(jnp.float32),
                         (0, 2, 3, 1, 4)).reshape(b, hkv * g * t, d)


# ---------------------------------------------------------------------------
# Public entry points. The "jnp" impl of each is the sanitised-gather +
# lax.scan reference built from the same step helpers — both the CPU
# serving path and the parity oracle for the Pallas kernels.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def paged_gqa(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
              table: jax.Array, length: jax.Array, *, scale: float,
              impl: str = "jnp"):
    """Paged GQA decode attention over plain bf16 pool blocks.

    q: (B, T, Hkv, G, D) · k_pool/v_pool: (NB, BS, Hkv, Dk/Dv) ·
    table: (B, MB) int32 · length: (B,) int32 prefix lengths.
    Returns unnormalised flash state (acc (B, Hkv, G, T, Dv) f32,
    m (B, Hkv, G, T) f32, l (B, Hkv, G, T) f32).
    """
    b, t, hkv, g, _ = q.shape
    nb, bs = k_pool.shape[:2]
    dv = v_pool.shape[-1]
    mb = table.shape[1]
    rows = hkv * g * t
    table = sanitize_table(table, nb)
    length = length.astype(jnp.int32)
    qr = _gqa_rows(q)
    k2 = k_pool.reshape(nb, bs * hkv, -1)
    v2 = v_pool.reshape(nb, bs * hkv, dv)
    kw = dict(scale=scale, hkv=hkv, rows_per_head=g * t)

    if impl == "jnp":
        def step(j, m, l, acc, qrow, tbl_row, ln):
            return _gqa_block(qrow, k2[tbl_row[j]], v2[tbl_row[j]], j * bs,
                              ln, m, l, acc, **kw)

        acc, m, l = _scan_rows(step, rows, dv, mb, qr, table, length)
    else:
        acc, m, l = _pool_call(
            functools.partial(_gqa_kernel, block_size=bs, **kw),
            impl=impl, b=b, mb=mb, rows=rows, dv=dv,
            prefetch=[table, length], row_operands=[qr],
            pool_operands=[k2, v2])
    return (acc.reshape(b, hkv, g, t, dv), m.reshape(b, hkv, g, t),
            l.reshape(b, hkv, g, t))


@functools.partial(jax.jit, static_argnames=(
    "d", "keep", "trunc", "exp_bits", "scale", "impl"))
def paged_gqa_packed(q: jax.Array, k_spec: dict, v_spec: dict,
                     table: jax.Array, length: jax.Array,
                     book: jax.Array, *, d: int, keep: int, trunc: int,
                     exp_bits: int, scale: float, impl: str = "jnp"):
    """Paged GQA decode attention over *packed* Cassandra spec blocks.

    ``k_spec``/``v_spec`` are the store's spec leaf dicts with layout
    (NB, BS, Hkv, 1, W) for the word planes and (NB, BS, Hkv, 1) for
    mode/emax. The Cassandra draft-view decode runs between the VMEM
    load and the QK dot — the bf16 KV never exists densely in HBM.
    ``book`` is the layer's exp_of_rank codebook (>=32 entries).
    Returns unnormalised flash state like ``paged_gqa``.
    """
    b, t, hkv, g, _ = q.shape
    nb, bs = k_spec["bitmap"].shape[:2]
    mb = table.shape[1]
    rows = hkv * g * t
    table = sanitize_table(table, nb)
    length = length.astype(jnp.int32)
    qr = _gqa_rows(q)
    book32 = book[:32].astype(jnp.int32)
    kf = _spec_rows(k_spec, (nb,), bs * hkv)
    vf = _spec_rows(v_spec, (nb,), bs * hkv)
    kw = dict(scale=scale, hkv=hkv, rows_per_head=g * t)
    dec = functools.partial(_decode_kv_rows, d=d, keep=keep, trunc=trunc,
                            exp_bits=exp_bits)

    if impl == "jnp":
        def step(j, m, l, acc, qrow, tbl_row, ln):
            kb = dec(*(leaf[tbl_row[j]] for leaf in kf), book32)
            vb = dec(*(leaf[tbl_row[j]] for leaf in vf), book32)
            return _gqa_block(qrow, kb, vb, j * bs, ln, m, l, acc, **kw)

        acc, m, l = _scan_rows(step, rows, d, mb, qr, table, length)
    else:
        acc, m, l = _pool_call(
            functools.partial(_gqa_packed_kernel, block_size=bs, d=d,
                              keep=keep, trunc=trunc, exp_bits=exp_bits,
                              **kw),
            impl=impl, b=b, mb=mb, rows=rows, dv=d,
            prefetch=[table, length, book32], row_operands=[qr],
            pool_operands=[*kf, *vf])
    return (acc.reshape(b, hkv, g, t, d), m.reshape(b, hkv, g, t),
            l.reshape(b, hkv, g, t))


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def paged_mla(q_eff: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
              kr_pool: jax.Array, table: jax.Array, length: jax.Array,
              *, scale: float, impl: str = "jnp"):
    """Paged MLA decode attention in latent space (absorbed math).

    q_eff: (B, T, H, L) f32 — q_nope absorbed through w_uk ·
    q_rope: (B, T, H, R) · c_pool: (NB, BS, L) · kr_pool: (NB, BS, R) ·
    table: (B, MB) · length: (B,).
    Returns (acc (B, H, T, L) f32, m (B, H, T) f32, l (B, H, T) f32).
    This is also the latent-space flash kernel for long MLA prefill.
    """
    b, t, h, latent = q_eff.shape
    nb, bs, _ = c_pool.shape
    mb = table.shape[1]
    rows = h * t
    table = sanitize_table(table, nb)
    length = length.astype(jnp.int32)

    def head_rows(x):                      # (B, T, H, X) -> (B, H*T, X)
        return jnp.transpose(x.astype(jnp.float32),
                             (0, 2, 1, 3)).reshape(b, rows, -1)

    qe, qr = head_rows(q_eff), head_rows(q_rope)

    if impl == "jnp":
        def step(j, m, l, acc, qer, qrr, tbl_row, ln):
            return _mla_block(qer, qrr, c_pool[tbl_row[j]],
                              kr_pool[tbl_row[j]], j * bs, ln, m, l, acc,
                              scale=scale)

        acc, m, l = _scan_rows(step, rows, latent, mb, qe, qr, table,
                               length)
    else:
        acc, m, l = _pool_call(
            functools.partial(_mla_kernel, scale=scale, block_size=bs),
            impl=impl, b=b, mb=mb, rows=rows, dv=latent,
            prefetch=[table, length], row_operands=[qe, qr],
            pool_operands=[c_pool, kr_pool])
    return (acc.reshape(b, h, t, latent), m.reshape(b, h, t),
            l.reshape(b, h, t))


# ---------------------------------------------------------------------------
# Suffix merge: the scratch/new tokens live outside the pool; fold them
# in with one more flash step per row, then normalise.
# ---------------------------------------------------------------------------


def merge_gqa_suffix(acc: jax.Array, m: jax.Array, l: jax.Array,
                     q: jax.Array, suf_k: jax.Array, suf_v: jax.Array,
                     suf_valid: jax.Array, *, scale: float) -> jax.Array:
    """Fold a (B, S, Hkv, D) suffix into paged flash state; normalise.

    ``suf_valid`` is (B, T, S) bool (per-query-token, so the causal
    triangle over the new tokens rides in). Returns (B, T, Hkv, G, Dv)
    f32 attention output.
    """
    def row(accr, mr, lr, qr, kr, vr, validr):
        # validr: (T, S). Score mask is per-query-token; value zeroing
        # uses "valid for any t" (a never-valid suffix row may be junk).
        vz = jnp.where(jnp.any(validr, axis=0)[:, None, None], vr, 0)
        s = jnp.einsum("thgd,shd->hgts", qr, kr.astype(jnp.float32)) * scale
        vm = validr[None, None]                            # (1, 1, T, S)
        s = jnp.where(vm, s, NEG_INF)
        m_new = jnp.maximum(mr, jnp.max(s, axis=-1))
        p = jnp.where(vm, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(mr - m_new)
        l_new = lr * corr + jnp.sum(p, axis=-1)
        acc_new = accr * corr[..., None] + jnp.einsum(
            "hgts,shd->hgtd", p, vz.astype(jnp.float32))
        out = acc_new / jnp.maximum(l_new[..., None], 1e-30)
        return out                                         # (Hkv,G,T,Dv)

    out = jax.vmap(row)(acc, m, l, q.astype(jnp.float32), suf_k, suf_v,
                        suf_valid)
    return jnp.transpose(out, (0, 3, 1, 2, 4))             # (B,T,Hkv,G,Dv)


def merge_mla_suffix(acc: jax.Array, m: jax.Array, l: jax.Array,
                     q_eff: jax.Array, q_rope: jax.Array,
                     suf_c: jax.Array, suf_kr: jax.Array,
                     suf_valid: jax.Array, *, scale: float) -> jax.Array:
    """Fold a (B, S, L)+(B, S, R) latent suffix in; normalise.

    ``suf_valid`` is (B, T, S) bool. Returns (B, T, H, L) f32 latent
    context (caller applies w_uv).
    """
    def row(accr, mr, lr, qer, qrr, cr, krr, validr):
        cz = jnp.where(jnp.any(validr, axis=0)[:, None], cr, 0)
        czf = cz.astype(jnp.float32)
        krf = jnp.where(jnp.any(validr, axis=0)[:, None], krr,
                        0).astype(jnp.float32)
        s = (jnp.einsum("thl,sl->hts", qer, czf)
             + jnp.einsum("thr,sr->hts", qrr, krf)) * scale
        vm = validr[None]                                  # (1, T, S)
        s = jnp.where(vm, s, NEG_INF)
        m_new = jnp.maximum(mr, jnp.max(s, axis=-1))
        p = jnp.where(vm, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(mr - m_new)
        l_new = lr * corr + jnp.sum(p, axis=-1)
        acc_new = accr * corr[..., None] + jnp.einsum("hts,sl->htl", p, czf)
        return acc_new / jnp.maximum(l_new[..., None], 1e-30)  # (H,T,L)

    out = jax.vmap(row)(acc, m, l, q_eff.astype(jnp.float32),
                        q_rope.astype(jnp.float32), suf_c, suf_kr,
                        suf_valid)
    return jnp.transpose(out, (0, 2, 1, 3))                # (B,T,H,L)
