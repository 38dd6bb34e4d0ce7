"""Fused Cassandra-decode + matmul Pallas kernel — the paper's decoder on
the TPU memory path.

``y = x @ draft_weight(spec)`` where the weight never exists densely in
HBM: each grid step streams one packed superblock tile (bitmap + 4-bit
sign|mant codes + 3-bit exponent rank codes) HBM→VMEM, reconstructs the
bf16 tile on the VPU, and feeds the MXU dot. HBM traffic is the *packed*
bytes (~5.4 bits/value at the paper defaults vs 16 bf16) — exactly the
paper's bandwidth win, with the VMEM reconstruction replacing the ASIC
decoder between DRAM and L2.

TPU adaptation of the exponent stream: the kernel consumes a fixed 3-bit
frequency-*rank* code per value (escape → block-max exponent) prepared
offline from the unary stream by ``ops.prepare_draft_operands``. Byte count
is identical to the unary region (the static-superblock budget is
``exp_bits``/value either way); decode becomes 8 vector selects instead of
a bit-serial scan. The paper-faithful unary decoder (parallel zero counter,
Alg. 1) lives in ``unary_decode.py`` and is used on the KV path.

Bit unpacking is per-lane word selection plus shifts, the bitmap
de-sparsification (the paper's decoder step 5) a prefix-count matmul and a
128-lane-windowed gather — see ``kernels.unpack``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import unpack as U

MANT_BITS = 7


def _decode_tile(bitmap, signmant, exp3, emax, book, *, block, keep, trunc,
                 exp_bits):
    """Reconstruct a (TN, block) bf16 draft-weight tile from packed rows.

    ``emax`` is a (TN, 1) int32 column; ``book`` the 8-entry rank
    codebook (array or SMEM ref)."""
    t_keep = MANT_BITS - trunc
    esc = (1 << exp_bits) - 1
    # sign|mant codes, (TN, keep)
    code = U.unpack_fixed(signmant, 1 + t_keep, keep)
    sign = (code >> t_keep) & 1
    mant = (code & ((1 << t_keep) - 1)) << trunc
    # 3-bit exponent rank codes -> exponents via 8-entry codebook selects
    r3 = U.unpack_fixed(exp3, exp_bits, keep)
    exp = jnp.where(r3 == esc, emax, 0)
    for r in range(esc):
        exp = exp + jnp.where(r3 == r, book[r], 0)
    kept16 = (sign << 15) | (exp << 7) | mant             # (TN, keep) i32
    dense16 = U.desparsify(kept16, U.unpack_fixed(bitmap, 1, block))
    return jax.lax.bitcast_convert_type(dense16.astype(jnp.uint16),
                                        jnp.bfloat16)


def _kernel(book_ref, x_ref, bitmap_ref, sm_ref, exp3_ref, emax_ref, o_ref,
            *, block, keep, trunc, exp_bits):
    k_idx = pl.program_id(2)
    w_tile = _decode_tile(bitmap_ref[0], sm_ref[0], exp3_ref[0],
                          emax_ref[0], book_ref, block=block, keep=keep,
                          trunc=trunc, exp_bits=exp_bits)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_tile.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("block", "keep", "trunc", "exp_bits",
                                   "tm", "tn", "interpret"))
def draft_matmul(x: jax.Array, bitmap: jax.Array, signmant: jax.Array,
                 exp3: jax.Array, emax: jax.Array, book: jax.Array,
                 *, block: int, keep: int, trunc: int, exp_bits: int = 3,
                 tm: int = 128, tn: int = 128,
                 interpret: bool = False) -> jax.Array:
    """x (M, K) @ packed-draft-weight (K, N) -> (M, N) fp32.

    Operand layout (superblock-major, from ``ops.prepare_draft_operands``):
      bitmap (NB, N, block//32) u32 · signmant (NB, N, Wsm) u32 ·
      exp3 (NB, N, We) u32 · emax (NB, N, 1) i32 · book (8,) i32
    so every block's last two dims are (tn, full) — Mosaic's tiling rule.
    """
    m, k_in = x.shape
    nb, n = bitmap.shape[0], bitmap.shape[1]
    assert nb * block == k_in, (nb, block, k_in)
    tm, tn = min(tm, m), min(tn, n)

    def packed_spec(a):
        return pl.BlockSpec((1, tn, a.shape[-1]),
                            lambda i, j, k, *_: (k, j, 0))

    if interpret:
        mode = {"interpret": True}
    else:
        mode = {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    return pl.pallas_call(
        partial(_kernel, block=block, keep=keep, trunc=trunc,
                exp_bits=exp_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // tm, n // tn, nb),
            in_specs=[pl.BlockSpec((tm, block), lambda i, j, k, *_: (i, k)),
                      packed_spec(bitmap), packed_spec(signmant),
                      packed_spec(exp3), packed_spec(emax)],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        **mode,
    )(book, x, bitmap, signmant, exp3, emax)
