"""Salience scoring and fixed-count per-superblock selection.

Weights use Wanda scores (|W| * per-input-channel activation L2 norm,
computed from a small calibration set — no training, ~128 samples per the
paper). The KV cache uses per-token magnitude scores (Mustafar): for each
token's key/value vector, the largest-magnitude entries survive.

TPU adaptation: instead of a global unstructured top-k (ragged), we keep a
*fixed count* per superblock (512 values for weights, head_dim for KV),
rounded to a multiple of 32 (weights) / 16 (KV) so bitmaps, nibble packing
and MX groups stay word-aligned. This is strictly finer-grained than the
structured pruning the paper argues against, and keeps every MXU tile's
de-sparsification work identical (see DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitops

WEIGHT_BLOCK = 512
WEIGHT_KEEP_MULTIPLE = 32
KV_KEEP_MULTIPLE = 16


def keep_count(block: int, prune_ratio: float, multiple: int) -> int:
    """Static keep count for a block: round((1-p)*block) to a multiple."""
    k = int(round(block * (1.0 - prune_ratio) / multiple)) * multiple
    return max(multiple, min(block, k))


def wanda_scores(w: jax.Array, act_norm: jax.Array) -> jax.Array:
    """Wanda importance: |W[i,j]| * ||act_i||_2, w is (in, out)."""
    return jnp.abs(w.astype(jnp.float32)) * act_norm.astype(jnp.float32)[:, None]


def calibration_act_norm(acts: jax.Array) -> jax.Array:
    """Per-input-channel L2 norm over a calibration batch (tokens, in)."""
    return jnp.sqrt(jnp.sum(jnp.square(acts.astype(jnp.float32)), axis=0))


@partial(jax.jit, static_argnames=("keep", "block"))
def select_topk_blocked(values: jax.Array, scores: jax.Array, keep: int,
                        block: int) -> dict[str, jax.Array]:
    """Partition a (..., N) tensor into kept/pruned per block of ``block``.

    Returns dict with
      ``bitmap``    (..., NB, block//32) uint32 — 1 bits mark kept positions
      ``kept``      (..., NB, keep)   values at kept positions (ordered by
                    position within the block — vital: de-sparsification is a
                    pure prefix-sum scatter, no index list needed)
      ``pruned``    (..., NB, block-keep) values at pruned positions
    """
    n = values.shape[-1]
    if n % block != 0:
        raise ValueError(f"last dim {n} not divisible by block {block}")
    nb = n // block
    v = values.reshape(*values.shape[:-1], nb, block)
    s = scores.reshape(*scores.shape[:-1], nb, block).astype(jnp.float32)
    kth = _kth_largest(s, keep)                              # (..., NB, 1)
    # break ties by position: among score==kth keep the earliest so the
    # total kept count is exactly `keep`
    ge = s > kth
    eq = s == kth
    n_ge = jnp.sum(ge, axis=-1, keepdims=True)
    eq_rank = jnp.cumsum(eq, axis=-1) - 1
    take_eq = eq & (eq_rank < (keep - n_ge))
    mask = ge | take_eq                                      # exactly keep ones
    bitmap = bitops.pack_bits(mask).astype(jnp.uint32)
    # stable compaction: kept values in position order
    return {"bitmap": bitmap, "kept": bitops.compact(v, mask, keep),
            "pruned": bitops.compact(v, ~mask, block - keep)}


def _kth_largest(s: jax.Array, k: int) -> jax.Array:
    """(..., n) non-negative f32 -> (..., 1) k-th largest value.

    Non-negative floats order like their bit patterns, so a 31-step
    binary search on the pattern (counting entries at or above each
    candidate) finds it with elementwise work — no sort, which is slow on
    a TPU."""
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    t = jnp.zeros((*s.shape[:-1], 1), jnp.int32)
    for b in range(30, -1, -1):
        cand = t | (1 << b)
        enough = jnp.sum(bits >= cand, axis=-1, keepdims=True) >= k
        t = jnp.where(enough, cand, t)
    return jax.lax.bitcast_convert_type(t, jnp.float32)


@partial(jax.jit, static_argnames=("block",))
def desparsify(bitmap: jax.Array, kept: jax.Array, block: int,
               pruned: jax.Array | None = None) -> jax.Array:
    """Scatter kept (and optionally pruned) values back to dense (..., NB*block).

    Bitmap-based de-sparsification (paper decoder step 5): position i takes
    kept[rank_i] where rank_i is the prefix-sum of the bitmap — zeros (or
    pruned values) elsewhere.
    """
    if pruned is not None and pruned.shape[-1] == 0:
        pruned = None                       # keep == block: nothing pruned
    mask = bitops.unpack_bits(bitmap, block)                  # (..., NB, block)
    dense = bitops.expand(kept, bitops.one_positions(bitmap, kept.shape[-1]),
                          block)
    if pruned is None:
        dense = jnp.where(mask, dense, jnp.zeros_like(dense))
    else:
        pdense = bitops.expand(
            pruned, bitops.one_positions(~bitmap, pruned.shape[-1]), block)
        dense = jnp.where(mask, dense, pdense)
    return dense.reshape(*dense.shape[:-2], -1)
