"""Bit-unpacking primitives shared by the Cassandra decode kernels.

Written for Mosaic (the TPU Pallas compiler) as well as for plain jnp, so
a kernel body and its jnp reference can call the same functions on the
same shapes. Every value is a 2-D ``(rows, lanes)`` array: Mosaic refuses
lane-splitting reshapes, has no ``cumsum`` and gathers along lanes only
within one 128-lane vreg, so

* fixed-width fields are pulled out of their uint32 words by selecting,
  per output lane, the word that holds the field and shifting it by a
  per-lane amount (:func:`unpack_fixed`);
* prefix counts are a matmul against a triangular 0/1 matrix
  (:func:`prefix_count`) — exact, since 0/1 products and sums below 2^24
  are exact in f32;
* de-sparsification gathers in 128-lane windows (:func:`desparsify`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def iota(shape: tuple[int, int], dim: int) -> jax.Array:
    """2-D int32 iota (Mosaic rejects 1-D iotas and 1-D mask reshapes)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def unpack_fixed(words: jax.Array, width: int, n: int) -> jax.Array:
    """(R, W) uint32 words -> (R, n) int32 fields of ``width`` bits.

    Field ``i`` occupies bits ``[width*i, width*(i+1))`` of the
    little-endian bit stream (``bitops.pack_codes`` layout); a field may
    straddle two words when ``width`` does not divide 32.
    """
    r, n_words = words.shape
    lane = iota((r, n), 1)
    off = lane * width
    word = off >> 5
    shift = off & 31

    def select(idx):
        out = jnp.zeros((r, n), jnp.uint32)
        for w in range(n_words):
            out = jnp.where(idx == w, words[:, w:w + 1], out)
        return out

    val = select(word) >> shift.astype(jnp.uint32)
    if 32 % width:
        straddle = shift + width > 32
        hi_shift = jnp.where(straddle, 32 - shift, 0).astype(jnp.uint32)
        hi = select(word + 1) << hi_shift
        val = val | jnp.where(straddle, hi, jnp.uint32(0))
    return (val & jnp.uint32((1 << width) - 1)).astype(jnp.int32)


def prefix_count(bits: jax.Array) -> jax.Array:
    """Inclusive prefix sum of (R, n) 0/1 int32 bits along lanes."""
    n = bits.shape[1]
    tri = (iota((n, n), 0) <= iota((n, n), 1)).astype(jnp.bfloat16)
    return jnp.dot(bits.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def desparsify(kept: jax.Array, bits: jax.Array) -> jax.Array:
    """Scatter (R, K) kept values to the set positions of (R, n) bits.

    Position ``p`` with ``bits[p] == 1`` takes ``kept[rank(p)]`` (its
    rank among set bits); the rest are 0 — ``pruning.desparsify``'s
    semantics for one row block. The gather runs per 128-lane window of
    ``kept`` so each gather reads a single vreg.
    """
    r, k = kept.shape
    n = bits.shape[1]
    rank = jnp.clip(prefix_count(bits) - 1, 0, k - 1)
    n_src = -(-k // LANES)
    if n_src * LANES > k:
        kept = jnp.concatenate(
            [kept, jnp.zeros((r, n_src * LANES - k), kept.dtype)], axis=1)
    chunks = []
    for c0 in range(0, n, LANES):
        rk = rank[:, c0:c0 + LANES]
        out = jnp.zeros(rk.shape, kept.dtype)
        for s in range(n_src):
            src = kept[:, s * LANES:(s + 1) * LANES]
            idx = jnp.clip(rk - s * LANES, 0, LANES - 1)
            g = jnp.take_along_axis(src, idx, axis=1)
            out = jnp.where(rk // LANES == s, g, out)
        chunks.append(out)
    dense = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, 1)
    return jnp.where(bits == 1, dense, 0)
