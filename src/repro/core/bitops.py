"""Bit-level manipulation of bfloat16 tensors.

bfloat16 layout (MSB..LSB): 1 sign | 8 exponent | 7 mantissa.

Cassandra partitions every bf16 value into bit fields so the draft model can
consume a *strict subset* of the target model's bits (sign + coded exponent +
high mantissa bits) while the dropped low mantissa bits are parked in the
verification data. Everything here is pure jnp and shape-preserving, so it
works under jit/pjit and inside Pallas reference oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SIGN_BITS = 1
EXP_BITS = 8
MANT_BITS = 7
EXP_BIAS = 127


def bf16_to_bits(x: jax.Array) -> jax.Array:
    """Bitcast bf16 -> uint16."""
    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.bfloat16)
    return jax.lax.bitcast_convert_type(x, jnp.uint16)


def bits_to_bf16(bits: jax.Array) -> jax.Array:
    """Bitcast uint16 -> bf16."""
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), jnp.bfloat16)


def split_fields(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split bf16 into (sign, exponent, mantissa) uint8 fields."""
    bits = bf16_to_bits(x).astype(jnp.uint32)
    sign = (bits >> 15) & 0x1
    exp = (bits >> 7) & 0xFF
    mant = bits & 0x7F
    return sign.astype(jnp.uint8), exp.astype(jnp.uint8), mant.astype(jnp.uint8)


def join_fields(sign: jax.Array, exp: jax.Array, mant: jax.Array) -> jax.Array:
    """Reassemble bf16 from (sign, exponent, mantissa) fields."""
    bits = (
        (sign.astype(jnp.uint32) << 15)
        | (exp.astype(jnp.uint32) << 7)
        | (mant.astype(jnp.uint32) & 0x7F)
    )
    return bits_to_bf16(bits.astype(jnp.uint16))


def truncate_mantissa(x: jax.Array, keep_bits: int) -> tuple[jax.Array, jax.Array]:
    """Split a bf16 tensor into (truncated_value, dropped_low_bits).

    ``truncated_value`` keeps only the top ``keep_bits`` of the 7 mantissa bits
    (low bits zeroed) — this is the draft-visible value. ``dropped_low_bits``
    is a uint8 tensor holding the (7-keep_bits) low mantissa bits — the
    verification payload. ``truncated | dropped == original`` bit-exactly.
    """
    if not 0 <= keep_bits <= MANT_BITS:
        raise ValueError(f"keep_bits must be in [0, {MANT_BITS}], got {keep_bits}")
    drop = MANT_BITS - keep_bits
    bits = bf16_to_bits(x).astype(jnp.uint32)
    low_mask = (1 << drop) - 1
    dropped = (bits & low_mask).astype(jnp.uint8)
    kept = bits & jnp.uint32(0xFFFF ^ low_mask)
    return bits_to_bf16(kept.astype(jnp.uint16)), dropped


def merge_mantissa(truncated: jax.Array, dropped_low_bits: jax.Array,
                   keep_bits: int) -> jax.Array:
    """Inverse of :func:`truncate_mantissa` — bit-exact reconstruction."""
    drop = MANT_BITS - keep_bits
    low_mask = (1 << drop) - 1
    bits = bf16_to_bits(truncated).astype(jnp.uint32)
    bits = bits | (dropped_low_bits.astype(jnp.uint32) & low_mask)
    return bits_to_bf16(bits.astype(jnp.uint16))


def pack_nibbles(vals: jax.Array) -> jax.Array:
    """Pack pairs of 4-bit values (uint8, last dim even) into uint8 bytes."""
    lo = vals[..., 0::2] & 0xF
    hi = vals[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles`."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], -1).astype(jnp.uint8)


def pack_codes(codes: jax.Array, width: int,
               n_bits: int | None = None) -> jax.Array:
    """Pack (..., K) integer codes of ``width`` bits each into uint32 words.

    ``n_bits`` (default: K*width rounded up to 32) fixes the region size so
    layouts stay static. Little-endian bit order within the region.
    """
    k = codes.shape[-1]
    if width == 0 or k == 0:
        return jnp.zeros((*codes.shape[:-1], 0), jnp.uint32)
    if n_bits is None:
        n_bits = ((k * width + 31) // 32) * 32
    shifts = jnp.arange(width, dtype=jnp.uint32)
    bits = (codes[..., None].astype(jnp.uint32) >> shifts) & 1
    flat = bits.reshape(*codes.shape[:-1], k * width).astype(jnp.bool_)
    pad = n_bits - k * width
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return pack_bits(flat)


def unpack_codes(words: jax.Array, width: int, k: int) -> jax.Array:
    """Inverse of :func:`pack_codes`; returns (..., K) uint32 codes.

    Decode arithmetic stays in uint8 for width <= 8 (all Cassandra code
    widths) — the unpack expansion is the dominant byte stream of the
    packed-KV decode path (§Perf iteration A3).
    """
    if width == 0 or k == 0:
        return jnp.zeros((*words.shape[:-1], k), jnp.uint32)
    bits = unpack_bits(words, words.shape[-1] * 32)
    sel = bits[..., : k * width].reshape(*bits.shape[:-1], k, width)
    if width <= 8:
        shifts = jnp.arange(width, dtype=jnp.uint8)
        out = jnp.sum(sel.astype(jnp.uint8) << shifts, axis=-1,
                      dtype=jnp.uint8)
        return out.astype(jnp.uint32)
    shifts = jnp.arange(width, dtype=jnp.uint32)
    return jnp.sum(sel.astype(jnp.uint32) << shifts, axis=-1).astype(jnp.uint32)


def pack_bits(bools: jax.Array) -> jax.Array:
    """Pack a boolean array (last dim multiple of 32) into uint32 words.

    Bit i of word w corresponds to element w*32+i (little-endian bit order).
    """
    *lead, n = bools.shape
    if n % 32 != 0:
        raise ValueError(f"last dim must be a multiple of 32, got {n}")
    b = bools.astype(jnp.uint32).reshape(*lead, n // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1).astype(jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits`; returns bool array with last dim ``n``.

    Words are byte-split first so the shift expansion runs in uint8 —
    4x smaller intermediates than shifting uint32 lanes (§Perf A3).
    """
    bytes_ = jax.lax.bitcast_convert_type(words, jnp.uint8)  # (..., W, 4)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (bytes_[..., None] >> shifts) & jnp.uint8(1)
    out = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return out[..., :n].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# Gather/scatter-free positional primitives. On a TPU, XLA runs gathers,
# scatters and sorts with per-element indices far slower than elementwise
# work; the format's encode and decode use these instead.
# ---------------------------------------------------------------------------

def _select_bit(word: jax.Array, m: jax.Array) -> jax.Array:
    """Bit index of the m-th (0-indexed) set bit of each uint32 ``word``:
    a 5-step binary search on popcounts of the low half-window."""
    pos = jnp.zeros(m.shape, jnp.int32)
    for half in (16, 8, 4, 2, 1):
        low = word & jnp.uint32((1 << half) - 1)
        cnt = jax.lax.population_count(low).astype(jnp.int32)
        high = m >= cnt
        m = jnp.where(high, m - cnt, m)
        word = jnp.where(high, word >> half, low)
        pos = pos + jnp.where(high, half, 0)
    return pos


def one_positions(words: jax.Array, k: int) -> jax.Array:
    """Bit positions (..., k) int32 of the first ``k`` set bits of packed
    (..., W) uint32 words (little-endian). Entries past the last set bit
    are unspecified."""
    n_words = words.shape[-1]
    ends = jnp.cumsum(jax.lax.population_count(words).astype(jnp.int32),
                      axis=-1)                          # set bits through word w
    j = jnp.arange(k, dtype=jnp.int32)
    word_idx = jnp.zeros((*words.shape[:-1], k), jnp.int32)
    for w in range(n_words - 1):
        word_idx = word_idx + (ends[..., w:w + 1] <= j)
    before = jnp.zeros_like(word_idx)
    word = jnp.zeros(word_idx.shape, jnp.uint32)
    for w in range(n_words):
        here = word_idx == w
        if w:
            before = jnp.where(here, ends[..., w - 1:w], before)
        word = jnp.where(here, words[..., w:w + 1], word)
    return 32 * word_idx + _select_bit(word, j - before)


def _shift_last(x: jax.Array, s: int) -> jax.Array:
    """Shift along the last axis by ``s`` (>0: toward higher indices),
    filling with zeros."""
    pad = jnp.zeros((*x.shape[:-1], abs(s)), x.dtype)
    if s > 0:
        return jnp.concatenate([pad, x[..., :-s]], axis=-1)
    return jnp.concatenate([x[..., -s:], pad], axis=-1)


def _route(x: jax.Array, dist: jax.Array, live: jax.Array, sign: int,
           steps) -> jax.Array:
    """Move each live element of (..., n) ``x`` by ``sign * dist`` lanes
    with a log-step shift network, one shift per bit of ``dist``; lanes
    left empty read 0. Collision-free when the moving elements keep their
    order and their distances are nondecreasing along them — compaction
    (LSB step first) and its inverse, expansion (MSB step first)."""
    for b in steps:
        mv = live & (((dist >> b) & 1) == 1)
        arrive = _shift_last(mv, sign << b)
        x = jnp.where(arrive, _shift_last(x, sign << b), x)
        dist = jnp.where(arrive, _shift_last(dist, sign << b), dist)
        live = arrive | (live & ~mv)
    return jnp.where(live, x, jnp.zeros((), x.dtype))


def compact(x: jax.Array, mask: jax.Array, k: int) -> jax.Array:
    """The first ``k`` elements of (..., n) ``x`` where ``mask``, in order —
    ``take_along_axis(x, argsort(~mask, stable=True))[..., :k]`` for masks
    with at least k set entries."""
    n = x.shape[-1]
    zeros_before = jnp.cumsum(~mask, axis=-1, dtype=jnp.int32)
    out = _route(jnp.where(mask, x, jnp.zeros((), x.dtype)),
                 jnp.where(mask, zeros_before, 0), mask, -1,
                 range(max(n - 1, 1).bit_length()))
    return out[..., :k]


def expand(vals: jax.Array, pos: jax.Array, n: int) -> jax.Array:
    """Scatter (..., k) ``vals`` to strictly increasing positions (..., k)
    ``pos`` of a zero (..., n) array — the inverse of :func:`compact`."""
    k = vals.shape[-1]
    if k == n:
        return vals
    lead = vals.shape[:-1]
    x = jnp.concatenate([vals, jnp.zeros((*lead, n - k), vals.dtype)], -1)
    dist = jnp.concatenate(
        [pos - jnp.arange(k, dtype=jnp.int32),
         jnp.zeros((*lead, n - k), jnp.int32)], -1)
    live = jnp.broadcast_to(jnp.arange(n) < k, x.shape)
    return _route(x, dist, live, 1, range((n - k).bit_length())[::-1])


def lookup256(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a table of at most 256 entries in [0, 256) and
    int indices in [0, 256), as two one-hot matmuls (exact: one nonzero
    bf16 integer term per output). Returns int32."""
    t = jnp.zeros(256, jnp.float32).at[:table.shape[0]].set(
        table.astype(jnp.float32)).reshape(16, 16).astype(jnp.bfloat16)
    i = idx.astype(jnp.int32)
    hi = jax.nn.one_hot(i >> 4, 16, dtype=jnp.bfloat16)
    lo = jax.nn.one_hot(i & 15, 16, dtype=jnp.bfloat16)
    rows = jnp.dot(hi, t, preferred_element_type=jnp.float32)
    return jnp.sum(rows * lo, axis=-1).astype(jnp.int32)


def histogram256(x: jax.Array) -> jax.Array:
    """Counts (256,) int32 of the uint8 values in ``x`` — ``bincount`` as
    one-hot matmuls over (high, low) nibble pairs, in chunks small enough
    that each f32 partial count is exact."""
    i = x.reshape(-1).astype(jnp.int32)
    chunk = min(1 << 22, max(i.shape[0], 1))
    i = jnp.pad(i, (0, -i.shape[0] % chunk), constant_values=-1)
    i = i.reshape(-1, chunk)                # padding -1 one-hots to zeros
    hi = jax.nn.one_hot(i >> 4, 16, dtype=jnp.bfloat16)
    lo = jax.nn.one_hot(jnp.where(i < 0, -1, i & 15), 16,
                        dtype=jnp.bfloat16)
    counts = jnp.einsum("cna,cnb->cab", hi, lo,
                        preferred_element_type=jnp.float32)
    return jnp.sum(counts.astype(jnp.int32), axis=0).reshape(256)
