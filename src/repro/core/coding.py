"""Exponent compression codecs for the Cassandra format.

The paper stores exponents of the *speculation data* in one of two ways:

* **Cassandra-1** — lossless unary coding over frequency-ranked exponent
  values (Fig. 5/6, Alg. 1). Every codeword is ``rank`` zeros followed by a
  terminating ``1``; more frequent exponents get shorter codes (avg ~2.85
  bits).
* **Cassandra-2** — MX shared-exponent groups (see :mod:`repro.core.mx`).

TPU adaptation (see DESIGN.md §2): XLA needs static shapes, so each
superblock gets a fixed exponent region of ``exp_bits`` bits per kept value
(default 3). A per-block 1-bit mode selects the representation inside that
region:

* ``mode 0`` — the paper's unary stream (bit-exact). Chosen when every rank
  is < 32 and the stream fits in the region, which holds for virtually every
  block of real weight/KV data (measured in benchmarks/entropy.py).
* ``mode 1`` — ``exp_bits``-wide delta from the per-block max exponent
  (draft-side approximation; the escape value reconstructs exact zero). A
  4-bit *correction* nibble on the verification side restores bit-exactness
  for any value within ``2^(2^exp_bits - 2 + 14)`` dynamic range of its block
  max — far beyond anything observed in real tensors.

Decoding mode 0 is the vectorised form of the paper's parallel zero counter:
the positions of the ``1`` bits are recovered from per-word popcounts, and
``rank_j = pos_j - pos_{j-1} - 1``.

All functions operate on blocked tensors ``(..., NB, K)`` (NB superblocks of
K kept exponents each) and are jit-safe.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitops

MAX_RANK = 32  # paper: ~32 unique exponent symbols; unary code len <= 32
CORR_BITS = 4


def region_words(k: int, exp_bits: int) -> int:
    """uint32 words of the per-block exponent region (static)."""
    return (k * exp_bits + 31) // 32


# ---------------------------------------------------------------------------
# Codebook (frequency-ranked exponent symbols)
# ---------------------------------------------------------------------------

def build_codebook(exps: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Frequency-ranked codebook over 8-bit exponent symbols.

    Returns ``(exp_of_rank[256], rank_of_exp[256])`` — rank 0 is the most
    frequent exponent. Ranks beyond the observed alphabet map past MAX_RANK
    so the encoder falls back to delta mode for blocks containing them.
    """
    return codebook_from_counts(bitops.histogram256(exps))


def codebook_from_counts(counts: jax.Array) -> tuple[jax.Array, jax.Array]:
    """:func:`build_codebook` from the exponents' (256,) histogram, so a
    tensor's book can be summed block by block."""
    order = jnp.argsort(-counts, stable=True)  # descending frequency
    exp_of_rank = order.astype(jnp.uint8)
    rank_of_exp = jnp.zeros(256, dtype=jnp.int32).at[order].set(jnp.arange(256))
    # exponents that never occur: force them past MAX_RANK
    rank_of_exp = jnp.where(counts[jnp.arange(256)] > 0, rank_of_exp, 255)
    return exp_of_rank, rank_of_exp.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Mode 0: unary coding (paper-faithful, lossless)
# ---------------------------------------------------------------------------

def unary_encode_block(ranks: jax.Array,
                       n_bits: int) -> tuple[jax.Array, jax.Array]:
    """Encode ranks (..., K) into a unary bitstream (..., n_bits) of bools.

    Returns ``(bits, ok)`` where ``ok`` marks blocks whose stream fits in the
    region AND whose ranks are all < MAX_RANK.
    """
    lens = ranks.astype(jnp.int32) + 1
    # position of each code's terminating 1
    ends = jnp.cumsum(lens, axis=-1) - 1
    total = ends[..., -1] + 1
    ok = (total <= n_bits) & jnp.all(ranks < MAX_RANK, axis=-1)
    # 1s at `ends` (clipped; invalid blocks are discarded by `ok`)
    pos = jnp.clip(ends, 0, n_bits - 1)
    bits = bitops.expand(jnp.ones(ranks.shape, jnp.bool_), pos, n_bits)
    return bits, ok


def unary_decode_words(words: jax.Array, k: int) -> jax.Array:
    """Decode a packed unary region (..., W) uint32 into ranks (..., K).

    Vectorised parallel-zero-counter (paper Alg. 1): ``pos_j``, the bit
    position of the (j+1)-th ``1``, comes from word popcounts
    (``bitops.one_positions``), and ``rank_j = pos_j - pos_{j-1} - 1``.
    Exact for every stream the encoder marks ``ok``; other streams
    (delta-mode blocks) decode to unused values.
    """
    pos = bitops.one_positions(words, k)
    prev = jnp.concatenate(
        [jnp.full((*pos.shape[:-1], 1), -1, pos.dtype), pos[..., :-1]],
        axis=-1)
    return jnp.clip(pos - prev - 1, 0, MAX_RANK - 1).astype(jnp.uint8)


def unary_decode_block(bits: jax.Array, k: int) -> jax.Array:
    """Decode a unary bitstream (..., n_bits) of bools into ranks (..., K)
    (``n_bits`` a multiple of 32); see :func:`unary_decode_words`."""
    return unary_decode_words(bitops.pack_bits(bits), k)


# ---------------------------------------------------------------------------
# Mode 1: delta-from-block-max (static width, draft-approximate)
# ---------------------------------------------------------------------------

def delta_encode_block(exps: jax.Array, emax: jax.Array, exp_bits: int,
                       corr_bits: int = CORR_BITS
                       ) -> tuple[jax.Array, jax.Array]:
    """Delta-code exps (..., K) against emax (...,). Returns (codes, corr).

    ``codes`` are ``exp_bits``-wide: clamp(emax-e, 0, esc-1), with the escape
    value ``esc = 2**exp_bits - 1`` marking e == 0 (exact zero/denormal).
    ``corr`` is the verification correction (``corr_bits`` wide): the
    remaining delta beyond the code's range, clamped to 2^corr_bits - 2
    (2^corr_bits - 1 = zero sentinel). ``corr_bits=8`` makes the correction
    exact for any bf16 exponent gap (online KV encode uses this).
    """
    esc = (1 << exp_bits) - 1
    cmax = (1 << corr_bits) - 1
    delta = emax[..., None].astype(jnp.int32) - exps.astype(jnp.int32)
    code = jnp.clip(delta, 0, esc - 1)
    code = jnp.where(exps == 0, esc, code)
    corr = jnp.clip(delta - code, 0, cmax - 1)
    corr = jnp.where(exps == 0, cmax, corr)
    return code.astype(jnp.uint8), corr.astype(jnp.uint8)


def delta_decode_block(codes: jax.Array, emax: jax.Array, exp_bits: int,
                       corr: jax.Array | None = None,
                       corr_bits: int = CORR_BITS) -> jax.Array:
    """Inverse of :func:`delta_encode_block` (draft view if corr is None)."""
    esc = (1 << exp_bits) - 1
    cmax = (1 << corr_bits) - 1
    delta = codes.astype(jnp.int32)
    if corr is not None:
        delta = delta + jnp.where(corr == cmax, 0, corr.astype(jnp.int32))
    e = emax[..., None].astype(jnp.int32) - delta
    e = jnp.clip(e, 0, 255)
    zero = (codes == esc) if corr is None else ((codes == esc) & (corr == cmax))
    return jnp.where(zero, 0, e).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Packed region codec (mode dispatch)
# ---------------------------------------------------------------------------

def _pack_fixed(codes: jax.Array, exp_bits: int, n_bits: int) -> jax.Array:
    """Pack (..., K) codes of exp_bits each into a (..., n_bits) bool array."""
    k = codes.shape[-1]
    shifts = jnp.arange(exp_bits, dtype=jnp.uint32)
    bits = (codes[..., None].astype(jnp.uint32) >> shifts) & 1
    flat = bits.reshape(*codes.shape[:-1], k * exp_bits).astype(jnp.bool_)
    pad = n_bits - k * exp_bits
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return flat


def trim_codebook(exp_of_rank: jax.Array) -> jax.Array:
    """Keep only the MAX_RANK entries the unary decoder can address."""
    return exp_of_rank[:MAX_RANK]


@partial(jax.jit, static_argnames=("exp_bits", "corr_bits"))
def encode_exponents(exps: jax.Array, rank_of_exp: jax.Array, exp_bits: int = 3,
                     corr_bits: int = CORR_BITS) -> dict[str, jax.Array]:
    """Encode blocked exponents (..., NB, K) into the packed spec region.

    Returns dict with:
      ``words``  (..., NB, region_words)  uint32 packed region
      ``mode``   (..., NB)                uint8  0=unary 1=delta
      ``emax``   (..., NB)                uint8  per-block max exponent
      ``corr``   (..., NB, K//2 or K)     uint8  verification corrections
                 (nibble-packed for corr_bits=4, raw bytes for corr_bits=8)
    """
    k = exps.shape[-1]
    n_bits = region_words(k, exp_bits) * 32
    ranks = bitops.lookup256(rank_of_exp, exps)
    ubits, ok = unary_encode_block(ranks, n_bits)
    emax = jnp.max(exps, axis=-1)
    dcodes, dcorr = delta_encode_block(exps, emax, exp_bits, corr_bits)
    dbits = _pack_fixed(dcodes, exp_bits, n_bits)
    mode = jnp.where(ok, 0, 1).astype(jnp.uint8)
    bits = jnp.where(ok[..., None], ubits, dbits)
    corr = jnp.where(ok[..., None], 0, dcorr).astype(jnp.uint8)
    return {
        "words": bitops.pack_bits(bits),
        "mode": mode,
        "emax": emax.astype(jnp.uint8),
        "corr": bitops.pack_nibbles(corr) if corr_bits == 4 else corr,
    }


@partial(jax.jit, static_argnames=("exp_bits", "k", "exact", "corr_bits"))
def decode_exponents(region: dict[str, jax.Array], exp_of_rank: jax.Array,
                     k: int, exp_bits: int = 3, exact: bool = False,
                     corr_bits: int = CORR_BITS) -> jax.Array:
    """Decode the packed spec region back to exponents (..., NB, K).

    ``exact=False`` is the draft view (speculation data only); ``exact=True``
    additionally applies the verification corrections.
    """
    uranks = unary_decode_words(region["words"], k)
    uexps = bitops.lookup256(exp_of_rank, uranks)
    dcodes = bitops.unpack_codes(region["words"], exp_bits, k)
    corr = None
    if exact and region.get("corr") is not None:
        # corr may have been trimmed away when every block is mode-0 (unary
        # is bit-exact without correction) — see format._trim_lossless.
        if corr_bits == 4:
            corr = bitops.unpack_nibbles(region["corr"])[..., :k]
        else:
            corr = region["corr"][..., :k]
    dexps = delta_decode_block(dcodes, region["emax"], exp_bits, corr=corr,
                               corr_bits=corr_bits)
    is_unary = (region["mode"] == 0)[..., None]
    return jnp.where(is_unary, uexps, dexps).astype(jnp.uint8)


def avg_code_bits(exps: jax.Array, rank_of_exp: jax.Array) -> jax.Array:
    """Average unary code length (bits/value) — reproduces Fig. 6(b)."""
    ranks = rank_of_exp[exps.reshape(-1).astype(jnp.int32)].astype(jnp.float32)
    return jnp.mean(jnp.minimum(ranks, MAX_RANK - 1) + 1.0)


def shannon_entropy(exps: jax.Array) -> jax.Array:
    """Shannon entropy (bits) of the exponent distribution — Fig. 6(a)."""
    counts = jnp.bincount(exps.reshape(-1).astype(jnp.int32), length=256)
    p = counts / jnp.maximum(jnp.sum(counts), 1)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.where(p > 0, p, 1.0)), 0.0))
