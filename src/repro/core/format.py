"""The Cassandra format: partition tensors into speculation + verification data.

This is the paper's core contribution (Fig. 4). A bf16 tensor is transformed
once into two packed pytrees:

* **speculation data** — what the draft model reads: bitmap (pruning mask),
  packed ``sign|mant_hi`` codes, and compressed exponents (unary/delta for
  Cassandra-1, MX shared-exponent for Cassandra-2).
* **verification data** — everything else: the pruned values (with their own
  entropy-coded exponents in Cassandra-1 — this is why the total footprint is
  *below* the bf16 baseline, Fig. 14), the dropped mantissa low bits of kept
  values, and exponent-correction nibbles.

``draft_*`` reconstructs the zero-padded draft view from speculation data
alone; ``target_*`` reconstructs the full tensor from both (bit-exact for
Cassandra-1, MX-container-exact for Cassandra-2).

Weights are blocked along their *input* (reduction) dimension, per output
column — so de-sparsification aligns with MXU matmul tiles. KV vectors are
blocked per (token, head) — the paper's per-token pruning.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import bitops, coding, mx, pruning

PyTree = Any


@dataclasses.dataclass(frozen=True)
class CassandraConfig:
    """Hyper-parameters of the format (paper defaults: 40% prune, 4-bit trunc)."""
    variant: int = 1              # 1 = unary/lossless, 2 = MX
    weight_prune: float = 0.4
    kv_prune: float = 0.4
    weight_trunc: int = 4         # mantissa bits dropped from the draft view
    kv_trunc: int = 4
    exp_bits: int = 3             # C-1 spec exponent region width (bits/value)
    mx_group: int = 32            # C-2 shared-exponent group (16 for KV)
    mx_draft_bits: int = 4        # C-2 draft mantissa bits
    gamma: int = 5                # draft length
    max_block: int = 512          # weight superblock (auto-shrunk to divide dims)

    def weight_keep(self, block: int) -> int:
        return pruning.keep_count(block, self.weight_prune,
                                  pruning.WEIGHT_KEEP_MULTIPLE)

    def kv_keep(self, block: int) -> int:
        return pruning.keep_count(block, self.kv_prune,
                                  pruning.KV_KEEP_MULTIPLE)

    def weight_block(self, n_in: int) -> int:
        for b in (self.max_block, 256, 128, 64, 32):
            if n_in % b == 0:
                return b
        raise ValueError(f"input dim {n_in} not divisible by any block size")


PAPER_DEFAULT = CassandraConfig()


# ---------------------------------------------------------------------------
# Shared partition machinery
# ---------------------------------------------------------------------------

def _split_kept(kept: jax.Array, trunc: int, variant: int, group: int,
                draft_bits: int) -> tuple[dict, dict]:
    """Split kept bf16 values (..., K) into draft/verification payloads."""
    t_keep = bitops.MANT_BITS - trunc     # mantissa bits visible to the draft
    if variant == 1:
        sign, exp, mant = bitops.split_fields(kept)
        mant_hi = (mant >> trunc).astype(jnp.uint32)
        mant_lo = (mant & ((1 << trunc) - 1)).astype(jnp.uint32)
        code = (sign.astype(jnp.uint32) << t_keep) | mant_hi
        spec = {"signmant": bitops.pack_codes(code, 1 + t_keep),
                "exp": exp}                # coded separately by the caller
        verif = {"mant_lo": bitops.pack_codes(mant_lo, trunc)}
        return spec, verif
    # Cassandra-2: MX
    enc = mx.mx_encode(kept, group=group)
    top = (enc["m16"].astype(jnp.uint32) >> (mx.CONTAINER_BITS - draft_bits))
    code = (enc["sign"].astype(jnp.uint32) << draft_bits) | top
    lo_bits = mx.CONTAINER_BITS - draft_bits
    m_lo = enc["m16"].astype(jnp.uint32) & ((1 << lo_bits) - 1)
    spec = {"signmant": bitops.pack_codes(code, 1 + draft_bits),
            "shared_exp": enc["shared_exp"]}
    verif = {"mant_lo": bitops.pack_codes(m_lo, lo_bits)}
    return spec, verif


def _join_kept_draft(spec: dict, k: int, trunc: int, variant: int, group: int,
                     draft_bits: int, exp_of_rank: jax.Array | None,
                     exp_bits: int, corr_bits: int = coding.CORR_BITS
                     ) -> jax.Array:
    """Reconstruct the draft view of kept values (low mantissa zeroed)."""
    t_keep = bitops.MANT_BITS - trunc
    if variant == 1:
        code = bitops.unpack_codes(spec["signmant"], 1 + t_keep, k)
        sign = (code >> t_keep) & 1
        mant = (code & ((1 << t_keep) - 1)) << trunc
        exp = coding.decode_exponents(
            {"words": spec["exp_words"], "mode": spec["exp_mode"],
             "emax": spec["exp_emax"], "corr": spec.get("exp_corr")},
            exp_of_rank, k, exp_bits, exact=False, corr_bits=corr_bits)
        return bitops.join_fields(sign.astype(jnp.uint8), exp,
                                  mant.astype(jnp.uint8))
    code = bitops.unpack_codes(spec["signmant"], 1 + draft_bits, k)
    sign = (code >> draft_bits) & 1
    m16 = (code & ((1 << draft_bits) - 1)) << (mx.CONTAINER_BITS - draft_bits)
    return mx.mx_decode({"sign": sign.astype(jnp.uint8),
                         "m16": m16.astype(jnp.uint16),
                         "shared_exp": spec["shared_exp"]}, group=group)


def _join_kept_target(spec: dict, verif: dict, k: int, trunc: int, variant: int,
                      group: int, draft_bits: int,
                      exp_of_rank: jax.Array | None, exp_bits: int,
                      corr_bits: int = coding.CORR_BITS) -> jax.Array:
    """Reconstruct kept values exactly (C-1) / MX-container-exactly (C-2)."""
    t_keep = bitops.MANT_BITS - trunc
    if variant == 1:
        code = bitops.unpack_codes(spec["signmant"], 1 + t_keep, k)
        sign = (code >> t_keep) & 1
        mant_hi = (code & ((1 << t_keep) - 1)) << trunc
        mant_lo = bitops.unpack_codes(verif["mant_lo"], trunc, k)
        exp = coding.decode_exponents(
            {"words": spec["exp_words"], "mode": spec["exp_mode"],
             "emax": spec["exp_emax"], "corr": verif.get("exp_corr")},
            exp_of_rank, k, exp_bits, exact=True, corr_bits=corr_bits)
        return bitops.join_fields(sign.astype(jnp.uint8), exp,
                                  (mant_hi | mant_lo).astype(jnp.uint8))
    code = bitops.unpack_codes(spec["signmant"], 1 + draft_bits, k)
    sign = (code >> draft_bits) & 1
    lo_bits = mx.CONTAINER_BITS - draft_bits
    m_hi = (code & ((1 << draft_bits) - 1)) << lo_bits
    m_lo = bitops.unpack_codes(verif["mant_lo"], lo_bits, k)
    return mx.mx_decode({"sign": sign.astype(jnp.uint8),
                         "m16": (m_hi | m_lo).astype(jnp.uint16),
                         "shared_exp": spec["shared_exp"]}, group=group)


# ---------------------------------------------------------------------------
# Tensor-level format
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "block", "keep", "group", "trunc",
                                   "corr_bits", "pruned_raw"))
def format_tensor(x: jax.Array, scores: jax.Array, cfg: CassandraConfig,
                  block: int, keep: int, group: int, trunc: int,
                  codebook: tuple[jax.Array, jax.Array] | None = None,
                  corr_bits: int = coding.CORR_BITS,
                  pruned_raw: bool = False,
                  pruned_codebook: tuple[jax.Array, jax.Array] | None = None,
                  ) -> tuple[dict, dict]:
    """Partition (..., N) bf16 into (speculation, verification) pytrees.

    Layout of the result (all static shapes):
      spec:  bitmap (...,NB,block//32)u32, signmant (...,NB,w)u32,
             C-1: exp_words/exp_mode/exp_emax (+ codebook), C-2: shared_exp
      verif: mant_lo (...,NB,w)u32, C-1: exp_corr, pruned_signmant,
             pruned exp region; C-2: pruned raw u16

    ``codebook`` — optional external (exp_of_rank, rank_of_exp) pair, used
    for online KV encoding where the codebook is cache-global and stationary
    (per-tensor books are built when None); it codes the pruned exponents
    too unless ``pruned_codebook`` is given. External books are not stored
    in the result. ``corr_bits=8`` guarantees exact
    reconstruction for arbitrary per-block exponent range. ``pruned_raw``
    stores pruned values as raw u16 even for Cassandra-1 (online KV path —
    skips entropy-coding the verification side).
    """
    x = x.astype(jnp.bfloat16)
    sel = pruning.select_topk_blocked(x, scores, keep, block)
    spec, verif = _split_kept(sel["kept"], trunc, cfg.variant, group,
                              cfg.mx_draft_bits)
    spec["bitmap"] = sel["bitmap"]
    if cfg.variant == 1:
        # entropy-code kept exponents
        _, kept_exp, _ = bitops.split_fields(sel["kept"])
        if codebook is None:
            exp_of_rank, rank_of_exp = coding.build_codebook(kept_exp)
            spec["codebook"] = coding.trim_codebook(exp_of_rank)
        else:
            exp_of_rank, rank_of_exp = codebook
        region = coding.encode_exponents(kept_exp, rank_of_exp, cfg.exp_bits,
                                         corr_bits)
        spec["exp_words"] = region["words"]
        spec["exp_mode"] = region["mode"]
        spec["exp_emax"] = region["emax"]
        verif["exp_corr"] = region["corr"]
        del spec["exp"]
        if keep == block:
            pass                             # nothing pruned — no payload
        elif pruned_raw:
            verif["pruned_raw"] = bitops.bf16_to_bits(sel["pruned"])
        else:
            # pruned values: sign+7mant byte + entropy-coded exps (Fig. 14)
            psign, pexp, pmant = bitops.split_fields(sel["pruned"])
            verif["pruned_signmant"] = ((psign.astype(jnp.uint8) << 7) | pmant)
            if codebook is None:
                p_of_rank, p_rank = coding.build_codebook(pexp)
                verif["pruned_codebook"] = coding.trim_codebook(p_of_rank)
            else:
                p_rank = (codebook if pruned_codebook is None
                          else pruned_codebook)[1]
            pregion = coding.encode_exponents(pexp, p_rank, cfg.exp_bits,
                                              corr_bits)
            verif["pruned_exp_words"] = pregion["words"]
            verif["pruned_exp_mode"] = pregion["mode"]
            verif["pruned_exp_emax"] = pregion["emax"]
            verif["pruned_exp_corr"] = pregion["corr"]
    else:
        verif["pruned_raw"] = bitops.bf16_to_bits(sel["pruned"])
    return spec, verif


@partial(jax.jit, static_argnames=("cfg", "block", "keep", "group", "trunc",
                                   "n", "corr_bits"))
def draft_tensor(spec: dict, cfg: CassandraConfig, block: int, keep: int,
                 group: int, trunc: int, n: int,
                 codebook: tuple[jax.Array, jax.Array] | None = None,
                 corr_bits: int = coding.CORR_BITS) -> jax.Array:
    """Draft view: kept values (truncated), zeros at pruned positions."""
    book = spec.get("codebook")
    if book is None and codebook is not None:
        book = codebook[0]
    kept = _join_kept_draft(spec, keep, trunc, cfg.variant, group,
                            cfg.mx_draft_bits, book, cfg.exp_bits, corr_bits)
    return pruning.desparsify(spec["bitmap"], kept, block)


@partial(jax.jit, static_argnames=("cfg", "block", "keep", "group", "trunc",
                                   "n", "corr_bits"))
def target_tensor(spec: dict, verif: dict, cfg: CassandraConfig, block: int,
                  keep: int, group: int, trunc: int, n: int,
                  codebook: tuple[jax.Array, jax.Array] | None = None,
                  corr_bits: int = coding.CORR_BITS) -> jax.Array:
    """Full reconstruction from speculation + verification data."""
    book = spec.get("codebook")
    if book is None and codebook is not None:
        book = codebook[0]
    kept = _join_kept_target(spec, verif, keep, trunc, cfg.variant, group,
                             cfg.mx_draft_bits, book, cfg.exp_bits, corr_bits)
    if keep == block:
        return pruning.desparsify(spec["bitmap"], kept, block)
    if cfg.variant == 1 and "pruned_raw" not in verif:
        pbook = verif.get("pruned_codebook")
        if pbook is None and codebook is not None:
            pbook = codebook[0]
        pcode = verif["pruned_signmant"].astype(jnp.uint32)
        pexp = coding.decode_exponents(
            {"words": verif["pruned_exp_words"], "mode": verif["pruned_exp_mode"],
             "emax": verif["pruned_exp_emax"],
             "corr": verif.get("pruned_exp_corr")},
            pbook, block - keep, cfg.exp_bits, exact=True, corr_bits=corr_bits)
        pruned = bitops.join_fields(((pcode >> 7) & 1).astype(jnp.uint8), pexp,
                                    (pcode & 0x7F).astype(jnp.uint8))
    else:
        pruned = bitops.bits_to_bf16(verif["pruned_raw"])
    return pruning.desparsify(spec["bitmap"], kept, block, pruned=pruned)


# ---------------------------------------------------------------------------
# Weight / KV entry points
# ---------------------------------------------------------------------------

def _trim_lossless(spec: dict, verif: dict, variant: int) -> tuple[dict, dict]:
    """Drop correction nibbles when every superblock is mode-0 (unary).

    Unary-coded exponents are bit-exact on their own; the 4-bit delta
    corrections only matter for overflowing (mode-1) blocks. Real weight/KV
    exponent distributions make mode-1 vanishingly rare (Fig. 6), so for
    whole tensors with no mode-1 block the corr arrays are pure overhead —
    trimming them is what puts the total footprint *below* bf16 (Fig. 14).
    Offline-only (concrete values; host sync). Online KV encode keeps corr.
    """
    if variant != 1:
        return spec, verif
    if not bool(jnp.any(spec["exp_mode"])):
        verif = {k: v for k, v in verif.items() if k != "exp_corr"}
    if "pruned_exp_mode" in verif and not bool(jnp.any(verif["pruned_exp_mode"])):
        verif = {k: v for k, v in verif.items() if k != "pruned_exp_corr"}
    return spec, verif


def format_weight(w: jax.Array, act_norm: jax.Array | None,
                  cfg: CassandraConfig) -> tuple[dict, dict]:
    """Format a (in, out) weight. Blocks along `in` per output column."""
    n_in = w.shape[0]
    block = cfg.weight_block(n_in)
    keep = cfg.weight_keep(block)
    wt = w.T  # (out, in): block along the reduction dim
    if act_norm is None:
        scores = jnp.abs(wt.astype(jnp.float32))
    else:
        scores = pruning.wanda_scores(w, act_norm).T
    spec, verif = format_tensor(wt, scores, cfg, block, keep, cfg.mx_group,
                                cfg.weight_trunc)
    return _trim_lossless(spec, verif, cfg.variant)


def draft_weight(spec: dict, cfg: CassandraConfig, shape: tuple[int, int]
                 ) -> jax.Array:
    n_in, n_out = shape
    block = cfg.weight_block(n_in)
    keep = cfg.weight_keep(block)
    wt = draft_tensor(spec, cfg, block, keep, cfg.mx_group, cfg.weight_trunc,
                      n_in)
    return wt.reshape(n_out, n_in).T


def target_weight(spec: dict, verif: dict, cfg: CassandraConfig,
                  shape: tuple[int, int]) -> jax.Array:
    n_in, n_out = shape
    block = cfg.weight_block(n_in)
    keep = cfg.weight_keep(block)
    wt = target_tensor(spec, verif, cfg, block, keep, cfg.mx_group,
                       cfg.weight_trunc, n_in)
    return wt.reshape(n_out, n_in).T


def kv_group(cfg: CassandraConfig, head_dim: int) -> int:
    g = min(16, cfg.mx_group)
    while head_dim % g != 0:
        g //= 2
    return g


def format_kv(kv: jax.Array, cfg: CassandraConfig) -> tuple[dict, dict]:
    """Format a (..., head_dim) KV tensor with per-token magnitude pruning."""
    d = kv.shape[-1]
    keep = cfg.kv_keep(d)
    scores = jnp.abs(kv.astype(jnp.float32))
    spec, verif = format_tensor(kv, scores, cfg, d, keep, kv_group(cfg, d),
                                cfg.kv_trunc)
    return _trim_lossless(spec, verif, cfg.variant)


def draft_kv(spec: dict, cfg: CassandraConfig, head_dim: int) -> jax.Array:
    keep = cfg.kv_keep(head_dim)
    return draft_tensor(spec, cfg, head_dim, keep, kv_group(cfg, head_dim),
                        cfg.kv_trunc, head_dim)


def target_kv(spec: dict, verif: dict, cfg: CassandraConfig,
              head_dim: int) -> jax.Array:
    keep = cfg.kv_keep(head_dim)
    return target_tensor(spec, verif, cfg, head_dim, keep,
                         kv_group(cfg, head_dim), cfg.kv_trunc, head_dim)


# ---------------------------------------------------------------------------
# Accounting (Fig. 14 / roofline inputs)
# ---------------------------------------------------------------------------

def tree_nbytes(tree: PyTree) -> int:
    """Total bytes of all leaves (works on arrays and ShapeDtypeStructs)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize for leaf in leaves)


def compression_summary(spec: dict, verif: dict, original_nbytes: int) -> dict:
    sb = tree_nbytes(spec)
    vb = tree_nbytes(verif)
    return {
        "spec_bytes": sb,
        "verif_bytes": vb,
        "total_bytes": sb + vb,
        "draft_ratio": sb / original_nbytes,
        "total_ratio": (sb + vb) / original_nbytes,
    }
