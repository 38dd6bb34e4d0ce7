"""Jit'd wrappers dispatching Pallas kernels (TPU) / interpret (CI) / jnp.

``prepare_draft_operands`` converts a Cassandra-1 spec into the kernel's
operand layout once at weight-load time: the unary/delta exponent region
becomes a byte-identical fixed 3-bit frequency-rank code (escape → block
max). Values whose exponent rank ≥ 7 (rare among magnitude-kept values)
decode to the block-max exponent — the "Cassandra-1T" kernel variant; the
deviation is measured in tests/test_kernels.py and benchmarks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitops, coding
from repro.core.format import CassandraConfig
from repro.kernels import draft_matmul as DM
from repro.kernels import kv_topk as KT
from repro.kernels import mx_decode as MX
from repro.kernels import unary_decode as UD

ESC = 7


def _tile(n: int, target: int = 128) -> int:
    t = min(target, n)
    while n % t:
        t -= 1
    return t


@partial(jax.jit, static_argnames=("cass", "shape"))
def prepare_draft_operands(spec: dict, cass: CassandraConfig,
                           shape: tuple[int, int]) -> dict:
    """Repack a C-1 spec into kernel operands (same total bytes)."""
    n_in, n_out = shape
    block = cass.weight_block(n_in)
    keep = cass.weight_keep(block)
    book32 = spec["codebook"]
    exps = coding.decode_exponents(
        {"words": spec["exp_words"], "mode": spec["exp_mode"],
         "emax": spec["exp_emax"], "corr": None},
        book32, keep, cass.exp_bits, exact=False)          # (N, NB, K) u8
    code3 = jnp.full(exps.shape, ESC, jnp.uint32)
    for r in range(ESC):
        code3 = jnp.where(exps == book32[r], jnp.uint32(r), code3)
    # escape decodes to emax — keep exact when the value IS emax.
    # Superblock-major (NB, N, ...) so a kernel block is (1, tn, full).
    def nb_major(a):
        return jnp.swapaxes(a, 0, 1)

    return {
        "bitmap": nb_major(spec["bitmap"]),
        "signmant": nb_major(spec["signmant"]),
        "exp3": nb_major(bitops.pack_codes(code3, cass.exp_bits)),
        "emax": nb_major(spec["exp_emax"].astype(jnp.int32))[..., None],
        "book": jnp.pad(book32[:ESC].astype(jnp.int32), (0, 8 - ESC)),
    }


def draft_matmul(x: jax.Array, spec: dict, cass: CassandraConfig,
                 shape: tuple[int, int], interpret: bool = False
                 ) -> jax.Array:
    """x (..., K) @ draft weight — fused decode+matmul kernel (C-1 only)."""
    if cass.variant != 1:
        raise NotImplementedError(
            f"the fused draft matmul decodes Cassandra-1 only, not "
            f"variant {cass.variant}; use the jnp path (kernels='jnp')")
    n_in, n_out = shape
    block = cass.weight_block(n_in)
    keep = cass.weight_keep(block)
    ops_ = prepare_draft_operands(spec, cass, shape)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, n_in)
    y = DM.draft_matmul(
        x2, ops_["bitmap"], ops_["signmant"], ops_["exp3"], ops_["emax"],
        ops_["book"], block=block, keep=keep, trunc=cass.weight_trunc,
        exp_bits=cass.exp_bits, tm=_tile(x2.shape[0]), tn=_tile(n_out),
        interpret=interpret)
    return y.reshape(*lead, n_out).astype(x.dtype)


def draft_weight_dense(spec: dict, cass: CassandraConfig,
                       shape: tuple[int, int], interpret: bool = False
                       ) -> jax.Array:
    """Decode the draft weight densely via the kernel (identity matmul)."""
    eye = jnp.eye(shape[0], dtype=jnp.bfloat16)
    return draft_matmul(eye, spec, cass, shape,
                        interpret=interpret).astype(jnp.bfloat16)


def draft_matmul_rank3_oracle(x: jax.Array, spec: dict,
                              cass: CassandraConfig,
                              shape: tuple[int, int]) -> jax.Array:
    """Pure-jnp oracle with the kernel's rank3 escape semantics."""
    n_in, n_out = shape
    block = cass.weight_block(n_in)
    keep = cass.weight_keep(block)
    ops_ = prepare_draft_operands(spec, cass, shape)
    code3 = jnp.swapaxes(
        bitops.unpack_codes(ops_["exp3"], cass.exp_bits, keep), 0, 1)
    exps = jnp.where(code3 == ESC, jnp.swapaxes(ops_["emax"], 0, 1),
                     jnp.take(ops_["book"], jnp.minimum(code3, ESC - 1)
                              ).astype(jnp.int32))
    t_keep = 7 - cass.weight_trunc
    code = bitops.unpack_codes(spec["signmant"], 1 + t_keep, keep)
    sign = (code >> t_keep) & 1
    mant = (code & ((1 << t_keep) - 1)) << cass.weight_trunc
    kept = bitops.join_fields(sign.astype(jnp.uint8),
                              exps.astype(jnp.uint8), mant.astype(jnp.uint8))
    from repro.core import pruning
    wt = pruning.desparsify(spec["bitmap"], kept, block)   # (N, K)
    w = wt.reshape(n_out, n_in).T
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))


def unary_decode(words: jax.Array, k: int, interpret: bool = False):
    flat = words.reshape(-1, words.shape[-1])
    out = UD.unary_decode(flat, k, tile=_tile(flat.shape[0], 8),
                          interpret=interpret)
    return out.reshape(*words.shape[:-1], k)


def mx_decode(sign, m16, shared_exp, group: int = 32,
              interpret: bool = False):
    lead = m16.shape[:-1]
    k = m16.shape[-1]
    flat = (sign.reshape(-1, k), m16.reshape(-1, k),
            shared_exp.reshape(-1, k // group))
    out = MX.mx_decode(*flat, group=group,
                       tile=_tile(flat[1].shape[0], 64), interpret=interpret)
    return out.reshape(*lead, k)


def kv_topk(v: jax.Array, keep: int, interpret: bool = False) -> dict:
    lead = v.shape[:-1]
    d = v.shape[-1]
    flat = v.reshape(-1, d)
    out = KT.kv_topk(flat, keep, tile=_tile(flat.shape[0], 32),
                     interpret=interpret)
    return {"bitmap": out["bitmap"].reshape(*lead, d // 32),
            "kept": out["kept"].reshape(*lead, keep)}
