"""Compile the main-path kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(unaligned block shapes, unsupported vector shape casts, programs that do
not fit HBM). Interpret-mode parity lives in ``test_kernels.py``; this
file guards what interpret mode cannot see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import format as fmt, packing
from repro.core.format import CassandraConfig
from repro.kernels import draft_matmul as DM, ops, paged_attention as PA
from repro.serving import kvcache as KC

jax.config.update("jax_platform_name", "cpu")

V5E_HBM_BYTES = 15.75 * 2**30     # what the v5e compiler reports as usable
# Qwen3-1.7B attention widths, serving's default KV block and a pool for
# 4 slots of 388 tokens (prompt 256 + 128 new + γ 3 + 1).
B, HKV, G, D, BS, MB = 4, 8, 2, 128, 16, 25
NB = B * MB + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to, but never read
    # back from, a persistent cache: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("t", [1, 4])
def test_paged_gqa_compiles(one_chip, t):
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    c = _compile(
        lambda q, k, v, tb, ln: PA.paged_gqa(q, k, v, tb, ln, scale=0.088,
                                             impl="pallas"),
        s((B, t, HKV, G, D), jnp.bfloat16), s((NB, BS, HKV, D), jnp.bfloat16),
        s((NB, BS, HKV, D), jnp.bfloat16), s((B, MB), jnp.int32),
        s((B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("t", [1, 4])
def test_paged_gqa_packed_compiles(one_chip, t):
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    cass = CassandraConfig()
    book = KC.default_kv_codebook()
    spec = jax.eval_shape(
        lambda x: KC.encode_store(cass, x, D, book),
        jax.ShapeDtypeStruct((NB, BS, HKV, D), jnp.bfloat16))["spec"]
    spec = {k: s(v.shape, v.dtype) for k, v in spec.items()}
    c = _compile(
        lambda q, ks, vs, tb, ln, bk: PA.paged_gqa_packed(
            q, ks, vs, tb, ln, bk, d=D, keep=cass.kv_keep(D),
            trunc=cass.kv_trunc, exp_bits=cass.exp_bits, scale=0.088,
            impl="pallas"),
        s((B, t, HKV, G, D), jnp.bfloat16), spec, spec, s((B, MB), jnp.int32),
        s((B,), jnp.int32), s((256,), jnp.uint8))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("t", [1, 4])
def test_paged_mla_compiles(one_chip, t):
    """Latent 512 / rope 64 (DeepSeek-V3 MLA widths), 16 heads."""
    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    h, lat, rope = 16, 512, 64
    c = _compile(
        lambda qe, qr, cp, kr, tb, ln: PA.paged_mla(
            qe, qr, cp, kr, tb, ln, scale=0.072, impl="pallas"),
        s((B, t, h, lat), jnp.float32), s((B, t, h, rope), jnp.float32),
        s((NB, BS, lat), jnp.bfloat16), s((NB, BS, rope), jnp.bfloat16),
        s((B, MB), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("k_in,n_out", [(2048, 6144), (6144, 2048)])
def test_draft_matmul_compiles(one_chip, k_in, n_out):
    """Qwen3-1.7B's FFN up/down projections, decode batch 4."""
    cass = CassandraConfig()
    block = cass.weight_block(k_in)
    keep = cass.weight_keep(block)

    def operands(w):
        spec, _ = fmt.format_tensor(
            w.T, jnp.abs(w.T.astype(jnp.float32)), cass, block, keep,
            cass.mx_group, cass.weight_trunc)
        return ops.prepare_draft_operands(spec, cass, (k_in, n_out))

    shapes = jax.eval_shape(
        operands, jax.ShapeDtypeStruct((k_in, n_out), jnp.bfloat16))
    args = [_sds(one_chip, shapes[k].shape, shapes[k].dtype)
            for k in ("bitmap", "signmant", "exp3", "emax", "book")]
    c = _compile(
        lambda x, *a: DM.draft_matmul(
            x, *a, block=block, keep=keep, trunc=cass.weight_trunc,
            exp_bits=cass.exp_bits, tm=4, tn=128),
        _sds(one_chip, (4, k_in), jnp.bfloat16), *args)
    assert "tpu_custom_call" in c.as_text()


def test_stacked_formatting_fits_one_chip(one_chip):
    """Per-layer formatting of Qwen2.5-3B's (36, 2048, 11008) FFN stack
    fits one v5e (the whole-stack vmap needed 16.26 GB of 15.75)."""
    w = _sds(one_chip, (36, 2048, 11008), jnp.bfloat16)
    c = packing._format_stack.lower(w, None, CassandraConfig()).compile()
    ma = c.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
