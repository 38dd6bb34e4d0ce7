"""Per-arch smoke tests: reduced configs, forward/train/prefill/decode,
shape + finiteness asserts, cache-consistency between full-seq and
incremental decode."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_config, layer_groups
from repro.models import (Runtime, forward_decode, forward_prefill,
                          forward_train, init_params, loss_fn)
from repro.serving import kvcache as KC

jax.config.update("jax_platform_name", "cpu")

# every test jit-compiles train+prefill+decode for a full arch — minutes of
# CPU across the 12-arch matrix
pytestmark = pytest.mark.slow

B, S = 2, 32


def make_batch(cfg, key, b=B, s=S):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (b, s), 0, cfg.vocab_size)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = 0.01 * jnp.ones(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "audio":
        batch["frame_embeds"] = 0.01 * jnp.ones(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch, smoke=True)
            params = init_params(cfg, jax.random.PRNGKey(0))
            cache[arch] = (cfg, params)
        return cache[arch]
    return get


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_finite(models, arch):
    cfg, params = models(arch)
    rt = Runtime(cfg=cfg, ssm_chunk=8)
    batch = make_batch(cfg, jax.random.PRNGKey(2))
    logits, aux = forward_train(rt, params, batch)
    s_total = S + (cfg.frontend_tokens if cfg.frontend == "vision" else 0)
    assert logits.shape == (B, s_total, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_loss_and_grad_step(models, arch):
    cfg, params = models(arch)
    rt = Runtime(cfg=cfg, ssm_chunk=8)
    batch = make_batch(cfg, jax.random.PRNGKey(3))
    loss, metrics = loss_fn(rt, params, batch)
    assert np.isfinite(float(loss))
    # one grad step must be finite too
    g = jax.grad(lambda p: loss_fn(rt, p, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ["llama3-8b", "qwen3-1.7b",
                                  "falcon-mamba-7b", "jamba-v0.1-52b",
                                  "deepseek-v3-671b", "whisper-medium",
                                  "phi-3-vision-4.2b"])
def test_prefill_decode_matches_full_forward(models, arch):
    """Incremental decode over the cache must equal full-seq logits."""
    cfg, params = models(arch)
    rt = Runtime(cfg=cfg, ssm_chunk=8)
    batch = make_batch(cfg, jax.random.PRNGKey(4))
    full_logits, _ = forward_train(rt, params, batch)

    split = S - 4
    pre = {k: (v[:, :split] if k in ("tokens", "labels") else v)
           for k, v in batch.items()}
    del pre["labels"]
    n_front = cfg.frontend_tokens if cfg.frontend == "vision" else 0
    cache = KC.init_cache(cfg, None, B, S + n_front + 8, packed=False)
    last_logits, cache = forward_prefill(rt, params, pre, cache)
    np.testing.assert_allclose(
        np.asarray(last_logits[:, 0]),
        np.asarray(full_logits[:, split - 1 + (
            cfg.frontend_tokens if cfg.frontend == "vision" else 0)]),
        rtol=2e-2, atol=2e-2)

    # decode the next 4 tokens one at a time
    from repro.serving.engine import commit
    for i in range(4):
        tok = batch["tokens"][:, split + i: split + i + 1]
        logits, upd, _ = forward_decode(rt, params, tok, cache)
        off = cfg.frontend_tokens if cfg.frontend == "vision" else 0
        np.testing.assert_allclose(
            np.asarray(logits[:, -1]),
            np.asarray(full_logits[:, split + i + off]),
            rtol=3e-2, atol=3e-2)
        cache = commit(rt, cache, upd, jnp.zeros(B, jnp.int32) - 0)


@pytest.mark.parametrize("arch", ["llama3-8b", "jamba-v0.1-52b"])
def test_packed_cache_prefill_decode(models, arch):
    """Packed (Cassandra) cache: target view reproduces plain decode."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params
    cfg, params = models(arch)
    cass = CassandraConfig(variant=1)
    packed = format_params(params, cass)
    rt = Runtime(cfg=cfg, cass=cass, view="target", ssm_chunk=8)
    batch = make_batch(cfg, jax.random.PRNGKey(5))
    pre = {"tokens": batch["tokens"][:, :S - 2]}
    cache = KC.init_cache(cfg, cass, B, S + 8, packed=True)
    last_logits, cache = forward_prefill(rt, params=packed, batch=pre,
                                         cache=cache)
    assert bool(jnp.all(jnp.isfinite(last_logits.astype(jnp.float32))))
    tok = batch["tokens"][:, S - 2: S - 1]
    logits, upd, _ = forward_decode(rt, packed, tok, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_mla_prefill_decode_drift_regression():
    """Regression for the deepseek-v3-671b MLA prefill-vs-incremental
    drift (ROADMAP known issue, present at seed).

    Audit result: the latent (c) / rope (kr) cache entries themselves were
    written consistently — the drift came from the *compute* split. The
    full-seq path materialised per-head K/V from the latent (with bf16
    k_nope/v round-trips) while cached decode ran absorbed in latent
    space; the two associations sat ~1e-2 apart in logits, and deepseek's
    MoE router amplified near-tie flips into O(0.1) logit jumps (26% of
    logits beyond 3% at smoke scale). Fix: the dense full-seq path now
    runs the same absorbed latent-space math as decode — the paths are
    bit-identical at smoke scale; this test pins a 100× tighter tolerance
    than the 3e-2 the matrix test allows. The >2048-token prefill path
    now runs ``_attend_flash_latent`` (absorbed-order scores/context,
    chunked): per-head K/V are never materialised and the only remaining
    prefill-vs-decode difference is the online-softmax association order
    (see test_mla_latent_flash_matches_absorbed below).
    """
    from repro.serving.engine import commit
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(
        name="mla-dense-drift", family="dense", n_layers=3, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=512, norm_eps=1e-6,
        block_pattern=("am",), mla=True, q_lora_rank=64, kv_lora_rank=64,
        qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = Runtime(cfg=cfg, ssm_chunk=8)
    batch = make_batch(cfg, jax.random.PRNGKey(4))
    full_logits, _ = forward_train(rt, params, batch)

    split = S - 4
    cache = KC.init_cache(cfg, None, B, S + 8, packed=False)
    _, cache = forward_prefill(
        rt, params, {"tokens": batch["tokens"][:, :split]}, cache)

    # latent/rope cache path audit: prefill-written c/kr for the prompt
    # must match the full-pass latents exactly (same code, same inputs)
    from repro.models.attention import mla_latent
    e0 = cache["dec"][0]["e0"]
    emb = jax.tree.map(lambda x: x[0], params["dec"][0]["e0"])
    from repro.models import layers as L_
    h = L_.norm(rt, emb["norm1"],
                L_.embed(params["embed"], batch["tokens"][:, :split]))
    c_ref, kr_ref = mla_latent(rt, emb["attn"], h, jnp.arange(split))
    np.testing.assert_array_equal(
        np.asarray(e0["c"][0][:, :split], np.float32),
        np.asarray(c_ref.astype(jnp.bfloat16), np.float32))
    np.testing.assert_array_equal(
        np.asarray(e0["kr"][0][:, :split], np.float32),
        np.asarray(kr_ref.astype(jnp.bfloat16), np.float32))

    for i in range(4):
        tok = batch["tokens"][:, split + i: split + i + 1]
        logits, upd, _ = forward_decode(rt, params, tok, cache)
        np.testing.assert_allclose(
            np.asarray(logits[:, -1], np.float32),
            np.asarray(full_logits[:, split + i], np.float32),
            rtol=3e-4, atol=3e-4)
        cache = commit(rt, cache, upd, jnp.zeros(B, jnp.int32))


def test_mla_latent_flash_matches_absorbed():
    """The >2048-token MLA prefill path (``_attend_flash_latent``) runs
    the same absorbed-order math as the dense latent softmax — only the
    online-softmax association differs, so the latent contexts agree to
    float tolerance at any chunking (the PR 2 leftover: the old naive
    path materialised per-head K/V and sat ~1e-2 off)."""
    from repro.models.attention import _attend_flash_latent
    b, s, h, lat, r = 2, 64, 4, 32, 16
    key = jax.random.PRNGKey(0)
    q_eff = jax.random.normal(key, (b, s, h, lat), jnp.float32)
    q_rope = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, r))
    c = (jax.random.normal(jax.random.fold_in(key, 2), (b, s, lat))
         ).astype(jnp.bfloat16)
    kr = (jax.random.normal(jax.random.fold_in(key, 3), (b, s, r))
          ).astype(jnp.bfloat16)
    scale = 1.0 / (32 + r) ** 0.5
    # absorbed dense reference (one softmax, same association as decode)
    sc = (jnp.einsum("bqhl,bkl->bhqk", q_eff, c.astype(jnp.float32))
          + jnp.einsum("bqhr,bkr->bhqk", q_rope,
                       kr.astype(jnp.float32))) * scale
    mask = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[None, None]
    sc = jnp.where(mask, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    ref = jnp.einsum("bhqk,bkl->bqhl", p, c.astype(jnp.float32))
    for chunk in (16, 64):
        out = _attend_flash_latent(q_eff, q_rope, c, kr, causal=True,
                                   scale=scale, chunk_q=chunk,
                                   chunk_k=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_layer_groups_cover_all_archs():
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        groups = layer_groups(cfg)
        n = sum(len(g.entries) * g.repeats for g in groups)
        assert n == cfg.n_layers, (arch, n, cfg.n_layers)


def test_moe_matches_reference():
    from repro.models import ffn as F
    cfg = get_config("dbrx-132b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(6))
    rt = Runtime(cfg=cfg)
    moe_p = jax.tree.map(lambda x: x[0], params["dec"][0]["e0"]["moe"])
    x = (jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.d_model))
         * 0.1).astype(jnp.bfloat16)
    out, aux = F.moe(rt, moe_p, x)
    expect = F.moe_reference(rt, moe_p, x)
    assert int(aux["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=5e-2, atol=5e-3)
