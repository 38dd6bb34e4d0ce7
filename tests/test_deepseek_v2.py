"""DeepSeek-V2-Lite's mechanisms at its smoke size on the CPU: YaRN rotary
scaling and the MLA softmax scale, MLA without q-LoRA served from a packed
paged latent pool, and the dropless expert layer that computes the share
of the experts a chip holds."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import packing as P
from repro.core.format import CassandraConfig
from repro.core.packing import format_params, resolve_views
from repro.models import Runtime, forward_train, init_params
from repro.models import ffn as F
from repro.models import layers as L
from repro.models.attention import mla_scale
from repro.serving import kvcache as KC
from repro.serving.engine import EngineConfig, chunk_prefill_step
from repro.serving.scheduler import Scheduler

CFG = get_config("deepseek-v2-lite", smoke=True)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(3))


def _moe_params(cfg, key=3):
    """The first expert layer's parameters of a model drawn at ``key``."""
    p = init_params(cfg, jax.random.PRNGKey(key))
    return jax.tree.map(lambda a: a[0], p["dec"][1]["e0"]["moe"])


def _x(t=32, key=7, dtype=jnp.float32):
    return (jax.random.normal(jax.random.PRNGKey(key), (2, t // 2, CFG.d_model))
            ).astype(dtype)


# -- YaRN and the softmax scale -----------------------------------------------


def test_yarn_frequencies_and_softmax_scale_match_the_published_formulas():
    """modeling_deepseek.py at the published numbers: correction
    dimensions low 10 and high 23 over rope 64, inverse frequencies
    f_e·m + (f_e/40)·(1 − m), and the softmax scale 192^-1/2 × mscale²
    with mscale(40, 0.707) = 0.1 · 0.707 · ln 40 + 1."""
    full = get_config("deepseek-v2-lite")
    y = full.yarn
    dim = 64

    def corr(beta):
        return dim * math.log(4096 / (2 * math.pi * beta)) / (2 * math.log(1e4))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    i = np.arange(dim // 2)
    f_e = 10000.0 ** (-2.0 * i / dim)
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    want = f_e * m + f_e / 40.0 * (1.0 - m)
    got = np.asarray(L.rope_freqs(dim, 1e4, y), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        f_e[-1] / 40, rel=1e-6)

    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert mla_scale(full) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                            rel=1e-12)
    assert mla_scale(full) * 192 ** 0.5 == pytest.approx(1.5896, abs=1e-4)
    # mscale == mscale_all_dim: cos and sin are not scaled
    assert y.cos_sin_factor == 1.0
    scaled = dataclasses.replace(y, mscale=1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, dim))
    ratio = (L.apply_rope(x, jnp.arange(5), 1e4, scaled)
             / L.apply_rope(x, jnp.arange(5), 1e4, y))
    np.testing.assert_allclose(np.asarray(ratio),
                               scaled.cos_sin_factor, rtol=1e-5)


def test_apply_rope_without_scaling_is_unchanged():
    """No rope scaling: bit for bit the plain half-split rotary embedding
    every other model serves with."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 4, 64)
                          ).astype(jnp.bfloat16)
    pos = jnp.arange(7) + 1000

    def plain(x, positions, theta):
        d = x.shape[-1]
        freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        ang = positions[None, :, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)

    for theta in (1e4, 1e6):
        np.testing.assert_array_equal(
            np.asarray(jax.jit(L.apply_rope, static_argnums=2)(
                x, pos, theta), np.float32),
            np.asarray(jax.jit(plain, static_argnums=2)(x, pos, theta),
                       np.float32))


# -- MLA served from the packed paged latent pool ------------------------------


def test_packed_paged_decode_matches_full_forward(params):
    """Prefill, then decoding one token at a time through the packed
    paged latent pool (Cassandra-1 target view, lossless), gives the full
    forward's logits. Tolerance: the full pass and the cached passes run
    the same bf16 arithmetic on operands of other shapes, 1e-2 of logits
    of order 1; a lost latent or a wrong rotary position moves them by
    order 1."""
    cass = CassandraConfig(variant=1, gamma=3)
    views = resolve_views(format_params(params, cass), cass)
    rt = Runtime(cfg=CFG, cass=cass, view="target")
    b, bs, mb, n_prompt, n_new = 2, 8, 5, 24, 4
    tokens = jax.random.randint(jax.random.PRNGKey(5), (b, n_prompt + n_new),
                                0, CFG.vocab_size)
    full, _ = forward_train(Runtime(cfg=CFG), params, {"tokens": tokens})
    cache = KC.init_paged_cache(CFG, cass, b, b * mb + 1, bs, mb, packed=True)
    assert KC.is_packed(cache["dec"][1]["e0"]["c"])
    assert KC.is_packed(cache["dec"][1]["e0"]["kr"])
    cache["block_table"] = 1 + jnp.arange(b * mb, dtype=jnp.int32
                                          ).reshape(b, mb)
    step = jax.jit(lambda p, c, t, v: chunk_prefill_step(rt, p, c, t, v))
    last, cache, _ = step(views, cache, tokens[:, :n_prompt],
                          jnp.full((b,), n_prompt, jnp.int32))
    got = [last]
    for i in range(n_new - 1):
        last, cache, _ = step(views, cache,
                              tokens[:, n_prompt + i:n_prompt + i + 1],
                              jnp.ones((b,), jnp.int32))
        got.append(last)
    want = full[:, n_prompt - 1:n_prompt + n_new - 1]
    np.testing.assert_allclose(np.asarray(jnp.stack(got, 1), np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_row_logits_do_not_depend_on_batch_neighbours(params):
    """The expert layer couples no rows: a row's logits through a served
    pass are bit for bit the same whatever rides beside it."""
    cass = CassandraConfig(variant=1, gamma=3)
    views = resolve_views(format_params(params, cass), cass)
    rt = Runtime(cfg=CFG, cass=cass, view="target")
    step = jax.jit(lambda p, c, t, v: chunk_prefill_step(rt, p, c, t, v))
    row = jax.random.randint(jax.random.PRNGKey(8), (1, 16), 0,
                             CFG.vocab_size)
    outs = []
    for other in (jnp.zeros((1, 16), jnp.int32),
                  jnp.full((1, 16), 7, jnp.int32)):
        cache = KC.init_paged_cache(CFG, cass, 2, 9, 8, 4, packed=True)
        cache["block_table"] = 1 + jnp.arange(8, dtype=jnp.int32
                                              ).reshape(2, 4)
        last, _, _ = step(views, cache, jnp.concatenate([row, other]),
                          jnp.full((2,), 16, jnp.int32))
        outs.append(np.asarray(last[0], np.float32))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_expert_stacks_pack_and_decode_to_their_views(params):
    """Expert weights stacked (layers, experts, in, out) are packed one
    matrix at a time and decode back to their views: the target view is
    the weights bit for bit, with the paper's pruning and with none (whose
    pruned streams are empty)."""
    w = params["dec"][1]["e0"]["moe"]["experts"]["w_up"]["w"]
    for cass in (CassandraConfig(variant=1),
                 CassandraConfig(variant=1, weight_prune=0.0, kv_prune=0.0,
                                 weight_trunc=0, kv_trunc=0)):
        packed = format_params(params, cass)
        assert "spec" in packed["dec"][1]["e0"]["moe"]["experts"]["w_up"]["w"]
        got = resolve_views(packed, cass)["dec"][1]["e0"]["moe"][
            "experts"]["w_up"]["w"]
        np.testing.assert_array_equal(np.asarray(got["target"], np.float32),
                                      np.asarray(w, np.float32))
        assert got["draft"].shape == w.shape



@pytest.mark.parametrize("variant,calibrated,prune", [
    (1, False, True), (1, True, True), (1, False, False), (2, False, True)])
def test_wide_weight_formats_in_column_blocks_bit_for_bit(
        params, monkeypatch, variant, calibrated, prune):
    """A 2-D weight past ``_BLOCK_ELEMS`` (the untied head) is formatted and
    decoded in blocks of output columns into the same bits as whole, its
    codebooks summed over the blocks; format_params packs the head so."""
    cass = (CassandraConfig(variant=variant) if prune else
            CassandraConfig(variant=variant, weight_prune=0.0, kv_prune=0.0,
                            weight_trunc=0, kv_trunc=0))
    w = params["lm_head"]["w"]                           # (128, 512)
    an = (jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (w.shape[0],)))
          + 0.1 if calibrated else None)
    whole = P._format_layer(w, an, cass)
    cols = P._format_cols(w, an, cass, 8)
    assert jax.tree.structure(cols) == jax.tree.structure(whole)
    for a, b in zip(jax.tree.leaves(cols), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    views = P._decode_cols(*whole, cass, w.shape, 8)
    want = P._decode_views(*whole, cass, w.shape)
    for k in ("draft", "target"):
        np.testing.assert_array_equal(np.asarray(views[k], np.float32),
                                      np.asarray(want[k], np.float32))
    np.testing.assert_array_equal(np.asarray(views["target"], np.float32),
                                  np.asarray(w, np.float32))
    monkeypatch.setattr(P, "_BLOCK_ELEMS", w.size // 8)
    assert P._col_blocks(*w.shape) == 8
    head = format_params(params, cass)["lm_head"]["w"]
    assert "spec" in head
    got = resolve_views({"w": head}, cass)["w"]
    np.testing.assert_array_equal(np.asarray(got["target"], np.float32),
                                  np.asarray(w, np.float32))


# -- the dropless expert layer and its shares --------------------------------


def test_dropless_equals_reference_where_capacity_dropped():
    """Every token identical, so all 32 pick the same 3 of 8 experts: the
    static capacity the layer used to have (int(32·3/8·1.25) + 1, padded
    to 16 slots an expert) dropped half of them. Dropless, none is
    dropped and the layer equals the expert-by-expert oracle."""
    p = _moe_params(CFG)
    rt = Runtime(cfg=CFG)
    x = jnp.broadcast_to(_x(2)[:1, :1], (2, 16, CFG.d_model))
    out, aux = F.moe(rt, p, x)
    assert int(aux["dropped"]) == 0
    assert int(aux["expert_tokens"]) == 32 * CFG.n_experts_per_tok
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(F.moe_reference(rt, p, x)),
                               rtol=1e-5, atol=1e-6)
    x = _x(dtype=jnp.bfloat16)
    out, aux = F.moe(rt, p, x)
    assert int(aux["dropped"]) == 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(F.moe_reference(rt, p, x), np.float32),
        rtol=2e-2, atol=2e-2)


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips holding 2 experts each: each share's expert is the
    uncut model's tensor, and the shares' routed parts summed, with the
    shared experts counted once, are the uncut layer."""
    uncut = _moe_params(CFG)
    x = _x()
    full, aux_full = F.moe(Runtime(cfg=CFG), uncut, x)
    shared = F.mlp(Runtime(cfg=CFG), uncut["shared"],
                   x.reshape(-1, CFG.d_model)).reshape(x.shape)
    total, tokens = shared, 0
    for first in range(0, CFG.n_experts, 2):
        cfg = dataclasses.replace(CFG, expert_first=first, experts_held=2)
        p = _moe_params(cfg)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            a, b[first:first + 2]), p["experts"], uncut["experts"])
        routed = {k: v for k, v in p.items() if k != "shared"}
        part, aux = F.moe(Runtime(cfg=cfg), routed, x)
        total, tokens = total + part, tokens + int(aux["expert_tokens"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-6)
    assert tokens == int(aux_full["expert_tokens"]) == \
        32 * CFG.n_experts_per_tok


def test_unnormalised_topk_combines_raw_probabilities():
    """``norm_topk_prob`` false: each picked expert is weighted by its raw
    softmax probability; true renormalises the top k to sum to 1."""
    p = _moe_params(CFG)
    x = _x()
    xt = x.reshape(-1, CFG.d_model)
    probs = jax.nn.softmax(xt @ p["router"]["w"], -1)
    top_p, top_e = jax.lax.top_k(probs, CFG.n_experts_per_tok)
    ys = jnp.stack([F.mlp(Runtime(cfg=CFG), jax.tree.map(lambda a: a[e],
                                                         p["experts"]), xt)
                    for e in range(CFG.n_experts)])
    shared = F.mlp(Runtime(cfg=CFG), p["shared"], xt)
    for norm in (False, True):
        cfg = dataclasses.replace(CFG, norm_topk_prob=norm)
        w = top_p / top_p.sum(-1, keepdims=True) if norm else top_p
        want = shared + jnp.einsum(
            "tk,tkd->td", w, ys[top_e, jnp.arange(xt.shape[0])[:, None]])
        out, _ = F.moe(Runtime(cfg=cfg), p, x)
        np.testing.assert_allclose(np.asarray(out).reshape(-1, CFG.d_model),
                                   np.asarray(want), rtol=1e-4, atol=1e-5)


def test_scheduler_counts_expert_tokens_and_no_drops(params):
    """Served on the normal path (packed weights and latent pool, paged,
    fused): the expert counters come out of each target pass, nothing is
    dropped, and a held expert sees at most every token of a pass."""
    cass = CassandraConfig(variant=1, gamma=3)
    views = resolve_views(format_params(params, cass), cass)
    sched = Scheduler(CFG, views, cass=cass, ecfg=EngineConfig(gamma=3),
                      num_slots=2, s_max=48, paged=True, block_size=8,
                      num_blocks=13, chunk_size=8)
    assert sched.expert_layers == 2
    rng = np.random.default_rng(0)
    reqs = [sched.submit(rng.integers(0, CFG.vocab_size, n), max_new=6)
            for n in (11, 5)]
    sched.run()
    assert all(len(r.output) == 6 for r in reqs)
    c = sched.metrics.counters
    assert c["moe_dropped"] == 0
    assert c["moe_expert_slots"] > 0
    assert c["moe_expert_slots"] % (CFG.n_held * sched.expert_layers) == 0
    passes = c["moe_expert_slots"] // (CFG.n_held * sched.expert_layers)
    # every expert held: each token of a pass lands k assignments here
    assert c["moe_expert_tokens"] % (CFG.n_experts_per_tok
                                     * sched.expert_layers) == 0
    assert 0 < c["moe_expert_tokens"] <= passes * sched.expert_layers \
        * 2 * 8 * CFG.n_experts_per_tok
