"""Dense KV views kept in a packed cache (``cache["view"]``).

The serving passes read the views, and no step decodes the packed pool:
commit writes the new tokens' views beside their packed bytes, a prefix
copy-on-write copies view blocks with pool blocks, and a swap-in decodes
only the restored blocks. These tests hold the views to what the packed
pool decodes to, through a scheduler that admits, retires, copies and
swaps, for a GQA and an MLA model under both Cassandra variants; hold
serving from them bit-identical to serving from views decoded afresh
every cycle; and read the counter of blocks decoded into the views.
"""
import importlib.util
import json
import sys
from functools import lru_cache, partial
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.format import CassandraConfig
from repro.core.packing import format_params, resolve_views
from repro.models import init_params
from repro.models import model as M
from repro.models.layers import Runtime
from repro.serving import kvcache as KC
from repro.serving import scheduler as S
from repro.serving.blockpool import blocks_needed
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")

GAMMA = 2
BS = GAMMA + 1
REPO = Path(__file__).resolve().parents[1]


@lru_cache(maxsize=None)
def _model(arch: str):
    """A GQA model (the registry's llama3-8b at smoke widths) or the MLA
    and expert-layer form of ``bench/tests/data/tiny-dsv2.json``."""
    if arch == "gqa":
        cfg = get_config("llama3-8b", smoke=True)
    else:
        conf = json.loads(
            (REPO / "bench/tests/data/tiny-dsv2.json").read_text())
        spec = importlib.util.spec_from_file_location(
            "deepseek_v2_arch", REPO / "bench/arch/deepseek_v2.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        cfg = mod.program_config(conf, get_config(conf["arch"]))
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@lru_cache(maxsize=None)
def _served(arch: str, variant: int):
    cfg, params = _model(arch)
    cass = CassandraConfig(variant=variant, gamma=GAMMA)
    return cfg, cass, resolve_views(format_params(params, cass), cass)


@partial(jax.jit, static_argnums=(0,))
def _decoded_views(cass, cache):
    """Both views of every pool block, decoded from the packed stores by
    ``read_store``, in ``cache["view"]``'s layout."""
    book = KC.cache_codebook(cache)
    out = {}
    for view, groups in cache["view"].items():
        out[view] = [
            {ekey: {nm: KC.read_store(cass, cache["dec"][gi][ekey][nm],
                                      leaf.shape[-1], view, book)
                    for nm, leaf in entry.items()}
             for ekey, entry in g.items()}
            for gi, g in enumerate(groups)]
    return out


def _mismatched_views(cass, cache) -> list[str]:
    """The view stores that differ, in any bit of any slot or any pool
    block but the trash block, from what the packed stores decode to."""
    want = _decoded_views(cass, cache)
    first = 1 if KC.is_paged(cache) else 0
    bad = []
    for (path, got), ref in zip(
            jax.tree_util.tree_leaves_with_path(cache["view"]),
            jax.tree.leaves(want)):
        g = np.asarray(got[:, first:]).view(np.uint16)
        r = np.asarray(ref[:, first:]).view(np.uint16)
        if not np.array_equal(g, r):
            bad.append(jax.tree_util.keystr(path))
    return bad


@pytest.mark.parametrize("variant", [1, 2])
def test_store_views_equal_read_store(variant):
    """The views commit writes for just-encoded tokens are bit for bit
    ``read_store``'s of their packed bytes, on values that span bf16's
    range (zeros of both signs, a subnormal, near the largest finite):
    Cassandra-1's target is the encoded bf16 itself, Cassandra-2's is
    exact only to its containers, so it is decoded."""
    cass = CassandraConfig(variant=variant, gamma=GAMMA)
    book = KC.default_kv_codebook()
    x = (jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 64))
         * 3).astype(jnp.bfloat16)
    x = x.at[0, 0, 0, :4].set(jnp.array([0.0, -0.0, 1e-40, -3e38],
                                        jnp.bfloat16))
    enc = KC.encode_store(cass, x, 64, book)
    got = KC.store_views(cass, enc, 64, book, x)
    for view, g in zip(("target", "draft"), got):
        want = KC.read_store(cass, enc, 64, view, book)
        np.testing.assert_array_equal(np.asarray(g).view(np.uint16),
                                      np.asarray(want).view(np.uint16))


def _trace(cfg, seed=3):
    """A long low-priority resident and short high-priority arrivals on
    a shared header: prefix hits, a divergence inside a cached block
    (copy-on-write), and, on a pool holding one worst-case chain,
    preemptions with swap-out and swap-in."""
    rng = np.random.default_rng(seed)
    header = rng.integers(0, cfg.vocab_size, 2 * BS)
    tail = rng.integers(0, cfg.vocab_size, BS + 1)
    div = np.concatenate([header, tail[:BS - 1],
                          (tail[BS - 1:BS] + 1) % cfg.vocab_size])
    return [
        (np.concatenate([header, tail]), 10, 0.0, 0),
        (np.concatenate([header, rng.integers(0, cfg.vocab_size, BS)]),
         3, 1.0, 1),
        (div, 3, 2.0, 1),
        (rng.integers(0, cfg.vocab_size, 2 * BS + 1), 3, 3.0, 1),
        (np.concatenate([header, tail[:1]]), 3, 4.0, 1),
    ]


def _sched(cfg, params, cass, **kw):
    s_max = 3 * BS + 1 + 10 + GAMMA + 1
    s_max += (-s_max) % BS
    kw = {"paged": True, "block_size": BS, "chunk_size": BS,
          "prefix_cache": True, "swap": True,
          "num_blocks": blocks_needed(s_max, BS) + 3, **kw}
    return Scheduler(cfg, params, cass=cass, ecfg=EngineConfig(gamma=GAMMA),
                     num_slots=2, s_max=s_max, rt_extra={"ssm_chunk": 8},
                     **kw)


@pytest.mark.parametrize("arch, variant", [
    ("gqa", 1), ("gqa", 2), ("mla", 1), ("mla", 2)])
def test_views_match_packed_pool_through_serving(arch, variant):
    """After every cycle of a run that admits, retires, copies a prefix
    block on write and swaps a row out and back in (twice, the prefix
    index kept across the reset), every block of the target and draft
    views but the trash block equals ``read_store`` of the packed pool,
    bit for bit."""
    cfg, cass, params = _served(arch, variant)
    sched = _sched(cfg, params, cass)
    bad = []
    for run in range(2):
        sched.reset()
        for prompt, max_new, arrival, priority in _trace(cfg):
            sched.submit(prompt.astype(np.int32), max_new=max_new,
                         arrival=arrival, priority=priority)
        for cycle in range(500):
            if not sched.step():
                break
            bad += [(run, cycle, name)
                    for name in _mismatched_views(cass, sched.cache)]
        assert sched.idle
    s = sched.summary()
    assert not bad, bad[:5]
    assert s["finished"] == 5 and s["prefix_hits"] >= 1
    assert s["cow_copies"] >= 1
    assert s["preemptions"] >= 1 and s["swap_in_blocks"] >= 1
    assert s["kv_view_decoded_blocks"] == s["swap_in_blocks"]


@pytest.mark.parametrize("variant", [1, 2])
def test_slot_views_match_packed_after_prefill_and_cycles(variant):
    """The slot layout (``Engine``'s): a full-sequence prefill and then
    speculative cycles leave the views what the packed stores decode to,
    in every slot of every row."""
    from repro.models import forward_prefill
    from repro.serving.engine import spec_decode_step
    cfg, cass, params = _served("gqa", variant)
    rt = Runtime(cfg=cfg, cass=cass, view="target", ssm_chunk=8)
    ecfg = EngineConfig(gamma=GAMMA)
    cache = KC.init_cache(cfg, cass, 2, 32, packed=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size)}
    logits, cache = jax.jit(
        lambda p, bt, c: forward_prefill(rt, p, bt, c))(params, batch, cache)
    assert not _mismatched_views(cass, cache)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    step = jax.jit(lambda p, c, t, k: spec_decode_step(rt, p, c, t, k, ecfg))
    for i in range(4):
        res, cache = step(params, cache, cur, jax.random.PRNGKey(i))
        cur = res.next_token[:, None]
        assert not _mismatched_views(cass, cache), i


def _with_verify_logits(fn):
    """``fn`` jitted, returning also the logits of its target pass."""
    def run(*args):
        seen, orig = [], M.forward_decode

        def spy(rt, *a, **k):
            out = orig(rt, *a, **k)
            if rt.view == "target":
                seen.append(out[0])
            return out
        M.forward_decode = spy
        try:
            out = fn(*args)
        finally:
            M.forward_decode = orig
        return out, seen[0]
    return jax.jit(run)


@pytest.mark.parametrize("step", ["unified", "spec"])
def test_kept_views_serve_bit_identical_to_decoded_views(step):
    """Two rows over 24 cycles, the second admitted at cycle 8 (a prefill
    role of the fused step, or a chunk between the alternating path's
    speculative cycles): the served tokens, each cycle's acceptance and
    last-position logits and its verify logits are bit-identical whether
    the passes read the views the cache keeps or views the test decodes
    from the packed pool with ``read_store`` before every cycle."""
    cfg, cass, params = _served("gqa", 1)
    rt = Runtime(cfg=cfg, cass=cass, view="target", ssm_chunk=8)
    ecfg = EngineConfig(gamma=GAMMA)
    b, mb, w = 2, 26, GAMMA + 1
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (8, 7)]
    admit_at, cycles = (0, 8), 24
    unified = _with_verify_logits(partial(S._masked_unified, rt, ecfg=ecfg))
    spec = _with_verify_logits(partial(S._masked_spec, rt, ecfg=ecfg))
    chunk = jax.jit(partial(S._masked_chunk, rt))

    def serve(decode_first: bool):
        cache = KC.init_paged_cache(cfg, cass, b, b * mb + 1, BS, mb,
                                    packed=True)
        cache["block_table"] = 1 + jnp.arange(b * mb, dtype=jnp.int32
                                              ).reshape(b, mb)

        def fresh(cache):
            if not decode_first:
                return cache
            return dict(cache, view=_decoded_views(cass, cache))
        pos, cur = [0, 0], np.zeros((b, 1), np.int32)
        decoding = np.zeros(b, bool)
        served, record = [[], []], []
        key = jax.random.PRNGKey(5)
        for c in range(cycles):
            key, sub = jax.random.split(key)
            rows = [r for r in range(b) if admit_at[r] <= c
                    and pos[r] < len(prompts[r])]
            if step == "spec":
                if rows:             # the alternating path's admission
                    toks = np.zeros((b, 8), np.int32)
                    valid = np.zeros(b, np.int32)
                    for r in rows:
                        toks[r, :len(prompts[r])] = prompts[r]
                        valid[r] = len(prompts[r])
                        pos[r] = len(prompts[r])
                    last, cache, _ = chunk(params, fresh(cache),
                                           jnp.asarray(toks),
                                           jnp.asarray(valid))
                    for r in rows:
                        cur[r, 0] = int(jnp.argmax(last[r]))
                        decoding[r] = True
                    record.append(("chunk", last))
                (res, cache), logits = spec(params, fresh(cache),
                                            jnp.asarray(cur), sub,
                                            jnp.asarray(decoding))
                last = None
            else:
                toks = np.zeros((b, w), np.int32)
                valid = np.zeros(b, np.int32)
                for r in rows:
                    part = prompts[r][pos[r]:pos[r] + w]
                    toks[r, :len(part)] = part
                    valid[r] = len(part)
                (res, last, cache, _), logits = unified(
                    params, fresh(cache), jnp.asarray(cur),
                    jnp.asarray(toks), jnp.asarray(valid),
                    jnp.asarray(decoding), sub)
                for r in rows:
                    pos[r] += int(valid[r])
                    if pos[r] == len(prompts[r]):
                        cur[r, 0] = int(jnp.argmax(last[r]))
            n = np.asarray(res.n_accepted)
            toks_out = np.asarray(res.tokens)
            for r in np.flatnonzero(decoding):
                served[r] += toks_out[r, :n[r] + 1].tolist()
                cur[r, 0] = int(res.next_token[r])
            if step == "unified":
                decoding |= np.array([pos[r] == len(prompts[r])
                                      for r in range(b)])
            record.append(("cycle", res._asdict(), last, logits))
        return served, record, cache

    kept, kept_record, kept_cache = serve(False)
    fresh_served, fresh_record, _ = serve(True)
    assert kept == fresh_served
    assert len(kept[0]) >= 20 and len(kept[1]) >= 10
    for a, bb in zip(kept_record, fresh_record):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(bb)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the draft differs from the target, so verify really decides
    accepted = [int(jnp.min(rec[1]["n_accepted"]))
                for rec in kept_record if rec[0] == "cycle"]
    assert min(accepted) < GAMMA
    assert not _mismatched_views(cass, kept_cache)


@pytest.mark.parametrize("case", ["steady", "swap_in", "kernel"])
def test_view_decode_counter(case):
    """``kv_view_decoded_blocks`` counts the blocks decoded from packed
    bytes into the views: none over a closed run with no preemption,
    the K restored blocks after swap-ins of K. The paged-kernel path keeps
    no views and reports one pool decode a cycle; the view path none."""
    cfg, cass, params = _served("gqa", 1)
    if case == "kernel":
        sched = _sched(cfg, params, cass, prefix_cache=False, swap=False,
                       attn_kernel="jnp")
        assert not KC.has_views(sched.cache)
        assert sched.summary()["subsystems"]["kv_pool_decodes"] == 1
        assert sched.summary()["kv_view_decoded_blocks"] == 0
        return
    steady = case == "steady"
    sched = _sched(cfg, params, cass, prefix_cache=False, swap=not steady,
                   **({"num_blocks": None} if steady else {}))
    assert KC.has_views(sched.cache)
    before = 0
    for prompt, max_new, arrival, priority in _trace(cfg)[:4]:
        sched.submit(prompt.astype(np.int32), max_new=max_new,
                     arrival=arrival, priority=0 if steady else priority)
    for _ in range(500):
        swapped_in = sched.summary()["swap_in_blocks"]
        if not sched.step():
            break
        s = sched.summary()
        # each swap-in's K blocks, counted as it lands
        assert (s["kv_view_decoded_blocks"] - before
                == s["swap_in_blocks"] - swapped_in)
        before = s["kv_view_decoded_blocks"]
    s = sched.summary()
    assert sched.idle and s["subsystems"]["kv_pool_decodes"] == 0
    if steady:
        assert s["preemptions"] == 0 and s["kv_view_decoded_blocks"] == 0
    else:
        assert s["swap_in_blocks"] >= 1
        assert s["kv_view_decoded_blocks"] == s["swap_in_blocks"]
