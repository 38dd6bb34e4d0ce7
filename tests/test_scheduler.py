"""Continuous-batching scheduler: admission, slot recycling, early exit,
and the fused mixed-role serving step.

Uses plain (uncompressed) params so draft == target: the speculative path
compiles once and accepts everything, which keeps this module in the fast
tier while still exercising the full admit → decode → retire → recycle
lifecycle. Schedulers are module-scoped and ``reset()`` between tests so
the jit cache is paid for once. The module-scoped schedulers run in fused
mode (the default), so every lifecycle test here also exercises
``unified_step``; the dedicated fused tests below additionally pin
bit-identity against the single-role reference steps and the alternating
scheduler, and guard the one-compile-bucket property.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import init_params
from repro.serving.blockpool import blocks_needed
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import FINISHED, Scheduler

jax.config.update("jax_platform_name", "cpu")

MAX_NEW = 6
GAMMA = 2
S_MAX = 8 + MAX_NEW + GAMMA + 1


@pytest.fixture(scope="module")
def model():
    cfg = get_config("llama3-8b", smoke=True)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def spec_sched(model):
    cfg, params = model
    return Scheduler(cfg, params, cass=None, ecfg=EngineConfig(gamma=GAMMA),
                     num_slots=2, s_max=S_MAX, rt_extra={"ssm_chunk": 8})


@pytest.fixture(scope="module")
def auto_sched(model):
    cfg, params = model
    return Scheduler(cfg, params, cass=None, ecfg=EngineConfig(gamma=GAMMA),
                     num_slots=2, s_max=S_MAX, speculative=False,
                     rt_extra={"ssm_chunk": 8})


def _prompts(cfg, n, length=8, seed=1):
    key = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                          (length,), 0, cfg.vocab_size))
            for i in range(n)]


def test_recycling_drains_queue(model, spec_sched):
    """5 requests through 2 slots: every request retires with exactly
    max_new tokens and slots are reused across the queue."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    reqs = [spec_sched.submit(p, max_new=MAX_NEW)
            for p in _prompts(cfg, 5)]
    done = spec_sched.run()
    assert len(done) == 5
    assert all(r.state == FINISHED for r in reqs)
    assert all(len(r.output) == MAX_NEW for r in reqs)
    assert spec_sched.idle
    # more requests than slots => at least one slot served two requests
    assert len({r.slot for r in reqs}) == 2


def test_recycled_slot_isolated(model, spec_sched):
    """A slot's previous occupant must not leak into the next: the same
    prompt produces identical tokens on first admission and after
    recycling behind a different request."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    p = _prompts(cfg, 2)
    a = spec_sched.submit(p[0], max_new=MAX_NEW)
    b = spec_sched.submit(p[1], max_new=MAX_NEW)
    c = spec_sched.submit(p[0], max_new=MAX_NEW)  # recycled slot
    spec_sched.run()
    assert a.output == c.output
    assert a.output != b.output


def test_eos_early_exit(model, spec_sched):
    """A row hitting EOS retires early and frees its slot mid-queue."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    p = _prompts(cfg, 1)[0]
    probe = spec_sched.submit(p, max_new=MAX_NEW)
    spec_sched.run()
    eos = probe.output[2]

    spec_sched.reset()
    spec_sched.eos_id = eos
    req = spec_sched.submit(p, max_new=MAX_NEW)
    spec_sched.run()
    assert req.output == probe.output[:3]
    assert req.output[-1] == eos
    assert len(req.output) < MAX_NEW


def test_eos_beyond_max_new_capped(model, spec_sched):
    """EOS landing past max_new must not extend delivery beyond max_new."""
    from repro.serving.scheduler import RUNNING, Request
    spec_sched.reset()
    spec_sched.eos_id = 7
    r = Request(rid=99, tokens=np.zeros(4, np.int32), max_new=4)
    r.state, r.slot = RUNNING, 0
    r.output = [1, 2, 3, 4, 5, 7]        # cycle overshot; EOS after cap
    spec_sched.slots[0] = r
    spec_sched._maybe_retire(r)
    assert r.output == [1, 2, 3, 4]
    assert r.done
    spec_sched.reset()


def test_ready_request_skips_future_arrival(model, spec_sched):
    """A request due now must not be head-of-line blocked by an earlier
    submission whose arrival is in the future."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    p = _prompts(cfg, 2)
    late = spec_sched.submit(p[0], max_new=MAX_NEW, arrival=40.0)
    ready = spec_sched.submit(p[1], max_new=MAX_NEW, arrival=0.0)
    spec_sched.run()
    assert ready.admitted_at == 0.0
    assert late.admitted_at >= 40.0


def test_future_arrivals_fast_forward(model, spec_sched):
    """Arrivals beyond the clock are admitted after a fast-forward, not
    spun on."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    p = _prompts(cfg, 2)
    spec_sched.submit(p[0], max_new=MAX_NEW, arrival=0.0)
    late = spec_sched.submit(p[1], max_new=MAX_NEW, arrival=50.0)
    done = spec_sched.run()
    assert len(done) == 2
    assert late.admitted_at >= 50.0


def test_oversized_request_rejected(model, spec_sched):
    cfg, _ = model
    with pytest.raises(ValueError):
        spec_sched.submit(np.zeros(S_MAX, np.int32), max_new=MAX_NEW)


@pytest.fixture(scope="module")
def paged_sched(model):
    """One paged scheduler for all paged tests (jit cache paid once): a
    deliberately tiny 7-usable-block pool so admission has to wait for
    frees, with the default chunk size shared with the slot scheduler."""
    cfg, params = model
    return Scheduler(cfg, params, cass=None, ecfg=EngineConfig(gamma=GAMMA),
                     num_slots=2, s_max=S_MAX, rt_extra={"ssm_chunk": 8},
                     paged=True, block_size=4, num_blocks=8)


def test_paged_matches_slot(model, spec_sched, paged_sched):
    """Lossless paging: the block-pool cache + table-gathered attention
    must produce the exact per-request outputs of the slot layout."""
    cfg, _ = model
    prompts = _prompts(cfg, 5)
    outs = []
    for sched in (spec_sched, paged_sched):
        sched.reset()
        sched.eos_id = None
        reqs = [sched.submit(p, max_new=MAX_NEW) for p in prompts]
        sched.run()
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]
    s = paged_sched.summary()
    assert s["pool_high_water_blocks"] <= s["pool_blocks"]
    # paged reserves per-request blocks, not the S_MAX bound
    assert (s["peak_reserved_tokens"]
            <= spec_sched.summary()["peak_reserved_tokens"])


def test_paged_stress_tiny_pool(model, paged_sched):
    """Randomized arrival/length mix through a pool too small for the
    full set: every request must still commit >= max_new tokens (the cap
    alone forces waiting, never corruption or deadlock), and the pool
    high-water mark must never exceed capacity."""
    cfg, _ = model
    sched = paged_sched
    sched.reset()
    sched.eos_id = None
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(7):
        plen = int(rng.integers(2, 9))
        max_new = int(rng.integers(2, MAX_NEW + 1))
        p = _prompts(cfg, 1, length=plen, seed=100 + i)[0]
        reqs.append(sched.submit(p, max_new=max_new,
                                 arrival=float(i) / 2.0))
    done = sched.run()
    assert len(done) == len(reqs)
    for r in reqs:
        assert len(r.output) >= r.max_new, (r.rid, r.output)
    s = sched.summary()
    assert s["pool_high_water_blocks"] <= s["pool_blocks"]
    # all blocks returned to the pool
    assert sched.pool.allocated_total == 0
    assert sched.pool.reserved_total == 0
    sched.pool.check_invariants()


def test_paged_duplicate_rids_ok(model, paged_sched):
    """Caller-supplied rids may collide (submit(rid=...)); paged
    reservations key on slots, so duplicate rids must not crash
    admission or leak blocks."""
    cfg, _ = model
    sched = paged_sched
    sched.reset()
    sched.eos_id = None
    reqs = [sched.submit(p, max_new=MAX_NEW, rid=7)
            for p in _prompts(cfg, 3)]
    done = sched.run()
    assert len(done) == 3
    assert all(len(r.output) == MAX_NEW for r in reqs)
    assert sched.pool.allocated_total == 0
    sched.pool.check_invariants()


# -- fused mixed-role step ---------------------------------------------------


def _decode_ready_cache(cfg, params, rt, b=2, s_max=24):
    """Prefill a tiny batch so decode-step inputs exist."""
    from repro.models import forward_prefill
    from repro.serving import kvcache as KC
    cache = KC.init_cache(cfg, None, b, s_max, packed=False)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, 8), 0,
                                          cfg.vocab_size)}
    logits, cache = jax.jit(
        lambda p, bt, c: forward_prefill(rt, p, bt, c))(params, batch, cache)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    return cache, cur


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def test_unified_zero_prefill_bit_identical_to_spec(model):
    """A fused step whose role vector is all-DECODE must be bit-identical
    to the PR 2 ``spec_decode_step`` — AcceptResult and the whole
    committed cache tree, garbage tail included (same pass, same
    shapes, same reduction orders)."""
    from repro.models.layers import Runtime
    from repro.serving.engine import (EngineConfig, spec_decode_step,
                                      unified_step)
    cfg, params = model
    rt = Runtime(cfg=cfg, view="plain", ssm_chunk=8)
    ecfg = EngineConfig(gamma=GAMMA)
    b, w = 2, GAMMA + 1
    cache, cur = _decode_ready_cache(cfg, params, rt, b=b)
    key = jax.random.PRNGKey(3)
    res_s, cache_s = jax.jit(
        lambda p, c, t, k: spec_decode_step(rt, p, c, t, k, ecfg)
    )(params, cache, cur, key)
    res_u, _, cache_u, _ = jax.jit(
        lambda p, c, t, ch, v, d, k: unified_step(rt, p, c, t, ch, v, d, k,
                                                  ecfg)
    )(params, cache, cur, jnp.zeros((b, w), jnp.int32),
      jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), key)
    assert _trees_equal(res_s._asdict(), res_u._asdict())
    assert _trees_equal(cache_s, cache_u)


def test_unified_zero_decode_bit_identical_to_chunk(model):
    """A fused step whose role vector is all-PREFILL must be bit-identical
    to ``chunk_prefill_step`` at the same chunk width: last-position
    logits and the whole committed cache tree."""
    from repro.models.layers import Runtime
    from repro.serving.engine import (EngineConfig, chunk_prefill_step,
                                      unified_step)
    cfg, params = model
    rt = Runtime(cfg=cfg, view="plain", ssm_chunk=8)
    ecfg = EngineConfig(gamma=GAMMA)
    b, w = 2, GAMMA + 1
    cache, cur = _decode_ready_cache(cfg, params, rt, b=b)
    chunk = jax.random.randint(jax.random.PRNGKey(5), (b, w), 0,
                               cfg.vocab_size)
    valid = jnp.full((b,), w, jnp.int32)
    last_c, cache_c, _ = jax.jit(
        lambda p, c, t, v: chunk_prefill_step(rt, p, c, t, v)
    )(params, cache, chunk, valid)
    _, last_u, cache_u, _ = jax.jit(
        lambda p, c, t, ch, v, d, k: unified_step(rt, p, c, t, ch, v, d, k,
                                                  ecfg)
    )(params, cache, cur, chunk, valid, jnp.zeros((b,), bool),
      jax.random.PRNGKey(3))
    assert bool(jnp.array_equal(last_c, last_u))
    assert _trees_equal(cache_c, cache_u)


def _packed_paged_ready(cfg, params, cass, b=2, bs=4, mb=6):
    """A packed paged cache holding an 8-token prompt per row (chunked
    prefill through disjoint block tables), and the rows' next token."""
    from repro.models.layers import Runtime
    from repro.serving import kvcache as KC
    from repro.serving.engine import chunk_prefill_step
    rt = Runtime(cfg=cfg, cass=cass, view="target", ssm_chunk=8)
    cache = KC.init_paged_cache(cfg, cass, b, b * mb + 1, bs, mb,
                                packed=True)
    cache["block_table"] = 1 + jnp.arange(b * mb, dtype=jnp.int32
                                          ).reshape(b, mb)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, 8), 0,
                                cfg.vocab_size)
    last, cache, _ = jax.jit(
        lambda p, c, t, v: chunk_prefill_step(rt, p, c, t, v)
    )(params, cache, tokens, jnp.full((b,), 8, jnp.int32))
    return rt, cache, jnp.argmax(last, -1).astype(jnp.int32)[:, None]


@pytest.mark.parametrize("step", ["unified", "spec"])
@pytest.mark.parametrize("variant", [1, 2])
def test_shared_kv_view_bit_identical_to_separate_decodes(model, step,
                                                          variant,
                                                          monkeypatch):
    """One speculative cycle on a packed paged cache: with the cycle's KV
    views from ``materialize_cache_view`` (Cassandra-1: one target decode,
    the draft view derived from it; Cassandra-2: its draft view decoded
    apart) the accepted tokens, the logits and the committed cache are
    bit-identical to a reference cycle that builds no views, in which
    every draft pass decodes the draft view and the verify pass the
    target view for itself (``read_store`` in ``_attn_entry``)."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params, resolve_views
    from repro.models import model as M
    from repro.serving.engine import spec_decode_step, unified_step
    cfg, params = model
    cass = CassandraConfig(variant=variant, gamma=GAMMA)
    params = resolve_views(format_params(params, cass), cass)
    rt, cache, cur = _packed_paged_ready(cfg, params, cass)
    ecfg = EngineConfig(gamma=GAMMA)
    b, w = cur.shape[0], GAMMA + 1
    key = jax.random.PRNGKey(3)

    def run():
        if step == "spec":
            res, out = jax.jit(
                lambda p, c, t, k: spec_decode_step(rt, p, c, t, k, ecfg)
            )(params, cache, cur, key)
            return res, None, out
        return jax.jit(
            lambda p, c, t, ch, v, d, k: unified_step(rt, p, c, t, ch, v, d,
                                                      k, ecfg)[:3]
        )(params, cache, cur, jnp.zeros((b, w), jnp.int32),
          jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), key)

    res, last, out = run()
    monkeypatch.setattr(M, "materialize_cache_view",
                        lambda rt, cache: (None, None))
    res_r, last_r, out_r = run()
    assert _trees_equal(res._asdict(), res_r._asdict())
    assert _trees_equal(last, last_r)
    assert _trees_equal(out, out_r)
    assert int(jnp.min(res.n_accepted)) < GAMMA   # the draft differs


@pytest.mark.parametrize("cass_variant, attn_kernel, decodes", [
    (None, "off", 0), (1, "off", 1), (2, "off", 2), (1, "jnp", 1)])
def test_kv_pool_decodes_reported(model, cass_variant, attn_kernel, decodes):
    """The summary says how many full-pool KV decodes a cycle holds: none
    for a plain cache, one for Cassandra-1 (verify's target decode feeds
    the draft view) and for the paged kernel, two for Cassandra-2."""
    from repro.core.format import CassandraConfig
    cfg, params = model
    cass = (CassandraConfig(variant=cass_variant, gamma=GAMMA)
            if cass_variant else None)
    sched = Scheduler(cfg, params, cass=cass, ecfg=EngineConfig(gamma=GAMMA),
                      num_slots=2, s_max=S_MAX, rt_extra={"ssm_chunk": 8},
                      paged=True, block_size=4, num_blocks=24,
                      attn_kernel=attn_kernel)
    assert sched.summary()["subsystems"]["kv_pool_decodes"] == decodes


@pytest.mark.parametrize("paged", [False, True])
def test_fused_matches_alternating_trace(model, paged):
    """Losslessness: a staggered mixed-length trace through the fused
    scheduler yields per-request outputs identical to the alternating
    (PR 2) scheduler, on both cache layouts. The alternating run uses
    chunk_size=γ+1 so its prefill passes see the fused pass width (the
    one shape a chunked prefill's logits may legitimately depend on)."""
    cfg, params = model
    key = jax.random.PRNGKey(7)
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (int(ln),), 0, cfg.vocab_size))
        for i, ln in enumerate([8, 5, 8, 3, 7])]
    outs = []
    for fused in (True, False):
        sched = Scheduler(cfg, params, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA),
                          num_slots=2, s_max=S_MAX,
                          rt_extra={"ssm_chunk": 8}, fused=fused,
                          chunk_size=GAMMA + 1, paged=paged,
                          block_size=4, num_blocks=10)
        reqs = [sched.submit(p, max_new=MAX_NEW, arrival=i / 2.0)
                for i, p in enumerate(prompts)]
        sched.run()
        assert all(r.done for r in reqs)
        outs.append([r.output for r in reqs])
        if fused:
            # interleaving win: admissions ride decode cycles instead of
            # stalling them, so the fused run never takes more cycles
            fused_cycles = sched.summary()["cycles"]
            assert sched.metrics.counters["mixed_cycles"] > 0
    assert outs[0] == outs[1]
    assert fused_cycles <= sched.summary()["cycles"]


def test_fused_single_compile_bucket(model, spec_sched):
    """Compile-count guard: ONE fused-step compilation serves admission,
    growth, retirement and every mixed role vector (plus at most one
    wide-chunk compile for zero-decode cold-start cycles). Asserted via
    the scheduler's trace counter and the jit cache itself."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    lens = [8, 3, 7, 5, 8, 2]
    reqs = [spec_sched.submit(
        _prompts(cfg, 1, length=ln, seed=50 + i)[0], max_new=MAX_NEW,
        arrival=i / 2.0) for i, ln in enumerate(lens)]
    done = spec_sched.run()
    assert len(done) == len(reqs)
    assert spec_sched.metrics.counters["mixed_cycles"] > 0      # roles really mixed
    assert spec_sched.trace_counts.get("unified", 0) == 1
    assert spec_sched._unified._cache_size() == 1
    # the only other bucket ever traced is the wide admission chunk for
    # zero-decode cycles; the alternating spec step never runs
    assert spec_sched.trace_counts.get("chunk", 0) <= 1
    assert "spec" not in spec_sched.trace_counts


def test_prefill_budget_caps_tokens_not_outputs(model, spec_sched):
    """``max_prefill_tokens_per_step`` caps what admission may consume of
    a mixed cycle but must not change any request's tokens. Arrivals are
    staggered so later admissions ride live decode cycles (zero-decode
    cycles use the wide admission bucket and are exempt)."""
    cfg, _ = model
    # length-6 prompts ride mixed cycles in a 2-slot pool (the planner's
    # cost model sends longer prompts to the wide stall bucket instead)
    prompts = _prompts(cfg, 4, length=6)
    # staggered retirement (mixed max_new) keeps decode live across the
    # later admissions, forcing them through budgeted mixed cycles
    arrivals = [0.0, 0.0, 1.0, 2.0]
    max_news = [2, MAX_NEW, MAX_NEW, MAX_NEW]
    baseline = []
    for budget in (None, 2):
        spec_sched.reset()
        spec_sched.eos_id = None
        spec_sched.max_prefill_tokens_per_step = budget
        try:
            reqs = [spec_sched.submit(p, max_new=mn, arrival=a)
                    for p, mn, a in zip(prompts, max_news, arrivals)]
            spec_sched.run()
        finally:
            spec_sched.max_prefill_tokens_per_step = None
        assert spec_sched.metrics.counters["mixed_cycles"] > 0
        peak = spec_sched.metrics.gauges["peak_prefill_tokens_per_cycle"]
        if budget is None:
            baseline = [r.output for r in reqs]
            assert peak > 2          # unbudgeted mixed cycles go wider
        else:
            assert [r.output for r in reqs] == baseline
            assert 0 < peak <= budget


def test_per_request_stop_tokens(model, spec_sched):
    """A request's own ``stop_tokens`` retire it early without affecting
    a same-prompt request that has none."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    p = _prompts(cfg, 1)[0]
    probe = spec_sched.submit(p, max_new=MAX_NEW)
    spec_sched.run()
    stop = probe.output[2]

    spec_sched.reset()
    stopped = spec_sched.submit(p, max_new=MAX_NEW, stop_tokens=[stop])
    free = spec_sched.submit(p, max_new=MAX_NEW)
    spec_sched.run()
    assert stopped.output == probe.output[:3]
    assert stopped.output[-1] == stop
    assert free.output == probe.output
    # global eos composes with per-request stops: earliest one wins
    spec_sched.reset()
    spec_sched.eos_id = probe.output[1]
    both = spec_sched.submit(p, max_new=MAX_NEW, stop_tokens=[stop])
    spec_sched.run()
    spec_sched.eos_id = None
    assert both.output == probe.output[:2]


def test_latency_accounting(model, spec_sched):
    """Every delivered token carries a commit stamp; TTFT/ITL summaries
    are well-formed and in cycle units."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    reqs = [spec_sched.submit(p, max_new=MAX_NEW, arrival=i / 2.0)
            for i, p in enumerate(_prompts(cfg, 3))]
    spec_sched.run()
    for r in reqs:
        assert len(r.token_cycles) == len(r.output) == len(r.token_walls)
        assert r.ttft_cycles is not None and r.ttft_cycles > 0
        assert (r.itl_cycles >= 0).all()
    s = spec_sched.latency_summary()
    assert s["ttft_cycles_p95"] >= s["ttft_cycles_p50"] > 0
    assert s["itl_cycles_p95"] >= s["itl_cycles_p50"] >= 0


# -- prefix sharing ----------------------------------------------------------


def _shared_header_trace(cfg, gamma, seed=21):
    """Prompts for the prefix-cache tests: a common 3-block header, four
    sharers with unique tails, one cold prompt, one mid-block divergence
    (copy-on-write), and a final full-prefix hit."""
    bs = gamma + 1
    key = jax.random.PRNGKey(seed)
    header = np.asarray(jax.random.randint(key, (3 * bs,), 0,
                                           cfg.vocab_size))
    tails = [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                           (bs + 1,), 0, cfg.vocab_size))
             for i in range(4)]
    cold = np.asarray(jax.random.randint(jax.random.fold_in(key, 9),
                                         (2 * bs,), 0, cfg.vocab_size))
    prompts = [np.concatenate([header, t]) for t in tails]
    prompts.append(cold)
    # diverges inside sharer 0's first tail block -> partial match (CoW)
    div = np.concatenate([header, tails[0][:bs - 1],
                          (tails[0][bs - 1:bs] + 1) % cfg.vocab_size])
    prompts.append(div)
    prompts.append(np.concatenate([header, tails[0][:1]]))  # full hit
    return prompts


def _run_prefix_trace(cfg, params, prompts, cass=None, paged=True,
                      prefix=False, gamma=GAMMA, max_new=MAX_NEW):
    bs = gamma + 1
    s_max = max(len(p) for p in prompts) + max_new + gamma + 1
    s_max += (-s_max) % bs
    sched = Scheduler(cfg, params, cass=cass, ecfg=EngineConfig(gamma=gamma),
                      num_slots=2, s_max=s_max, rt_extra={"ssm_chunk": 8},
                      paged=paged, block_size=bs, chunk_size=bs,
                      prefix_cache=prefix)
    reqs = [sched.submit(p, max_new=max_new, arrival=2.0 * i)
            for i, p in enumerate(prompts)]
    sched.run()
    return sched, reqs


def test_prefix_cache_lossless_and_wins(model):
    """The tentpole's losslessness pin: per-request outputs with the
    prefix cache on are bitwise identical to cache-off runs on BOTH
    layouts (slot and paged), while admission really shares: cached
    header blocks are aliased, a mid-block divergence takes the
    copy-on-write path, the full-prefix hit skips its header's prefill
    and beats the cold run's TTFT, and every step still compiles once.
    block == chunk == γ+1 keeps every prefill pass the fused riding
    width at block-aligned boundaries, so warm starts replay a subset
    of the cold run's passes."""
    cfg, params = model
    prompts = _shared_header_trace(cfg, GAMMA)
    outs, ttfts, scheds = {}, {}, {}
    for mode in ("slot", "paged", "prefix"):
        sched, reqs = _run_prefix_trace(
            cfg, params, prompts, paged=mode != "slot",
            prefix=mode == "prefix")
        outs[mode] = [r.output for r in reqs]
        ttfts[mode] = [r.ttft_cycles for r in reqs]
        scheds[mode] = sched
    assert outs["prefix"] == outs["paged"] == outs["slot"]
    on, off = scheds["prefix"], scheds["paged"]
    s = on.summary()
    assert s["prefix_hits"] >= 4                  # sharers + div + full hit
    assert s["prefix_blocks_aliased"] >= 8
    assert s["cow_copies"] >= 1                   # the mid-block divergence
    assert s["prefill_tokens"] < off.summary()["prefill_tokens"]
    # the full-prefix hit (last request) skips its header entirely
    assert ttfts["prefix"][-1] < ttfts["paged"][-1]
    # zero recompiles: one trace per step, CoW included
    assert all(c == 1 for c in on.trace_counts.values()), on.trace_counts
    assert on.trace_counts["unified"] == 1
    # drained pool: nothing live, cached blocks parked (not leaked)
    assert on.pool.allocated_total == 0 and on.pool.reserved_total == 0
    assert on.pool.parked_total > 0
    on.pool.check_invariants()
    on.prefix.check_invariants()


@pytest.mark.slow
def test_prefix_cache_reuses_pool_capacity(model):
    """Sharing must show up as pool capacity: the same shared-header
    trace holds strictly fewer reserved-peak tokens with the cache on,
    and a pool too small for the cache-off trace still serves it with
    sharing (aliased headers draw no reservation)."""
    cfg, params = model
    prompts = _shared_header_trace(cfg, GAMMA)
    peaks = {}
    for prefix in (False, True):
        sched, reqs = _run_prefix_trace(cfg, params, prompts,
                                        prefix=prefix)
        assert all(len(r.output) >= MAX_NEW for r in reqs)
        peaks[prefix] = sched.summary()["peak_reserved_tokens"]
    assert peaks[True] < peaks[False]


@pytest.mark.slow
def test_prefix_cache_lossless_packed(model):
    """Same pin on the Cassandra-packed store: sharing aliases packed
    blocks (spec + verif streams) without decoding them, and outputs
    stay bitwise identical to the cache-off packed run."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params
    cfg, params = model
    cass = CassandraConfig(variant=1, gamma=GAMMA)
    packed = format_params(params, cass)
    prompts = _shared_header_trace(cfg, GAMMA)
    outs = {}
    for prefix in (False, True):
        sched, reqs = _run_prefix_trace(cfg, packed, prompts, cass=cass,
                                        prefix=prefix, max_new=4)
        outs[prefix] = [r.output for r in reqs]
        if prefix:
            assert sched.summary()["prefix_hits"] >= 4
    assert outs[True] == outs[False]


def test_prefix_cache_tiny_pool_waits_not_corrupts(model):
    """Eviction under pressure: a pool sized well below the trace's
    total footprint must still serve every request to completion —
    cached blocks are surrendered LRU-leaf-first when reservations need
    the space, never while a row still pins them."""
    cfg, params = model
    bs = GAMMA + 1
    prompts = _shared_header_trace(cfg, GAMMA)
    s_max = max(len(p) for p in prompts) + MAX_NEW + GAMMA + 1
    s_max += (-s_max) % bs
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=s_max, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=bs, chunk_size=bs,
                      num_blocks=2 * blocks_needed(s_max, bs) + 2,
                      prefix_cache=True)
    reqs = [sched.submit(p, max_new=MAX_NEW, arrival=float(i))
            for i, p in enumerate(prompts)]
    done = sched.run()
    assert len(done) == len(reqs)
    assert all(len(r.output) >= MAX_NEW for r in reqs)
    assert sched.pool.allocated_total == 0
    sched.pool.check_invariants()
    sched.prefix.check_invariants()


def test_serving_knob_validation(model):
    """Inconsistent serving knobs fail at construction with ValueErrors,
    not jit-time shape errors or silent planner inversions."""
    cfg, params = model

    def mk(**kw):
        kw.setdefault("rt_extra", {"ssm_chunk": 8})
        kw.setdefault("num_slots", 2)
        kw.setdefault("s_max", S_MAX)
        return Scheduler(cfg, params, ecfg=EngineConfig(gamma=GAMMA), **kw)

    with pytest.raises(ValueError, match="chunk_size"):
        mk(chunk_size=GAMMA)                  # wide bucket < riding width
    with pytest.raises(ValueError, match="paged"):
        mk(prefix_cache=True)                 # prefix sharing needs paging
    with pytest.raises(ValueError, match="multiple of"):
        mk(paged=True, prefix_cache=True, block_size=4, chunk_size=6)
    with pytest.raises(ValueError, match="allocatable"):
        mk(paged=True, prefix_cache=True, block_size=GAMMA + 1,
           chunk_size=GAMMA + 1, num_blocks=8, prefix_cache_blocks=9)
    with pytest.raises(ValueError, match="prefix_cache_blocks"):
        mk(prefix_cache_blocks=4)             # cap without the cache
    with pytest.raises(ValueError, match="max_prefill_tokens_per_step"):
        mk(max_prefill_tokens_per_step=0)
    with pytest.raises(ValueError, match="s_max"):
        mk(s_max=GAMMA + 1)
    ssm_cfg = get_config("falcon-mamba-7b", smoke=True)
    with pytest.raises(ValueError, match="SSM"):
        Scheduler(ssm_cfg, None, ecfg=EngineConfig(gamma=GAMMA),
                  num_slots=2, s_max=S_MAX, paged=True, prefix_cache=True,
                  block_size=GAMMA + 1, chunk_size=GAMMA + 1)
    with pytest.raises(ValueError, match="paged"):
        mk(attn_kernel="jnp")                 # kernel walks block tables
    with pytest.raises(ValueError, match="attn_kernel"):
        mk(paged=True, block_size=4, attn_kernel="cuda")


# -- paged-attention kernel (attn_kernel knob) -------------------------------


def _run_attn_kernel_trace(cfg, params, cass, attn_kernel, max_new=MAX_NEW):
    sched = Scheduler(cfg, params, cass=cass,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=S_MAX, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=4, num_blocks=24,
                      attn_kernel=attn_kernel)
    reqs = [sched.submit(p, max_new=max_new, arrival=float(i))
            for i, p in enumerate(_prompts(cfg, 4))]
    sched.run()
    return sched, [r.output for r in reqs]


def test_attn_kernel_lossless_packed_gqa(model):
    """ISSUE 8 losslessness pin: serving through the table-walking
    paged-attention kernel — Cassandra-packed cache, so the draft pass
    decodes KV *inside* the kernel and never materialises the dense
    draft view — produces per-request outputs bitwise identical to the
    gather-then-attend path, with every step still compiling once."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params
    cfg, params = model
    cass = CassandraConfig(variant=1, gamma=GAMMA)
    packed = format_params(params, cass)
    _, base = _run_attn_kernel_trace(cfg, packed, cass, "off")
    on, outs = _run_attn_kernel_trace(cfg, packed, cass, "jnp")
    assert outs == base
    # zero recompiles after warmup: one trace per step bucket
    assert all(c == 1 for c in on.trace_counts.values()), on.trace_counts


def test_attn_kernel_lossless_plain(model):
    """Plain bf16 pools through the kernel == gather path, autoregressive
    (no speculation: the kernel also serves the variant-0 baseline)."""
    cfg, params = model
    outs = {}
    for impl in ("off", "jnp"):
        sched = Scheduler(cfg, params, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                          s_max=S_MAX, rt_extra={"ssm_chunk": 8},
                          paged=True, block_size=4, num_blocks=24,
                          speculative=False, attn_kernel=impl)
        reqs = [sched.submit(p, max_new=MAX_NEW)
                for p in _prompts(cfg, 3)]
        sched.run()
        outs[impl] = [r.output for r in reqs]
    assert outs["jnp"] == outs["off"]


@pytest.mark.slow
def test_attn_kernel_interpret_e2e(model):
    """Slow tier: the actual Pallas kernel (interpret mode on CPU)
    through a full packed serving trace — bitwise identical tokens."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params
    cfg, params = model
    cass = CassandraConfig(variant=1, gamma=GAMMA)
    packed = format_params(params, cass)
    _, base = _run_attn_kernel_trace(cfg, packed, cass, "off")
    _, outs = _run_attn_kernel_trace(cfg, packed, cass, "interpret")
    assert outs == base


@pytest.mark.slow
def test_attn_kernel_mla_paged(model):
    """MLA decode through the paged latent-flash kernel (plain pools —
    the rope dim is too narrow to pack): tokens == gather path."""
    mcfg = get_config("deepseek-v3-671b", smoke=True)
    mparams = init_params(mcfg, jax.random.PRNGKey(3))
    outs = {}
    for impl in ("off", "jnp", "interpret"):
        sched = Scheduler(mcfg, mparams, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                          s_max=S_MAX, rt_extra={"ssm_chunk": 8},
                          paged=True, block_size=4, num_blocks=24,
                          speculative=False, attn_kernel=impl)
        reqs = [sched.submit(p, max_new=MAX_NEW)
                for p in _prompts(mcfg, 3)]
        sched.run()
        outs[impl] = [r.output for r in reqs]
    assert outs["jnp"] == outs["off"]
    assert outs["interpret"] == outs["off"]


# -- preemption + host swap --------------------------------------------------


def _oversub_trace(cfg, seed=7, prompt_len=8, long_new=16, short_new=4):
    """One long background generation admitted first, then short
    interactive requests arriving while it is mid-generation — the
    preemption regime. With the tight pool below, only one worst-case
    chain fits at a time, so each short arrival must preempt."""
    key = jax.random.PRNGKey(seed)
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size))
        for i in range(3)]
    max_news = [long_new, short_new, short_new]
    arrivals = [0.0, 2.0, 4.0]
    return prompts, max_news, arrivals


def _run_swap_trace(cfg, params, cass=None, num_blocks=40, swap=False,
                    priorities=None, gamma=GAMMA, long_new=16):
    prompts, max_news, arrivals = _oversub_trace(cfg, long_new=long_new)
    s_max = 8 + long_new + gamma + 1
    sched = Scheduler(cfg, params, cass=cass, ecfg=EngineConfig(gamma=gamma),
                      num_slots=2, s_max=s_max, rt_extra={"ssm_chunk": 8},
                      paged=True, block_size=4, num_blocks=num_blocks,
                      swap=swap)
    priorities = priorities or [0] * len(prompts)
    reqs = [sched.submit(p, max_new=mn, arrival=a, priority=pr)
            for p, mn, a, pr in zip(prompts, max_news, arrivals,
                                    priorities)]
    sched.run()
    return sched, reqs


def test_swap_preempt_resume_lossless(model):
    """The tentpole's losslessness pin (plain stores, fast tier): a pool
    holding one worst-case chain at a time forces the long resident row
    to be preempted (spilled to the host store) for each short arrival
    and resumed after — and every request's outputs are bitwise
    identical to the same trace through a big never-preempting pool.
    The queue head's TTFT must beat the no-preemption wait on the same
    tight pool, the host spill store must drain, and every step (spill
    and restore included) must compile exactly once."""
    cfg, params = model
    big, big_reqs = _run_swap_trace(cfg, params, num_blocks=40, swap=False)
    assert big.summary()["preemptions"] == 0
    tight, tight_reqs = _run_swap_trace(cfg, params, num_blocks=9,
                                        swap=False)
    swap, swap_reqs = _run_swap_trace(cfg, params, num_blocks=9, swap=True)
    s = swap.summary()
    assert s["preemptions"] >= 1 and s["swap_resumes"] >= 1
    assert s["swap_out_blocks"] >= 1          # a mid-generation victim:
    assert s["swap_in_blocks"] >= 1           # real bytes spilled+restored
    assert [r.output for r in swap_reqs] == [r.output for r in big_reqs]
    # the interactive queue head stops waiting behind the long row
    assert (swap_reqs[1].ttft_cycles < tight_reqs[1].ttft_cycles)
    # zero recompiles: one trace per step, spill/restore included
    assert all(c == 1 for c in swap.trace_counts.values()), \
        swap.trace_counts
    assert swap.trace_counts["spill"] == 1
    assert swap.trace_counts["restore"] == 1
    # drained: no chain left host-side, no swapped key in the pool
    assert len(swap.spill) == 0 and swap.pool.swapped_total == 0
    assert s["peak_swapped_tokens"] > 0 and s["spill_peak_bytes"] > 0
    assert swap.pool.allocated_total == 0 and swap.pool.reserved_total == 0
    swap.pool.check_invariants()


@pytest.mark.slow
def test_swap_preempt_resume_lossless_packed(model):
    """Same pin on the Cassandra-packed store (slow tier): spill and
    restore are leaf-wise bit-copies of the spec+verif streams (never
    decoded), so preempt-then-resume stays bitwise on packed pools."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params
    cfg, params = model
    cass = CassandraConfig(variant=1, gamma=GAMMA)
    packed = format_params(params, cass)
    big, big_reqs = _run_swap_trace(cfg, packed, cass=cass, num_blocks=40,
                                    swap=False, long_new=12)
    swap, swap_reqs = _run_swap_trace(cfg, packed, cass=cass, num_blocks=9,
                                      swap=True, long_new=12)
    s = swap.summary()
    assert s["preemptions"] >= 1 and s["swap_out_blocks"] >= 1
    assert [r.output for r in swap_reqs] == [r.output for r in big_reqs]
    swap.pool.check_invariants()


def test_swap_priority_orders_victims_and_admission(model):
    """Lower-priority rows are preempted first; a higher-priority ready
    request is admitted ahead of an earlier lower-priority one; and the
    all-default-priority path stays plain FIFO (the bitwise-default
    satellite: equal priorities reproduce the no-priority outputs)."""
    cfg, params = model
    # equal priorities == the FIFO baseline, bitwise
    base, base_reqs = _run_swap_trace(cfg, params, num_blocks=9, swap=True)
    zero, zero_reqs = _run_swap_trace(cfg, params, num_blocks=9, swap=True,
                                      priorities=[0, 0, 0])
    assert [r.output for r in zero_reqs] == [r.output for r in base_reqs]
    # a HIGH-priority long row resists preemption: the short heads now
    # have lower priority than the resident, so nothing may be swapped
    high, high_reqs = _run_swap_trace(cfg, params, num_blocks=9, swap=True,
                                      priorities=[1, 0, 0])
    assert high.summary()["preemptions"] == 0
    # outputs are unchanged either way (losslessness is policy-free)
    assert [r.output for r in high_reqs] == [r.output for r in base_reqs]
    # priority also reorders admission among READY requests: two same-
    # arrival requests admit high-priority-first, beating submit order
    prompts, max_news, _ = _oversub_trace(cfg)
    sched = Scheduler(cfg, params, cass=None, ecfg=EngineConfig(gamma=GAMMA),
                      num_slots=1, s_max=8 + 16 + GAMMA + 1,
                      rt_extra={"ssm_chunk": 8}, paged=True, block_size=4)
    lo = sched.submit(prompts[1], max_new=4, arrival=0.0, priority=0)
    hi = sched.submit(prompts[2], max_new=4, arrival=0.0, priority=5)
    sched.run()
    assert hi.admitted_at < lo.admitted_at


def test_swap_store_cap_stops_preemption(model):
    """A full host spill store makes victims ineligible: preemption
    stops (the head waits, as without swap) and no chain is ever
    dropped — outputs stay identical to the big-pool run."""
    cfg, params = model
    big, big_reqs = _run_swap_trace(cfg, params, num_blocks=40, swap=False)
    prompts, max_news, arrivals = _oversub_trace(cfg)
    s_max = 8 + 16 + GAMMA + 1
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=s_max, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=4, num_blocks=9, swap=True,
                      swap_store_blocks=blocks_needed(s_max, 4))
    reqs = [sched.submit(p, max_new=mn, arrival=a)
            for p, mn, a in zip(prompts, max_news, arrivals)]
    sched.run()
    # at most one chain fits the store at a time; everything completes
    # and the outputs still match the big-pool run exactly
    assert len(reqs) == len(sched.finished)
    assert [r.output for r in reqs] == [r.output for r in big_reqs]
    assert sched.spill.peak_blocks <= blocks_needed(s_max, 4)
    assert len(sched.spill) == 0
    sched.pool.check_invariants()


def test_prefix_cache_persists_across_reset(model):
    """ROADMAP follow-up satellite: parked chains survive
    ``Scheduler.reset()`` — a header prefilled in run 1 is a warm hit in
    run 2, with bitwise-identical outputs and strictly fewer prefill
    tokens computed."""
    cfg, params = model
    prompts = _shared_header_trace(cfg, GAMMA)
    bs = GAMMA + 1
    s_max = max(len(p) for p in prompts) + MAX_NEW + GAMMA + 1
    s_max += (-s_max) % bs
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=s_max, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=bs, chunk_size=bs, prefix_cache=True)
    cold_reqs = [sched.submit(p, max_new=MAX_NEW, arrival=2.0 * i)
                 for i, p in enumerate(prompts)]
    sched.run()
    cold = sched.summary()
    cold_outs = [r.output for r in cold_reqs]
    assert sched.pool.parked_total > 0
    sched.reset()
    # the index survived the reset: same pool object, chains parked
    assert sched.pool.parked_total > 0 and len(sched.prefix) > 0
    warm_reqs = [sched.submit(p, max_new=MAX_NEW, arrival=2.0 * i)
                 for i, p in enumerate(prompts)]
    sched.run()
    warm = sched.summary()
    assert [r.output for r in warm_reqs] == cold_outs
    # the FIRST request of the warm run already hits the parked header
    assert warm["prefix_hits"] > cold["prefix_hits"]
    assert warm["prefill_tokens"] < cold["prefill_tokens"]
    sched.pool.check_invariants()
    sched.prefix.check_invariants()


def test_bucket_wall_times_exposed(model, spec_sched):
    """Cost-model refresh seed satellite: ``summary()`` exposes measured
    per-bucket wall times for every step the run used, in the same
    bucket names ``trace_counts`` uses."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    reqs = [spec_sched.submit(p, max_new=MAX_NEW, arrival=i / 2.0)
            for i, p in enumerate(_prompts(cfg, 3))]
    spec_sched.run()
    walls = spec_sched.summary()["bucket_wall_ms"]
    assert "unified" in walls
    for name, w in walls.items():
        assert w["calls"] >= 1
        assert w["total_ms"] > 0
        assert w["mean_ms"] == pytest.approx(w["total_ms"] / w["calls"])
    # every traced step that ran has a measured wall-time bucket
    assert set(spec_sched.trace_counts) <= set(walls) | {"cow"}


def test_swap_knob_validation(model):
    """Preemption knob combinations fail fast at construction."""
    cfg, params = model

    def mk(**kw):
        kw.setdefault("rt_extra", {"ssm_chunk": 8})
        kw.setdefault("num_slots", 2)
        kw.setdefault("s_max", S_MAX)
        return Scheduler(cfg, params, ecfg=EngineConfig(gamma=GAMMA), **kw)

    with pytest.raises(ValueError, match="paged"):
        mk(swap=True)                         # swap needs the paged layout
    with pytest.raises(ValueError, match="swap_store_blocks"):
        mk(swap_store_blocks=4)               # cap without swap
    with pytest.raises(ValueError, match="one full row chain"):
        mk(paged=True, swap=True, block_size=4, swap_store_blocks=1)
    ssm_cfg = get_config("falcon-mamba-7b", smoke=True)
    with pytest.raises(ValueError, match="SSM|recurrent"):
        Scheduler(ssm_cfg, None, ecfg=EngineConfig(gamma=GAMMA),
                  num_slots=2, s_max=S_MAX, paged=True, swap=True,
                  block_size=4)


# -- MoE serving parity ------------------------------------------------------


def test_moe_fused_matches_alternating_trace():
    """The expert layer is dropless, so it couples no rows of a batch:
    the fused mixed-role trace must match the alternating reference
    bit-for-bit on a real MoE config, whatever rides beside each row."""
    cfg = get_config("dbrx-132b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(2))
    rt_extra = {"ssm_chunk": 8}
    key = jax.random.PRNGKey(13)
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (int(ln),), 0, cfg.vocab_size))
        for i, ln in enumerate([7, 4, 6, 3])]
    outs = []
    for fused in (True, False):
        sched = Scheduler(cfg, params, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA),
                          num_slots=2, s_max=S_MAX, rt_extra=rt_extra,
                          fused=fused, chunk_size=GAMMA + 1)
        reqs = [sched.submit(p, max_new=4, arrival=i / 2.0)
                for i, p in enumerate(prompts)]
        sched.run()
        assert all(r.done for r in reqs)
        if fused:
            assert sched.metrics.counters["mixed_cycles"] > 0
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]


def test_autoregressive_matches_speculative(model, spec_sched, auto_sched):
    """Plain params: the speculative scheduler (identity draft) and the
    autoregressive scheduler are the same greedy decoder."""
    cfg, _ = model
    prompts = _prompts(cfg, 3)
    outs = []
    for sched in (spec_sched, auto_sched):
        sched.reset()
        sched.eos_id = None
        reqs = [sched.submit(p, max_new=MAX_NEW) for p in prompts]
        sched.run()
        outs.append([r.output for r in reqs])
    assert all(len(o) == MAX_NEW for o in outs[0] + outs[1])
    # q=1 AR and q=γ+1 verify passes reduce in different orders, so a
    # near-tie argmax may flip on some platforms; require agreement on
    # most traces rather than bitwise equality of all of them
    assert sum(a == b for a, b in zip(outs[0], outs[1])) >= 2
    # aggregate throughput: autoregressive is bounded by 1 tok/cycle/slot;
    # identity-draft speculation must beat it on the same trace
    auto_tpc = auto_sched.summary()["tokens_per_cycle"]
    spec_tpc = spec_sched.summary()["tokens_per_cycle"]
    assert auto_tpc <= auto_sched.num_slots + 1e-9
    assert spec_tpc > auto_tpc


def test_randomized_trace_compiles_each_step_once(model):
    """Seeded randomized schedules: several rounds of mixed traces —
    shared headers (prefix hits + copy-on-write), cold prompts, varied
    lengths/budgets/arrivals, and a pool tight enough to preempt — must
    never grow any compile bucket past one. ``trace_counts`` persists
    across ``reset()``, so a recompile in ANY round fails the assert;
    this is the speclint recompile-arg contract checked dynamically."""
    cfg, params = model
    rng = np.random.default_rng(2026)
    bs = GAMMA + 1
    key = jax.random.PRNGKey(77)
    headers = [np.asarray(jax.random.randint(jax.random.fold_in(key, h),
                                             (2 * bs,), 0, cfg.vocab_size))
               for h in range(2)]
    long_new = 12
    s_max = 4 * bs + long_new + GAMMA + 1    # max prompt is header+tail
    s_max += (-s_max) % bs
    # one worst-case chain + a little: shorts must wait behind the long
    # resident, making it a preemption victim
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=s_max, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=bs, chunk_size=bs, prefix_cache=True,
                      swap=True, num_blocks=blocks_needed(s_max, bs) + 3)
    for _ in range(3):
        sched.reset()
        for i in range(5):
            tail_len = int(rng.integers(1, 2 * bs + 1))
            tail = rng.integers(0, cfg.vocab_size, tail_len)
            if rng.random() < 0.7:           # sharer: warm header path
                prompt = np.concatenate(
                    [headers[int(rng.integers(2))], tail])
            else:                            # cold prompt
                prompt = rng.integers(0, cfg.vocab_size, 2 * bs + tail_len)
            if i == 0:                       # long low-priority resident
                max_new, priority, arrival = long_new, 0, 0.0
            else:                            # short interactive arrivals
                max_new = int(rng.integers(2, 7))
                priority = 1
                arrival = 0.5 + float(rng.random() * 2.0)
            sched.submit(prompt.astype(np.int32), max_new=max_new,
                         arrival=arrival, priority=priority)
        sched.run()
    counts = sched.trace_counts
    assert all(c == 1 for c in counts.values()), counts
    assert counts.get("unified", 0) == 1
    # the schedule really exercised the mixed regimes it claims to
    assert sched.summary()["prefix_hits"] >= 1
    assert sched.summary()["preemptions"] >= 1 and "spill" in counts
    sched.check_invariants()


def test_invariant_check_catches_pool_corruption(model):
    """The ``debug_invariants`` knob (satellite of the speclint PR):
    with the periodic check armed every step, hand-corrupting the
    allocator's free list makes the very next ``step()`` raise instead
    of silently serving from inconsistent state."""
    cfg, params = model
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=S_MAX, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=4, debug_invariants=1)
    prompts = _prompts(cfg, 2)
    for p in prompts:
        sched.submit(p, max_new=MAX_NEW)
    assert sched.step()                      # healthy state passes
    sched.pool._free.append(sched.pool._free[-1])   # duplicate a block
    with pytest.raises(AssertionError):
        sched.step()


# -- wall-clock hygiene + SLOs & goodput -------------------------------------


def test_wall_clock_step_immune(model, spec_sched, monkeypatch):
    """Satellite bugfix pin: wall intervals are taken off
    ``perf_counter``, so a stepping system clock (NTP jump, suspend)
    can never yield negative bucket walls or non-monotone token stamps.
    Pre-fix, intervals came off ``time.time()`` and this trace would
    book hour-negative walls."""
    import repro.serving.scheduler as sched_mod
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    t = [1e9]

    def broken_epoch_clock():
        t[0] -= 3600.0              # steps BACKWARD an hour per call
        return t[0]

    monkeypatch.setattr(sched_mod.time, "time", broken_epoch_clock)
    for p in _prompts(cfg, 3):
        spec_sched.submit(p, max_new=MAX_NEW)
    prev = {}
    while not spec_sched.idle:
        spec_sched.step()
        for name, (calls, total) in spec_sched.metrics.walls.items():
            assert total >= 0.0, (name, total)
            pc, pt = prev.get(name, (0, 0.0))
            assert calls >= pc and total >= pt   # monotone accumulation
            prev[name] = (calls, total)
    for r in spec_sched.finished:
        walls = np.asarray(r.token_walls, np.float64)
        assert np.all(np.diff(walls) >= 0.0)


def test_submit_capacity_bound_matches_decode_mode(model, spec_sched,
                                                   auto_sched):
    """Satellite bugfix: admission charges the real decode horizon —
    γ+1 scatter positions for speculative rows, ONE for autoregressive.
    Pre-fix both modes were sized at +1 past outputs, so speculative
    requests γ tokens oversized were accepted (verify would scatter
    past the cache); the fix also documents the AR bound so AR prompts
    filling the cache to the last token still fit."""
    spec_sched.reset()
    auto_sched.reset()
    auto_sched.eos_id = None
    spec_fit = S_MAX - MAX_NEW - (GAMMA + 1)
    spec_sched.submit(np.zeros(spec_fit, np.int32) + 3, max_new=MAX_NEW)
    with pytest.raises(ValueError, match="cache slots"):
        spec_sched.submit(np.zeros(spec_fit + 1, np.int32) + 3,
                          max_new=MAX_NEW)
    spec_sched.reset()
    # the AR horizon is one token: two more prompt tokens fit in the
    # same cache, and the accepted bound really runs to completion
    ar_fit = S_MAX - MAX_NEW - 1
    assert ar_fit == spec_fit + GAMMA
    r = auto_sched.submit(np.zeros(ar_fit, np.int32) + 3, max_new=MAX_NEW)
    with pytest.raises(ValueError, match="cache slots"):
        auto_sched.submit(np.zeros(ar_fit + 1, np.int32) + 3,
                          max_new=MAX_NEW)
    auto_sched.run()
    assert len(r.output) == MAX_NEW
    auto_sched.reset()


def test_latency_summary_empty_is_none_not_nan(model, spec_sched):
    """Satellite bugfix: a run with no finished requests (or no
    measurable ITL) reports ``None`` for every latency key — not NaN,
    not an exception — so summaries stay JSON-serializable and
    comparisons read as missing, not as poisoned numbers."""
    spec_sched.reset()
    s = spec_sched.latency_summary()
    keys = ["ttft_cycles_mean", "ttft_cycles_p50", "ttft_cycles_p95",
            "itl_cycles_mean", "itl_cycles_p50", "itl_cycles_p95",
            "itl_ms_p50", "itl_ms_p95"]
    assert all(k in s and s[k] is None for k in keys), s
    g = spec_sched.goodput_summary()
    assert g["slo_finished"] == 0 and g["slo_hit_rate"] is None
    # a max_new=1 run has TTFTs but zero inter-token gaps
    cfg, _ = model
    spec_sched.eos_id = None
    spec_sched.submit(_prompts(cfg, 1)[0], max_new=1)
    spec_sched.run()
    s = spec_sched.latency_summary()
    assert s["ttft_cycles_mean"] is not None
    assert s["itl_cycles_p95"] is None
    assert spec_sched.summary()["slo_hit_rate"] is None


def test_preempted_resumes_ahead_of_later_arrivals(model):
    """A preempted request re-enters the queue with its ORIGINAL
    arrival (appendleft), so it resumes ahead of later same-priority
    arrivals instead of re-queuing at the tail — preemption parks work,
    it does not demote it."""
    cfg, params = model
    prompts, max_news, arrivals = _oversub_trace(cfg)
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=1,
                      s_max=8 + 16 + GAMMA + 1, rt_extra={"ssm_chunk": 8},
                      paged=True, block_size=4, num_blocks=9, swap=True)
    order = []
    admit, resume = sched._admit, sched._admit_resumed

    def log_admit(r, *a, **k):
        order.append(r.rid)
        return admit(r, *a, **k)

    def log_resume(r, *a, **k):
        order.append(r.rid)
        return resume(r, *a, **k)

    sched._admit, sched._admit_resumed = log_admit, log_resume
    reqs = [sched.submit(p, max_new=mn, arrival=a)
            for p, mn, a in zip(prompts, max_news, arrivals)]
    sched.run()
    assert sched.summary()["preemptions"] >= 1
    assert all(r.state == FINISHED for r in reqs)
    long_rid, c_rid = reqs[0].rid, reqs[2].rid
    # the long row's re-admission precedes C's first admission
    assert order[0] == long_rid
    assert order.index(long_rid, 1) < order.index(c_rid)
    assert reqs[0].admitted_at == 0.0    # stamp survives the round trip


def test_all_default_scheduling_is_bitwise_pre_slo(model):
    """Both gating directions of the SLO machinery: a goodput-capable
    scheduler given no SLOs, and a legacy (``slo_aware=False``)
    scheduler given SLOs, must each make decision-for-decision the
    pre-SLO FIFO schedule — same admissions, same preemptions, same
    cycle count, same tokens."""
    cfg, params = model

    def run(slo_aware, with_slos):
        prompts, max_news, arrivals = _oversub_trace(cfg)
        sched = Scheduler(cfg, params, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                          s_max=8 + 16 + GAMMA + 1,
                          rt_extra={"ssm_chunk": 8}, paged=True,
                          block_size=4, num_blocks=9, swap=True,
                          slo_aware=slo_aware)
        reqs = []
        for p, mn, a in zip(prompts, max_news, arrivals):
            slo = ({"ttft_deadline_ms": 400.0, "itl_target_ms": 50.0}
                   if with_slos and mn == 4 else {})
            reqs.append(sched.submit(p, max_new=mn, arrival=a, **slo))
        sched.run()
        s = sched.summary()
        return ([r.output for r in reqs], [r.admitted_at for r in reqs],
                s["preemptions"], s["cycles"])

    baseline = run(True, False)        # goodput-capable, nobody asked
    legacy = run(False, True)          # SLOs submitted, knob off
    assert baseline == legacy


def test_slo_deadlines_jump_the_backlog(model):
    """The tentpole end-to-end at test scale: an interactive request
    with a feasible TTFT deadline is admitted over a deadline-free
    backlog (EDF admission + the goodput victim policy preempting a
    background row), hits a deadline the FIFO schedule blows — and no
    request's tokens change (scheduling only reorders work)."""
    cfg, params = model
    bs = GAMMA + 1
    long_new, inter_new, d = 32, 4, 8.0
    prompt_len = 2 * bs
    s_max = prompt_len + long_new + GAMMA + 1
    s_max += (-s_max) % bs
    sched = Scheduler(cfg, params, cass=None,
                      ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                      s_max=s_max, rt_extra={"ssm_chunk": 8}, paged=True,
                      block_size=bs, chunk_size=bs,
                      num_blocks=2 * blocks_needed(s_max, bs) + 2,
                      swap=True)
    key = jax.random.PRNGKey(5)

    def mk(i):
        return np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size))

    # warm the cost model so the ms deadline below means d cycles (a
    # cold submit would take the nominal ms≡cycles exchange rate, then
    # deflate once real measurements arrive); enough decode cycles that
    # the fit survives the model's compile-call discard
    sched.submit(mk(9), max_new=8)
    sched.submit(mk(10), max_new=8)
    sched.run()
    assert sched.cost.warm
    deadline_ms = d * sched.cost.cycle_ms()

    def run(slo_aware):
        sched.slo_aware = slo_aware
        sched.reset()
        longs = [sched.submit(mk(i), max_new=long_new) for i in range(4)]
        inter = sched.submit(mk(8), max_new=inter_new, arrival=3.0,
                             ttft_deadline_ms=deadline_ms)
        sched.run()
        return longs, inter, sched.summary()

    longs_f, inter_f, s_f = run(False)
    longs_s, inter_s, s_s = run(True)
    assert inter_f.ttft_cycles > d       # FIFO blows the deadline
    assert inter_s.ttft_cycles <= d      # EDF + preemption hits it
    assert s_s["preemptions"] >= 1
    assert s_s["slo_finished"] == 1 and s_f["slo_finished"] == 1
    # lossless: the SLO schedule changes admission order, not tokens
    assert inter_s.output == inter_f.output
    assert [r.output for r in longs_s] == [r.output for r in longs_f]
    assert all(c == 1 for c in sched.trace_counts.values()), \
        sched.trace_counts


def test_slo_knob_validation(model, spec_sched):
    """Malformed per-request SLOs fail loudly at submit() — before the
    request is queued — and the engine-level validator is the same
    routine ``launch.serve`` uses for its default-SLO flags."""
    from repro.serving.engine import validate_request_slos
    spec_sched.reset()
    p = np.zeros(4, np.int32) + 3
    for bad in (0, -1.0, float("nan"), float("inf"), True, "soon"):
        with pytest.raises(ValueError, match="ttft_deadline_ms"):
            spec_sched.submit(p, max_new=2, ttft_deadline_ms=bad)
        with pytest.raises(ValueError, match="itl_target_ms"):
            spec_sched.submit(p, max_new=2, itl_target_ms=bad)
    assert not spec_sched.queue          # rejected before queueing
    with pytest.raises(ValueError, match="itl_target_ms"):
        validate_request_slos(itl_target_ms=-3.0)
    validate_request_slos(ttft_deadline_ms=250.0, itl_target_ms=40.0)


def test_cost_model_observes_real_walls(model, spec_sched):
    """``_stamp_wall`` feeds the online cost model: after a run every
    measured step bucket is fitted, the cycle<->ms exchange rate is a
    real measurement, and the fit PERSISTS across ``reset()`` — the
    model keeps refining across runs while ``metrics.walls`` starts
    fresh."""
    cfg, _ = model
    spec_sched.reset()
    spec_sched.eos_id = None
    for p in _prompts(cfg, 3):
        spec_sched.submit(p, max_new=MAX_NEW)
    spec_sched.run()
    cost = spec_sched.cost
    assert cost.warm
    assert set(spec_sched.metrics.walls) <= set(cost.buckets)
    assert cost.cycle_ms() > 0
    snap = spec_sched.summary()["cost_model"]
    assert snap["warm"] is True and snap["cycle_ms"] > 0
    calls = {n: b.calls for n, b in cost.buckets.items()}
    spec_sched.reset()
    assert spec_sched.cost is cost       # same model, still warm
    assert cost.warm
    assert {n: b.calls for n, b in cost.buckets.items()} == calls
    assert spec_sched.metrics.walls == {}   # raw walls start fresh


def test_deadline_beats_priority_in_goodput_mode(model, spec_sched):
    """In goodput mode a feasible deadline outranks raw priority —
    ``priority`` demotes to the tie break — while legacy mode still
    ranks by priority and ignores SLO fields entirely."""
    cfg, _ = model
    try:
        spec_sched.reset()
        spec_sched.eos_id = None
        cyc_ms = spec_sched.cost.cycle_ms()   # warm from earlier runs
        p = _prompts(cfg, 4)

        def trace(slo_aware):
            spec_sched.slo_aware = slo_aware
            spec_sched.reset()
            spec_sched.submit(p[0], max_new=MAX_NEW, priority=10)
            spec_sched.submit(p[1], max_new=4, priority=10)
            hi = spec_sched.submit(p[2], max_new=2, priority=5)
            dl = spec_sched.submit(p[3], max_new=2,
                                   ttft_deadline_ms=16.0 * cyc_ms)
            spec_sched.run()
            return hi, dl

        hi, dl = trace(True)
        assert dl.admitted_at < hi.admitted_at
        hi, dl = trace(False)     # legacy: priority rules, SLOs inert
        assert hi.admitted_at < dl.admitted_at
    finally:
        spec_sched.slo_aware = True
        spec_sched.reset()
