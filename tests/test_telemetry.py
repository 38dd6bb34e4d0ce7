"""Serving telemetry: tracer ring, metrics registry, exporters, and the
zero-sync/zero-recompile guarantee on a real oversubscribed trace.

The expensive fixture runs ONE preempt/spill/restore trace through a
telemetry-off and a tracing-on scheduler (module-scoped: compiled once).
Everything downstream — bitwise identity, bucket-key regression, the
Perfetto schema checks, the jsonl round-trip — reads the captured runs.
Pure-host unit tests (Tracer/Histogram/MetricsRegistry) need no model.
"""
import copy
import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import init_params
from repro.serving.costmodel import CostModel
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import Scheduler
from repro.serving import telemetry as TM
from repro.serving.telemetry import (Histogram, MetricsRegistry, Telemetry,
                                     Tracer, format_stats_lines,
                                     metrics_jsonl, perfetto_trace)

jax.config.update("jax_platform_name", "cpu")

GAMMA = 2
LONG_NEW = 16
S_MAX = 8 + LONG_NEW + GAMMA + 1


# -- pure-host units ---------------------------------------------------------

def test_tracer_ring_bound_and_dropped():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(20):
        tr.emit(TM.CYCLE, rid=i, cycle=float(i), args=(GAMMA, 1, 1))
    assert len(tr.ring) == 8
    assert tr.emitted == 20
    assert tr.dropped == 12
    # oldest events were the ones evicted
    assert [e[3] for e in tr.events()] == list(range(12, 20))
    tr.reset()
    assert tr.emitted == 0 and tr.dropped == 0 and not tr.events()


def test_tracer_disabled_is_noop():
    tr = Tracer(capacity=4, enabled=False)
    tr.emit(TM.SUBMIT, rid=0)
    assert tr.emitted == 0 and not tr.events()


def test_tracer_rejects_bad_capacity():
    with pytest.raises(ValueError):
        Tracer(capacity=0)
    with pytest.raises(ValueError):
        Telemetry(trace=True, trace_capacity=-1)


def test_histogram_small_domain():
    h = Histogram()
    for v in (2, 0, 2, 3.0):
        h.observe(v)
    s = h.snapshot()
    assert s["counts"] == {"0": 1, "2": 2, "3": 1}
    assert s["n"] == 4 and s["min"] == 0 and s["max"] == 3
    assert s["mean"] == pytest.approx(7 / 4)


def test_registry_walls_feed_cost_model_through_one_key():
    cost = CostModel(warmup_discard=1)
    m = MetricsRegistry(cost=cost)
    m.observe_wall("unified", 0.002)   # warmup: discarded from the fit
    m.observe_wall("unified", 0.004)
    # the wall view counts every call; the cost fit drops the warmup one
    assert m.wall_snapshot()["unified"]["calls"] == 2
    assert m.wall_snapshot()["unified"]["total_ms"] == pytest.approx(6.0)
    assert "unified" in cost            # visible from the FIRST call
    assert cost.snapshot()["buckets"]["unified"]["calls"] == 1
    # every wall key is a cost key by construction
    assert set(m.walls) <= set(cost.buckets)


def test_registry_reset_keeps_cost_model():
    cost = CostModel(warmup_discard=0)
    m = MetricsRegistry(cost=cost)
    m.inc("cycles")
    m.observe_wall("unified", 0.001)
    m.reset()
    assert m.counters == {} and m.walls == {}
    assert "unified" in cost            # the model outlives the run


def test_snapshot_derived_metrics():
    m = MetricsRegistry()
    m.declare("cycles", "committed", "accepted", "drafted")
    s = m.snapshot()
    assert s["tokens_per_cycle"] == 0
    assert s["acceptance"] is None      # nothing drafted: not 0/0
    assert "prefix_hit_rate" not in s   # subsystem off: key absent
    m.inc("cycles", 4)
    m.inc("committed", 10)
    m.inc("accepted", 6)
    m.inc("drafted", 8)
    m.set_config("prefix_cache", True)
    m.inc("prefix_queries", 4)
    m.inc("prefix_hits", 3)
    s = m.snapshot()
    assert s["tokens_per_cycle"] == pytest.approx(2.5)
    assert s["acceptance"] == pytest.approx(0.75)
    assert s["prefix_hit_rate"] == pytest.approx(0.75)


# -- the real-trace fixture --------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = get_config("llama3-8b", smoke=True)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _submit_oversub(sched, cfg, seed=7):
    """One long background generation, then short arrivals that must
    preempt it (the pool only fits one worst-case chain)."""
    key = jax.random.PRNGKey(seed)
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(key, i), (8,), 0, cfg.vocab_size))
        for i in range(3)]
    max_news = [LONG_NEW, 4, 4]
    arrivals = [0.0, 2.0, 4.0]
    return [sched.submit(p, max_new=mn, arrival=a)
            for p, mn, a in zip(prompts, max_news, arrivals)]


@pytest.fixture(scope="module")
def oversub(model):
    cfg, params = model
    runs = {}
    for mode in ("off", "on"):
        sched = Scheduler(cfg, params, cass=None,
                          ecfg=EngineConfig(gamma=GAMMA), num_slots=2,
                          s_max=S_MAX, rt_extra={"ssm_chunk": 8},
                          paged=True, block_size=4, num_blocks=9,
                          swap=True,
                          telemetry=Telemetry(trace=mode == "on"))
        reqs = _submit_oversub(sched, cfg)
        sched.run()
        runs[mode] = {"sched": sched, "summary": sched.summary(),
                      "outputs": [list(r.output) for r in reqs]}
    return runs


# -- the zero-sync / zero-recompile guarantee --------------------------------

def test_tracing_is_bitwise_lossless(oversub):
    on, off = oversub["on"], oversub["off"]
    assert on["outputs"] == off["outputs"]
    # same compile buckets, same trace counts: instrumentation created
    # zero extra executables
    assert on["summary"]["trace_counts"] == off["summary"]["trace_counts"]
    # and the trace actually stressed the preemption machinery
    assert on["summary"]["preemptions"] >= 1
    assert on["summary"]["swap_resumes"] >= 1
    assert on["summary"]["telemetry"]["trace_events"] > 0
    assert off["summary"]["telemetry"]["trace_events"] == 0


def test_every_traced_bucket_has_wall_and_cost(oversub):
    """The satellite regression: ``trace_counts``, ``bucket_wall_ms``
    and ``cost_model`` must agree on bucket keys for a fresh run — the
    old hand-maintained stores drifted (spill/restore showed up in
    trace_counts but not in the wall dict)."""
    for mode in ("off", "on"):
        s = oversub[mode]["summary"]
        traced = set(s["trace_counts"])
        assert {"spill", "restore"} <= traced      # oversub exercised swap
        assert traced <= set(s["bucket_wall_ms"])
        assert traced <= set(s["cost_model"]["buckets"])
        cost = oversub[mode]["sched"].cost
        assert all(b in cost for b in traced)


def test_lifecycle_events_cover_the_taxonomy(oversub):
    kinds = {e[2] for e in oversub["on"]["sched"].telemetry.tracer.events()}
    assert {TM.SUBMIT, TM.ADMIT, TM.PREFILL_CHUNK, TM.CYCLE, TM.PREEMPT,
            TM.SPILL, TM.RESTORE, TM.RESUME, TM.RETIRE, TM.STEP,
            TM.COUNTERS} <= kinds
    assert kinds <= set(TM.LIFECYCLE_KINDS)


# -- exporters ---------------------------------------------------------------

def test_perfetto_schema_and_monotone_tracks(oversub):
    trace = perfetto_trace(oversub["on"]["sched"].telemetry.tracer)
    evs = trace["traceEvents"]
    assert evs and trace["otherData"]["dropped_events"] == 0
    json.dumps(trace)                       # fully JSON-serializable
    assert all(e["ph"] in ("X", "i", "C", "M") for e in evs)
    # request lifecycle spans on slot tracks, device-step spans, and the
    # pool-occupancy counter track all present
    assert any(e["ph"] == "X" and e.get("cat") == "request" for e in evs)
    assert any(e["ph"] == "X" and e.get("cat") == "device" for e in evs)
    # the step track is back-computed from host walls, and says so
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert "step walls (host)" in tracks and "device steps" not in tracks
    assert {e["name"] for e in evs if e["ph"] == "C"} >= {
        "pool_blocks", "resident_tokens", "queue_depth",
        "accepted_tokens_per_cycle"}
    # a preempted request's span closes as preempt; a finished one as
    # retire — the lifecycle is visible, not just instants
    closers = {e["args"]["closed_by"] for e in evs
               if e["ph"] == "X" and e.get("cat") == "request"}
    assert {"preempt", "retire"} <= closers
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    # timestamps are non-decreasing within every track
    by_track: dict = {}
    for e in evs:
        if e["ph"] == "M":
            continue
        by_track.setdefault((e.get("tid"), e["ph"] == "C"), []).append(
            e["ts"])
    for ts_list in by_track.values():
        assert ts_list == sorted(ts_list)


def test_perfetto_empty_tracer():
    assert perfetto_trace(Tracer(enabled=True))["traceEvents"] == []


def test_metrics_jsonl_round_trip(oversub):
    text = metrics_jsonl(oversub["on"]["summary"])
    rows = [json.loads(line) for line in text.strip().splitlines()]
    names = [r["name"] for r in rows]
    assert len(names) == len(set(names))    # dotted flattening collides never
    byname = {r["name"]: r for r in rows}
    assert byname["committed"]["kind"] == "scalar"
    assert byname["config.swap"]["value"] is True
    assert any(n.startswith("wall.") for n in names)
    assert any(n.startswith("traces.") for n in names)
    assert any(n.startswith("hist.") for n in names)


# -- ring bound on the live scheduler ---------------------------------------

def test_ring_bound_under_oversubscription(model, oversub):
    """A tiny ring on the preempt/resume trace: the tracer must drop
    oldest events, never grow, and the run must stay correct."""
    cfg, _ = model
    sched = oversub["on"]["sched"]
    baseline = oversub["on"]["outputs"]
    sched.telemetry.trace_capacity = 16     # persists through reset()
    sched.reset()
    reqs = _submit_oversub(sched, cfg)
    sched.run()
    tr = sched.telemetry.tracer
    assert tr.capacity == 16
    assert len(tr.ring) == 16
    assert tr.dropped > 0 and tr.emitted > 16
    s = sched.summary()
    assert s["telemetry"]["trace_dropped"] == tr.dropped
    assert [list(r.output) for r in reqs] == baseline
    # a saturated ring still exports a valid trace
    json.dumps(perfetto_trace(tr))


# -- the one stats formatter -------------------------------------------------

def test_format_stats_lines_sections(oversub):
    s = oversub["on"]["summary"]
    lines = format_stats_lines(s, mode="fused", wall_s=1.0, n_done=3,
                               slots=2)
    tags = [line.split()[0] for line in lines]
    assert tags[:2] == ["[sched:fused]", "[latency]"]
    assert "[paged]" in tags and "[swap]" in tags
    assert "[prefix]" not in tags and "[slo]" not in tags   # subsystems off


def test_format_stats_lines_raises_on_missing_key(oversub):
    s = copy.deepcopy(oversub["on"]["summary"])
    del s["preemptions"]
    with pytest.raises(KeyError):
        format_stats_lines(s, mode="fused", wall_s=1.0, n_done=3, slots=2)


def test_slo_line_prints_even_with_nothing_finished():
    """The old serve.py guard keyed on ``slo_finished`` truthiness, so a
    run where SLOs were declared but none finished printed NOTHING. The
    formatter keys on the declared flag and renders rate=None."""
    s = {
        "cycles": 3, "prefill_cycles": 1, "mixed_cycles": 0,
        "tokens_per_cycle": 0.0, "acceptance": None,
        "ttft_cycles_p50": None, "ttft_cycles_p95": None,
        "itl_cycles_p50": None, "itl_cycles_p95": None,
        "slo_hits": 0, "slo_finished": 0, "slo_hit_rate": None,
        "cost_model": CostModel().snapshot(),
        "subsystems": {"slo_declared": True, "slo_aware": False,
                       "paged": False, "swap": False,
                       "prefix_cache": False, "attn_kernel": "off"},
    }
    lines = format_stats_lines(s, mode="fused", wall_s=0.1, n_done=0,
                               slots=2)
    slo = [line for line in lines if line.startswith("[slo]")]
    assert len(slo) == 1
    assert "rate=None" in slo[0] and "fifo" in slo[0]
