"""The trace reduction's readings on the recorded v5e traces, pinned as
numbers, and how program spans and scopes split the fused step.

``test_trace_reduce.py`` checks properties of the reduction; this file
pins what it reads on the recorded trace, so that a change to
``trace_reduce`` that moves a reading of an accepted metric shows here.

``scoped_trace.json.gz`` is one ``serve_unified`` execution of
``qwen3-1.7b.reason-long`` on a TPU v5e with the program's ``cass.*``
named scopes and ``sched.*`` host spans, and ``scoped_op_scopes.json``
the program's map from its ops to their scopes
(``telemetry.hlo_op_scopes`` over ``Scheduler.unified_step_text()``),
kept for the ops in that slice.
"""
import gzip
import json
from collections import defaultdict
from pathlib import Path

import pytest

import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "recorded_trace.json.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    return T.Trace.from_json(gzip.decompress(path.read_bytes()))


def test_recorded_trace_reads_its_pinned_numbers(recorded):
    r = T.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.5, abs=1e-12)
    assert r["busy_s"] == pytest.approx(0.499981041, abs=1e-12)
    assert r["devices"] == 1
    assert r["programs"] == {
        "broadcast_in_dim": {"seconds": pytest.approx(5.42e-07, abs=1e-15),
                             "calls": 1},
        "_threefry_split": {"seconds": pytest.approx(3.247e-06, abs=1e-15),
                            "calls": 1},
        "_unstack": {"seconds": pytest.approx(8.63e-07, abs=1e-15),
                     "calls": 1},
        "serve_unified": {"seconds": pytest.approx(0.13710364, abs=1e-12),
                          "calls": 1}}
    assert [name for name, _ in r["idle_gaps"]] == ["bench.step"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        1.8959e-05, abs=1e-12)
    assert r["device_ops"][0] == ["%fusion.562",
                                  pytest.approx(0.01267121, abs=1e-12)]


def test_program_spans_name_gaps_with_the_same_total(recorded):
    """A scheduler span nested in each benchmark step (the program's
    ``sched.*`` spans on the profiler's clock) takes the gaps inside it;
    the gaps' total and every other reading stay as they were."""
    nested = [("sched.wait", s + 1, e - 1) for n, s, e in recorded.spans
              if n == "bench.step"]
    both = T.Trace(modules=recorded.modules, ops=recorded.ops,
                   spans=recorded.spans + nested)
    plain, named = T.reduce(recorded), T.reduce(both)
    assert [name for name, _ in named["idle_gaps"]] == ["sched.wait"]
    assert sum(v for _, v in named["idle_gaps"]) == pytest.approx(
        sum(v for _, v in plain["idle_gaps"]), abs=1e-15)
    for key in ("window_s", "busy_s", "programs", "device_ops"):
        assert named[key] == plain[key]


@pytest.fixture(scope="module")
def scoped():
    trace = T.Trace.from_json(gzip.decompress(
        (DATA / "scoped_trace.json.gz").read_bytes()))
    scopes = json.loads((DATA / "scoped_op_scopes.json").read_text())
    return trace, scopes


def test_scoped_trace_gaps_name_scheduler_spans(scoped):
    trace, _ = scoped
    r = T.reduce(trace)
    assert r["programs"]["serve_unified"]["calls"] == 1
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"sched.plan", "sched.dispatch", "sched.wait",
                         "sched.harvest"}
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_program_scopes_split_the_step(scoped):
    """Every op of the step goes to one of the three phases but a rest
    under 1%; the packed KV decode is most of the step."""
    trace, scopes = scoped
    lo, hi = T.window(trace)
    dev = "/device:TPU:0"
    (s0, e0), = [(s, e) for n, s, e in T.clip_events(trace.modules[dev],
                                                     lo, hi)
                 if T.program_name(n) == "serve_unified"]
    ops = [ev for ev in trace.ops[dev] if s0 <= ev[1] < e0]
    by_path = defaultdict(float)
    for name, secs in T.self_times(ops):
        by_path[scopes.get(name.lstrip("%"), "")] += secs
    step = (e0 - s0) / 1e9
    assert sum(by_path.values()) == pytest.approx(step, rel=1e-3)
    phase = defaultdict(float)
    for path, secs in by_path.items():
        phase[path.split("/")[0]] += secs
    assert (phase["cass.draft"] + phase["cass.verify"]
            + phase["cass.commit"]) >= 0.97 * step
    assert phase[""] < 0.01 * step
    decode = sum(v for k, v in by_path.items() if "cass.kv_decode" in k)
    assert decode > 0.5 * step
    assert phase["cass.draft"] == pytest.approx(0.2225, rel=0.01)
    assert phase["cass.verify"] == pytest.approx(0.1563, rel=0.01)
    assert phase["cass.commit"] == pytest.approx(0.0166, rel=0.01)
