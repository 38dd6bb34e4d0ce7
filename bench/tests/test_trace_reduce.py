"""Trace reduction: busy union, per-program time, gap attribution."""
import gzip
from pathlib import Path

import pytest

import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000                       # ns


def small_trace():
    """A 100 ms window on one chip: two executions of the step program,
    the second overlapping a copy, and host spans that explain each gap."""
    dev = "/device:TPU:0"
    return T.Trace(
        modules={dev: [("jit_serve_unified(12)", 10 * MS, 40 * MS),
                       ("jit_serve_unified(12)", 60 * MS, 90 * MS),
                       ("jit_copy(3)", 85 * MS, 95 * MS),
                       ("jit_serve_unified(12)", 99 * MS, 130 * MS)]},
        ops={dev: [("fusion.1", 10 * MS, 25 * MS),
                   ("fusion.2", 25 * MS, 40 * MS),
                   ("fusion.1", 60 * MS, 80 * MS),
                   ("fusion.2", 80 * MS, 90 * MS),
                   ("copy.3", 85 * MS, 95 * MS),
                   ("fusion.1", 99 * MS, 130 * MS)]},
        spans=[("bench.window", 0, 100 * MS),
               ("bench.step", 0, 50 * MS),
               ("bench.wait", 40 * MS, 58 * MS),
               ("bench.step", 58 * MS, 100 * MS)])


def test_busy_is_the_union_clipped_to_the_window():
    r = T.reduce(small_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # 10-40, 60-95 (copy overlaps), 99-100 (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(0.030 + 0.035 + 0.001)
    assert r["devices"] == 1


def test_programs_count_executions_that_start_in_the_window():
    p = T.reduce(small_trace())["programs"]
    assert p["serve_unified"]["calls"] == 3
    assert p["serve_unified"]["seconds"] == pytest.approx(0.030 + 0.030
                                                          + 0.001)
    assert p["copy"] == {"seconds": pytest.approx(0.010), "calls": 1}


def test_gaps_go_to_the_innermost_span_at_their_midpoint():
    r = T.reduce(small_trace())
    gaps = dict(r["idle_gaps"])
    # 0-10 in step; 40-60 midpoint 50 in wait; 95-99 in the second step
    assert gaps == {"bench.step": pytest.approx(0.014),
                    "bench.wait": pytest.approx(0.020)}
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.015 + 0.020 + 0.001)


def test_json_round_trip():
    t = small_trace()
    assert T.reduce(T.Trace.from_json(t.to_json())) == T.reduce(t)


def test_recorded_chip_trace():
    """A few cycles of a traced run on one TPU v5e, as load_xplane kept
    them: device busy must lie within the window, and the step program
    must be found."""
    path = DATA / "recorded_trace.json.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    r = T.reduce(T.Trace.from_json(gzip.decompress(path.read_bytes())))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["programs"]["serve_unified"]["calls"] > 0
    idle = r["window_s"] - r["busy_s"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
