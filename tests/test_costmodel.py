"""Online measured cost model: bucket fits, cold-start fallback, and the
cycle<->ms exchange rate the SLO planner trades in.

Pure host-side unit tests (no model, no jit) — the scheduler-integrated
behavior (observations from ``_stamp_wall``, bitwise-default gating) is
pinned in ``test_scheduler.py``.
"""
import pytest

from repro.serving.costmodel import BucketCost, CostModel


def test_cold_start_is_the_cycle_unit_model():
    """Unmeasured, every bucket costs one nominal cycle and ms<->cycles
    is the identity — measured-cost comparisons degrade to exactly the
    cycle-count comparisons the pre-SLO planner made."""
    cm = CostModel()
    assert not cm.warm
    assert cm.bucket_ms("unified") == 1.0
    assert cm.bucket_ms("chunk") == 1.0
    assert cm.cycle_ms() == 1.0
    assert cm.ms_to_cycles(7.5) == 7.5
    assert cm.cycles_to_ms(3.0) == 3.0


def test_observe_running_mean_and_exchange_rate():
    cm = CostModel(warmup_discard=0)
    cm.observe("unified", 4.0)
    cm.observe("unified", 8.0)
    cm.observe("chunk", 2.0)
    assert cm.warm
    assert cm.bucket_ms("unified") == pytest.approx(6.0)
    assert cm.bucket_ms("chunk") == pytest.approx(2.0)
    # unmeasured buckets still fall back to the nominal cycle cost
    assert cm.bucket_ms("spill") == 1.0
    # the exchange rate is the measured decode-cycle mean
    assert cm.cycle_ms() == pytest.approx(6.0)
    assert cm.ms_to_cycles(12.0) == pytest.approx(2.0)
    assert cm.cycles_to_ms(2.0) == pytest.approx(12.0)


def test_decode_bucket_preference_order():
    """A fused run measures "unified"; the alternating/AR baselines
    measure "spec"/"auto" — the exchange rate uses the first present."""
    cm = CostModel(warmup_discard=0)
    cm.observe("auto", 3.0)
    assert cm.cycle_ms() == pytest.approx(3.0)
    cm.observe("spec", 5.0)
    assert cm.cycle_ms() == pytest.approx(5.0)
    cm.observe("unified", 9.0)
    assert cm.cycle_ms() == pytest.approx(9.0)


def test_negative_observations_clamped():
    """A misbehaving clock must never poison the fit (the satellite bug:
    intervals off a non-monotonic clock can be negative)."""
    cm = CostModel(warmup_discard=0)
    cm.observe("unified", -50.0)
    cm.observe("unified", 4.0)
    assert cm.bucket_ms("unified") == pytest.approx(2.0)   # (0 + 4) / 2
    assert cm.cycle_ms() > 0


def test_refresh_refits_from_step_walls():
    """``refresh`` bulk-fits from a ``Scheduler.metrics.walls``-shaped
    dict (name -> [calls, total_seconds]), replacing prior state."""
    cm = CostModel(warmup_discard=0)
    cm.observe("unified", 100.0)
    cm.refresh({"unified": [4, 0.008], "chunk": [2, 0.002]})
    assert cm.bucket_ms("unified") == pytest.approx(2.0)
    assert cm.bucket_ms("chunk") == pytest.approx(1.0)
    # negative totals (pre-fix clocks) clamp to zero, not negative cost
    cm.refresh({"unified": [4, -0.008]})
    assert cm.bucket_ms("unified") == 0.0


def test_tokens_per_call_fit():
    b = BucketCost()
    assert b.ms_per_token is None
    cm = CostModel(warmup_discard=0)
    cm.observe("chunk", 4.0, tokens=8)
    cm.observe("chunk", 4.0, tokens=8)
    assert cm.buckets["chunk"].ms_per_token == pytest.approx(0.5)


def test_warmup_discard_drops_the_compile_call():
    """Each jit bucket's first call pays trace+compile (seconds); the
    default model drops it so the fit is the steady-state cost, not a
    compile-dominated mean that inflates every ms->cycles conversion."""
    cm = CostModel()                       # default: warmup_discard=1
    cm.observe("unified", 3000.0)          # trace + compile
    assert not cm.warm
    assert cm.cycle_ms() == 1.0            # still the cold fallback
    cm.observe("unified", 4.0)
    cm.observe("unified", 6.0)
    assert cm.bucket_ms("unified") == pytest.approx(5.0)
    assert cm.buckets["unified"].discarded == 1
    # each bucket warms independently
    cm.observe("spill", 900.0)
    assert cm.bucket_ms("spill") == 1.0


def test_snapshot_is_json_shaped():
    cm = CostModel(warmup_discard=0)
    cm.observe("unified", 2.0)
    cm.observe("chunk", 3.0, tokens=6)
    snap = cm.snapshot()
    assert snap["warm"] is True
    assert snap["cycle_ms"] == pytest.approx(2.0)
    assert snap["buckets"]["unified"]["calls"] == 1
    assert snap["buckets"]["chunk"]["ms_per_token"] == pytest.approx(0.5)
    assert "ms_per_token" not in snap["buckets"]["unified"]


def test_nominal_cycle_validation():
    with pytest.raises(ValueError, match="nominal_cycle_ms"):
        CostModel(nominal_cycle_ms=0.0)
    with pytest.raises(ValueError, match="warmup_discard"):
        CostModel(warmup_discard=-1)
