"""``chip_smoke.py``'s phases at smoke size on the CPU.

The chip run serves Qwen3-1.7B at published widths with the Pallas
kernels; here the same phase and check functions serve the smoke config
with the kernels in interpret mode, so a broken phase, comparison or
``serve.run`` return value shows up without a chip.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")

SHAPE = dict(requests=3, slots=2, prompt_len=8, max_new=6, gamma=3,
             block_size=4)


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def phases(chip_smoke):
    return chip_smoke.run_phases("qwen3-1.7b", smoke=True,
                                 attn_impl="interpret", shape=SHAPE)


def test_serve_run_returns_finished_requests_and_summary(phases):
    for p in phases:
        assert sorted(p.outputs) == list(range(SHAPE["requests"]))
        assert all(len(o) == SHAPE["max_new"] for o in p.outputs.values())
        assert all(len(t) == SHAPE["prompt_len"] for t in p.prompts.values())
        assert p.summary["finished"] == SHAPE["requests"]
        assert p.summary["wall_s"] > 0
    assert phases[0].summary["acceptance"] is None          # bf16 AR
    assert phases[1].summary["acceptance"] is not None
    assert phases[2].summary["subsystems"]["attn_kernel"] == "interpret"


def test_phases_agree_token_for_token(chip_smoke, phases):
    a, b, c = (p.outputs for p in phases)
    assert a == b == c
    ok, lines = chip_smoke.judge(
        phases, chip_smoke.reference_logits_fn("qwen3-1.7b", smoke=True),
        SHAPE["max_new"])
    assert ok, lines
    assert sum("token for token" in line for line in lines) == 2


def test_judge_fails_a_divergence_that_is_no_near_tie(chip_smoke, phases):
    """A token swapped for one the reference ranks far below the top
    is a fault, not a near-tie."""
    import dataclasses
    import numpy as np
    ref = chip_smoke.reference_logits_fn("qwen3-1.7b", smoke=True)
    a = phases[0]
    seq = np.concatenate([a.prompts[0], np.asarray(a.outputs[0], np.int32)])
    z = ref(seq)[SHAPE["prompt_len"] - 1 + 2]
    worst = int(np.argmin(z))
    bad = dict(phases[1].outputs)
    bad[0] = bad[0][:2] + [worst] + bad[0][3:]
    broken = [a, dataclasses.replace(phases[1], outputs=bad), phases[2]]
    ok, lines = chip_smoke.judge(broken, ref, SHAPE["max_new"])
    assert not ok
    assert any("request 0 first diverges at token 2" in line
               and "FAIL" in line for line in lines), lines


def test_main_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err
