"""The ``deepseek_v2`` model type: a served tiny run judged by its plain
reference, the reference's fp8 control, a planted fault, and the work
count by hand. All at toy widths on the CPU, through the whole harness
except its look for a chip (``harness.run_cell`` is called directly)."""
import json
import shutil
import time

import numpy as np
import pytest

import harness
from conftest import BENCH, DATA, TINY_MIX

CELL = "tiny-dsv2.reason"
SEED = 2**33 + 7


@pytest.fixture
def dsv2_root(tiny_root):
    """The tiny checkout with a DeepSeek-V2 cell: 3 layers (one dense),
    a share of 4 of 8 routed experts, the toy closed mix."""
    bench_dir = tiny_root / "bench"
    shutil.copy(DATA / "tiny-dsv2.json",
                bench_dir / "configs" / "tiny-dsv2.json")
    shutil.copy(DATA / "limits.json", bench_dir / "cells" / f"{CELL}.json")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dsv2", "source": "test",
                             "file": "bench/configs/tiny-dsv2.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-dsv2",
                               "traffic": TINY_MIX, "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return tiny_root


def run(root, **kw):
    return harness.run_cell(root, CELL, SEED, 4.0, False,
                            time.perf_counter(), "TPU v5 lite", **kw)


def test_sound_run_is_correct_and_fp8_control_is_not(dsv2_root):
    res = run(dsv2_root, controls=("fp8",))
    limit = res["checks"]["widest_gap"]["limit"]
    assert res["correct"], res["checks"]
    assert res["compare"]["tokens_compared"] >= 8
    assert res["window_compiles"] == 0
    assert res["control"]["fp8"]["widest_gap"] > limit
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0


def test_altered_token_is_not_correct(dsv2_root, monkeypatch):
    """A token altered where the scheduler hands it out."""
    from repro.serving.scheduler import Scheduler
    harvest = Scheduler._harvest_decode_row

    def altered(self, req, tokens, valid, n, nxt, cycle=None):
        tokens = np.array(tokens)
        tokens[req.slot, 0] = (tokens[req.slot, 0] + 1) % 512
        return harvest(self, req, tokens, valid, n, nxt, cycle=cycle)

    monkeypatch.setattr(Scheduler, "_harvest_decode_row", altered)
    res = run(dsv2_root)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > \
        10 * res["checks"]["widest_gap"]["limit"]


def test_deepseek_v2_lite_work_by_hand():
    conf = json.loads((BENCH / "configs" / "deepseek-v2-lite.json"
                       ).read_text())
    dsv2 = harness.load_arch(BENCH, conf)
    # MLA: q 2048x(16x192), kv_a 2048x576, kv_b 512x(16x256), o 2048x2048
    attention = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert attention == 13_762_560
    dense = attention + 3 * 2048 * 10944                  # layer 0
    # router 2048x64, shared SwiGLU of 2816, and 6 of 64 experts of 1408
    # picked a token, 8 of the 64 held here: 0.75 of an expert
    expert = attention + 131_072 + 3 * 2048 * 2816 \
        + 0.75 * 3 * 2048 * 1408
    assert dense == 81_002_496 and expert == 37_683_200
    total = dense + 12 * expert + 2048 * 102400           # untied head
    assert dsv2.matmul_params(conf) == total == 742_916_096
    # position 9 sees 10 keys: 13 layers x 2 x 16 heads x (128 + 64 + 128)
    assert dsv2.token_flops(conf, [9]) == 2 * total + 13 * 10_240 * 10
    assert dsv2.span_flops(conf, 100, 104) == sum(
        dsv2.token_flops(conf, [p]) for p in range(100, 104))


def test_deepseek_v2_lite_file_gives_its_numbers_once_in_two_forms():
    """The file gives the served config.json at its top level, in the
    catalog's form, and as ``hf_config``: the two agree key for key, and
    a file whose two forms disagree is refused."""
    conf = json.loads((BENCH / "configs" / "deepseek-v2-lite.json"
                       ).read_text())
    dsv2 = harness.load_arch(BENCH, conf)
    hf = dsv2.published(conf)
    assert {k for k in hf if k != "architectures"} <= set(conf)
    assert hf["first_k_dense_replace"] == conf["first_k_dense_replace"] == 1
    with pytest.raises(ValueError, match="num_hidden_layers"):
        dsv2.published({**conf, "num_hidden_layers": 27})
