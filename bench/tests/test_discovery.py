"""A configuration, a traffic mix, a traffic kind, a cell and a per-layer
metric are added by new files and new entries alone: the harness finds
them by name, and no file that is already there is edited."""
import json
import shutil
import time

import harness
from conftest import DATA, TINY_MIX


def _snapshot(root):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
            and "src" not in p.relative_to(root).parts}


def test_new_files_are_found(tiny_root):
    bench_dir = tiny_root / "bench"
    before = _snapshot(tiny_root)
    conf = json.loads((DATA / "tiny.json").read_text())
    conf["architecture"].update(qk_norm=False, qkv_bias=True)   # Qwen2
    (bench_dir / "configs" / "tiny-qwen2.json").write_text(json.dumps(conf))
    # a new kind of traffic, with a mix of it and the cell's own rate
    shutil.copy(DATA / "steady.py", bench_dir / "traffic" / "steady.py")
    mix = {"kind": "steady", "slots": 2,
           "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 32},
           "max_new": {"dist": "fixed", "value": 6}}
    (bench_dir / "traffic" / "tiny-steady.json").write_text(json.dumps(mix))
    limits = json.loads((DATA / "limits.json").read_text())
    (bench_dir / "cells" / "tiny-qwen2.steady.json").write_text(
        json.dumps({**limits, "rate_per_s": 6.0}))
    (bench_dir / "cells" / "tiny-qwen2.reason.json").write_text(
        json.dumps(limits))
    (bench_dir / "metrics" / "prompt_tokens_offered.py").write_text(
        '"""Prompt tokens offered in the window."""\n\n\n'
        'def read(r):\n'
        '    return sum(rec.n_prompt for rec in r.records)\n')
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-qwen2", "source": "test",
                             "file": "bench/configs/tiny-qwen2.json",
                             "reduced": [], "why": "CPU test"})
    for name, mix_name in (("tiny-qwen2.steady", "tiny-steady"),
                           ("tiny-qwen2.reason", TINY_MIX)):
        bench["workloads"].append({"name": name, "config": "tiny-qwen2",
                                   "traffic": mix_name, "chips": 1,
                                   "why": "CPU test"})
    bench["per_layer"].append({
        "name": "prompt_tokens_offered", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "itl_p95_ms",
        "workloads": ["tiny-qwen2.steady", "tiny-qwen2.reason"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _snapshot(tiny_root)
    edited = [p for p in before if p.name != "BENCHMARK.json"
              and after[p] != before[p]]
    assert edited == []

    res = harness.run_cell(tiny_root, "tiny-qwen2.steady", 3, 2.0, True,
                           time.perf_counter(), "TPU v5 lite")
    assert res["correct"], res["checks"]
    offered = res["metrics"]["prompt_tokens_offered"]
    assert offered["unit"] == "tokens" and offered["value"] > 0
    assert res["attempted"] == round(6.0 * 2.0)

    res = harness.run_cell(tiny_root, "tiny-qwen2.reason", 3, 2.0, False,
                           time.perf_counter(), "TPU v5 lite")
    assert res["correct"], res["checks"]
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
