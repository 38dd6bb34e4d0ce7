"""A configuration of any model family, a traffic mix, a traffic kind, a
cell and a per-layer metric are added by new files and new entries alone:
the harness finds them by name, and no file that is already there is
edited. A configuration's model type (``hf_config.model_type``) names its
module, ``bench/arch/<model_type>.py``, which gives the program's
``ModelConfig`` (``program_config``), the plain reference (``Dims``,
``init_weights``, ``gaps``, ``loader_weights``) and the useful-work count
(``token_flops``, ``span_flops``); ``harness.load_arch`` says more."""
import json
import shutil
import time

import pytest

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


def test_new_model_type_is_found(tiny_root):
    """A configuration of a model type that has no module yet, added with
    its module (here the qwen3 module copied under the name ``qwen2``),
    its mix and its cell, reaches ``correct``."""
    bench_dir = tiny_root / "bench"
    before = _snapshot(tiny_root)
    shutil.copy(bench_dir / "arch" / "qwen3.py",
                bench_dir / "arch" / "qwen2.py")
    conf = json.loads((DATA / "tiny-bias.json").read_text())
    conf["hf_config"]["model_type"] = "qwen2"
    (bench_dir / "configs" / "tiny-2.json").write_text(json.dumps(conf))
    shutil.copy(DATA / "limits.json",
                bench_dir / "cells" / "tiny-2.reason.json")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-2", "source": "test",
                             "file": "bench/configs/tiny-2.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-2.reason", "config": "tiny-2",
                               "traffic": TINY_MIX, "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-2.reason")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _snapshot(tiny_root)
    assert [p for p in before if p.name != "BENCHMARK.json"
            and after[p] != before[p]] == []

    res = harness.run_cell(tiny_root, "tiny-2.reason", 5, 2.0, False,
                           time.perf_counter(), "TPU v5 lite")
    assert res["correct"], res["checks"]
    assert res["compare"]["tokens_compared"] >= 8


def test_model_type_without_module_fails_at_once(tiny_root):
    conf_path = tiny_root / "bench" / "configs" / "tiny.json"
    conf = json.loads(conf_path.read_text())
    conf["hf_config"]["model_type"] = "no_such_type"
    conf_path.write_text(json.dumps(conf))
    t0 = time.perf_counter()
    with pytest.raises(FileNotFoundError, match="no_such_type"):
        harness.run_cell(tiny_root, "tiny.reason", 5, 2.0, False, t0,
                         "TPU v5 lite")
    assert time.perf_counter() - t0 < 5.0


def test_non_dense_model_is_served(tiny_root):
    """A module whose ``program_config`` gives a mixture-of-experts
    ``ModelConfig``: set-up, warm-up and the traffic's window serve it as
    they serve a dense one."""
    bench_dir = tiny_root / "bench"
    shutil.copy(DATA / "tiny_moe.py", bench_dir / "arch" / "tiny_moe.py")
    conf = json.loads((DATA / "tiny-moe.json").read_text())
    arch = harness.load_arch(bench_dir, conf)
    assert harness.program_config(conf, arch).family == "moe"
    mix = json.loads((DATA / f"{TINY_MIX}.json").read_text())
    kind = harness.load_kind(bench_dir, mix)
    vocab = conf["hf_config"]["vocab_size"]
    sched = harness.build(conf, arch, mix, kind, False)
    harness.warm_up(sched, vocab, 5)
    state = kind.start(sched, mix, {}, 5, vocab)
    win = kind.window(sched, state, 1.0, harness.WindowTrace(None))
    served = [t for r in win.records if r.req is not None
              for t in r.req.output]
    assert len(served) > len(win.records)
    assert all(0 <= t < vocab for t in served)
