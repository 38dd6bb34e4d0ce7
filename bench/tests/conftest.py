"""CPU tests of the benchmark: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.

``tiny_root`` builds a checkout-shaped directory in a temp dir: a copy of
``bench/``, the program's ``src`` linked in, and a ``BENCHMARK.json`` with
the real cells plus a toy cell (``tiny.reason``) at toy widths that the
CPU serves in seconds.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(REPO / "src")]

TINY_CELL, TINY_MIX = "tiny.reason", "tiny-reason"


def make_root(base: Path) -> Path:
    root = base / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    shutil.copy(DATA / "tiny.json", root / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / f"{TINY_MIX}.json",
                root / "bench" / "traffic" / f"{TINY_MIX}.json")
    shutil.copy(DATA / "limits.json",
                root / "bench" / "cells" / f"{TINY_CELL}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": TINY_MIX, "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_root(tmp_path)
