"""What decides ``correct``: the reference, its control and a planted fault.

All at toy widths on the CPU, through the whole harness except its look
for a chip (``harness.run_cell`` is called directly).
"""
import json
import time

import jax
import numpy as np
import pytest

import harness
from conftest import BENCH, DATA, TINY_CELL

SEED = 2**33 + 5


def run(root, cell, **kw):
    return harness.run_cell(root, cell, SEED, 4.0, False,
                            time.perf_counter(), "TPU v5 lite", **kw)


def weight_cases():
    """Each module under ``bench/arch`` with each test configuration of
    its model type (``data/*.json`` by ``hf_config.model_type``); a
    module with none is a case of its own that fails."""
    confs = {}
    for path in sorted(DATA.glob("*.json")):
        model_type = json.loads(path.read_text()).get(
            "hf_config", {}).get("model_type")
        confs.setdefault(model_type, []).append(path.name)
    cases = []
    for module in sorted((BENCH / "arch").glob("*.py")):
        names = confs.get(module.stem, [None])
        cases += [pytest.param(module.stem, n, id=f"{module.stem}-{n}")
                  for n in names]
    return cases


@pytest.mark.parametrize("model_type,conf_name", weight_cases())
def test_reference_weights_equal_the_loaders(model_type, conf_name):
    """The reference draws its own weights; they must be the program's,
    bit for bit, or it would judge another model."""
    from repro.models import init_params
    assert conf_name, f"no test configuration of {model_type} in {DATA}"
    conf = json.loads((DATA / conf_name).read_text())
    arch = harness.load_arch(BENCH, conf)
    key = harness.weights_key(conf)
    prog = arch.loader_weights(
        init_params(harness.program_config(conf, arch), key))
    ref = arch.init_weights(arch.Dims.from_config(conf), key)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    jax.tree_util.tree_map_with_path(
        lambda path, p, r: np.testing.assert_array_equal(
            p, r, jax.tree_util.keystr(path)), prog, ref)


def test_sound_run_is_correct_and_control_is_not(tiny_root):
    res = run(tiny_root, TINY_CELL, controls=("int8", "fp8"))
    limit = res["checks"]["widest_gap"]["limit"]
    assert res["correct"], res["checks"]
    assert res["compare"]["tokens_compared"] >= 8
    assert res["window_compiles"] == 0
    assert res["control"]["fp8"]["widest_gap"] > limit
    assert res["metrics"]["itl_p95_ms"]["value"] > 0


def test_altered_token_is_not_correct(tiny_root, monkeypatch):
    """A token altered where the scheduler hands it out."""
    from repro.serving.scheduler import Scheduler
    harvest = Scheduler._harvest_decode_row

    def altered(self, req, tokens, valid, n, nxt, cycle=None):
        tokens = np.array(tokens)
        tokens[req.slot, 0] = (tokens[req.slot, 0] + 1) % 512
        return harvest(self, req, tokens, valid, n, nxt, cycle=cycle)

    monkeypatch.setattr(Scheduler, "_harvest_decode_row", altered)
    res = run(tiny_root, TINY_CELL)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > \
        10 * res["checks"]["widest_gap"]["limit"]
