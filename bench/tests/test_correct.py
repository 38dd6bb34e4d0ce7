"""What decides ``correct``: the reference, its control and a planted fault.

All at toy widths on the CPU, through the whole harness except its look
for a chip (``harness.run_cell`` is called directly).
"""
import json
import time

import numpy as np
import pytest

import harness
import reference as R
from conftest import DATA, TINY_CELL

SEED = 2**33 + 5


def run(root, cell, **kw):
    return harness.run_cell(root, cell, SEED, 4.0, False,
                            time.perf_counter(), "TPU v5 lite", **kw)


@pytest.mark.parametrize("arch", [{"qk_norm": True, "qkv_bias": False},
                                  {"qk_norm": False, "qkv_bias": True}])
def test_reference_weights_equal_the_loaders(arch):
    """The reference draws its own weights; they must be the program's,
    bit for bit, or it would judge another model."""
    from repro.models import init_params
    conf = json.loads((DATA / "tiny.json").read_text())
    conf["architecture"].update(arch)
    key = harness.weights_key(conf)
    prog = init_params(harness.program_config(conf), key)
    ref = R.init_weights(R.Dims.from_config(conf), key)
    np.testing.assert_array_equal(prog["embed"]["table"], ref["embed"])
    layer = prog["dec"][0]["e0"]
    pairs = {"wq": layer["attn"]["wq"], "wk": layer["attn"]["wk"],
             "wv": layer["attn"]["wv"], "wo": layer["attn"]["wo"],
             "w_up": layer["ffn"]["w_up"], "w_down": layer["ffn"]["w_down"],
             "w_gate": layer["ffn"]["w_gate"]}
    for name, p in pairs.items():
        np.testing.assert_array_equal(p["w"], ref["layers"][name], name)
    if arch["qkv_bias"]:
        for n in "qkv":
            np.testing.assert_array_equal(layer["attn"][f"w{n}"]["b"],
                                          ref["layers"][f"b{n}"])


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
