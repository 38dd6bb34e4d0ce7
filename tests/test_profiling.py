"""Profiler hooks: the fused step's named phases in its compiled text, the
scheduler's host spans in a ``jax.profiler`` trace, and serving under an
active profiler bitwise equal to serving without one.

One packed, paged scheduler at smoke widths (module-scoped: compiled
once) serves the same requests twice, the second time inside
``jax.profiler.trace``; the tests read those two runs, the trace they
left and the step's compiled text. ``data/unified_step_v5e.hlo.txt.gz``
is the same step compiled for one TPU v5e at the benchmark cell's
shapes, as ``Scheduler.unified_step_text()`` returned it there.
"""
import glob
import gzip
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.format import CassandraConfig
from repro.core.packing import format_params, resolve_views
from repro.models import init_params
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import hlo_op_scopes

jax.config.update("jax_platform_name", "cpu")

GAMMA = 2
MAX_NEW = 6
PHASE_SCOPES = ("cass.draft", "cass.verify", "cass.commit")
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("llama3-8b", smoke=True)
    cass = CassandraConfig(variant=1, gamma=GAMMA)
    params = resolve_views(
        format_params(init_params(cfg, jax.random.PRNGKey(0)), cass), cass)
    sched = Scheduler(cfg, params, cass=cass, ecfg=EngineConfig(gamma=GAMMA),
                      num_slots=2, s_max=32, rt_extra={"ssm_chunk": 8},
                      paged=True, block_size=4, chunk_size=4)

    def serve():
        sched.reset()
        reqs = [sched.submit(np.arange(6 + i) % cfg.vocab_size,
                             max_new=MAX_NEW, arrival=float(i))
                for i in range(3)]
        sched.run()
        return [list(r.output) for r in reqs]

    plain = serve()
    directory = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(directory)):
        profiled = serve()
    counts = dict(sched.trace_counts)
    text = sched.unified_step_text()
    return {"sched": sched, "plain": plain, "profiled": profiled,
            "counts": counts, "text": text,
            "xplane": sorted(glob.glob(str(directory / "**/*.xplane.pb"),
                                       recursive=True))}


def test_profiled_serving_is_bitwise_lossless(served):
    assert served["profiled"] == served["plain"]
    assert all(len(o) >= MAX_NEW for o in served["plain"])
    # neither the spans nor the lowering for the text traced anew
    assert served["counts"]["unified"] == 1
    assert served["sched"].trace_counts == served["counts"]


def test_step_text_names_every_phase(served):
    scopes = hlo_op_scopes(served["text"])
    parts = {c for path in scopes.values() for c in path.split("/")}
    assert set(PHASE_SCOPES) | {"cass.kv_view_write", "cass.kv_derive",
                                "cass.kv_gather"} <= parts
    # the passes read the views the cache keeps: nothing decodes the
    # packed KV, and commit writes the new tokens' views, the draft view
    # derived from the target
    paths = set(scopes.values())
    assert "cass.commit/cass.kv_view_write/cass.kv_derive" in paths
    assert "cass.kv_decode" not in parts
    assert served["sched"].summary()["subsystems"]["kv_pool_decodes"] == 0


def test_no_op_falls_under_two_phases(served):
    for name, path in hlo_op_scopes(served["text"]).items():
        phases = [c for c in path.split("/") if c in PHASE_SCOPES]
        assert len(phases) == 1, (name, path)


def test_trace_holds_scheduler_spans_and_scoped_ops(served):
    from jax.profiler import ProfileData
    assert served["xplane"], "the profiler wrote no trace"
    prof = ProfileData.from_file(served["xplane"][-1])
    spans, ops = set(), []
    for plane in prof.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sched."):
                    spans.add(ev.name)
                elif dict(ev.stats).get("hlo_module") == "jit_serve_unified":
                    ops.append(ev.name)
    assert {"sched.admit", "sched.plan", "sched.dispatch", "sched.wait",
            "sched.harvest"} <= spans
    # the trace names the step's ops as its compiled text does, so the
    # text's scope map labels (nearly) every one of them
    scopes = hlo_op_scopes(served["text"])
    assert ops
    assert sum(op in scopes for op in ops) >= 0.95 * len(ops)


HLO = """\
HloModule jit_step, is_scheduled=true

%fused_decode (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %sin.1 = f32[4]{0} sine(%param_0.1), metadata={op_name="jit(step)/cass.verify/while/body/cass.kv_decode/sin"}
}

%body (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg.1), index=1
  %copy.7 = f32[4]{0} copy(%gte.1)
  %sin_fusion = f32[4]{0} fusion(%copy.7), kind=kLoop, calls=%fused_decode
  %copy.8 = f32[4]{0} copy(%gte.1)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%copy.8, %sin_fusion)
}

%cond (arg.2: (s32[], f32[4])) -> pred[] {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true), metadata={op_name="jit(step)/cass.verify/while/cond/lt"}
}

ENTRY %main.9 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %convert.3 = f32[4]{0} convert(%p.1)
  %key.7 = f32[4]{0} sine(%p.1), metadata={op_name="jit(step)/sin"}
  %draft = f32[4]{0} add(%convert.3, %key.7), metadata={op_name="jit(step)/cass.draft/add" source_file="x.py"}
  %while.2 = (s32[], f32[4]{0}) while(%draft), condition=%cond, body=%body, metadata={op_name="jit(step)/cass.verify/while"}
  %gte.2 = f32[4]{0} get-tuple-element(%while.2), index=1
  %neg.4 = f32[4]{0} negate(%gte.2), metadata={op_name="jit(step)/neg"}
  %add.5 = f32[4]{0} add(%neg.4, %key.7), metadata={op_name="jit(step)/cass.commit/add"}
  ROOT %copy.9 = f32[4]{1:T(128)} copy(%add.5)
}
"""


def test_hlo_op_scopes_parses_fusions_whiles_and_roots():
    scopes = hlo_op_scopes(HLO)
    # own metadata, a ROOT line, a while op and a fused op inside
    assert scopes["draft"] == "cass.draft"
    assert scopes["add.5"] == "cass.commit"
    assert scopes["while.2"] == "cass.verify"
    assert scopes["sin.1"] == "cass.verify/cass.kv_decode"
    assert scopes["lt.1"] == "cass.verify"
    # ops under no scope: a fusion takes its fused computation's scope,
    # a copy, convert or select its users', a loop body's copy feeding
    # only the loop's result the loop's
    assert scopes["sin_fusion"] == "cass.verify/cass.kv_decode"
    assert scopes["copy.7"] == "cass.verify/cass.kv_decode"
    assert scopes["copy.8"] == "cass.verify"
    assert scopes["convert.3"] == "cass.draft"
    assert scopes["neg.4"] == scopes["gte.2"] == "cass.commit"
    # with no users and in no loop, an output's relayout its operands'
    assert scopes["copy.9"] == "cass.commit"
    # an op whose users share no scope, in no loop, is left out
    assert "key.7" not in scopes and "p.1" not in scopes


def test_hlo_op_scopes_on_the_v5e_step_text():
    """The fused step as compiled for one TPU v5e at Qwen3-1.7B widths
    (28 layers, 4 slots, a 401-block packed pool): the ops that took most
    device time in a traced run there get their phase, compiler copies
    included, and a relayout of a pool leaf that both phases read gets
    none."""
    text = gzip.decompress(
        (DATA / "unified_step_v5e.hlo.txt.gz").read_bytes()).decode()
    scopes = hlo_op_scopes(text)
    assert set(scopes.values()) == {
        "cass.draft", "cass.draft/cass.kv_decode", "cass.draft/cass.kv_gather",
        "cass.verify", "cass.verify/cass.kv_decode",
        "cass.verify/cass.kv_gather", "cass.commit"}
    assert scopes["fusion.560"] == "cass.draft/cass.kv_decode"
    assert scopes["fusion.1187"] == "cass.verify/cass.kv_decode"
    assert scopes["bitcast_add_fusion.10"] == "cass.draft"
    assert scopes["fusion.1205"] == "cass.verify"
    assert scopes["fusion.1113"] == "cass.draft/cass.kv_gather"
    assert scopes["copy.1899"] == "cass.commit"     # the cache's relayout
    assert "copy.1734" not in scopes


def test_serve_profile_dir_writes_trace_and_scope_map(tmp_path):
    """``serve --profile-dir`` leaves the profiler's trace and the fused
    step's op -> phase map beside it."""
    import json
    from repro.launch import serve
    done, _ = serve.run([
        "--arch", "llama3-8b", "--smoke", "--variant", "1", "--gamma", "2",
        "--scheduler", "--paged", "--block-size", "4", "--slots", "2",
        "--requests", "2", "--max-new", "4", "--prompt-len", "6",
        "--profile-dir", str(tmp_path)])
    assert len(done) == 2
    assert glob.glob(str(tmp_path / "**/*.xplane.pb"), recursive=True)
    scopes = json.loads((tmp_path / "op_scopes.json").read_text())
    assert {"cass.commit/cass.kv_view_write/cass.kv_derive",
            "cass.commit"} <= set(scopes.values())
