"""One benchmark run of one cell: set-up, measured window, readings, check.

Everything is found by name. The cell in ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); the mix's ``kind`` names the module
that offers its requests and drives the window (``bench/traffic/<kind>.py``,
see :func:`load_kind`); the cell's own numbers, its correctness limit
among them, are in ``bench/cells/<cell>.json``; each metric is read by
``bench/metrics/<metric>.py`` from the :class:`Readings` of the run.

The system under test is the program's normal served path: weights from
``repro.models.init_params`` at the configuration's fixed ``weights_key``,
``repro.core.packing.format_params`` and ``resolve_views``, and
``repro.serving.scheduler.Scheduler`` in paged mode with the program's
defaults for everything the configuration does not pin. The run's seed
draws the traffic only, so every seed serves the same model. The window
drives ``Scheduler.submit`` and ``Scheduler.step`` alone; the benchmark
keeps the wall clock and the token stamps' reading itself.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from trace_reduce import WINDOW_SPAN

SAMPLE_REQUESTS = 8          # requests compared, at most


@dataclasses.dataclass
class RequestRecord:
    index: int
    user: int
    n_prompt: int
    due: float | None            # perf_counter the request fell due
    submitted: float | None = None
    failed: str | None = None
    req: object = None           # the scheduler's Request

    @classmethod
    def of(cls, spec, due: float | None) -> "RequestRecord":
        return cls(index=spec.index, user=spec.user,
                   n_prompt=len(spec.prompt), due=due)


@dataclasses.dataclass
class Window:
    """What a traffic kind's ``window`` hands back."""
    records: list                # every request offered, set-up's included
    t0: float                    # perf_counter at window open
    t1: float                    # perf_counter at window close
    t_end: float                 # perf_counter when following stopped
    at_close: tuple              # snapshot() at the close


@dataclasses.dataclass
class Readings:
    """What one run recorded; every metric reader takes its number here."""
    t0: float                    # perf_counter at window open
    t1: float                    # perf_counter at window close
    t_end: float                 # perf_counter when following stopped
    setup_s: float
    records: list
    counters0: dict
    counters1: dict
    walls0: dict                 # bucket -> [calls, seconds]
    walls1: dict
    summary: dict                # Scheduler.summary() after the window
    events: list                 # lifecycle tracer events (traced runs)
    peak_bytes: int
    hf: dict                     # the configuration's published numbers
    peaks: dict                  # the device's peak rates
    trace: dict | None = None    # trace_reduce.reduce() of the traced run
    traced: tuple | None = None  # perf_counter start, stop of the trace

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def stamps(self, rec: RequestRecord) -> list:
        return list(rec.req.token_walls) if rec.req is not None else []


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(bench_dir: Path, mix: dict):
    """The module of a mix's ``kind``: ``bench/traffic/<kind>.py``. It
    gives ``max_context(mix)``, the longest prompt plus output it sends;
    ``start(sched, mix, cell, seed, vocab)``, the set-up that the traffic
    needs, returning its state; and ``window(sched, state, seconds, tr)``,
    which opens the profiler window ``tr``, offers the requests for
    ``seconds`` and returns a :class:`Window`."""
    path = Path(bench_dir) / "traffic" / f"{mix['kind']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic kind {mix['kind']!r} at {path}")
    return load_module(path)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry of ``arch`` with every size taken from the file."""
    from repro.configs import get_config
    hf, arch = conf["hf_config"], conf["architecture"]
    base = get_config(conf["arch"])
    if base.family != "dense":
        raise ValueError(f"{conf['arch']}: only dense decoders are served "
                         "by this harness")
    return dataclasses.replace(
        base, n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], qk_norm=arch["qk_norm"],
        qkv_bias=arch["qkv_bias"])


def weights_key(conf: dict):
    """The PRNG key of the configuration's weights: fixed in its file, so
    that every seed serves the same model."""
    import jax
    return jax.random.PRNGKey(int(conf["weights_key"]))


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        self.compiles = self.cache_loads = 0
        self.seconds = 0.0

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory(phase: str) -> dict:
    """The allocator's bytes in use and peak on the fullest chip now,
    printed on standard error: which phase sets the process's peak."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    m = {"phase": phase,
         "bytes_in_use": max(int(s.get("bytes_in_use", 0)) for s in stats),
         "peak_bytes_in_use": max(int(s.get("peak_bytes_in_use", 0))
                                  for s in stats)}
    print(f"[mem] {phase}: in use {m['bytes_in_use']} B, peak "
          f"{m['peak_bytes_in_use']} B", file=sys.stderr, flush=True)
    return m


class WindowTrace:
    """The profiler over the first ``TRACE_S`` seconds of the window (all
    of it when shorter), in a run of its own: ten seconds hold some 25
    steady cycles, and a longer trace only costs minutes to read.
    ``directory`` None traces nothing."""

    TRACE_S = 10.0

    def __init__(self, directory: Path | None):
        self.directory, self.active = directory, False
        self.t0 = self.t1 = None

    def start(self) -> None:
        if self.directory is None:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.directory),
                                 profiler_options=opts)
        self._span = span(WINDOW_SPAN)
        self._span.__enter__()
        self.t0, self.active = time.perf_counter(), True

    def tick(self) -> None:
        if self.active and time.perf_counter() - self.t0 >= self.TRACE_S:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def build(conf: dict, mix: dict, kind, trace: bool):
    """Weights -> packed format -> dense views -> paged scheduler."""
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params, resolve_views
    from repro.models import init_params
    from repro.serving.engine import EngineConfig
    from repro.serving.scheduler import Scheduler
    from repro.serving.telemetry import Telemetry

    cfg = program_config(conf)
    srv = conf["serving"]
    gamma = srv["gamma"]
    cass = CassandraConfig(variant=srv["variant"], gamma=gamma)
    params = init_params(cfg, weights_key(conf))
    params = format_params(params, cass)
    params = resolve_views(params, cass)
    s_max = kind.max_context(mix) + gamma + 1
    sched = Scheduler(cfg, params, cass=cass,
                      ecfg=EngineConfig(gamma=gamma, greedy=srv["greedy"]),
                      num_slots=mix["slots"], s_max=s_max, paged=True,
                      block_size=srv["block_size"],
                      num_blocks=srv["kv_pool_blocks"],
                      chunk_size=srv["chunk_size"],
                      telemetry=Telemetry(trace=trace, trace_capacity=1 << 20))
    return sched


def warm_up(sched, vocab: int, seed: int) -> None:
    """Run every program the window can dispatch once, then reset: a
    wide admission chunk (empty decode pool), mixed cycles (a prompt
    riding decode rows), drained and free-running decode, retirement."""
    rng = np.random.default_rng([int(seed), 9])
    g = sched.ecfg.gamma
    long = rng.integers(0, vocab, sched.chunk_size + g + 2)
    sched.submit(long, max_new=4 * (g + 1), rid=0)
    sched.step()
    for i in range(1, sched.num_slots):
        sched.submit(rng.integers(0, vocab, 2 * (g + 1) + i),
                     max_new=2 * (g + 1) + i, rid=i)
    while not sched.idle:
        sched.step()
    sched.reset()


# ---------------------------------------------------------------------------
# What traffic kinds call
# ---------------------------------------------------------------------------

def snapshot(sched) -> tuple[dict, dict]:
    """The scheduler's counters and wall buckets (calls, seconds) now."""
    return (dict(sched.metrics.counters),
            {k: list(v) for k, v in sched.metrics.walls.items()})


def submit(sched, rec: RequestRecord, spec) -> None:
    """Offer one request; a refusal is recorded as the request's failure."""
    with span("bench.submit"):
        try:
            rec.req = sched.submit(spec.prompt, max_new=spec.max_new,
                                   arrival=sched.clock, rid=spec.index)
        except ValueError as e:
            rec.failed = f"refused: {e}"
    rec.submitted = time.perf_counter()


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def compare(conf: dict, seed: int, records,
            control: str | None = None) -> dict:
    """Reference gaps of every token served so far to every request, or,
    past ``SAMPLE_REQUESTS`` of them, to a sample drawn from the seed with
    the one with most served tokens always in it."""
    import reference as R
    served = [r for r in records if r.req is not None and r.req.output]
    if len(served) > SAMPLE_REQUESTS:
        longest = max(served, key=lambda r: len(r.req.output))
        rest = [r for r in served if r is not longest]
        rng = np.random.default_rng([int(seed), 11])
        pick = rng.permutation(len(rest))[:SAMPLE_REQUESTS - 1]
        served = [longest] + [rest[i] for i in sorted(pick)]
    dims = R.Dims.from_config(conf)
    weights = R.init_weights(dims, weights_key(conf))
    gaps, n = [], 0
    for r in served:
        g = R.gaps(dims, weights, np.asarray(r.req.tokens),
                   np.asarray(r.req.output, np.int32), control=control)
        gaps.append(float(g.max()))
        n += len(g)
    del weights
    return {"widest_gap": max(gaps) if gaps else None,
            "tokens_compared": n, "requests_compared": len(served),
            "per_request": gaps}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, device_kind: str,
             controls: tuple = ()) -> dict:
    """Set up, measure, read and check one run; returns the result line's
    fields (without ``device``) and the numbers compared. ``controls``
    (``"int8"``, ``"fp8"``) also read each control's gaps on the same
    prompts and served tokens (``bench/calibrate.py``; the benchmark's own
    runs never do)."""
    import jax
    bench = load_json(root / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == cell_name),
                None)
    if cell is None:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    conf = load_json(root / conf_entry["file"])
    bench_dir = root / "bench"
    mix = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    own = load_json(bench_dir / "cells" / f"{cell_name}.json")
    kind = load_kind(bench_dir, mix)
    peaks = load_json(bench_dir / "peaks.json")
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    section = "per_layer" if trace else "end_to_end"
    wanted = cell_metrics(bench, cell_name, section)
    vocab = conf["hf_config"]["vocab_size"]

    phases = []
    sched = build(conf, mix, kind, trace)
    phases.append(memory("built"))
    warm_up(sched, vocab, seed)
    phases.append(memory("warmed up"))
    state = kind.start(sched, mix, own, seed, vocab)
    phases.append(memory("set up"))

    c0, w0 = snapshot(sched)
    trace_dir = root / ".bench" / "trace"
    tr = WindowTrace(trace_dir if trace else None)
    with CompileCounter() as cc:
        setup_s = time.perf_counter() - t_start
        win = kind.window(sched, state, seconds, tr)
    c1, w1 = win.at_close
    summary = sched.summary()
    events = list(sched.tracer.events()) if trace else []
    phases.append(memory("window closed"))
    peak = phases[-1]["peak_bytes_in_use"]
    readings = Readings(
        t0=win.t0, t1=win.t1, t_end=win.t_end, setup_s=setup_s,
        records=win.records, counters0=c0, counters1=c1,
        walls0=w0, walls1=w1, summary=summary, events=events,
        peak_bytes=peak, hf=conf["hf_config"],
        peaks=peaks["devices"][device_kind],
        traced=(tr.t0, tr.t1) if trace else None)
    del sched, state
    gc.collect()

    breakdown = None
    if trace:
        import trace_reduce as T
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        if files:
            readings.trace = T.reduce(T.load_xplane(files[-1]))
            breakdown = {k: readings.trace[k]
                         for k in ("device_ops", "idle_gaps")}
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in wanted:
        value = load_module(bench_dir / "metrics" / f"{m['name']}.py"
                            ).read(readings)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    records = win.records
    cmp = compare(conf, seed, records)
    ctl = {c: compare(conf, seed, records, control=c) for c in controls}
    checks = {
        "widest_gap": {"value": cmp["widest_gap"],
                       "limit": own["widest_gap_max"]},
        "tokens_compared": {"value": cmp["tokens_compared"],
                            "limit": own["tokens_compared_min"]}}
    correct = (cmp["widest_gap"] is not None
               and cmp["widest_gap"] <= own["widest_gap_max"]
               and cmp["tokens_compared"] >= own["tokens_compared_min"])
    return {"correct": bool(correct), "attempted": len(records),
            "failed": sum(1 for r in records if r.failed),
            "metrics": metrics, "breakdown": breakdown,
            "checks": checks, "compare": cmp, "control": ctl,
            "window_compiles": cc.compiles + cc.cache_loads,
            "memory_peak_bytes": peak, "memory_phases": phases,
            "pool": {k: summary.get(k) for k in
                     ("pool_blocks", "pool_high_water_blocks")},
            "trace": readings.trace}
