"""One-chip smoke run of the served Cassandra path at Qwen3-1.7B widths.

    python chip_smoke.py

Serves the same requests three times through the normal entry point,
``repro.launch.serve.run`` in paged continuous-batching mode, all in this
one process, with seeded random weights at Qwen3-1.7B's published widths
(28 layers, d_model 2048, 16/8 heads, head_dim 128, d_ff 6144, vocab
151,936):

  (a) ``--variant 0``                        bf16 autoregressive baseline
  (b) ``--variant 1``                        Cassandra-1, gather attention
  (c) ``--variant 1 --attn-kernel pallas``   Cassandra-1, Pallas kernels

8 requests through 4 slots, prompt 256, 128 new tokens, gamma 3, KV block
16. Speculative decoding is lossless, so (b) must emit (a)'s tokens and
(c) must emit (b)'s. A full-sequence forward of the same weights is the
reference: its logits must be finite, and its argmax must give the served
tokens of (a) position by position. Tokens may differ only at a near-tie
of that reference (``NEAR_TIE_LOGITS``).

Per phase it prints the device, compile seconds, wall time and tokens/s
(a smoke run, not a benchmark: the wall includes compilation), acceptance,
``peak_bytes_in_use`` and the agreement. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
It exits non-zero without printing that line when JAX finds no TPU, when
a phase raises, a request falls short of its 128 tokens, a logit is not
finite or tokens diverge anywhere but at a near-tie.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "qwen3-1.7b"
SHAPE = dict(requests=8, slots=4, prompt_len=256, max_new=128, gamma=3,
             block_size=16)
SEED = 0

# Two paths that compute the same logits in a different float order can
# rank two tokens differently only where their logits nearly tie. Logits
# are bf16 products (``layers.unembed`` casts after the bf16 matmul): at
# the top logits of a random-init model (|z| in [4, 8)) one bf16 ulp is
# 1/32, and an accumulation-order difference carried through 28 bf16
# layers moves a logit by a few ulps. So a swap whose reference logits
# are within 4 ulps there is a near-tie; a faulty path picks tokens far
# outside the reference's top few (typically several units below).
NEAR_TIE_LOGITS = 0.125


@dataclasses.dataclass
class Phase:
    name: str
    label: str
    outputs: dict            # rid -> list of generated token ids
    prompts: dict            # rid -> (prompt_len,) int32 prompt
    summary: dict
    total_s: float           # serve.run wall: init, formatting, serving
    compile_s: dict          # compiled program name -> seconds
    cache_hits: int
    cache_misses: int
    peak_bytes: int | None


class CompileLog:
    """Collects XLA compile times and persistent-cache hits/misses."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.hits = self.misses = 0

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

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


def serve_argv(arch: str, smoke: bool, variant: int, attn_kernel: str,
               shape: dict) -> list[str]:
    argv = ["--arch", arch, "--scheduler", "--paged",
            "--variant", str(variant), "--attn-kernel", attn_kernel,
            "--requests", str(shape["requests"]),
            "--slots", str(shape["slots"]),
            "--prompt-len", str(shape["prompt_len"]),
            "--max-new", str(shape["max_new"]),
            "--gamma", str(shape["gamma"]),
            "--block-size", str(shape["block_size"]),
            "--seed", str(SEED)]
    return argv + (["--smoke"] if smoke else [])


def run_phases(arch: str, smoke: bool, attn_impl: str,
               shape: dict = SHAPE) -> list[Phase]:
    """Serve phases (a), (b), (c); ``attn_impl`` is (c)'s paged kernel
    ("pallas" on the chip, "interpret" in CPU tests)."""
    import jax
    from repro.launch import serve

    specs = (("a", "bf16 autoregressive", 0, "off"),
             ("b", "Cassandra-1, gather attention", 1, "off"),
             ("c", f"Cassandra-1, {attn_impl} paged kernels", 1, attn_impl))
    dev = jax.devices()
    device = f"{dev[0].device_kind} x{len(dev)} ({dev[0].platform})"
    phases = []
    t_run = time.perf_counter()
    for name, label, variant, attn in specs:
        t0 = time.perf_counter()
        print(f"[smoke] phase ({name}) {label} starts at "
              f"{t0 - t_run:.1f}s", flush=True)
        with CompileLog() as log:
            done, summary = serve.run(
                serve_argv(arch, smoke, variant, attn, shape))
        total = time.perf_counter() - t0
        stats = dev[0].memory_stats() or {}
        phases.append(Phase(
            name=name, label=label,
            outputs={r.rid: list(r.output) for r in done},
            prompts={r.rid: np.asarray(r.tokens, np.int32) for r in done},
            summary=summary, total_s=total, compile_s=log.seconds,
            cache_hits=log.hits,
            cache_misses=log.misses,
            peak_bytes=stats.get("peak_bytes_in_use")))
        # printed as each phase ends, so a cut run still reports it
        print("\n".join(phase_lines(phases[-1], device)), flush=True)
        del done, summary
        gc.collect()        # drop the phase's scheduler, cache and params
    return phases


def reference_logits_fn(arch: str, smoke: bool):
    """Full-sequence forward of the served weights: (S,) tokens -> (S, V)
    f32 logits (row i predicts token i+1)."""
    import jax
    from repro.configs import get_config
    from repro.models import forward_train, init_params
    from repro.models.layers import Runtime

    cfg = get_config(arch, smoke=smoke)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    rt = Runtime(cfg=cfg)

    @jax.jit
    def logits(p, tokens):
        return forward_train(rt, p, {"tokens": tokens[None]})[0][0]

    return lambda seq: np.asarray(logits(params, np.asarray(seq, np.int32)))


def _first_divergence(ref: list, out: list) -> int | None:
    for i, (a, b) in enumerate(zip(ref, out)):
        if a != b:
            return i
    return None if len(ref) == len(out) else min(len(ref), len(out))


def _gap_line(z: np.ndarray, want: int, got: int) -> tuple[float, str]:
    top2 = np.sort(z)[-2:]
    gap = float(z[want] - z[got])
    return gap, (f"reference logit gap {gap:.4f} (token {want} vs {got}), "
                 f"top-2 gap {float(top2[1] - top2[0]):.4f}, "
                 f"tolerance {NEAR_TIE_LOGITS}")


def judge(phases: list[Phase], logits_fn, max_new: int
          ) -> tuple[bool, list[str]]:
    """Check lengths, the reference forward and cross-phase agreement.
    Returns (ok, report lines)."""
    ok, lines = True, []
    for p in phases:
        short = {rid: len(o) for rid, o in p.outputs.items()
                 if len(o) != max_new}
        if short or not p.outputs:
            ok = False
            lines.append(f"[check] phase ({p.name}) FAIL: requests short of "
                         f"max_new={max_new}: {short or 'no requests'}")

    # reference forward over phase (a)'s first request: finite logits,
    # and its argmax reproduces the served autoregressive tokens
    a = phases[0]
    rid0 = min(a.outputs)
    prompt = a.prompts[rid0]
    seq = np.concatenate([prompt, np.asarray(a.outputs[rid0], np.int32)])
    z = logits_fn(seq)
    finite = bool(np.isfinite(z).all())
    served = np.asarray(a.outputs[rid0])
    pred = z[len(prompt) - 1:len(seq) - 1].argmax(-1)
    n_agree = int((pred == served).sum())
    worst = 0.0
    for i in np.flatnonzero(pred != served):
        zi = z[len(prompt) - 1 + i]
        worst = max(worst, abs(float(zi[served[i]] - zi[pred[i]])))
    tf_ok = finite and worst <= NEAR_TIE_LOGITS
    ok &= tf_ok
    lines.append(
        f"[check] reference forward, phase (a) request {rid0}: logits "
        f"finite={finite}, argmax agrees at {n_agree}/{len(served)} "
        f"positions, largest disagreeing gap {worst:.4f} (tolerance "
        f"{NEAR_TIE_LOGITS}) -> {'ok' if tf_ok else 'FAIL'}")

    for ref, cur in zip(phases, phases[1:]):
        exact, ties = 0, 0
        for rid, want in ref.outputs.items():
            got = cur.outputs.get(rid, [])
            pos = _first_divergence(want, got)
            if pos is None:
                exact += 1
                continue
            prompt = ref.prompts[rid]
            if pos >= min(len(want), len(got)):
                ok = False
                lines.append(f"[check] ({cur.name}) vs ({ref.name}) request "
                             f"{rid}: length {len(got)} vs {len(want)} FAIL")
                continue
            # causal: the full sequence's row len(prompt)+pos-1 sees only
            # the prefix, and one sequence length means one compile
            zs = logits_fn(np.concatenate(
                [prompt, np.asarray(want, np.int32)]))[len(prompt) + pos - 1]
            gap, text = _gap_line(zs, want[pos], got[pos])
            tie = bool(np.isfinite(zs).all()) and abs(gap) <= NEAR_TIE_LOGITS
            ties += tie
            ok &= tie
            lines.append(f"[check] ({cur.name}) vs ({ref.name}) request {rid} "
                         f"first diverges at token {pos}: {text} -> "
                         f"{'near-tie' if tie else 'FAIL'}")
        lines.append(f"[check] ({cur.name}) vs ({ref.name}): {exact}/"
                     f"{len(ref.outputs)} requests token for token, {ties} "
                     f"near-tie divergences")
    return bool(ok), lines


def phase_lines(p: Phase, device: str) -> list[str]:
    s = p.summary
    tokens = sum(len(o) for o in p.outputs.values())
    wall = s["wall_s"]
    comp = sorted(p.compile_s.items(), key=lambda kv: -kv[1])
    comp_txt = ", ".join(f"{k} {v:.1f}s" for k, v in comp[:6])
    steps = ", ".join(f"{k} {v['mean_ms']:.2f}ms x{v['calls']}"
                      for k, v in s["bucket_wall_ms"].items()
                      if not k.endswith((".dispatch", ".overlap")))
    peak = ("n/a" if p.peak_bytes is None
            else f"{p.peak_bytes} B ({p.peak_bytes / 2**30:.2f} GiB)")
    return [
        f"[phase {p.name}] {p.label} on {device}",
        f"[phase {p.name}] compile: {sum(p.compile_s.values()):.1f}s XLA "
        f"over {len(p.compile_s)} programs, persistent cache hits="
        f"{p.cache_hits} misses={p.cache_misses} ({comp_txt})",
        f"[phase {p.name}] smoke, not a benchmark: serving wall {wall:.1f}s "
        f"incl. step compiles, {tokens} tokens, {tokens / wall:.1f} "
        f"tokens/s; phase total {p.total_s:.1f}s incl. weight init and "
        f"formatting; step means: {steps}",
        f"[phase {p.name}] acceptance={s['acceptance']}, cycles="
        f"{s['cycles']}, tokens/cycle={s['tokens_per_cycle']:.2f}",
        f"[phase {p.name}] peak_bytes_in_use (process so far): {peak}",
    ]


def main() -> int:
    t0 = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = f"{dev.device_kind} x{len(devices)} ({dev.platform})"
    print(f"[smoke] device: {device}; compile cache: {cache_dir}", flush=True)

    phases = run_phases(ARCH, smoke=False, attn_impl="pallas")
    with CompileLog() as log:
        ok, checks = judge(phases, reference_logits_fn(ARCH, smoke=False),
                           SHAPE["max_new"])
    print(f"[check] reference forward compile {sum(log.seconds.values()):.1f}s")
    for line in checks:
        print(line)
    print(f"[smoke] total wall {time.perf_counter() - t0:.1f}s; "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
