"""Benchmark entry point: one process, one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. It loads the cell's configuration and
traffic mix, builds the served path, draws the traffic from the seed,
warms up every program the window dispatches, measures for ``--seconds``,
compares what was served with the plain reference, and prints as its
last line of standard output one JSON object: ``correct``, ``attempted``,
``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, traced, ``breakdown``; the numbers
compared with their limits come last, under ``compared``, and are also
the last lines of standard error.

It exits with code 2 and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for. JAX's persistent compilation cache lives
in ``<checkout>/.jax_cache``, so only the first run in a checkout
compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    # the cache key holds the directory: a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"[bench] {args.workload} seed={args.seed} on {kind} "
          f"x{len(devices)}; compile cache {cache}", file=sys.stderr,
          flush=True)

    import harness
    res = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, kind)
    return report(res, devices, kind, bool(args.trace))


def report(res: dict, devices, kind: str, trace: bool) -> int:
    cmp = res["compare"]
    print(f"[bench] window compiles (should be 0): {res['window_compiles']}",
          file=sys.stderr)
    pool = res["pool"]
    print(f"[bench] KV pool high water {pool['pool_high_water_blocks']} of "
          f"{pool['pool_blocks']} blocks", file=sys.stderr)
    print(f"[bench] compared {cmp['tokens_compared']} served tokens of "
          f"{cmp['requests_compared']} requests; widest gap per request "
          f"{cmp['per_request']}", file=sys.stderr)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if trace and res["trace"] is not None:
        device["busy_s"] = res["trace"]["busy_s"]
        device["window_s"] = res["trace"]["window_s"]
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if trace and res["breakdown"] is not None:
        line["breakdown"] = res["breakdown"]
    line["compared"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
