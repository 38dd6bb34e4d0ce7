"""Readings behind a cell's correctness limit, many seeds in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 30 \\
        [--control-seeds 1,2,3] [--controls int8,fp8]

Runs the cell as ``bench/run.py`` does (set-up, warm-up, window, the
reference comparison) once per seed, in one process so that each seed
after the first finds every program compiled. For each seed it prints one
JSON line: the widest gap of the served tokens below the reference's best
logit (the *lower reading* is the largest over seeds), and on the control
seeds the same for each control, the reference with int8 or float8
weights (its *upper reading* is the smallest over those). The last line sums them up.
The limit in ``bench/cells/<workload>.json`` is set between the two.
Not part of a benchmark run: it needs a TPU, like ``bench/run.py``.
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


def _ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=_ints)
    ap.add_argument("--control-seeds", default=[], type=_ints)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    import harness
    controls = tuple(args.controls.split(","))
    lower, upper = [], {c: [] for c in controls}
    t = T_START
    for seed in args.seeds:
        res = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                               False, t, dev.device_kind,
                               controls=controls
                               if seed in args.control_seeds else ())
        cmp, ctl = res["compare"], res["control"]
        lower.append(cmp["widest_gap"])
        for c, v in ctl.items():
            upper[c].append(v["widest_gap"])
        print(json.dumps({
            "seed": seed, "widest_gap": cmp["widest_gap"],
            "tokens_compared": cmp["tokens_compared"],
            "per_request": cmp["per_request"],
            "control": {c: {"widest_gap": v["widest_gap"],
                            "per_request": v["per_request"]}
                        for c, v in ctl.items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "attempted": res["attempted"], "failed": res["failed"],
            "window_compiles": res["window_compiles"]}), flush=True)
        t = time.perf_counter()
    print(json.dumps({"workload": args.workload, "seeds": len(lower),
                      "lower_reading": max(lower),
                      "upper_reading": {c: min(v) for c, v in upper.items()
                                        if v}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
