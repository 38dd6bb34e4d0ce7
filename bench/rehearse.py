"""Compile a cell's serving programs for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <name> [--layers 28,32,36]

For each depth (default: the configuration's) it compiles, for one chip of
a described ``v5e:2x2`` topology, the two programs the window dispatches
(``serve_unified`` and the wide admission ``serve_chunk``) at the cell's
slots, pool and chunk, and prints their ``memory_analysis``, beside the
bytes the process holds while it loads: the bfloat16 weights, the packed
tree before trimming (an upper bound on the trimmed one) and the two dense
views. The load peak is the larger of formatting (weights + packed) and
view decoding (packed + views); the serving peak is the views and the
rest of the weights, the cache and the larger step's temporaries.
Nothing runs, so it says nothing about time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
HBM = 16 * 2**30


def nbytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def rehearse(conf: dict, mix: dict, layers: int,
             pool: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.format import CassandraConfig
    from repro.core.packing import format_params, resolve_views
    from repro.models import init_params
    from repro.serving.blockpool import blocks_needed
    from repro.serving.engine import EngineConfig
    from repro.serving.scheduler import Scheduler

    import harness
    cfg = dataclasses.replace(harness.program_config(conf), n_layers=layers)
    srv = conf["serving"]
    gamma, slots, bs = srv["gamma"], mix["slots"], srv["block_size"]
    cass = CassandraConfig(variant=srv["variant"], gamma=gamma)
    kind = harness.load_kind(BENCH_DIR, mix)
    s_max = kind.max_context(mix) + gamma + 1
    max_blocks = blocks_needed(s_max, bs)
    num_blocks = pool or srv.get("kv_pool_blocks") or \
        slots * max_blocks + 1

    weights = jax.eval_shape(lambda k: init_params(cfg, k),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    packed = jax.eval_shape(lambda p: format_params(p, cass, trim=False),
                            weights)
    views = jax.eval_shape(lambda p: resolve_views(p, cass), packed)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    # the scheduler holds its parameters untouched until a step runs, so
    # it takes their shapes; its cache is real, on the host
    sched = Scheduler(cfg, views, cass=cass,
                      ecfg=EngineConfig(gamma=gamma, greedy=srv["greedy"]),
                      num_slots=slots, s_max=s_max, paged=True,
                      block_size=bs, num_blocks=num_blocks,
                      chunk_size=srv["chunk_size"])
    unified, chunk = sched._unified, sched._chunk
    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         sched.cache)
    i32 = jnp.int32
    out = {"layers": layers, "num_blocks": num_blocks,
           "weights_bytes": nbytes(weights),
           "packed_untrimmed_bytes": nbytes(packed),
           "views_bytes": nbytes(views), "cache_bytes": nbytes(cache)}
    steps = {
        "serve_unified": unified.lower(
            on_chip(views), on_chip(cache),
            on_chip(jax.ShapeDtypeStruct((slots, 1), i32)),
            on_chip(jax.ShapeDtypeStruct((slots, gamma + 1), i32)),
            on_chip(jax.ShapeDtypeStruct((slots,), i32)),
            on_chip(jax.ShapeDtypeStruct((slots,), jnp.bool_)),
            on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))),
        "serve_chunk": chunk.lower(
            on_chip(views), on_chip(cache),
            on_chip(jax.ShapeDtypeStruct((slots, srv["chunk_size"]), i32)),
            on_chip(jax.ShapeDtypeStruct((slots,), i32)))}
    temp = 0
    for name, lowered in steps.items():
        ma = lowered.compile().memory_analysis()
        out[name] = {"argument": ma.argument_size_in_bytes,
                     "output": ma.output_size_in_bytes,
                     "temp": ma.temp_size_in_bytes,
                     "alias": ma.alias_size_in_bytes}
        temp = max(temp, ma.temp_size_in_bytes)
    load = max(out["weights_bytes"] + out["packed_untrimmed_bytes"],
               out["packed_untrimmed_bytes"] + out["views_bytes"])
    non_packed = out["weights_bytes"] - out["views_bytes"] // 2
    serve = out["views_bytes"] + non_packed + out["cache_bytes"] + temp
    out["load_peak_bound_gib"] = load / 2**30
    out["serving_peak_gib"] = serve / 2**30
    out["fits_16_gib"] = max(load, serve) < HBM
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", default=None,
                    help="comma-separated depths (default: the config's)")
    ap.add_argument("--pool", default=None,
                    help="comma-separated KV pool sizes in blocks "
                    "(default: the config's)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    depths = ([int(x) for x in args.layers.split(",")] if args.layers
              else [conf["hf_config"]["num_hidden_layers"]])
    pools = ([int(x) for x in args.pool.split(",")] if args.pool
             else [None])
    for layers in depths:
        for pool in pools:
            print(json.dumps({"workload": args.workload,
                              **rehearse(conf, mix, layers, pool)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
