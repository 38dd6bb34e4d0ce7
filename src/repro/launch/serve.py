"""Serving driver: batched requests through the Cassandra engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --variant 1 --gamma 3 --max-new 32 --requests 4

``--variant 0`` runs the bf16 autoregressive baseline. Reports tokens,
cycles, acceptance rate and the bandwidth-model speedup estimate.

``--scheduler`` serves the same requests through the continuous-batching
scheduler instead of the fixed-batch engine: requests are admitted into
``--slots`` cache rows, finish independently, and free slots are
recycled by the queue. By default the scheduler runs the FUSED serving
step: each cycle carries prefill-chunk rows and speculative-decode rows
in the same batch (one compile bucket), so admission rides decode cycles
instead of stalling them; ``--max-prefill-tokens-per-step`` caps how
much of a cycle admission may consume, and ``--alternating`` selects the
prefill/decode-alternating reference scheduler instead:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --variant 1 --scheduler --slots 2 --requests 6 --max-new 32

``--stop-token`` (repeatable) demonstrates per-request stop conditions:
odd-numbered requests stop at the given token ids, even-numbered ones
run to ``--max-new`` — both retire their slot the cycle the condition
lands.

``--paged`` switches the scheduler's KV cache from per-row (slots, S_max)
regions to a global pool of ``--block-size``-token blocks addressed
through per-request block tables: short requests stop stranding the
S_max tail, and ``--num-blocks`` caps total KV memory independently of
the per-request bound (lossless — outputs are identical to the slot
layout):

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --variant 1 --scheduler --paged --block-size 16 --num-blocks 24 \
      --slots 4 --requests 8 --max-new 32

``--swap`` (with ``--paged``) turns on preemption + host swap-out, so
the pool can be oversubscribed: shrink ``--num-blocks`` below the trace's
footprint and the scheduler swaps long-running victims' KV blocks to a
host spill store instead of making the queue head wait behind them
(``--swap-store-blocks`` caps host residency). Preempt-then-resume is
lossless — the same trace with a big pool prints identical tokens:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
      --variant 1 --scheduler --paged --swap --block-size 4 \\
      --num-blocks 12 --slots 2 --requests 6 --max-new 32

``--ttft-deadline-ms`` / ``--itl-target-ms`` attach per-request SLOs
(first token due within the deadline; max tolerated inter-token gap).
Any declared SLO flips the scheduler into deadline-hit goodput mode:
admission becomes earliest-feasible-deadline-first over the online
measured cost model, the wide-cycle choice and the preemption victim
policy weigh deadlines first, and ``--priority`` demotes to the tie
break. ``--fifo`` keeps the legacy decision paths (deadlines are still
tracked and the [slo] hit rate still prints). SLOs never change a
request's tokens — only when they land:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
      --variant 1 --scheduler --paged --swap --block-size 4 \\
      --num-blocks 12 --slots 2 --requests 6 --max-new 32 \\
      --ttft-deadline-ms 2000

``--prefix-cache`` (with ``--paged``) turns on prefix sharing: admission
aliases cached prompt-prefix blocks into each row's block table instead
of re-prefilling and re-storing them, and the run reports hit rate,
matched tokens, and copy-on-write copies. ``--shared-header`` gives all
requests a common header (half the prompt) so hits occur on this
synthetic trace — it works with the cache off too, so the same trace can
be replayed both ways and must print identical tokens (losslessness at
the CLI). ``--prefix-cache-blocks`` caps how many evictable
blocks the cache may park after their requests retire:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --variant 1 --scheduler --paged --prefix-cache --shared-header \
      --block-size 8 --chunk-size 16 --slots 4 --requests 8 --max-new 32
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.format import CassandraConfig
from repro.core.packing import (Calibrator, format_params, params_nbytes,
                                resolve_views)
from repro.core.speculative import speedup_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, forward_train
from repro.models.layers import Runtime
from repro.serving.engine import Engine, EngineConfig, validate_request_slos
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import (Telemetry, format_stats_lines,
                                     hlo_op_scopes, write_metrics,
                                     write_trace)


def run(argv=None):
    """Parse ``argv`` (default ``sys.argv``), serve, print the report.

    Returns ``(finished_requests, summary)`` in scheduler mode — the
    ``Request`` objects in completion order and ``Scheduler.summary()``
    plus the serving wall time under ``wall_s`` — and
    ``(tokens, stats)`` from the fixed-batch engine.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--variant", type=int, default=1,
                    help="0=bf16 baseline, 1=Cassandra-1, 2=Cassandra-2")
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--greedy", action="store_true", default=True)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--calibrate", action="store_true",
                    help="Wanda calibration pass before formatting")
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous batching through --slots cache rows")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV: a global block pool + per-request "
                    "block tables instead of per-row (slots, S_max) "
                    "regions (scheduler mode only)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block; smaller blocks waste less "
                    "on the last partial block but widen the block table")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="total pool blocks incl. the reserved trash "
                    "block; default sizes the pool to the slot layout's "
                    "capacity (slots x ceil(S_max/block) + 1). Shrink it "
                    "to cap KV memory — admission then waits for blocks")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prefill chunk: prompts are prefilled in fixed "
                    "chunks of this many tokens so all admissions share "
                    "one compile bucket")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share cached prompt-prefix blocks across "
                    "requests (radix index + copy-on-write; requires "
                    "--paged)")
    ap.add_argument("--shared-header", action="store_true",
                    help="give all requests a common prompt header "
                    "(half the prompt) so the prefix cache has "
                    "something to hit; works with the cache off too, "
                    "making losslessness observable at the CLI (same "
                    "trace, same tokens, cache on or off)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=None,
                    help="max evictable blocks the prefix cache may keep "
                    "parked after their requests retire (default: "
                    "bounded only by the pool)")
    ap.add_argument("--swap", action="store_true",
                    help="preemption + host swap-out: oversubscribe the "
                    "pool — when the queue head cannot reserve, swap a "
                    "resident victim's KV blocks to a host spill store "
                    "and admit immediately (requires --paged)")
    ap.add_argument("--swap-store-blocks", type=int, default=None,
                    help="max pool blocks the host spill store may hold "
                    "(default: unbounded); a full store stops preemption, "
                    "never drops a chain")
    ap.add_argument("--priority", type=int, action="append", default=None,
                    help="per-request priority (repeatable, cycled over "
                    "requests): higher admitted first, lower preempted "
                    "first; default 0 keeps plain FIFO")
    ap.add_argument("--alternating", action="store_true",
                    help="use the prefill/decode-alternating scheduler "
                    "(the fused mixed-role step is the default)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the pipelined dispatch/harvest overlap "
                    "(one-cycle-deep async pipeline, default-on in fused "
                    "mode): every step then blocks synchronously before "
                    "the host plans the next cycle. Lossless either way "
                    "— same tokens, overlap on or off")
    ap.add_argument("--max-prefill-tokens-per-step", type=int, default=None,
                    help="fused mode: cap prefill tokens per mixed cycle "
                    "so admission bursts can't monopolise a cycle")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    help="per-request stop token id(s); applied to odd-"
                    "numbered requests (repeatable, scheduler mode)")
    ap.add_argument("--ttft-deadline-ms", type=float, default=None,
                    help="per-request TTFT SLO: first token due within "
                    "this many ms of arrival (applied to every request; "
                    "flips the scheduler into deadline-hit goodput "
                    "mode — EDF admission + deadline-protecting "
                    "preemption over the online measured cost model)")
    ap.add_argument("--itl-target-ms", type=float, default=None,
                    help="per-request ITL SLO: max tolerated inter-token "
                    "gap in ms (applied to every request)")
    ap.add_argument("--attn-kernel", default="off",
                    choices=["off", "jnp", "interpret", "pallas"],
                    help="paged-attention decode kernel for the serving "
                    "hot path (requires --paged): 'off' keeps the "
                    "gather-then-attend path, 'jnp' the gather-free scan "
                    "reference, 'interpret'/'pallas' the Pallas kernel "
                    "that walks the block table in-kernel (interpret = "
                    "CPU). Lossless — same tokens as 'off'")
    ap.add_argument("--attn-chunk-q", type=int, default=None,
                    help="flash-attention query chunk for the dense "
                    "prefill path (default: attention.DEFAULT_CHUNK_Q; "
                    "serving configs may pin per arch)")
    ap.add_argument("--attn-chunk-k", type=int, default=None,
                    help="flash-attention key chunk for the dense "
                    "prefill path (default: attention.DEFAULT_CHUNK_K)")
    ap.add_argument("--fifo", action="store_true",
                    help="disable SLO-aware goodput scheduling: keep the "
                    "legacy priority-then-FIFO decision paths even when "
                    "requests declare SLOs (deadlines still reported)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace_event JSON of "
                    "the run's request lifecycle (open in "
                    "chrome://tracing or ui.perfetto.dev); enables the "
                    "in-memory lifecycle tracer, which never touches "
                    "device values — outputs are bitwise identical to "
                    "a trace-off run")
    ap.add_argument("--metrics-out", default=None,
                    help="write the run's metrics snapshot as "
                    "newline-delimited JSON (one metric per line)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="lifecycle tracer ring bound (events); a full "
                    "ring drops oldest events, never grows")
    ap.add_argument("--profile-dir", default=None,
                    help="run the serving loop under jax.profiler.trace "
                    "into this directory: the device timeline with the "
                    "fused step's cass.* phase scopes and the "
                    "scheduler's sched.* host spans (open with "
                    "TensorBoard's profile plugin or xprof); scheduler "
                    "mode also writes op_scopes.json there, the fused "
                    "step's HLO op name -> phase scope map")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    profiled = (jax.profiler.trace(args.profile_dir) if args.profile_dir
                else contextlib.nullcontext())
    # fail on malformed SLOs before paying for model init
    validate_request_slos(ttft_deadline_ms=args.ttft_deadline_ms,
                          itl_target_ms=args.itl_target_ms)
    if args.paged and not args.scheduler:
        ap.error("--paged requires --scheduler (the fixed-batch engine "
                 "has no block pool)")
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (sharing aliases "
                 "physical pool blocks through block tables)")
    if args.swap and not args.paged:
        ap.error("--swap requires --paged (preemption spills and "
                 "restores pool blocks through block tables)")
    if args.attn_kernel != "off" and not args.paged:
        ap.error("--attn-kernel requires --paged (the kernel walks the "
                 "block table in-kernel)")

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)

    b = args.requests
    prompt = {"tokens": jax.random.randint(
        jax.random.fold_in(key, 1), (b, args.prompt_len), 0,
        cfg.vocab_size)}
    if args.shared_header:
        # a shared system-prompt header (half the prompt) so the prefix
        # cache has something to hit on this synthetic trace
        header = prompt["tokens"][0, :args.prompt_len // 2]
        prompt["tokens"] = prompt["tokens"].at[:, :header.shape[0]].set(
            header[None, :])
    if cfg.frontend == "vision":
        prompt["patch_embeds"] = jnp.zeros(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "audio":
        prompt["frame_embeds"] = jnp.zeros(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)

    cass = None
    if args.variant:
        cass = CassandraConfig(variant=args.variant, gamma=args.gamma)
        calib = None
        if args.calibrate:
            calib = Calibrator()
            rt = Runtime(cfg=cfg, collector=calib, ssm_chunk=8)
            forward_train(rt, params, {**prompt,
                                       "labels": prompt["tokens"]})
        params = format_params(params, cass, calib=calib)
        nb = params_nbytes(params)
        total = sum(nb.values())
        print(f"[format] spec={nb['spec']/1e6:.1f}MB "
              f"verif={nb['verif']/1e6:.1f}MB plain={nb['plain']/1e6:.1f}MB "
              f"(draft reads {nb['spec']/max(total,1)*100:.0f}% of resident)")
        # decode once to dense draft/target views, not on every serving
        # pass; rebinding frees the packed tree
        params = resolve_views(params, cass)

    ecfg = EngineConfig(gamma=args.gamma, greedy=args.greedy)
    rt_extra = {"ssm_chunk": 8 if args.smoke else 64}
    if args.attn_chunk_q is not None:
        rt_extra["attn_chunk_q"] = args.attn_chunk_q
    if args.attn_chunk_k is not None:
        rt_extra["attn_chunk_k"] = args.attn_chunk_k

    telem = Telemetry(trace=args.trace_out is not None,
                      trace_capacity=args.trace_capacity)

    if args.scheduler:
        s_max = args.prompt_len + args.max_new + args.gamma + 1
        sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                          num_slots=args.slots, s_max=s_max,
                          speculative=args.variant != 0, rt_extra=rt_extra,
                          paged=args.paged, block_size=args.block_size,
                          num_blocks=args.num_blocks,
                          chunk_size=args.chunk_size,
                          fused=not args.alternating,
                          max_prefill_tokens_per_step=(
                              args.max_prefill_tokens_per_step),
                          prefix_cache=args.prefix_cache,
                          prefix_cache_blocks=args.prefix_cache_blocks,
                          swap=args.swap,
                          swap_store_blocks=args.swap_store_blocks,
                          slo_aware=not args.fifo,
                          attn_kernel=args.attn_kernel,
                          overlap=not args.no_overlap,
                          telemetry=telem)
        t0 = time.perf_counter()
        for i in range(args.requests):
            # odd-numbered requests carry the per-request stop list; even
            # ones run to max_new (per-request conditions, not global EOS)
            prio = (args.priority[i % len(args.priority)]
                    if args.priority else 0)
            sched.submit(prompt["tokens"][i % b], max_new=args.max_new,
                         arrival=i / 4.0,
                         stop_tokens=args.stop_token if i % 2 else None,
                         priority=prio,
                         ttft_deadline_ms=args.ttft_deadline_ms,
                         itl_target_ms=args.itl_target_ms)
        with profiled:
            done = sched.run()
        dt = time.perf_counter() - t0
        s = sched.summary()
        s["wall_s"] = dt
        mode = "fused" if sched.fused else "alternating"
        # the ONE stats formatter: every section keys off the summary's
        # subsystems config, so an enabled subsystem always prints (even
        # with zero activity) and a missing key raises instead of
        # silently formatting nothing
        for line in format_stats_lines(s, mode=mode, wall_s=dt,
                                       n_done=len(done), slots=args.slots):
            print(line)
        if args.trace_out:
            write_trace(args.trace_out, sched.telemetry.tracer)
            print(f"[telemetry] perfetto trace -> {args.trace_out} "
                  f"({s['telemetry']['trace_events']} events, "
                  f"{s['telemetry']['trace_dropped']} dropped)")
        if args.metrics_out:
            write_metrics(args.metrics_out, s)
            print(f"[telemetry] metrics jsonl -> {args.metrics_out}")
        if args.profile_dir:
            print(f"[telemetry] profiler trace -> {args.profile_dir}")
            if sched.fused:
                path = os.path.join(args.profile_dir, "op_scopes.json")
                scopes = hlo_op_scopes(sched.unified_step_text())
                with open(path, "w") as f:
                    json.dump(scopes, f, indent=0, sort_keys=True)
                print(f"[telemetry] {len(scopes)} scoped ops of "
                      f"serve_unified -> {path}")
        for r in sorted(done, key=lambda r: r.rid):
            print(f"  req {r.rid}: {len(r.output)} tokens, "
                  f"first {r.output[:8]}")
        return done, s

    eng = Engine(cfg, params, cass=cass, ecfg=ecfg, rt_extra=rt_extra)
    t0 = time.perf_counter()
    with profiled:
        tokens, stats = eng.generate(prompt, max_new=args.max_new,
                                     key=jax.random.fold_in(key, 2),
                                     speculative=args.variant != 0,
                                     telemetry=telem)
    dt = time.perf_counter() - t0
    if args.profile_dir:
        print(f"[telemetry] profiler trace -> {args.profile_dir}")
    if args.trace_out:
        write_trace(args.trace_out, telem.tracer)
        print(f"[telemetry] perfetto trace -> {args.trace_out}")
    if args.metrics_out:
        write_metrics(args.metrics_out, telem.metrics.snapshot())
        print(f"[telemetry] metrics jsonl -> {args.metrics_out}")
    print(f"[serve] {tokens.shape[0]} reqs, cycles={stats['cycles']}, "
          f"tokens/cycle={stats.get('tokens_per_cycle', 1.0):.2f}, "
          f"acceptance={stats['acceptance']}, wall={dt:.1f}s")
    if args.variant and stats["acceptance"] is not None:
        est = speedup_model(stats["acceptance"], args.gamma,
                            draft_cost_ratio=0.33)
        print(f"[model] bandwidth-model speedup estimate at this "
              f"acceptance: {est:.2f}x over bf16")
    print("first request tokens:",
          [int(t) for t in tokens[0] if int(t) >= 0][:24])
    return tokens, stats


if __name__ == "__main__":
    enable_compile_cache()
    run()
