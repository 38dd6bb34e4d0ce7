"""Continuous-batching serving throughput: fused vs alternating vs AR.

Replays the same request trace through the scheduler three ways — the
fused mixed-role serving step (``unified_step``; admission piggybacks on
decode cycles), the alternating prefill/decode scheduler (the PR 2
reference), and the bf16 autoregressive baseline — at arrival rates
λ ∈ {1, 4, 16} requests per decode cycle (request i arrives at cycle i/λ;
λ=16 is effectively a burst). Each row reports tokens/s (wall),
tokens-per-cycle, acceptance, TTFT, and p50/p95 inter-token latency in
cycles, as a JSON report.

``--fused-gate`` turns the fused-vs-alternating comparison into a hard
gate (nightly CI): at every λ ≥ 4 the fused scheduler must improve p95
inter-token latency without reducing aggregate throughput.

``--paged`` additionally replays a mixed-prompt-length trace through the
slot layout and the paged (block-pool) layout and reports KV residency:
tokens resident per MB of KV memory held, peak reserved tokens, and
whether per-request outputs are identical (lossless paging). The slot
layout must reserve the longest request's S_max for every row; paging
reserves per-request blocks, so mixed lengths fit ≥1.5× more resident
tokens at equal memory.

``--prefix`` replays a prefix-reuse trace (70% of prompts share a
``--prefix-header``-token header, staggered arrivals) through the paged
scheduler with the radix prefix cache on and off. ``--prefix-gate``
(nightly CI) hard-fails unless cache-on outputs are bitwise identical to
cache-off, prefill tokens computed drop >= 40%, peak reserved residency
is no worse, the full-prefix-hit request's TTFT beats its cold TTFT, and
every jit step still compiles exactly once.

``--oversub`` replays an oversubscription trace (long background
generations + late short interactive arrivals) through a pool sized to
``--oversub-frac`` (~60%) of the measured peak residency, preemption +
host swap on vs off. ``--swap-gate`` (nightly CI) hard-fails unless
preempt-then-resume outputs are bitwise identical to a big-pool run, at
least one preemption fires, the queue head's TTFT beats the
no-preemption wait, host-spilled bytes are honestly reported, and every
jit step (spill/restore included) compiles exactly once.

``--overlap-gate`` (nightly CI) replays the oversubscription trace with
the pipelined dispatch/harvest overlap on: the preempting (swap) run's
best-rep tokens/s must land within 5% of the never-preempted run on the
SAME tight pool (the queue head waits instead of preempting — equal
capacity, so the comparison isolates the preemption machinery's cost,
which double-buffered spill/restore makes ~free), the run must measure
a positive overlap ratio (``unified.overlap`` present in
``bucket_wall_ms``), outputs must be bitwise identical to both the
big-pool reference and a ``--no-overlap`` synchronous replay, and every
jit step must still compile exactly once.

``--slo`` replays a Poisson-arrival mixed-SLO trace (long deadline-free
background generations saturating the slots + interactive requests with
TTFT deadlines and ITL targets arriving at rate ``--slo-rate``) through
ONE scheduler three ways: FIFO (``slo_aware`` off — the pre-SLO decision
paths), SLO-aware (EDF admission + deadline-protecting preemption
over the online measured cost model), and an all-default replay with no
SLOs submitted. Deadlines are submitted in
milliseconds through the warmup-measured cycle cost; the gate judges
hits deterministically in cycle space. ``--slo-gate`` (nightly CI)
hard-fails unless FIFO's deadline-hit rate is below 60% at this λ while
SLO-aware scheduling hits >= 85%, per-request outputs are bitwise
identical between the runs (scheduling only reorders work), an all-
default (no-SLO) replay makes decision-for-decision the same schedule
as FIFO (the bitwise-default pin), and every jit step still compiles
exactly once across all runs.

  PYTHONPATH=src python benchmarks/throughput.py [--trained] \
      [--rates 1,4,16] [--fused-gate] [--paged] [--prefix-gate] \
      [--swap-gate] [--slo-gate] [--out /tmp/throughput.json]
"""
import argparse
import json
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.core.format import CassandraConfig
from repro.core.packing import resolve_views
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving.blockpool import blocks_needed
from repro.serving.engine import EngineConfig
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import Telemetry, write_metrics, write_trace

import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import common  # noqa: E402


def run_trace(sched: Scheduler, prompts, max_new, lam: float
              ) -> tuple[dict, list]:
    if isinstance(max_new, int):
        max_new = [max_new] * len(prompts)
    sched.reset()
    reqs = [sched.submit(p, max_new=mn, arrival=i / lam)
            for i, (p, mn) in enumerate(zip(prompts, max_new))]
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    s = sched.summary()
    s["wall_s"] = dt
    s["tokens_per_s"] = s["committed"] / max(dt, 1e-9)
    s["completed"] = len(done)
    return s, [r.output for r in reqs]


def check_fused_gate(report: dict) -> list:
    """Fused must beat alternating where it claims to: at every λ ≥ 4,
    better p95 inter-token latency (ties broken by the mean) at no
    aggregate-throughput cost. Tokens/cycle is the throughput gate: it is
    deterministic, and a fused cycle costs the same device work as an
    alternating decode cycle (γ drafts + one γ+1-wide pass), so fewer
    cycles at equal per-cycle cost IS aggregate tokens/s. Wall tokens/s
    swings ±40% between identical runs on shared runners, so it only
    guards against catastrophic (>2x) regressions."""
    failures = []
    rows = {(r["mode"], r["lambda"]): r for r in report["runs"]}
    for (mode, lam), f in rows.items():
        if mode != "fused" or lam < 4:
            continue
        a = rows.get(("alternating", lam))
        if a is None:
            continue
        # latency keys are None when nothing finished (latency_summary
        # reports "no data" instead of raising) — treat as 0 here
        f = {k: (v if v is not None else 0) for k, v in f.items()}
        a = {k: (v if v is not None else 0) for k, v in a.items()}
        itl_better = (f["itl_cycles_p95"] < a["itl_cycles_p95"]
                      or (f["itl_cycles_p95"] == a["itl_cycles_p95"]
                          and f["itl_cycles_mean"] < a["itl_cycles_mean"]))
        if not itl_better:
            failures.append(
                f"λ={lam}: fused p95 ITL {f['itl_cycles_p95']:.2f}cyc "
                f"(mean {f['itl_cycles_mean']:.3f}) is not better than "
                f"alternating {a['itl_cycles_p95']:.2f}cyc "
                f"(mean {a['itl_cycles_mean']:.3f})")
        if f["tokens_per_cycle"] < 0.99 * a["tokens_per_cycle"]:
            failures.append(
                f"λ={lam}: fused tokens/cycle {f['tokens_per_cycle']:.3f} "
                f"< alternating {a['tokens_per_cycle']:.3f}")
        if f["tokens_per_s"] < 0.5 * a["tokens_per_s"]:
            failures.append(
                f"λ={lam}: fused tokens/s {f['tokens_per_s']:.1f} fell "
                f">2x below alternating {a['tokens_per_s']:.1f}")
    return failures


def _kv_bytes_per_token(sched: Scheduler) -> float:
    """Bytes of attention-store KV per resident token (layout-agnostic —
    both layouts use identical per-token stores)."""
    from repro.core.format import tree_nbytes
    attn = [e for g in sched.cache["dec"] for e in g.values()
            if "conv" not in e]
    tokens = (sched.num_blocks * sched.block_size if sched.paged
              else sched.num_slots * sched.s_max)
    return tree_nbytes(attn) / max(tokens, 1)


def run_paged_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Mixed-length trace through slot vs paged layouts at equal settings:
    residency per MB and per-request output identity (lossless paging)."""
    lens = [int(x) for x in args.mixed_lens.split(",")]
    key = jax.random.PRNGKey(args.seed + 2)
    prompts = [jax.device_get(jax.random.randint(
        jax.random.fold_in(key, i), (lens[i % len(lens)],), 0,
        cfg.vocab_size)) for i in range(args.requests)]
    s_max = max(lens) + args.max_new + args.gamma + 1
    block = args.block_size
    s_max += (-s_max) % block      # align so both layouts see one capacity
    out = {"s_max": s_max, "block_size": block, "runs": {}}
    outputs = {}
    for mode in ("slot", "paged"):
        # construct per mode (and drop before the next) so only one KV
        # cache + executable set is resident at a time
        sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                          num_slots=args.slots, s_max=s_max,
                          rt_extra=rt_extra, paged=mode == "paged",
                          block_size=block, overlap=not args.no_overlap)
        reqs = [sched.submit(p, max_new=args.max_new, arrival=i / 4.0)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        sched.run()
        s = sched.summary()
        s["fused"] = sched.fused
        bpt = _kv_bytes_per_token(sched)
        held_mb = s["peak_reserved_tokens"] * bpt / 1e6
        s["wall_s"] = time.perf_counter() - t0
        s["kv_bytes_per_token"] = bpt
        s["peak_kv_held_mb"] = held_mb
        s["resident_tokens_per_mb"] = (s["peak_resident_tokens"]
                                       / max(held_mb, 1e-9))
        out["runs"][mode] = s
        outputs[mode] = [r.output for r in reqs]
        print(f"[paged-compare:{mode:>5}] resident peak="
              f"{s['peak_resident_tokens']} tok, held="
              f"{held_mb:.3f}MB, tokens/MB="
              f"{s['resident_tokens_per_mb']:.0f}")
        del sched
    ratio = (out["runs"]["paged"]["resident_tokens_per_mb"]
             / max(out["runs"]["slot"]["resident_tokens_per_mb"], 1e-9))
    out["residency_ratio"] = ratio
    out["outputs_identical"] = outputs["slot"] == outputs["paged"]
    # hard gates — this benchmark is the only automated exercise of the
    # packed+paged combination, so regressions here must fail the run
    # (nightly CI), not just print
    out["passed"] = out["outputs_identical"] and ratio >= 1.5
    print(f"[paged-compare] paged fits {ratio:.2f}x more resident tokens "
          f"per MB than the slot layout "
          f"(outputs identical: {out['outputs_identical']})")
    if not out["passed"]:
        print("[paged-compare] FAIL: expected identical outputs and "
              ">=1.5x residency")
    return out


def run_prefix_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Prefix-reuse trace through the paged layout, cache on vs off.

    70% of the requests share a ``--prefix-header``-token header (the
    shared-system-prompt regime), the last of them a *full-prefix* hit
    (header + 1 token). ``block_size`` and ``chunk_size`` are pinned to
    the fused riding width γ+1, so every prefill pass in both runs is
    γ+1 wide at block-aligned boundaries — warm-start passes are a
    subset of the cold run's and outputs must be bitwise identical."""
    gamma = args.gamma
    block = gamma + 1
    header_len = args.prefix_header - args.prefix_header % block
    n = args.prefix_requests
    key = jax.random.PRNGKey(args.seed + 3)
    header = jax.device_get(jax.random.randint(
        jax.random.fold_in(key, 1000), (header_len,), 0, cfg.vocab_size))
    prompts, sharer = [], []
    for i in range(n):
        # ~70% share the header; the last request is always the
        # full-prefix hit (header + 1 token) the TTFT gate measures
        if i % 10 < 7 or i == n - 1:
            tail_len = 1 if i == n - 1 else 2 * block
            tail = jax.device_get(jax.random.randint(
                jax.random.fold_in(key, i), (tail_len,), 0,
                cfg.vocab_size))
            prompts.append(np.concatenate([header, tail]))
            sharer.append(True)
        else:                              # 30% cold traffic
            prompts.append(jax.device_get(jax.random.randint(
                jax.random.fold_in(key, i), (6 * block,), 0,
                cfg.vocab_size)))
            sharer.append(False)
    s_max = header_len + 2 * block + args.max_new + gamma + 1
    s_max += (-s_max) % block
    out = {"header_tokens": header_len, "requests": n,
           "block_size": block, "runs": {}}
    outputs, ttfts = {}, {}
    for mode in ("off", "on"):
        sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                          num_slots=args.slots, s_max=s_max,
                          rt_extra=rt_extra, paged=True, block_size=block,
                          chunk_size=block, prefix_cache=mode == "on",
                          overlap=not args.no_overlap)
        reqs = [sched.submit(p, max_new=args.max_new, arrival=4.0 * i)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        sched.run()
        s = sched.summary()
        s["wall_s"] = time.perf_counter() - t0
        out["runs"][mode] = s
        outputs[mode] = [r.output for r in reqs]
        ttfts[mode] = [r.ttft_cycles for r in reqs]
        print(f"[prefix-compare:{mode:>3}] prefill tokens computed="
              f"{s['prefill_tokens']}, hits={s['prefix_hits']}/"
              f"{s['prefix_queries']}, matched={s['prefix_matched_tokens']}"
              f" tok, cow={s['cow_copies']}, peak reserved="
              f"{s['peak_reserved_tokens']} tok")
        del sched
    on, off = out["runs"]["on"], out["runs"]["off"]
    out["outputs_identical"] = outputs["on"] == outputs["off"]
    out["prefill_reduction"] = 1.0 - (on["prefill_tokens"]
                                      / max(off["prefill_tokens"], 1))
    out["full_hit_ttft_cycles"] = ttfts["on"][n - 1]
    out["full_hit_cold_ttft_cycles"] = ttfts["off"][n - 1]
    out["sharer_ttft_mean"] = float(np.mean(
        [t for t, sh in zip(ttfts["on"], sharer) if sh]))
    failures = []
    if not out["outputs_identical"]:
        failures.append("prefix cache is not lossless: cache-on outputs "
                        "differ from cache-off")
    if out["prefill_reduction"] < 0.40:
        failures.append(
            f"prefill tokens computed only dropped "
            f"{out['prefill_reduction']:.0%} (< 40%) on the shared-header "
            "trace")
    if on["peak_reserved_tokens"] > off["peak_reserved_tokens"]:
        failures.append(
            f"residency regressed: peak reserved {on['peak_reserved_tokens']}"
            f" tok with the cache vs {off['peak_reserved_tokens']} without")
    if not (out["full_hit_ttft_cycles"] < out["full_hit_cold_ttft_cycles"]):
        failures.append(
            f"full-prefix-hit TTFT {out['full_hit_ttft_cycles']:.1f}cyc "
            f"does not beat cold {out['full_hit_cold_ttft_cycles']:.1f}cyc")
    for name, cnt in on["trace_counts"].items():
        if cnt > 1:
            failures.append(f"cache-on run traced step '{name}' {cnt}x — "
                            "zero-recompile contract broken")
    out["failures"] = failures
    out["passed"] = not failures
    print(f"[prefix-compare] prefill tokens {off['prefill_tokens']}→"
          f"{on['prefill_tokens']} (-{out['prefill_reduction']:.0%}), "
          f"full-hit ttft {out['full_hit_cold_ttft_cycles']:.1f}→"
          f"{out['full_hit_ttft_cycles']:.1f}cyc, outputs identical: "
          f"{out['outputs_identical']}")
    for msg in failures:
        print(f"[prefix-gate] FAIL: {msg}")
    return out


def run_oversub_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Oversubscription trace through three pool configurations.

    The trace is the preemption regime: two long background generations
    (priority 0) admitted first, then short interactive requests
    (priority 1 — the latency tier preemption exists to protect)
    arriving while the long rows are mid-generation. Three runs:

    * **big** — pool comfortably above peak residency (reference outputs
      + the peak-high-water measurement that sizes the tight pool)
    * **tight** — pool at ``--oversub-frac`` (default ~60%) of the
      measured peak, swap OFF: the queue head waits behind the slowest
      resident generation (the no-preemption TTFT baseline)
    * **swap** — the same tight pool, swap ON: the victim policy spills
      a long row to the host store and admits the head immediately

    ``--swap-gate`` hard-fails unless: swap-run outputs are bitwise
    identical to the big-pool run, at least one preemption actually
    fired, the first interactive request's TTFT with swap beats the
    no-preemption wait, swapped bytes are reported (honest residency:
    host-side spill is accounted, never netted against the pool), and
    every jit step still compiled exactly once (spill/restore included).

    ``block_size == chunk_size == γ+1`` pins every prefill pass to the
    riding width at block-aligned boundaries, so preempt-then-resume
    replays the exact pass schedule of the uninterrupted run — the same
    alignment argument the prefix-cache gate uses."""
    gamma = args.gamma
    block = gamma + 1
    key = jax.random.PRNGKey(args.seed + 4)
    n_long, n_short = 2, max(args.oversub_requests - 2, 2)
    long_new = 4 * args.max_new
    prompts, max_news, arrivals, prios = [], [], [], []
    for i in range(n_long):
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, i), (2 * block,), 0, cfg.vocab_size)))
        max_news.append(long_new)
        arrivals.append(0.0)
        prios.append(0)
    for i in range(n_short):
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, 100 + i), (2 * block,), 0,
            cfg.vocab_size)))
        max_news.append(args.max_new)
        # arrive once the long rows are mid-generation, spaced out so
        # each admission finds the pool full of long-row blocks
        arrivals.append(4.0 + 3.0 * i)
        prios.append(1)
    s_max = 2 * block + long_new + gamma + 1
    s_max += (-s_max) % block
    head = n_long                       # the first interactive request

    def one_run(num_blocks, swap):
        sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                          num_slots=args.slots, s_max=s_max,
                          rt_extra=rt_extra, paged=True, block_size=block,
                          chunk_size=block, num_blocks=num_blocks,
                          swap=swap, overlap=not args.no_overlap)
        reqs = [sched.submit(p, max_new=mn, arrival=a, priority=pr)
                for p, mn, a, pr in zip(prompts, max_news, arrivals,
                                        prios)]
        t0 = time.perf_counter()
        sched.run()
        s = sched.summary()
        s["wall_s"] = time.perf_counter() - t0
        s["num_blocks"] = num_blocks
        outs = [r.output for r in reqs]
        ttfts = [r.ttft_cycles for r in reqs]
        del sched
        return s, outs, ttfts

    from repro.serving.blockpool import blocks_needed
    per_req = blocks_needed(2 * block + long_new + gamma + 1, block)
    big_blocks = args.slots * blocks_needed(s_max, block) + 1
    big, big_outs, big_ttfts = one_run(big_blocks, swap=False)
    # size the tight pool at ~oversub-frac of the measured peak, but
    # never below one request's worst-case chain (submit would reject)
    tight_blocks = max(int(big["pool_high_water_blocks"]
                           * args.oversub_frac), per_req) + 1
    tight, tight_outs, tight_ttfts = one_run(tight_blocks, swap=False)
    swap, swap_outs, swap_ttfts = one_run(tight_blocks, swap=True)
    out = {"block_size": block, "requests": len(prompts),
           "head_request": head,
           "big_pool_blocks": big_blocks,
           "tight_pool_blocks": tight_blocks,
           "peak_high_water_blocks": big["pool_high_water_blocks"],
           "runs": {"big": big, "tight": tight, "swap": swap}}
    out["outputs_identical"] = swap_outs == big_outs
    out["tight_outputs_identical"] = tight_outs == big_outs
    out["head_ttft_big"] = big_ttfts[head]
    out["head_ttft_no_preempt"] = tight_ttfts[head]
    out["head_ttft_swap"] = swap_ttfts[head]
    print(f"[oversub] pool {big_blocks}->{tight_blocks} blocks "
          f"({args.oversub_frac:.0%} of peak {big['pool_high_water_blocks']}"
          f"), preemptions={swap['preemptions']} "
          f"(resumes={swap['swap_resumes']}), spilled "
          f"{swap['swap_out_blocks']} blocks out / "
          f"{swap['swap_in_blocks']} restored, peak swapped="
          f"{swap['peak_swapped_tokens']} tok "
          f"({swap['spill_peak_bytes'] / 1e6:.3f}MB host)")
    print(f"[oversub] queue-head TTFT: big={big_ttfts[head]:.1f}cyc, "
          f"no-preemption={tight_ttfts[head]:.1f}cyc, "
          f"swap={swap_ttfts[head]:.1f}cyc "
          f"(outputs identical to big pool: {out['outputs_identical']})")
    failures = []
    if not out["outputs_identical"]:
        failures.append("preempt-then-resume is not lossless: swap-run "
                        "outputs differ from the big-pool run")
    if swap["preemptions"] < 1:
        failures.append("the oversubscribed trace never preempted — the "
                        "tight pool is not actually oversubscribed")
    if not (out["head_ttft_swap"] < out["head_ttft_no_preempt"]):
        failures.append(
            f"queue-head TTFT with swap ({out['head_ttft_swap']:.1f}cyc) "
            f"does not beat the no-preemption wait "
            f"({out['head_ttft_no_preempt']:.1f}cyc)")
    if swap["swap_out_blocks"] < 1 or swap["spill_peak_bytes"] <= 0:
        failures.append("no KV bytes ever spilled — every victim was "
                        "zero-progress, so the swap path (spill/restore "
                        "device steps, host accounting) went unexercised")
    for name, cnt in swap["trace_counts"].items():
        if cnt > 1:
            failures.append(f"swap run traced step '{name}' {cnt}x — "
                            "zero-recompile contract broken")
    out["failures"] = failures
    out["passed"] = not failures
    for msg in failures:
        print(f"[swap-gate] FAIL: {msg}")
    return out


def run_overlap_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Oversubscription trace with the pipelined overlap on: preemption
    must cost ~nothing.

    Same trace shape as ``run_oversub_compare`` (long background rows,
    late short interactive arrivals, ``block == chunk == γ+1``). Four
    schedulers:

    * **big** — pool above peak residency, overlap ON: the bitwise
      reference outputs + the peak measurement that sizes the tight pool
    * **tight** — the tight pool, swap OFF, overlap ON: the
      never-preempted run at the same capacity (the queue head waits
      behind the slowest resident) — the throughput baseline, so the
      gate prices the preemption machinery, not the smaller pool
    * **overlap** — the tight pool + swap, overlap ON: preemptions fire
      but the spill/restore copies double-buffer against the adjacent
      fused steps, so throughput must stay within
      ``--overlap-tolerance`` (default 5%) of the tight run
    * **sync** — the tight pool + swap with ``overlap=False``: the
      synchronous path the pipeline is pinned against, bitwise

    Each overlap-on configuration replays the trace ``reps`` times and
    the throughput gate compares best reps (wall noise on shared
    runners, same policy as the telemetry gate). Recompiles count across
    all reps, so the zero-recompile check also proves the deferred
    harvest added no compile buckets."""
    gamma = args.gamma
    block = gamma + 1
    key = jax.random.PRNGKey(args.seed + 4)     # the oversub trace shape
    n_long, n_short = 2, max(args.oversub_requests - 2, 2)
    # longer background rows than --oversub: the spill/restore round
    # trip is a fixed cost (a handful of cycles), so the gate needs
    # enough committed tokens behind it to price the *machinery*, not
    # the trace being tiny
    long_new = 6 * args.max_new
    prompts, max_news, arrivals, prios = [], [], [], []
    for i in range(n_long):
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, i), (2 * block,), 0, cfg.vocab_size)))
        max_news.append(long_new)
        arrivals.append(0.0)
        prios.append(0)
    for i in range(n_short):
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, 100 + i), (2 * block,), 0,
            cfg.vocab_size)))
        max_news.append(args.max_new)
        arrivals.append(4.0 + 3.0 * i)
        prios.append(1)
    s_max = 2 * block + long_new + gamma + 1
    s_max += (-s_max) % block

    def replay(num_blocks, swap, overlap, reps):
        sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                          num_slots=args.slots, s_max=s_max,
                          rt_extra=rt_extra, paged=True, block_size=block,
                          chunk_size=block, num_blocks=num_blocks,
                          swap=swap, overlap=overlap)
        best, outs_ref, identical = None, None, True
        for _ in range(reps):
            sched.reset()
            reqs = [sched.submit(p, max_new=mn, arrival=a, priority=pr)
                    for p, mn, a, pr in zip(prompts, max_news, arrivals,
                                            prios)]
            t0 = time.perf_counter()
            sched.run()
            dt = time.perf_counter() - t0
            s = sched.summary()
            s["wall_s"] = dt
            s["tokens_per_s"] = s["committed"] / max(dt, 1e-9)
            s["num_blocks"] = num_blocks
            outs = [r.output for r in reqs]
            if outs_ref is None:
                outs_ref = outs
            elif outs != outs_ref:
                identical = False   # nondeterminism — fails the gate
            if best is None or s["tokens_per_s"] > best["tokens_per_s"]:
                best = s
        del sched
        return best, outs_ref, identical

    from repro.serving.blockpool import blocks_needed
    per_req = blocks_needed(2 * block + long_new + gamma + 1, block)
    big_blocks = args.slots * blocks_needed(s_max, block) + 1
    reps = 3
    big, big_outs, big_det = replay(big_blocks, swap=False, overlap=True,
                                    reps=1)
    tight_blocks = max(int(big["pool_high_water_blocks"]
                           * args.oversub_frac), per_req) + 1
    tight, _tight_outs, tight_det = replay(tight_blocks, swap=False,
                                           overlap=True, reps=reps)
    over, over_outs, over_det = replay(tight_blocks, swap=True,
                                       overlap=True, reps=reps)
    sync, sync_outs, _ = replay(tight_blocks, swap=True, overlap=False,
                                reps=1)
    out = {"block_size": block, "requests": len(prompts), "reps": reps,
           "tolerance": args.overlap_tolerance,
           "big_pool_blocks": big_blocks,
           "tight_pool_blocks": tight_blocks,
           "runs": {"big": big, "tight": tight, "overlap": over,
                    "sync": sync}}
    out["outputs_identical"] = over_outs == big_outs
    out["sync_outputs_identical"] = sync_outs == over_outs
    out["throughput_frac"] = (over["tokens_per_s"]
                              / max(tight["tokens_per_s"], 1e-9))
    out["overlap_ratio"] = over.get("overlap_ratio")
    print(f"[overlap] preempting tokens/s="
          f"{over['tokens_per_s']:.1f} vs never-preempted "
          f"{tight['tokens_per_s']:.1f} on the same tight pool "
          f"({out['throughput_frac']:.1%}), "
          f"preemptions={over['preemptions']}, overlap ratio="
          f"{out['overlap_ratio'] if out['overlap_ratio'] is None else format(out['overlap_ratio'], '.2f')}"
          f" (outputs identical: big={out['outputs_identical']}, "
          f"sync={out['sync_outputs_identical']})")
    failures = []
    if not out["outputs_identical"]:
        failures.append("pipelined preempt-then-resume is not lossless: "
                        "overlap-run outputs differ from the big-pool run")
    if not out["sync_outputs_identical"]:
        failures.append("overlap changed tokens: pipelined outputs "
                        "differ from the --no-overlap synchronous replay")
    if not (tight_det and over_det):
        failures.append("outputs differed between reps of the same "
                        "configuration — the pipeline is nondeterministic")
    if over["preemptions"] < 1:
        failures.append("the oversubscribed trace never preempted — the "
                        "tight pool is not actually oversubscribed")
    if out["throughput_frac"] < 1.0 - args.overlap_tolerance:
        failures.append(
            f"preempting throughput {over['tokens_per_s']:.1f} tok/s "
            f"fell {1 - out['throughput_frac']:.1%} below the "
            f"never-preempted same-pool run's {tight['tokens_per_s']:.1f} "
            f"(> {args.overlap_tolerance:.0%} tolerance) — preemption "
            "is not overlap-free")
    if "unified.overlap" not in over["bucket_wall_ms"]:
        failures.append("no 'unified.overlap' wall bucket — the deferred "
                        "harvest never measured overlapped device time")
    if not (out["overlap_ratio"] and out["overlap_ratio"] > 0):
        failures.append(
            f"measured overlap ratio {out['overlap_ratio']} is not > 0 — "
            "the pipeline never hid device time behind host work")
    for name, cnt in over["trace_counts"].items():
        if cnt > 1:
            failures.append(f"overlap run traced step '{name}' {cnt}x — "
                            "zero-recompile contract broken")
    out["failures"] = failures
    out["passed"] = not failures
    for msg in failures:
        print(f"[overlap-gate] FAIL: {msg}")
    return out


def run_slo_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Poisson-arrival mixed-SLO trace: FIFO vs SLO-aware goodput.

    The trace is the deadline regime the SLO rewiring exists for: long
    deadline-free background generations saturate the slots and the
    queue from cycle 0, while short interactive requests with TTFT
    deadlines (and ITL targets) arrive Poisson at ``--slo-rate``
    requests per cycle. ONE scheduler (paged + swap, ``block == chunk ==
    γ+1`` so preemption stays bitwise-safe) replays it three ways:

    * **fifo** — ``slo_aware`` off: the pre-SLO decision paths. The
      interactive requests queue behind the whole background backlog
      (same priority, and SRPT blocks preemption for a FIFO head), so
      their deadlines blow by tens of cycles.
    * **slo** — ``slo_aware`` on: EDF admission jumps the feasible
      deadlines over the deadline-free backlog, and the victim policy
      swaps out a background row (costing zero goodput) to seat them.
    * **default** — the same trace with NO SLOs submitted: must make
      decision-for-decision the same schedule as the fifo run (the
      all-default bitwise pin — SLO machinery never engages unasked).

    Deadlines are *submitted* in milliseconds through the warmup-
    measured cycle cost (the online model converts them back at the
    decision points), but the gate judges hits deterministically in
    cycle space: first token within ``--slo-deadline-cycles`` of
    arrival, every inter-token gap within the ITL target. ``--slo-gate``
    hard-fails unless FIFO's hit rate is < 60% at this λ while SLO-aware
    hits >= 85%, outputs are bitwise identical across all three runs,
    the default run reproduces FIFO's admission schedule, and every jit
    step compiled exactly once across the whole replay."""
    gamma = args.gamma
    block = gamma + 1
    rng = np.random.default_rng(args.seed + 6)
    key = jax.random.PRNGKey(args.seed + 6)
    # 4 slots: enough parallel service that the SLO-aware run can absorb
    # λ interactive arrivals once it evicts the background rows — with 2
    # slots the interactive backlog itself outgrows the deadline and no
    # admission policy can save it
    slots = 4
    n_batch, n_inter = 2 * slots, args.slo_requests
    long_new, inter_new = 4 * args.max_new, args.max_new
    d_ttft = float(args.slo_deadline_cycles)    # cycles, gate units
    d_itl = 4.0                                 # max inter-token gap, cycles
    prompt_len = 2 * block
    prompts, max_news, arrivals, kinds = [], [], [], []
    for i in range(n_batch):
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)))
        max_news.append(long_new)
        arrivals.append(0.0)
        kinds.append("batch")
    t = 4.0
    for i in range(n_inter):
        t += float(rng.exponential(1.0 / args.slo_rate))
        prompts.append(jax.device_get(jax.random.randint(
            jax.random.fold_in(key, 100 + i), (prompt_len,), 0,
            cfg.vocab_size)))
        max_news.append(inter_new)
        arrivals.append(t)
        kinds.append("interactive")
    s_max = prompt_len + long_new + gamma + 1
    s_max += (-s_max) % block
    num_blocks = slots * blocks_needed(s_max, block) + 2
    sched = Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                      num_slots=slots, s_max=s_max, rt_extra=rt_extra,
                      paged=True, block_size=block, chunk_size=block,
                      num_blocks=num_blocks, swap=True,
                      overlap=not args.no_overlap)
    # warmup: trace the chunk + unified buckets and seed the cost
    # model's cycle<->ms exchange rate with real measurements, so the
    # ms deadlines below correspond to the intended cycle budgets
    for i in range(2):
        sched.submit(prompts[n_batch + i], max_new=4, arrival=float(i))
    sched.run()
    cyc_ms = sched.cost.cycle_ms()

    def one_run(slo_aware, with_slos):
        sched.slo_aware = slo_aware
        sched.reset()
        reqs = []
        for p, mn, a, kind in zip(prompts, max_news, arrivals, kinds):
            slo = {}
            if with_slos and kind == "interactive":
                slo = {"ttft_deadline_ms": d_ttft * cyc_ms,
                       "itl_target_ms": d_itl * cyc_ms}
            reqs.append(sched.submit(p, max_new=mn, arrival=a, **slo))
        t0 = time.perf_counter()
        sched.run()
        s = sched.summary()
        s["wall_s"] = time.perf_counter() - t0
        return s, reqs

    def hit(req, kind):
        """Deterministic cycle-space SLO verdict for the gate."""
        if kind != "interactive":
            return None
        if req.ttft_cycles is None or req.ttft_cycles > d_ttft:
            return False
        gaps = req.itl_cycles
        return not (gaps.size and float(gaps.max()) > d_itl)

    out = {"requests": len(prompts), "interactive": n_inter,
           "slo_rate": args.slo_rate, "ttft_deadline_cycles": d_ttft,
           "itl_target_cycles": d_itl, "cycle_ms_at_submit": cyc_ms,
           "block_size": block, "num_blocks": num_blocks, "runs": {}}
    results = {}
    for mode, slo_aware, with_slos in (("fifo", False, True),
                                       ("slo", True, True),
                                       ("default", True, False)):
        s, reqs = one_run(slo_aware, with_slos)
        hits = [hit(r, k) for r, k in zip(reqs, kinds)]
        n_hit = sum(1 for h in hits if h)
        s["slo_hit_rate_cycle_space"] = n_hit / max(n_inter, 1)
        ttfts = [r.ttft_cycles for r, k in zip(reqs, kinds)
                 if k == "interactive"]
        s["interactive_ttft_mean_cycles"] = float(np.mean(
            [t for t in ttfts if t is not None] or [np.nan]))
        out["runs"][mode] = s
        results[mode] = ([r.output for r in reqs],
                         [r.admitted_at for r in reqs])
        if mode != "default":
            print(f"[slo:{mode:>7}] deadline hits {n_hit}/{n_inter} "
                  f"({s['slo_hit_rate_cycle_space']:.0%}), interactive "
                  f"ttft mean={s['interactive_ttft_mean_cycles']:.1f}cyc, "
                  f"preemptions={s['preemptions']}, "
                  f"cycles={s['cycles']}")
    fifo, slo = out["runs"]["fifo"], out["runs"]["slo"]
    out["outputs_identical"] = (results["fifo"][0] == results["slo"][0]
                                == results["default"][0])
    out["default_matches_fifo_schedule"] = (
        results["default"][1] == results["fifo"][1]
        and out["runs"]["default"]["cycles"] == fifo["cycles"])
    failures = []
    if not out["outputs_identical"]:
        failures.append("SLO scheduling is not lossless: per-request "
                        "outputs differ between the fifo/slo/default runs")
    if not out["default_matches_fifo_schedule"]:
        failures.append("all-default run diverged from the pre-SLO FIFO "
                        "schedule — the SLO machinery engaged unasked")
    if fifo["slo_hit_rate_cycle_space"] >= 0.60:
        failures.append(
            f"FIFO hit rate {fifo['slo_hit_rate_cycle_space']:.0%} is not "
            f"< 60% — λ={args.slo_rate} is not a regime where FIFO "
            "misses badly, the gate discriminates nothing")
    if slo["slo_hit_rate_cycle_space"] < 0.85:
        failures.append(
            f"SLO-aware hit rate {slo['slo_hit_rate_cycle_space']:.0%} "
            "< 85% — goodput scheduling is not rescuing the deadlines")
    for name, cnt in slo["trace_counts"].items():
        if cnt > 1:
            failures.append(f"step '{name}' traced {cnt}x across the "
                            "replay — zero-recompile contract broken")
    out["failures"] = failures
    out["passed"] = not failures
    print(f"[slo] hit rate fifo={fifo['slo_hit_rate_cycle_space']:.0%} → "
          f"slo-aware={slo['slo_hit_rate_cycle_space']:.0%} at "
          f"λ={args.slo_rate}/cycle (outputs identical: "
          f"{out['outputs_identical']}, default≡fifo: "
          f"{out['default_matches_fifo_schedule']}, cycle_ms="
          f"{cyc_ms:.2f})")
    for msg in failures:
        print(f"[slo-gate] FAIL: {msg}")
    del sched
    return out


def run_telemetry_compare(cfg, params, cass, ecfg, args, rt_extra) -> dict:
    """Same paged trace through a telemetry-off and a tracing-on
    scheduler: outputs and trace_counts must be bitwise identical (the
    tracer adds no compile buckets and changes no tokens), and the
    traced run's best-of-N tokens/s must stay within --telemetry-overhead
    of the untraced run's. Wall time on shared runners is noisy, so each
    mode replays the trace ``reps`` times interleaved and the gate
    compares the best rep of each — steady-state overhead, not scheduler
    jitter. The tracing run's final rep feeds --trace-out/--metrics-out."""
    lens = [int(x) for x in args.mixed_lens.split(",")]
    key = jax.random.PRNGKey(args.seed + 5)
    prompts = [jax.device_get(jax.random.randint(
        jax.random.fold_in(key, i), (lens[i % len(lens)],), 0,
        cfg.vocab_size)) for i in range(args.requests)]
    s_max = max(lens) + args.max_new + args.gamma + 1
    s_max += (-s_max) % args.block_size
    scheds = {
        "off": Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                         num_slots=args.slots, s_max=s_max,
                         rt_extra=rt_extra, paged=True,
                         block_size=args.block_size,
                         overlap=not args.no_overlap,
                         telemetry=Telemetry(trace=False)),
        "on": Scheduler(cfg, params, cass=cass, ecfg=ecfg,
                        num_slots=args.slots, s_max=s_max,
                        rt_extra=rt_extra, paged=True,
                        block_size=args.block_size,
                        overlap=not args.no_overlap,
                        telemetry=Telemetry(trace=True)),
    }
    reps = 3
    out = {"reps": reps, "overhead_budget": args.telemetry_overhead,
           "runs": {}}
    best = {}
    outputs: dict = {}
    for mode, sched in scheds.items():  # warm both compile caches first
        run_trace(sched, prompts[:2], max_new=4, lam=4.0)
    for rep in range(reps):
        for mode, sched in scheds.items():
            s, outs = run_trace(sched, prompts, max_new=args.max_new,
                                lam=4.0)
            if rep == 0:
                outputs[mode] = outs
            elif outputs[mode] != outs:
                outputs[mode] = None  # nondeterminism — fails the gate
            if mode not in best or s["tokens_per_s"] > best[mode]:
                best[mode] = s["tokens_per_s"]
            out["runs"][mode] = s
    on, off = out["runs"]["on"], out["runs"]["off"]
    out["tokens_per_s_best"] = dict(best)
    out["overhead_frac"] = 1.0 - best["on"] / max(best["off"], 1e-9)
    failures = []
    if outputs["on"] is None or outputs["on"] != outputs["off"]:
        failures.append("telemetry is not lossless: per-request outputs "
                        "differ between the traced and untraced runs")
    if on["trace_counts"] != off["trace_counts"]:
        failures.append(
            f"tracing changed compile buckets: on={on['trace_counts']} "
            f"vs off={off['trace_counts']}")
    if best["on"] < (1.0 - args.telemetry_overhead) * best["off"]:
        failures.append(
            f"telemetry overhead {out['overhead_frac']:.1%} exceeds the "
            f"{args.telemetry_overhead:.0%} budget (best tokens/s "
            f"on={best['on']:.1f} vs off={best['off']:.1f})")
    if on["telemetry"]["trace_events"] == 0:
        failures.append("tracing run recorded zero events — the gate "
                        "measured nothing")
    out["failures"] = failures
    out["passed"] = not failures
    print(f"[telemetry] overhead={out['overhead_frac']:+.1%} of "
          f"{args.telemetry_overhead:.0%} budget (best tokens/s "
          f"on={best['on']:.1f} off={best['off']:.1f}), "
          f"events={on['telemetry']['trace_events']}, outputs identical: "
          f"{outputs['on'] is not None and outputs['on'] == outputs['off']}")
    for msg in failures:
        print(f"[telemetry-gate] FAIL: {msg}")
    if args.trace_out:
        write_trace(args.trace_out, scheds["on"].telemetry.tracer)
        print(f"[telemetry] trace written to {args.trace_out} "
              f"(load in Perfetto / chrome://tracing)")
    if args.metrics_out:
        write_metrics(args.metrics_out, on)
        print(f"[telemetry] metrics written to {args.metrics_out}")
    del scheds
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--rates", default="1,4,16")
    ap.add_argument("--fused-gate", action="store_true",
                    help="fail the run unless the fused scheduler beats "
                    "alternating on p95 inter-token latency at λ>=4 "
                    "without losing aggregate throughput (nightly gate)")
    ap.add_argument("--max-prefill-tokens-per-step", type=int, default=None,
                    help="fused mode: cap prefill tokens per cycle so "
                    "admission bursts can't monopolise a cycle's compute")
    ap.add_argument("--paged", action="store_true",
                    help="also compare slot vs paged KV residency on a "
                    "mixed-length trace (lossless paging check)")
    ap.add_argument("--mixed-lens", default="8,12,8,64",
                    help="cycled prompt lengths for the --paged trace")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV block size (tokens per block)")
    ap.add_argument("--prefix", action="store_true",
                    help="also replay a prefix-reuse trace (70%% shared "
                    "header) with the radix prefix cache on vs off")
    ap.add_argument("--prefix-gate", action="store_true",
                    help="fail the run unless the prefix cache is "
                    "bitwise lossless, cuts prefill tokens >= 40%% on "
                    "the shared-header trace, holds residency, and "
                    "beats cold TTFT on a full-prefix hit (nightly gate)")
    ap.add_argument("--oversub", action="store_true",
                    help="also replay an oversubscription trace (pool "
                    "sized to a fraction of the measured peak residency) "
                    "with preemption + host swap on vs off")
    ap.add_argument("--swap-gate", action="store_true",
                    help="fail the run unless preempt-then-resume is "
                    "bitwise lossless on the oversubscribed trace, >=1 "
                    "preemption fires, the queue head's TTFT beats the "
                    "no-preemption wait, swapped bytes are reported, and "
                    "every step compiles exactly once (nightly gate)")
    ap.add_argument("--overlap-gate", action="store_true",
                    help="fail the run unless the pipelined "
                    "dispatch/harvest overlap keeps the oversubscribed "
                    "(preempt+swap) trace's tokens/s within "
                    "--overlap-tolerance of the never-preempted run, "
                    "measures overlap ratio > 0, stays bitwise identical "
                    "to both the big-pool run and a --no-overlap replay, "
                    "and compiles every step exactly once (nightly gate)")
    ap.add_argument("--overlap-tolerance", type=float, default=0.05,
                    help="tokens/s fraction the oversubscribed overlap "
                    "run may lose to the never-preempted run before "
                    "--overlap-gate fails")
    ap.add_argument("--no-overlap", action="store_true",
                    help="run every scheduler with the pipelined "
                    "dispatch/harvest overlap disabled (the synchronous "
                    "pre-PR-10 step loop); the --overlap-gate compare "
                    "constructs its own on/off pair regardless")
    ap.add_argument("--slo", action="store_true",
                    help="also replay a Poisson-arrival mixed-SLO trace "
                    "(deadline-free background + interactive TTFT/ITL "
                    "deadlines) with FIFO vs SLO-aware scheduling")
    ap.add_argument("--slo-gate", action="store_true",
                    help="fail the run unless, at --slo-rate, FIFO's "
                    "deadline-hit rate is < 60%% while SLO-aware "
                    "scheduling hits >= 85%%, outputs are bitwise "
                    "identical across runs, the all-default replay "
                    "matches the pre-SLO FIFO schedule, and every step "
                    "compiles exactly once (nightly gate)")
    ap.add_argument("--slo-rate", type=float, default=0.5,
                    help="Poisson arrival rate of interactive SLO "
                    "requests (requests per decode cycle) in the --slo "
                    "trace")
    ap.add_argument("--slo-requests", type=int, default=8,
                    help="interactive SLO-carrying requests in the "
                    "--slo trace (on top of 8 background generations)")
    ap.add_argument("--slo-deadline-cycles", type=float, default=12,
                    help="TTFT deadline (in decode cycles; submitted in "
                    "ms through the measured cycle cost) for the --slo "
                    "trace's interactive requests")
    ap.add_argument("--oversub-frac", type=float, default=0.6,
                    help="tight-pool size as a fraction of the big-pool "
                    "run's measured peak residency")
    ap.add_argument("--oversub-requests", type=int, default=6,
                    help="requests in the --oversub trace (2 long "
                    "background + the rest short interactive)")
    ap.add_argument("--prefix-header", type=int, default=64,
                    help="shared header length for the --prefix trace")
    ap.add_argument("--prefix-requests", type=int, default=10,
                    help="requests in the --prefix trace")
    ap.add_argument("--telemetry", action="store_true",
                    help="also replay the mixed-length paged trace with "
                    "lifecycle tracing on vs off (losslessness + "
                    "overhead measurement)")
    ap.add_argument("--telemetry-gate", action="store_true",
                    help="fail the run unless tracing is bitwise "
                    "lossless, adds zero compile buckets, and costs "
                    "<= --telemetry-overhead of untraced best-rep "
                    "tokens/s (nightly gate)")
    ap.add_argument("--telemetry-overhead", type=float, default=0.03,
                    help="tokens/s fraction the traced run may lose to "
                    "the untraced run before --telemetry-gate fails")
    ap.add_argument("--trace-out", default="",
                    help="write the tracing run's Perfetto/Chrome "
                    "trace_event JSON here (with --telemetry[-gate])")
    ap.add_argument("--metrics-out", default="",
                    help="write the tracing run's metrics snapshot as "
                    "newline-JSON here (with --telemetry[-gate])")
    ap.add_argument("--trained", action="store_true",
                    help="use the cached 300-step smoke checkpoint "
                    "(realistic acceptance) instead of random init")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    rates = [float(r) for r in args.rates.split(",")]
    if any(r <= 0 for r in rates):
        ap.error(f"--rates must be positive (got {args.rates})")
    if args.trained:
        cfg, params = common.trained_smoke_model(args.arch, seed=args.seed)
    else:
        cfg = get_config(args.arch, smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
    cass = CassandraConfig(variant=1, gamma=args.gamma)
    packed = resolve_views(
        common.calibrated_format(cfg, params, cass) if args.trained
        else common.calibrated_format(cfg, params, cass, calibrate=False),
        cass)

    # a serving-shaped trace: mixed prompt lengths and output budgets so
    # retirement desynchronises and admission overlaps live decode — the
    # regime the fused step exists for (uniform requests retire in
    # lock-step, leaving nothing to piggyback admission on)
    key = jax.random.PRNGKey(args.seed + 1)
    lens = [max(4, args.prompt_len * f // 4) for f in (4, 2, 3, 6)]
    max_news = [max(4, args.max_new * f // 4) for f in (4, 6, 3, 5)]
    prompts = [jax.device_get(jax.random.randint(
        jax.random.fold_in(key, i), (lens[i % len(lens)],), 0,
        cfg.vocab_size)) for i in range(args.requests)]
    req_max_new = [max_news[i % len(max_news)]
                   for i in range(args.requests)]
    s_max = max(lens) + max(max_news) + args.gamma + 1
    rt_extra = {"ssm_chunk": 8}

    ecfg = EngineConfig(gamma=args.gamma)
    scheds = {
        "fused": Scheduler(cfg, packed, cass=cass, ecfg=ecfg,
                           num_slots=args.slots, s_max=s_max,
                           rt_extra=rt_extra, fused=True,
                           overlap=not args.no_overlap,
                           max_prefill_tokens_per_step=(
                               args.max_prefill_tokens_per_step)),
        "alternating": Scheduler(cfg, packed, cass=cass, ecfg=ecfg,
                                 num_slots=args.slots, s_max=s_max,
                                 rt_extra=rt_extra, fused=False),
        "autoregressive": Scheduler(cfg, params, cass=None, ecfg=ecfg,
                                    num_slots=args.slots, s_max=s_max,
                                    speculative=False, rt_extra=rt_extra),
    }
    report = {"arch": args.arch, "requests": args.requests,
              "slots": args.slots, "max_new": args.max_new,
              "gamma": args.gamma, "trained": args.trained, "runs": []}
    outputs: dict = {}
    for mode, sched in scheds.items():
        # warm the compile cache so per-λ walls compare decode, not trace
        run_trace(sched, prompts[:2], max_new=4, lam=rates[0])
        for lam in rates:
            s, outs = run_trace(sched, prompts, max_new=req_max_new,
                                lam=lam)
            outputs[(mode, lam)] = outs
            row = {"mode": mode, "lambda": lam, **s}
            report["runs"].append(row)
            print(f"[{mode:>14}] λ={lam:<4g} tokens/s={s['tokens_per_s']:8.1f}"
                  f"  tokens/cycle={s['tokens_per_cycle']:5.2f}"
                  f"  cycles={s['cycles']:4d}"
                  f"  ttft_p95={s.get('ttft_cycles_p95') or 0:5.1f}cyc"
                  f"  itl_p95={s.get('itl_cycles_p95') or 0:4.1f}cyc"
                  f"  acceptance={s['acceptance']}")
        # one fused compile bucket must serve the whole λ sweep: every
        # admission/growth/retirement mix, with zero post-warmup recompiles
        if mode == "fused":
            report["fused_unified_traces"] = sched.trace_counts.get(
                "unified", 0)
    # the fused step commits the same per-request tokens as the
    # alternating reference (chunk-width near-ties aside, see tests for
    # the strict equal-width identity check) — report it per λ
    report["fused_outputs_identical"] = {
        str(lam): outputs[("fused", lam)] == outputs[("alternating", lam)]
        for lam in rates}
    if args.paged:
        report["paged_compare"] = run_paged_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    if args.prefix or args.prefix_gate:
        report["prefix_compare"] = run_prefix_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    if args.oversub or args.swap_gate:
        report["oversub_compare"] = run_oversub_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    if args.overlap_gate:
        report["overlap_compare"] = run_overlap_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    if args.slo or args.slo_gate:
        report["slo_compare"] = run_slo_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    if args.telemetry or args.telemetry_gate:
        report["telemetry_compare"] = run_telemetry_compare(
            cfg, packed, cass, ecfg, args, rt_extra)
    byl = {(r["mode"], r["lambda"]): r for r in report["runs"]}
    for lam in rates:
        f, a, ar = (byl[("fused", lam)], byl[("alternating", lam)],
                    byl[("autoregressive", lam)])
        print(f"λ={lam:<4g} fused vs alternating: "
              f"{f['tokens_per_cycle'] / max(a['tokens_per_cycle'], 1e-9):.2f}x"
              f" tokens/cycle, itl_p95 {a.get('itl_cycles_p95') or 0:.1f}→"
              f"{f.get('itl_cycles_p95') or 0:.1f}cyc, ttft_p95 "
              f"{a.get('ttft_cycles_p95') or 0:.1f}→"
              f"{f.get('ttft_cycles_p95') or 0:.1f}cyc "
              f"(spec vs AR: "
              f"{f['tokens_per_cycle'] / max(ar['tokens_per_cycle'], 1e-9):.2f}x"
              f" tokens/cycle)")
    failures = check_fused_gate(report)
    if report["fused_unified_traces"] != 1:
        failures.append(
            f"fused step traced {report['fused_unified_traces']}x across "
            "the sweep — the one-compile-bucket contract is broken")
    report["fused_gate"] = {"checked": args.fused_gate,
                            "failures": failures}
    for msg in failures:
        print(f"[fused-gate] FAIL: {msg}")
    if not failures:
        print("[fused-gate] fused beats alternating on p95 ITL at λ>=4 "
              "at no aggregate-throughput cost")
    out = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out)
        print(f"report written to {args.out}")
    else:
        print(out)
    if args.paged and not report["paged_compare"]["passed"]:
        raise SystemExit(1)
    if args.prefix_gate and not report["prefix_compare"]["passed"]:
        raise SystemExit(1)
    if args.swap_gate and not report["oversub_compare"]["passed"]:
        raise SystemExit(1)
    if args.overlap_gate and not report["overlap_compare"]["passed"]:
        raise SystemExit(1)
    if args.slo_gate and not report["slo_compare"]["passed"]:
        raise SystemExit(1)
    if args.telemetry_gate and not report["telemetry_compare"]["passed"]:
        raise SystemExit(1)
    if args.fused_gate and failures:
        raise SystemExit(1)
    return report


if __name__ == "__main__":
    enable_compile_cache()
    main()
