"""Continuous-batching speculative serving scheduler.

The paper's serving scenario (§VI) is memory-budgeted edge decode: many
independent requests, low instantaneous batch, long reasoning outputs. The
fixed-batch ``Engine.generate`` loop cannot admit or retire requests — the
whole batch runs until the *slowest* row finishes. This scheduler
multiplexes a request queue through one jit'd serving step per cycle.

* **Fused serving step (default)** — ``step()`` is a *planner*: each
  cycle it builds one ``CyclePlan`` work descriptor (which rows consume
  prompt-chunk tokens, which run a draft+verify cycle, which idle) and
  executes it with a single ``engine.unified_step`` call. Admission
  piggybacks on decode cycles — a prefilling row never stalls resident
  decode rows — and every role mix (admission, growth, retirement, all
  roles at once) hits the ONE fused compile bucket. Prefill advances up
  to γ+1 tokens per row per cycle (the fused pass width is the verify
  width, keeping decode rows bit-identical to the alternating path);
  ``max_prefill_tokens_per_step`` caps the per-cycle prefill token total
  so a burst of admissions cannot monopolise the cycle's compute. The
  planner keeps a second, wide ``chunk_size`` admission bucket for the
  cycles where riding is wrong: an empty decode pool (cold start —
  nothing to piggyback on or stall), or a token-cost comparison showing
  the prompt's extra slot-occupancy under γ+1-wide riding exceeds one
  stall of the resident decode rows (``_plan_wide_cycle``). Both buckets
  compile once at warmup — zero recompiles for any later role mix.
* **Alternating mode** (``fused=False``) — the PR 2 reference: cycles
  alternate between ``chunk_prefill_step`` (admission chunks, decode rows
  frozen) and ``spec_decode_step`` (decode, prefilling rows frozen).
  Kept as the losslessness/latency baseline; ``speculative=False``
  (autoregressive) always uses it.
* **Cache layouts** — ``paged=False``: a fixed (B, S_max) slot cache, one
  contiguous row per request (short requests strand the row tail).
  ``paged=True``: a global pool of fixed-size token blocks shared by all
  rows, addressed through a per-row block table (``serving.blockpool``).
  A request *reserves* its worst-case blocks at admission (no mid-flight
  OOM) but blocks are allocated lazily as the sequence grows into them,
  so resident memory tracks actual tokens, not the S_max bound.
* **Prefix sharing** (``prefix_cache=True``, paged only) — a host-side
  radix index (``serving.prefixcache``) maps block-aligned prompt-prefix
  runs to ref-counted physical blocks. Admission matches the longest
  cached prefix, aliases the matched blocks into the row's table with
  zero copies, seeds ``pos``/``length`` past the matched tokens, and
  reserves only the *unshared* blocks; prefill then starts mid-prompt
  (a full-prefix hit rides one γ+1-wide cycle — TTFT ≈ 1 cycle). A
  request diverging inside a cached block gets a fresh block and one
  device-side block copy (copy-on-write; shared blocks are never
  written). Retired requests park their indexed blocks — resident but
  evictable (LRU leaf order) the moment reservations need the space.
* **Preemption + host swap** (``swap=True``, paged only) — the pool can
  be *oversubscribed*: when the queue head cannot reserve (blocks or
  slots), the planner's victim policy may swap a resident row OUT — its
  committed block contents are gathered device-side
  (``kvcache.spill_pool_blocks``, one fixed-width traced bucket) into a
  host ``SpillStore`` (``serving.swapstore``), its physical blocks and
  reservation return to the pool (``BlockAllocator.swap_out``; shared
  prefix blocks just drop a pin and stay matchable in the radix cache),
  and the head admits immediately. The victim requeues and resumes as an
  ordinary admission: a prefix match re-aliases whatever the cache still
  holds, and a batched ``restore_pool_blocks`` swap-in brings back the
  private tail bit-exactly. The victim policy reuses the planner's
  token-cost model: preempt the lowest-priority resident row whose
  remaining-work cycles beat the head's time-to-first-token (plus the
  swap round-trip margin); among equal priorities only rows with MORE
  remaining work than the head's total are eligible, so preemption is
  shortest-remaining-first and can never thrash between two long rows.
  Preempt-then-resume is lossless: restored bytes are bit-copies, so
  per-request outputs are identical to a never-preempted run.
* **Retirement** — per-row early exit on ``max_new``, the global
  ``eos_id``, or any of the request's own ``stop_tokens``; the slot (and
  its blocks, when paged) is freed immediately for the next request.
* **Async overlap** (``overlap=True``, fused mode's default) — the
  serving loop is a one-cycle-deep dispatch/harvest pipeline. Each
  ``step()`` dispatches cycle N and defers its ``device_get`` to the
  top of call N+1 (a ``PendingCycle`` record carries the plan, the
  step's non-donated device result handles, and the wall stamps), so
  host planning + harvest of cycle N−1 run while the device works. Two
  regimes keep it lossless: whenever a scheduling decision could read
  stale state (queued requests, prefilling rows, pending CoW, non-greedy
  sampling) the call *drains* first — harvest precedes admission, so
  every decision sees exactly the synchronous state and pipelining is
  purely across the call boundary. On pure-decode stretches the call
  *free-runs*: it dispatches first, chaining ``cur`` device-side off the
  pending cycle's ``next_token`` handle with the device-authoritative
  ``length`` (committed by ``engine.commit`` in-step), then harvests the
  previous cycle in the shadow of the new dispatch. A retire decision
  that lands one cycle late makes the retired row a *zombie* for one
  already-dispatched cycle — its results are discarded at harvest,
  never delivered (outputs stay bitwise identical to ``overlap=False``
  at zero extra recompiles; the only cost is one trailing zombie cycle
  when the pool empties). Spill/restore copies double-buffer against the
  next fused step (``SpillStore.put_async`` + a restore completion
  marker, both landed at the next harvest), and the next prefill
  chunk's operands are staged on device during the current verify.
* **Latency accounting** — every delivered token records its commit
  cycle and wall time, so ``summary()`` reports TTFT and p50/p95
  inter-token latency (the fused-vs-alternating headline in
  ``benchmarks/throughput.py``).
* **Profiler spans** — a cycle's host phases open spans on the JAX
  profiler's clock (``telemetry.span``): ``sched.admit``, ``sched.plan``,
  ``sched.dispatch``, ``sched.wait`` (the sanctioned block on the step)
  and ``sched.harvest``. A profiled run shows each device idle gap
  beside the phase that held the host; the fused step's own ops carry
  the ``cass.*`` named scopes (``engine.unified_step``).

γ=0 / ``speculative=False`` degrades to continuous-batching autoregressive
decode — the serving baseline for ``benchmarks/throughput.py``.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_groups
from repro.core.format import CassandraConfig
from repro.models import model as M
from repro.models.layers import Runtime
from repro.serving import kvcache as KC
from repro.serving.blockpool import (BlockAllocator, TRASH_BLOCK,
                                     blocks_needed)
from repro.serving.costmodel import CostModel
from repro.serving.engine import (EngineConfig, autoregressive_step,
                                  chunk_prefill_step, spec_decode_step,
                                  unified_step, validate_request_slos,
                                  validate_serving_knobs)
from repro.serving.prefixcache import PrefixCache, PrefixMatch
from repro.serving.swapstore import SpillStore
from repro.serving import telemetry as TM
from repro.serving.telemetry import Telemetry

QUEUED, RUNNING, FINISHED = "queued", "running", "finished"
# preempted: swapped out to the host SpillStore, waiting to resume
SWAPPED = "swapped"

# cycles a preemption is budgeted to cost the victim (spill + restore
# dispatch) — part of the bar the queue head's TTFT gain must clear
SWAP_MARGIN_CYCLES = 2


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request moving through the scheduler lifecycle.

    ``priority`` orders admission (higher admitted first among ready
    requests; FIFO within a priority — the all-default case is bitwise
    the pre-priority FIFO) and shields against preemption (lower
    priority preempted first). A preempted request carries its
    ``swap_key`` into the host ``SpillStore`` until it resumes.

    ``ttft_deadline_ms`` / ``itl_target_ms`` are per-request SLOs: once
    any queued request declares one, the scheduler's three decision
    points (admission order, wide-cycle choice, preemption victims)
    switch to deadline-hit goodput and ``priority`` demotes to the tie
    break. SLOs never change a request's tokens — only when they land."""
    rid: int
    tokens: np.ndarray                  # (L,) int prompt
    max_new: int
    arrival: float = 0.0                # scheduler-clock cycle of arrival
    stop_tokens: tuple = ()             # per-request stop ids (besides eos)
    priority: int = 0                   # higher = admitted first, kept last
    ttft_deadline_ms: float | None = None   # first token due within (SLO)
    itl_target_ms: float | None = None      # max inter-token gap (SLO)
    state: str = QUEUED
    slot: int = -1
    pos: int = 0                        # prompt tokens prefilled so far
    prefix_matched: int = 0             # prompt tokens seeded from the cache
    prefill_done: bool = False
    output: list = dataclasses.field(default_factory=list)
    token_cycles: list = dataclasses.field(default_factory=list)
    token_walls: list = dataclasses.field(default_factory=list)
    admitted_at: float = -1.0
    finished_at: float = -1.0
    swap_key: object = None             # SpillStore key while SWAPPED
    preemptions: int = 0                # times this request was swapped out

    @property
    def done(self) -> bool:
        return self.state == FINISHED

    @property
    def ttft_cycles(self) -> float | None:
        """Cycles from arrival to the first delivered token."""
        if not self.token_cycles:
            return None
        return self.token_cycles[0] - self.arrival

    @property
    def itl_cycles(self) -> np.ndarray:
        """Inter-token gaps in cycles (speculative bursts contribute 0s)."""
        return np.diff(np.asarray(self.token_cycles, np.float64))

    @property
    def has_slo(self) -> bool:
        return (self.ttft_deadline_ms is not None
                or self.itl_target_ms is not None)


@dataclasses.dataclass
class CyclePlan:
    """One fused cycle's work descriptor, built by the planner.

    ``chunk_tokens`` (slots, γ+1) / ``prefill_valid`` (slots,) carry each
    prefilling row's next prompt tokens; ``decode_mask`` (slots,) marks
    rows running a draft+verify cycle. Rows in neither set idle frozen.
    """
    chunk_tokens: np.ndarray
    prefill_valid: np.ndarray
    decode_mask: np.ndarray
    prefilling: list
    decoding: list


@dataclasses.dataclass
class PendingCycle:
    """One dispatched-but-unharvested serving cycle — the depth-1 record
    of the dispatch/harvest pipeline.

    ``res``/``last`` are the step's *device* result handles. They are
    non-donated jit outputs (the cache is the only donated operand), so
    they stay valid across the next cycle's dispatch; all outputs of one
    executable materialize together, so blocking on any one of them at
    harvest proves the whole cycle — KV commits included — has landed.
    ``clock`` is the scheduler clock at dispatch: every harvest-side
    stamp (token cycles, retirement, tracer events) uses it, so deferred
    harvests book to the cycle that produced them, exactly like the
    synchronous path."""
    kind: str                   # "unified" | "chunk" (wide admission)
    plan: CyclePlan | None      # unified cycles
    prefilling: list            # chunk cycles: rows fed this chunk
    valid: np.ndarray | None    # chunk cycles: per-slot token counts
    res: object                 # unified: SpecResult device handles
    last: object                # last-position logits device handle
    clock: float                # scheduler clock at dispatch
    t0: float                   # perf_counter at dispatch start
    t_dispatch: float           # perf_counter when dispatch returned
    stats: dict                 # target pass's expert-layer device scalars


def _freeze_rows(cache0: dict, cache: dict, active: jax.Array) -> dict:
    """Pin per-row live state of rows not active in this step.

    ``length`` and the SSM recurrent state (conv window + h) are per-row
    *live* state that a masked step would otherwise clobber with garbage.
    KV writes need no restore: a frozen row's scatter lands at positions
    >= its pinned length — masked stale data in the slot layout, its own
    stale region or the trash block in the paged layout.
    """
    out = dict(cache)
    out["length"] = jnp.where(active, cache["length"], cache0["length"])
    new_dec = []
    for g0, g1 in zip(cache0["dec"], cache["dec"]):
        gd = dict(g1)
        for ekey, e1 in g1.items():
            if isinstance(e1, dict) and "conv" in e1:
                e0 = g0[ekey]

                def mask(old, new):
                    act = active.reshape((1, -1) + (1,) * (new.ndim - 2))
                    return jnp.where(act, new, old)

                gd[ekey] = {"conv": mask(e0["conv"], e1["conv"]),
                            "h": mask(e0["h"], e1["h"])}
        new_dec.append(gd)
    out["dec"] = new_dec
    return out


def _masked_spec(rt: Runtime, params, cache: dict, cur: jax.Array,
                 key: jax.Array, active: jax.Array, ecfg: EngineConfig):
    res, new_cache = spec_decode_step(rt, params, cache, cur, key, ecfg)
    return res, _freeze_rows(cache, new_cache, active)


def _masked_auto(rt: Runtime, params, cache: dict, cur: jax.Array,
                 key: jax.Array, active: jax.Array):
    nxt, new_cache = autoregressive_step(rt, params, cache, cur, key)
    return nxt, _freeze_rows(cache, new_cache, active)


def _masked_chunk(rt: Runtime, params, cache: dict, tokens: jax.Array,
                  valid: jax.Array):
    last, new_cache, stats = chunk_prefill_step(rt, params, cache, tokens,
                                                valid)
    return last, _freeze_rows(cache, new_cache, valid > 0), stats


def _masked_unified(rt: Runtime, params, cache: dict, cur: jax.Array,
                    chunk_tokens: jax.Array, prefill_valid: jax.Array,
                    decode_mask: jax.Array, key: jax.Array,
                    ecfg: EngineConfig):
    res, last, new_cache, stats = unified_step(rt, params, cache, cur,
                                               chunk_tokens, prefill_valid,
                                               decode_mask, key, ecfg)
    active = decode_mask | (prefill_valid > 0)
    return res, last, _freeze_rows(cache, new_cache, active), stats


class Scheduler:
    """Continuous-batching front end over the speculative decode step."""

    def __init__(self, cfg: ModelConfig, params,
                 cass: CassandraConfig | None = None,
                 ecfg: EngineConfig = EngineConfig(),
                 num_slots: int = 4, s_max: int = 256,
                 eos_id: int | None = None, speculative: bool = True,
                 rt_extra: dict = {}, paged: bool = False,
                 block_size: int = 16, num_blocks: int | None = None,
                 chunk_size: int = 32, fused: bool = True,
                 max_prefill_tokens_per_step: int | None = None,
                 prefix_cache: bool = False,
                 prefix_cache_blocks: int | None = None,
                 swap: bool = False,
                 swap_store_blocks: int | None = None,
                 slo_aware: bool = True,
                 attn_kernel: str = "off",
                 overlap: bool = True,
                 debug_invariants: int | None = None,
                 telemetry: Telemetry | None = None):
        if cfg.frontend:
            raise NotImplementedError(
                "scheduler admission is token-prompt only for now")
        self.cfg, self.cass, self.ecfg = cfg, cass, ecfg
        self.params = params
        self.num_slots, self.s_max = num_slots, s_max
        self.eos_id, self.speculative = eos_id, speculative
        self.paged, self.block_size = paged, block_size
        self.chunk_size = chunk_size
        # the fused step IS a speculative cycle; the autoregressive
        # baseline keeps the alternating prefill/decode loop
        self.fused = fused and speculative
        # validate on the raw knobs BEFORE deriving pool sizes, so e.g.
        # block_size=0 reads as a ValueError, not a ZeroDivisionError
        # (the default-pool prefix_cache_blocks bound is re-checked by
        # PrefixCache against the resolved pool capacity)
        validate_serving_knobs(
            cfg, gamma=ecfg.gamma, num_slots=num_slots, s_max=s_max,
            chunk_size=chunk_size, fused=self.fused,
            speculative=speculative, paged=paged, block_size=block_size,
            num_blocks=num_blocks, prefix_cache=prefix_cache,
            prefix_cache_blocks=prefix_cache_blocks,
            max_prefill_tokens_per_step=max_prefill_tokens_per_step,
            swap=swap, swap_store_blocks=swap_store_blocks,
            attn_kernel=attn_kernel)
        self.attn_kernel = attn_kernel
        # one-cycle-deep dispatch/harvest pipelining (async overlap).
        # Like ``fused`` it degrades silently: the alternating and
        # autoregressive baselines stay synchronous.
        self.overlap = overlap and self.fused
        if paged:
            self.max_blocks = blocks_needed(s_max, block_size)
            # default pool: capacity-equivalent to the slot layout (+trash)
            self.num_blocks = (num_blocks if num_blocks is not None
                               else num_slots * self.max_blocks + 1)
        self.max_prefill_tokens_per_step = max_prefill_tokens_per_step
        self.prefix_cache_enabled = prefix_cache
        self.prefix_cache_blocks = prefix_cache_blocks
        self.swap = swap
        self.swap_store_blocks = swap_store_blocks
        # SLO-aware goodput scheduling: on by default, but it only ever
        # ACTIVATES once some queued request declares an SLO — the
        # all-default run takes the legacy (pre-SLO) decision paths
        # byte for byte (pinned by tests and the nightly gate)
        self.slo_aware = slo_aware
        # online measured cost model (tokens -> ms per compile bucket),
        # fed one observation per device step by _stamp_wall; persists
        # across reset() like the compiled steps it measures
        self.cost = CostModel()
        # observability bundle (serving.telemetry): lifecycle tracer +
        # metrics registry. The registry is the ONE keyed store serving
        # numbers live in, and its wall observations feed the cost model
        # through the same bucket keys — ``bucket_wall_ms`` and
        # ``cost_model`` can no longer diverge. The tracer is fed only
        # host-authoritative values (planner decisions, harvested numpy
        # results, allocator transitions): telemetry on/off is bitwise
        # identical serving with zero extra syncs or compiles.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.telemetry.bind_cost(self.cost)
        # run the cross-registry check_invariants() every N steps
        # (0 = off). Defaults from REPRO_DEBUG_INVARIANTS so the test
        # suite turns it on globally (tests/conftest.py) without every
        # construction site opting in.
        if debug_invariants is None:
            env = os.environ.get("REPRO_DEBUG_INVARIANTS", "")
            debug_invariants = int(env) if env else 0
        self.debug_invariants = int(debug_invariants)
        self.rt = Runtime(cfg=cfg, cass=cass,
                          view="target" if cass else "plain",
                          attn_kernel=attn_kernel, **rt_extra)
        # expert layers in a pass: their statistics are fetched at harvest
        self.expert_layers = sum(g.repeats * sum(e[1] == "M"
                                                 for e in g.entries)
                                 for g in layer_groups(cfg))
        packed = cass is not None
        if paged:
            self.cache = KC.init_paged_cache(
                cfg, cass, num_slots, self.num_blocks, block_size,
                self.max_blocks, packed=packed)
            self.capacity = self.max_blocks * block_size
        else:
            self.cache = KC.init_cache(cfg, cass, num_slots, s_max,
                                       packed=packed)
            self.capacity = s_max
        # trace_counts[name] increments when jit (re)traces that step — the
        # compile-count guard: a serving run must trace each step at most
        # once, whatever mix of admission/growth/retirement it sees
        self.trace_counts: dict[str, int] = {}
        self._spec = self._jit_step(
            "spec", partial(_masked_spec, self.rt, ecfg=ecfg))
        self._auto = self._jit_step("auto", partial(_masked_auto, self.rt))
        self._chunk = self._jit_step(
            "chunk", partial(_masked_chunk, self.rt))
        self._unified = self._jit_step(
            "unified", partial(_masked_unified, self.rt, ecfg=ecfg))

        def counted_cow(cache, src, dst):
            self.trace_counts["cow"] = self.trace_counts.get("cow", 0) + 1
            return KC.copy_pool_blocks(cache, src, dst)
        # copy-on-write block copies; src/dst are traced (slots,) vectors
        # padded with trash->trash no-ops, so the step compiles once
        self._cow = jax.jit(counted_cow, donate_argnums=(0,))

        def counted_spill(cache, blocks):
            self.trace_counts["spill"] = (
                self.trace_counts.get("spill", 0) + 1)
            return KC.spill_pool_blocks(cache, blocks)

        def counted_restore(cache, blocks, data):
            self.trace_counts["restore"] = (
                self.trace_counts.get("restore", 0) + 1)
            # -> (cache, marker): the marker is a scalar output of the
            # SAME executable as the scatter, so blocking on it proves
            # the restore landed without syncing any cache leaf
            return KC.restore_pool_blocks_marked(cache, blocks, data)
        # preemption's device<->host transfer halves: ``blocks`` is a
        # traced (max_blocks,) vector padded with trash entries, so every
        # spill/restore of any real size shares ONE compile bucket each
        self._spill = jax.jit(counted_spill)
        self._restore = jax.jit(counted_restore, donate_argnums=(0,))
        self._reset_state()

    def _jit_step(self, name: str, fn):
        """jit with a trace counter (cache is arg 1 in every step, donated)."""
        def counted(*args):
            self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
            return fn(*args)
        # the compiled program's name: compile logs and profiles key on it
        counted.__name__ = counted.__qualname__ = f"serve_{name}"
        return jax.jit(counted, donate_argnums=(1,))

    def _reset_state(self) -> None:
        prev_slots: list = getattr(self, "slots", [])
        prev_pool: BlockAllocator | None = getattr(self, "pool", None)
        prev_prefix: PrefixCache | None = getattr(self, "prefix", None)
        self.slots: list[Request | None] = [None] * self.num_slots
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.lengths = np.zeros(self.num_slots, np.int64)
        self.cur = np.zeros((self.num_slots, 1), np.int32)
        self.clock = 0.0                                # decode-cycle clock
        self.key = jax.random.PRNGKey(0)
        # per-run observability state restarts with the run (ring +
        # counters); the bound cost model and the trace on/off knob
        # persist, like the compiled steps they describe
        self.telemetry.reset()
        self.tracer = self.telemetry.tracer
        self.metrics = self.telemetry.metrics
        # zero-init the full counter set so every snapshot carries every
        # key from cycle 0
        self.metrics.declare(
            "cycles", "prefill_cycles", "mixed_cycles", "prefill_tokens",
            "committed", "accepted", "drafted", "admitted", "finished",
            "prefix_queries", "prefix_hits", "prefix_matched_tokens",
            "prefix_blocks_aliased", "cow_copies", "preemptions",
            "swap_resumes", "swap_out_blocks", "swap_in_blocks",
            "swap_matched_blocks", "moe_expert_tokens", "moe_expert_slots",
            "moe_dropped")
        for peak in ("peak_prefill_tokens_per_cycle",
                     "peak_resident_tokens", "peak_reserved_tokens",
                     "peak_swapped_tokens"):
            self.metrics.gauge(peak, 0)
        self._next_rid = 0
        self._next_swap_key = 0
        self._steps_since_check = 0
        self._slo_seen = False      # any request this run declared an SLO
        self.prefix: PrefixCache | None = None
        self._pending_cow: list[tuple[int, int]] = []
        # pipeline state: the one-cycle-deep pending record, the staged
        # next-chunk device operands, and deferred spill/restore
        # completions awaiting their harvest-point stamp. reset()
        # DISCARDS them (device handles just drop) — a fresh run never
        # harvests the previous run's in-flight cycle.
        self._pending: PendingCycle | None = None
        self._prefetch: tuple | None = None
        self._inflight: list[tuple] = []
        if self.paged:
            if prev_pool is not None and prev_prefix is not None:
                # persist the radix index across reset (ROADMAP
                # follow-up): retire every live owner so only parked
                # (cacheable) chains stay resident — their device bytes
                # are intact (parked blocks are never on the free list),
                # so the next run's admissions match them warm
                for slot, r in enumerate(prev_slots):
                    if r is not None:
                        prev_pool.release(slot)
                for key in prev_pool.swapped_keys():
                    prev_pool.drop_swapped(key)
                # per-run peak: the persisted pool's high-water restarts
                # at its current occupancy (parked chains), matching the
                # freshly-zeroed peak_* stats
                prev_pool.high_water = (prev_pool.allocated_total
                                        + prev_pool.parked_total)
                self.pool = prev_pool
                self.prefix = prev_prefix
            else:
                self.pool = BlockAllocator(self.num_blocks)
                if self.prefix_cache_enabled:
                    self.prefix = PrefixCache(self.pool, self.block_size,
                                              self.prefix_cache_blocks)
            self.table = np.full((self.num_slots, self.max_blocks),
                                 TRASH_BLOCK, np.int32)
            # per-slot logical->physical block lists (shared prefix blocks
            # first, then blocks charged to the slot's reservation)
            self.row_blocks: list[list[int]] = \
                [[] for _ in range(self.num_slots)]
            # per-slot (trie node, block index) insert watermark so
            # incremental prefix indexing never re-walks committed blocks
            self.row_index: list[tuple] = [(None, 0)] * self.num_slots
        # host spill store for preempted rows (fresh per run — swapped
        # requests of the previous run were dropped with the queue)
        self.spill = SpillStore(self.swap_store_blocks) if self.swap \
            else None
        # subsystem on/off flags: the formatter and exporters key off
        # these, so a disabled subsystem reads as an explicit "off"
        # rather than a silently-absent stats section
        self.metrics.set_config("paged", self.paged)
        self.metrics.set_config("prefix_cache", self.prefix is not None)
        self.metrics.set_config("swap", self.swap)
        self.metrics.set_config("slo_aware", self.slo_aware)
        self.metrics.set_config("slo_declared", self._slo_seen)
        self.metrics.set_config("attn_kernel", self.attn_kernel)
        self.metrics.set_config("kv_pool_decodes", M.kv_pool_decodes(
            self.rt, self.cache, drafts=self.speculative))
        self.metrics.set_config("fused", self.fused)
        self.metrics.set_config("speculative", self.speculative)
        self.metrics.set_config("overlap", self.overlap)

    def reset(self) -> None:
        """Clear queue/slots/stats for a fresh run reusing the compiled
        steps — admission re-prefills over a slot's region (or re-points
        its block table), so stale cache contents from the previous run
        are harmless. The prefix index PERSISTS across reset: parked
        chains stay resident and matchable (a warm header from the last
        run still skips its prefill), while live rows are released so
        their private blocks return to the pool."""
        self._reset_state()

    def unified_step_text(self) -> str:
        """The compiled text of the fused serving step (``serve_unified``)
        for the live cache and weights: lowered with the operand shapes a
        dispatch passes, so it is the program that runs (a compile-cache
        hit once the step has run). ``telemetry.hlo_op_scopes`` maps its
        instructions to the step's named phases."""
        b, w = self.num_slots, self.ecfg.gamma + 1
        return self._unified.lower(
            self.params, self.cache, jnp.asarray(self.cur),
            jnp.zeros((b, w), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), bool), jax.random.split(self.key)[1]
        ).compile().as_text()

    # -- queue -------------------------------------------------------------

    def _worst_case_tokens(self, n_prompt: int, max_new: int) -> int:
        """Cache tokens a request can touch: prompt + outputs + the
        decode horizon past the last committed token. A speculative
        verify pass scatters γ+1 positions past the current length; the
        autoregressive step writes exactly one — sizing AR requests at
        the speculative bound would spuriously reject prompts that fit
        (the width ``_remaining_cycles`` already gets right)."""
        horizon = self.ecfg.gamma + 1 if self.speculative else 1
        return n_prompt + max_new + horizon

    def submit(self, tokens, max_new: int, arrival: float = 0.0,
               rid: int | None = None,
               stop_tokens=None, priority: int = 0,
               ttft_deadline_ms: float | None = None,
               itl_target_ms: float | None = None) -> Request:
        """Queue one request. ``stop_tokens`` is an optional per-request
        list of token ids that end generation early (delivered inclusive,
        like EOS) — on top of the scheduler-global ``eos_id``.
        ``priority`` (default 0) orders admission among ready requests
        (higher first; FIFO within a priority, so all-default submission
        is bitwise the plain FIFO) and the preemption victim policy
        (lower-priority rows are swapped out first).

        ``ttft_deadline_ms`` (first token due within that many ms of
        arrival) and ``itl_target_ms`` (max tolerated inter-token gap)
        declare the request's SLOs. Submitting any SLO flips the
        scheduler into goodput mode (``slo_aware``): admission becomes
        earliest-deadline-first over the measured cost model, and
        ``priority`` demotes to the tie break."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        validate_request_slos(ttft_deadline_ms=ttft_deadline_ms,
                              itl_target_ms=itl_target_ms)
        need = self._worst_case_tokens(len(tokens), max_new)
        if need > self.capacity:
            raise ValueError(
                f"request needs {need} cache slots (prompt {len(tokens)} "
                f"+ max_new {max_new} + decode horizon), "
                f"capacity={self.capacity}")
        if self.paged and blocks_needed(
                need, self.block_size) > self.pool.capacity:
            raise ValueError(
                f"request needs {blocks_needed(need, self.block_size)} "
                f"blocks, pool has {self.pool.capacity}")
        req = Request(rid=self._next_rid if rid is None else rid,
                      tokens=tokens, max_new=max_new, arrival=arrival,
                      stop_tokens=tuple(stop_tokens or ()),
                      priority=priority,
                      ttft_deadline_ms=ttft_deadline_ms,
                      itl_target_ms=itl_target_ms)
        self._next_rid = req.rid + 1
        if req.has_slo:
            self._slo_seen = True
            self.metrics.set_config("slo_declared", True)
        self.queue.append(req)
        self.tracer.emit(TM.SUBMIT, rid=req.rid, cycle=self.clock,
                         args=(len(tokens), max_new))
        return req

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.slots)

    # -- admission ---------------------------------------------------------

    def _request_blocks(self, req: Request) -> int:
        return blocks_needed(
            self._worst_case_tokens(len(req.tokens), req.max_new),
            self.block_size)

    def _admission_plan(self, req: Request
                        ) -> tuple[int, PrefixMatch | None, int]:
        """(blocks to reserve, cached-prefix match, parked blocks the
        admission would pin). The reservation charges only *unshared*
        blocks: fully-matched prefix blocks are aliased, not allocated.
        The copy-on-write block of a partial match IS charged (it is a
        private divergence copy)."""
        need = self._request_blocks(req)
        if self.prefix is None:
            return need, None, 0
        m = self.prefix.match(req.tokens)
        pinned = list(m.nodes)
        if m.partial is not None and m.partial_len > 0:
            pinned.append(m.partial)
        pins = sum(1 for n in pinned if self.pool.is_parked(n.block))
        return need - len(m.nodes), m, pins

    def _resume_plan(self, req: Request) -> tuple[int, list, int]:
        """(blocks to reserve, matched trie nodes to re-alias, parked
        blocks the resume would pin) for a SWAPPED request. Resume is an
        ordinary admission-shaped prefix match — whatever chain the radix
        cache still holds is aliased instead of restored — capped at the
        row's own committed full blocks so a since-deepened cache can
        never fast-forward the row past its saved position. The spilled
        chain covers everything the match does not."""
        chain = self.spill.get(req.swap_key)
        need = self._request_blocks(req)
        if self.prefix is None:
            return need, [], 0
        m = self.prefix.match(req.tokens)
        usable = min(len(m.nodes), req.pos // self.block_size,
                     chain.n_blocks)
        nodes = list(m.nodes[:usable])
        pins = sum(1 for n in nodes if self.pool.is_parked(n.block))
        return need - len(nodes), nodes, pins

    def _admit_resumed(self, req: Request, slot: int,
                       plan: tuple[int, list, int]) -> None:
        """Swap a preempted request back in: re-reserve, re-alias the
        still-cached prefix, restore the spilled tail bit-exactly, and
        re-seed the slot's host state (length, position, last token).
        Output, latency stamps and ``admitted_at`` survive untouched —
        the request continues, it does not restart."""
        chain = self.spill.get(req.swap_key)
        n_reserve, nodes, _ = plan
        req.state, req.slot = RUNNING, slot
        self.slots[slot] = req
        self.pool.swap_in(req.swap_key, slot, n_reserve)
        self.table[slot, :] = TRASH_BLOCK
        blocks: list[int] = []
        for node in nodes:
            self.pool.share(slot, node.block)
            blocks.append(node.block)
        matched = len(nodes)
        restore_n = chain.n_blocks - matched
        for _ in range(restore_n):
            blocks.append(self.pool.alloc(slot))
        if restore_n:
            vec = np.full(self.max_blocks, TRASH_BLOCK, np.int32)
            vec[:restore_n] = blocks[matched:]
            data = jax.tree.map(
                jnp.asarray,
                chain.slice_blocks(matched, chain.n_blocks,
                                   self.max_blocks))
            t0 = time.perf_counter()
            self.cache, marker = self._restore(self.cache,
                                               jnp.asarray(vec), data)
            if self.overlap:
                # double-buffered restore: no wait here — the H2D copy
                # + scatter overlap the fused step this admission rides
                # (dispatched after it, so program order guarantees the
                # step reads restored blocks). The completion marker is
                # blocked on — and the full wall stamped — at the next
                # harvest point.
                self._stamp_wall("restore.dispatch", t0)
                self._inflight.append(
                    ("restore", marker, time.perf_counter() - t0,
                     self.clock))
            else:
                # the restore is async-dispatched; block on the
                # executable's scalar completion marker — NOT a cache
                # leaf — so the stamped wall covers the real
                # host->device transfer + scatter (the cost-model seed
                # the other buckets measure) without transferring or
                # pinning the whole cache
                # speclint: disable=sync-block(stamp the restore completion marker, not its dispatch)
                jax.block_until_ready(marker)
                self._stamp_wall("restore", t0)
            self.tracer.emit(TM.RESTORE, rid=req.rid, slot=slot,
                             cycle=self.clock, args=(restore_n,))
        self.row_blocks[slot] = blocks
        self.row_index[slot] = (nodes[-1] if nodes else None, matched)
        if blocks:
            self.table[slot, :len(blocks)] = blocks
        self.lengths[slot] = chain.length
        self.cur[slot, 0] = chain.cur
        req.pos = chain.pos
        self.spill.pop(req.swap_key)
        req.swap_key = None
        self.metrics.inc("swap_resumes")
        self.metrics.inc("swap_in_blocks", restore_n)
        self.metrics.inc("swap_matched_blocks", matched)
        self.tracer.emit(TM.RESUME, rid=req.rid, slot=slot,
                         cycle=self.clock, args=(matched, restore_n))

    def _admit(self, req: Request, slot: int,
               plan: tuple[int, PrefixMatch | None, int] | None) -> None:
        if req.state == SWAPPED:
            self._admit_resumed(req, slot, plan)
            return
        req.state, req.slot, req.admitted_at = RUNNING, slot, self.clock
        req.pos, req.prefill_done, req.output = 0, False, []
        req.prefix_matched = 0
        req.token_cycles, req.token_walls = [], []
        self.slots[slot] = req
        self.lengths[slot] = 0
        if self.paged:
            n_reserve, m, _ = plan
            # reservations are keyed by slot, not rid: slots are unique
            # while occupied, whereas callers may reuse rids
            self.pool.reserve(slot, n_reserve)
            self.table[slot, :] = TRASH_BLOCK
            blocks: list[int] = []
            if m is not None:
                self.metrics.inc("prefix_queries")
                for node in m.nodes:
                    self.pool.share(slot, node.block)
                    blocks.append(node.block)
                matched = m.full_tokens
                self.metrics.inc("prefix_blocks_aliased", len(m.nodes))
                if m.partial is not None and m.partial_len > 0:
                    # diverges inside a cached block: pin the source for
                    # the row's lifetime (it must survive until the copy
                    # lands) and take a fresh block to diverge in
                    self.pool.share(slot, m.partial.block)
                    dst = self.pool.cow(slot, m.partial.block)
                    self._pending_cow.append((m.partial.block, dst))
                    blocks.append(dst)
                    matched += m.partial_len
                    self.metrics.inc("cow_copies")
                if matched:
                    self.metrics.inc("prefix_hits")
                    self.metrics.inc("prefix_matched_tokens", matched)
                self.metrics.observe("prefix_hit_depth", matched)
                # seed the row past the matched tokens: prefill starts
                # mid-prompt, and a full-prefix hit rides one decode-width
                # cycle (TTFT ~ 1 cycle) instead of re-prefilling
                req.pos = req.prefix_matched = matched
                self.lengths[slot] = matched
            self.row_blocks[slot] = blocks
            # the matched chain is already indexed: start incremental
            # insertion at its tail (the CoW block, if any, is indexed
            # once prefill fills it)
            if self.prefix is not None:
                self.row_index[slot] = (
                    m.nodes[-1] if m.nodes else None, len(m.nodes))
            if blocks:
                self.table[slot, :len(blocks)] = blocks
        self.metrics.inc("admitted")
        self.tracer.emit(TM.ADMIT, rid=req.rid, slot=slot,
                         cycle=self.clock, args=(req.prefix_matched,))

    # -- SLO goodput model ---------------------------------------------------

    @property
    def _slo_active(self) -> bool:
        """Goodput mode engages only when enabled AND some request this
        run declared an SLO — an all-default run never leaves the legacy
        decision paths (they stay bitwise the pre-SLO scheduler)."""
        return self.slo_aware and self._slo_seen

    def _ttft_deadline_cycles(self, req: Request) -> float | None:
        """Absolute cycle the first token is due (None = no deadline).
        ms converts through the online cost model; cold start treats
        ms as cycles (the nominal exchange rate)."""
        if req.ttft_deadline_ms is None:
            return None
        return req.arrival + self.cost.ms_to_cycles(req.ttft_deadline_ms)

    def _next_event_deadline_cycles(self, req: Request) -> float | None:
        """Absolute cycle by which the request's NEXT delivered token
        must land to keep its declared SLOs intact: the TTFT deadline
        before the first token, the last commit plus the ITL target
        after. None = this request's next token is unconstrained."""
        if not req.token_cycles:
            return self._ttft_deadline_cycles(req)
        if req.itl_target_ms is None:
            return None
        return (req.token_cycles[-1]
                + self.cost.ms_to_cycles(req.itl_target_ms))

    def _admit_to_first_token_cycles(self, req: Request,
                                     matched: int) -> int:
        """Cycles from admitting ``req`` now to its first (or, resumed,
        next) token: prefill of the unmatched prompt at the riding
        width, plus the cycle that commits the token."""
        width = self.ecfg.gamma + 1 if self.speculative else 1
        unprefilled = max(len(req.tokens) - max(req.pos, matched), 0)
        return -(-unprefilled // width) + 1

    def _admission_key(self, idx: int, req: Request) -> tuple:
        """EDF admission order: (feasibility class, deadline, -priority,
        queue index). Class 0 = deadline still hittable if admitted this
        cycle, earliest first; class 1 = no pending deadline; class 2 =
        deadline already hopeless (served after everyone it could still
        help — a lost deadline must not drag live ones down with it).
        ``priority`` and FIFO order only break ties."""
        dl = self._next_event_deadline_cycles(req)
        if dl is None:
            return (1, 0.0, -req.priority, idx)
        feasible = (self.clock
                    + self._admit_to_first_token_cycles(req, req.pos)
                    <= dl)
        return (0 if feasible else 2, dl, -req.priority, idx)

    def _next_ready_index(self) -> int | None:
        """Queue index of the next request to admit. Legacy (no SLOs
        anywhere): the highest ``priority`` among *ready* requests
        (arrival <= clock), FIFO within a priority — with all-default
        priorities this is exactly the first ready request, the
        pre-priority FIFO behavior. A future arrival queued ahead never
        head-of-line-blocks one that is already due.

        Goodput mode (``_slo_active``): earliest-feasible-deadline-first
        over the measured cost model (``_admission_key``), with
        ``priority`` demoted to the tie break."""
        if self._slo_active:
            best, best_key = None, None
            for i, r in enumerate(self.queue):
                if r.arrival > self.clock:
                    continue
                key = self._admission_key(i, r)
                if best is None or key < best_key:
                    best, best_key = i, key
            return best
        best, best_p = None, None
        for i, r in enumerate(self.queue):
            if r.arrival > self.clock:
                continue
            if best is None or r.priority > best_p:
                best, best_p = i, r.priority
        return best

    # -- preemption (victim policy + host swap) ------------------------------

    def _remaining_cycles(self, req: Request) -> int:
        """Token-cost-model estimate of a row's remaining work, in the
        same worst-case cycle units ``_plan_wide_cycle`` trades in:
        γ+1-wide prefill passes for the unprefilled prompt plus one
        cycle per still-owed token (the autoregressive decode bound)."""
        width = self.ecfg.gamma + 1 if self.speculative else 1
        prefill = 0 if req.prefill_done else \
            -(-max(len(req.tokens) - req.pos, 0) // width)
        return prefill + max(req.max_new - len(req.output), 0)

    def _head_admit_cycles(self, head: Request, matched: int) -> int:
        """Cycles from admission to the head's first token (its TTFT if
        admitted now): prefill of the unmatched prompt at the riding
        width, plus the cycle that commits the first token, plus the
        swap round-trip margin a preemption spends to make room."""
        return (self._admit_to_first_token_cycles(head, matched)
                + SWAP_MARGIN_CYCLES)

    def _victim_slo_at_risk(self, req: Request) -> bool:
        """Would preempting this resident row sacrifice an SLO it can
        still hit? True when its next-token deadline is live and still
        reachable if the row stays resident (a prefilling row delivers
        after its remaining chunks; a decode row commits next cycle).
        Rows with no pending deadline — or an already-hopeless one —
        are fair game: swapping them out costs zero goodput."""
        dl = self._next_event_deadline_cycles(req)
        if dl is None:
            return False
        return self.clock + self._admit_to_first_token_cycles(
            req, req.pos) <= dl

    def _preempt(self, victim: Request) -> None:
        """Swap ``victim`` out: flush any copy-on-write it is owed, spill
        its committed blocks' contents to the host store (device gather
        BEFORE the allocator frees them), release blocks + reservation
        (``swap_out`` — shared prefix blocks just drop a pin and stay
        matchable), and requeue it at the front with its original
        arrival. Everything a bit-exact resume needs (length, prompt
        position, last committed token, KV bytes) is in the chain."""
        if self._pending_cow:
            self._flush_cow()
        slot = victim.slot
        n_res = blocks_needed(int(self.lengths[slot]), self.block_size)
        vec = np.full(self.max_blocks, TRASH_BLOCK, np.int32)
        vec[:n_res] = self.row_blocks[slot][:n_res]
        # mint an opaque token disjoint from slot-index owners: a bare
        # int would collide with slot 0/1 in the pool's reservation maps
        # and trip its swapped-key invariants
        key = ("swap", self._next_swap_key)
        self._next_swap_key += 1
        t0 = time.perf_counter()
        bytes_before = self.spill.nbytes
        data = self._spill(self.cache, jnp.asarray(vec))
        if self.overlap:
            # double-buffered spill: stage the gather's device handles
            # (its output buffer is separate from the cache, and any
            # later step that rewrites the freed blocks is dispatched
            # after it — program order makes block reuse race-free);
            # the device_get lands at the next harvest point.
            self.spill.put_async(key, data, n_res,
                                 length=int(self.lengths[slot]),
                                 pos=victim.pos,
                                 cur=int(self.cur[slot, 0]))
            self._stamp_wall("spill.dispatch", t0)
            self._inflight.append(
                ("spill", key, time.perf_counter() - t0, self.clock))
        else:
            self.spill.put(key, data, n_res,
                           length=int(self.lengths[slot]),
                           pos=victim.pos, cur=int(self.cur[slot, 0]))
            self._stamp_wall("spill", t0)
        self.tracer.emit(TM.SPILL, rid=victim.rid, slot=slot,
                         cycle=self.clock,
                         args=(n_res, self.spill.nbytes - bytes_before))
        self.pool.swap_out(slot, key, n_res)
        self.table[slot, :] = TRASH_BLOCK
        self.row_blocks[slot] = []
        self.row_index[slot] = (None, 0)
        self.slots[slot] = None
        self.lengths[slot] = 0
        victim.state, victim.slot, victim.swap_key = SWAPPED, -1, key
        victim.preemptions += 1
        self.queue.appendleft(victim)
        self.metrics.inc("preemptions")
        self.metrics.inc("swap_out_blocks", n_res)
        self.tracer.emit(TM.PREEMPT, rid=victim.rid, slot=slot,
                         cycle=self.clock, args=(n_res,))

    def _plan_for(self, req: Request):
        """The request's admission plan — resume-shaped for a SWAPPED
        request, fresh-shaped otherwise. Both are (blocks to reserve,
        cached match, parked blocks the admission would pin)."""
        return (self._resume_plan(req) if req.state == SWAPPED
                else self._admission_plan(req))

    def _try_preempt_for(self, head: Request, matched: int):
        """Victim policy: free capacity for the queue head by swapping
        out resident rows. Reuses the planner's token-cost model —
        preempt only rows whose remaining-work cycles beat the head's
        admission-to-first-token cost (the head gains more TTFT than the
        victim loses progress). Victim order: lowest priority first,
        most remaining work within a priority. Anti-thrash: an
        equal-priority victim additionally needs MORE remaining work
        than the head's total (shortest-remaining-first), so two long
        rows can never preempt each other in a loop.

        Goodput mode (``_slo_active``) maximises deadline hits instead:
        rows whose live SLO is still winnable are never sacrificed
        (``_victim_slo_at_risk``), SLO-free rows go out before
        blown-SLO rows, and ``priority`` demotes to the tie break. A
        deadline-free head keeps the full legacy bar (priority shield +
        SRPT) — it has no deadline to justify hurting anyone for.

        Returns the head's refreshed plan once it fits the pool, else
        None (no eligible victim, or everything eligible still wasn't
        enough — any rows already preempted stay out and resume on
        their own merit)."""
        head_cost = self._head_admit_cycles(head, matched)
        head_rem = self._remaining_cycles(head)
        slo_mode = self._slo_active
        head_dl = (self._next_event_deadline_cycles(head)
                   if slo_mode else None)
        cands = []
        for r in self.slots:
            if r is None:
                continue
            rem = self._remaining_cycles(r)
            if rem <= head_cost:
                continue                    # not worth the head's wait
            if slo_mode:
                if self._victim_slo_at_risk(r):
                    continue                # never sacrifice a live SLO
                if head_dl is None:
                    # deadline-free head: keep the legacy gain bar
                    if r.priority > head.priority:
                        continue            # never preempt upward
                    if r.priority == head.priority and rem <= head_rem:
                        continue            # anti-thrash: SRPT order
                cands.append(((1 if r.has_slo else 0), r.priority,
                              -rem, r.slot, r))
                continue
            if r.priority > head.priority:
                continue                    # never preempt upward
            if r.priority == head.priority and rem <= head_rem:
                continue                    # anti-thrash: SRPT order
            cands.append((r.priority, -rem, r.slot, r))
        for cand in sorted(cands, key=lambda c: c[:-1]):
            victim = cand[-1]
            n_res = blocks_needed(int(self.lengths[victim.slot]),
                                  self.block_size)
            if not self.spill.can_hold(n_res):
                continue                    # host store full: skip victim
            self._preempt(victim)
            plan = self._plan_for(head)
            if self.pool.can_reserve(plan[0], plan[2]):
                return plan
        return None

    def _admit_ready(self) -> None:
        """Admit ready requests in priority-then-FIFO order. When paged,
        the head-of-line request gates on pool reservation (its unshared
        blocks plus any parked cache blocks it would pin); it waits
        (rather than being skipped) so small requests cannot starve it —
        unless preemption (``swap=True``) can free the capacity by
        swapping out a resident row the victim policy deems cheaper."""
        while True:
            idx = self._next_ready_index()
            if idx is None:
                return
            req = self.queue[idx]
            slot = next((s for s in range(self.num_slots)
                         if self.slots[s] is None), None)
            plan = self._plan_for(req) if self.paged else None
            fits = plan is None or self.pool.can_reserve(plan[0], plan[2])
            if slot is None or not fits:
                if not self.swap:
                    return
                plan = self._try_preempt_for(
                    req, self._matched_plan_tokens(plan))
                if plan is None:
                    return
                # preemption requeued victims at the front — re-resolve
                # the head's queue position and the (now free) slot
                idx = next(i for i, r in enumerate(self.queue) if r is req)
                slot = next((s for s in range(self.num_slots)
                             if self.slots[s] is None), None)
                if slot is None:
                    return
            del self.queue[idx]
            self._admit(req, slot, plan)

    @staticmethod
    def _matched_plan_tokens(plan) -> int:
        """Cached-prefix tokens the head's plan would skip (TTFT
        estimate input for the victim policy; 0 without the cache)."""
        if plan is None:
            return 0
        m = plan[1]
        if m is None:
            return 0
        if isinstance(m, PrefixMatch):
            return m.full_tokens
        return sum(len(n.key) for n in m)       # resume plan: node list

    # -- retirement --------------------------------------------------------

    def _maybe_retire(self, req: Request, cycle: float | None = None
                      ) -> None:
        cyc = self.clock if cycle is None else cycle
        # never deliver past max_new, even when a stop lands beyond it
        capped = req.output[:req.max_new]
        stops = set(req.stop_tokens)
        if self.eos_id is not None:
            stops.add(self.eos_id)
        cut = next((i + 1 for i, t in enumerate(capped) if t in stops),
                   None) if stops else None
        if cut is not None:
            req.output = capped[:cut]
        elif len(req.output) >= req.max_new:
            req.output = capped
        else:
            return
        # truncation also drops the trimmed tokens' latency samples
        req.token_cycles = req.token_cycles[:len(req.output)]
        req.token_walls = req.token_walls[:len(req.output)]
        req.state, req.finished_at = FINISHED, cyc
        self.tracer.emit(TM.RETIRE, rid=req.rid, slot=req.slot,
                         cycle=cyc, args=(len(req.output),))
        self.slots[req.slot] = None
        if self.paged:
            # refcounted release: blocks shared with other rows stay live,
            # blocks the prefix cache indexed are parked (evictable), the
            # rest return to the free list
            self.pool.release(req.slot)
            self.row_blocks[req.slot] = []
            self.table[req.slot, :] = TRASH_BLOCK
        self.finished.append(req)
        self.metrics.inc("finished")

    def _stamp_wall(self, name: str, t0: float) -> None:
        """Fold one device-step invocation's wall time into the registry
        (``observe_wall`` feeds the ``bucket_wall_ms`` view and the
        online cost model through the SAME bucket key — the per-bucket
        fit refreshes as cycles retire) and emit a STEP trace event.
        Intervals are taken off ``time.perf_counter()`` (the monotonic
        clock): an NTP step across ``time.time()`` would make
        ``bucket_wall_ms`` negative and poison the cost model."""
        self._stamp_wall_at(name, time.perf_counter() - t0)

    def _stamp_wall_at(self, name: str, dt: float,
                       cycle: float | None = None) -> None:
        """``_stamp_wall`` with a pre-computed interval and an explicit
        cycle: the pipelined harvest books a cycle's walls one call
        late, so the stamps carry the *dispatch-time* clock, keeping the
        trace and the per-cycle views aligned with the synchronous
        path."""
        self.metrics.observe_wall(name, dt)
        self.tracer.emit(TM.STEP,
                         cycle=self.clock if cycle is None else cycle,
                         args=(name, dt * 1e3))

    def _record_tokens(self, req: Request, k: int,
                       cycle: float | None = None) -> None:
        """Stamp ``k`` just-committed tokens with their cycle's end time.
        perf_counter, not epoch time: the stamps are only ever diffed
        into inter-token gaps, which must stay non-negative."""
        now = time.perf_counter()
        cyc = self.clock if cycle is None else cycle
        req.token_cycles.extend([cyc + 1.0] * k)
        req.token_walls.extend([now] * k)

    def _harvest_decode_row(self, req: Request, tokens: np.ndarray,
                            valid: np.ndarray, n: np.ndarray,
                            nxt: np.ndarray,
                            cycle: float | None = None) -> None:
        """Fold one decode row's cycle results into the request: extend
        its output with the accepted run, stamp the tokens, advance the
        host length by n+1, and retire if a stop condition landed. Shared
        by the fused and alternating paths — retirement/accounting fixes
        apply to both (the losslessness tests compare them). ``cycle``
        is the results' dispatch-time clock (deferred harvests)."""
        slot = req.slot
        before = len(req.output)
        req.output.extend(tokens[slot][valid[slot]].tolist())
        self._record_tokens(req, len(req.output) - before, cycle=cycle)
        self.lengths[slot] += int(n[slot]) + 1
        self.cur[slot, 0] = nxt[slot]
        if self.speculative:
            # per-cycle acceptance-length histogram: THE control input
            # every adaptive-γ method hangs off (k ∈ [0, γ])
            self.metrics.observe("acceptance_len", int(n[slot]))
        self._maybe_retire(req, cycle=cycle)
        # delivered tokens only: retirement truncates past stops/max_new
        delivered = len(req.output) - before
        self.metrics.inc("committed", delivered)
        self.tracer.emit(TM.CYCLE, rid=req.rid, slot=slot,
                         cycle=self.clock if cycle is None else cycle,
                         args=(self.ecfg.gamma if self.speculative else 0,
                               int(n[slot]), delivered))

    def _fast_forward(self) -> bool:
        """No resident work: jump the clock to the next queued arrival
        (True) or report the scheduler idle (False)."""
        if self.queue:
            self.clock = max(self.clock,
                             min(r.arrival for r in self.queue))
            return True
        return False

    # -- device-state sync ---------------------------------------------------

    def _grow_blocks(self, req: Request, n_tokens: int) -> None:
        """Allocate pool blocks until ``req`` covers ``n_tokens`` and map
        them into its table row (within its admission reservation).
        Shared prefix blocks occupy the head of the row's logical list;
        only the unshared tail draws on the reservation."""
        blocks = self.row_blocks[req.slot]
        while len(blocks) * self.block_size < n_tokens:
            blocks.append(self.pool.alloc(req.slot))
        self.table[req.slot, :len(blocks)] = blocks

    def _flush_cow(self) -> None:
        """Dispatch pending copy-on-write block copies (device-side, one
        fixed-width jit step; trash->trash pairs pad the batch). Runs
        before the cycle's serving step so a diverging row's seeded
        tokens are resident before anything reads them."""
        k = self.num_slots
        while self._pending_cow:
            batch, self._pending_cow = (self._pending_cow[:k],
                                        self._pending_cow[k:])
            src = np.full(k, TRASH_BLOCK, np.int32)
            dst = np.full(k, TRASH_BLOCK, np.int32)
            for i, (s, d) in enumerate(batch):
                src[i], dst[i] = s, d
            t0 = time.perf_counter()
            self.cache = self._cow(self.cache, jnp.asarray(src),
                                   jnp.asarray(dst))
            # dispatch-only stamp (no block_until_ready — CoW stays
            # zero-sync): "cow" appears in bucket_wall_ms/cost_model
            # whenever it appears in trace_counts, closing the
            # divergent-bucket-keys hole summary() used to have
            self._stamp_wall("cow", t0)

    def _index_prefix(self, req: Request) -> None:
        """Register the row's newly-committed full prompt blocks in the
        radix cache (incremental: resumes from the slot's watermark)."""
        if self.prefix is None:
            return
        slot = req.slot
        node, start = self.row_index[slot]
        node, _ = self.prefix.insert(req.tokens, self.row_blocks[slot],
                                     req.pos, node=node, start=start)
        # the returned node's depth, not pos//block_size, is the resume
        # point: insert may have stopped early (foreign identical run)
        # or restarted from the root (stale hint)
        self.row_index[slot] = (node, node.depth)

    def _push_host_state(self) -> None:
        if self._pending_cow:
            self._flush_cow()
        self.cache["length"] = jnp.asarray(self.lengths, jnp.int32)
        if self.paged:
            self.cache["block_table"] = jnp.asarray(self.table)

    def _track_residency(self, cycle: float | None = None) -> None:
        resident = int(sum(self.lengths[r.slot] for r in self.slots
                           if r is not None))
        self.metrics.gauge_max("peak_resident_tokens", resident)
        if self.paged:
            # reserved (not merely allocated) blocks are the honest
            # memory-held figure: a reservation is unusable by anyone
            # else, as is a shared block that outlived its reservation
            # (uncharged). Parked cache blocks are excluded — they are
            # reclaimable on demand.
            reserved = (self.pool.reserved_total
                        + self.pool.uncharged_total) * self.block_size
        else:
            reserved = sum(r is not None for r in self.slots) * self.s_max
        self.metrics.gauge_max("peak_reserved_tokens", reserved)
        if self.paged and self.swap:
            # honest accounting for oversubscription: swapped rows hold
            # ZERO device blocks — their tokens live host-side and are
            # reported separately, never netted against pool residency
            self.metrics.gauge_max(
                "peak_swapped_tokens",
                self.pool.swapped_blocks_total * self.block_size)
        if self.tracer.enabled:
            # counter-track sample for the Perfetto export — host ints
            # off the allocator's dict sizes, zero device traffic
            occ = self.pool.occupancy() if self.paged else None
            self.tracer.emit(TM.COUNTERS,
                             cycle=self.clock if cycle is None else cycle,
                             args=(
                resident,
                occ["allocated"] if occ else 0,
                occ["parked"] if occ else 0,
                occ["swapped_blocks"] if occ else 0,
                len(self.queue)))

    # -- prefill -----------------------------------------------------------

    def _dispatch_wide(self, prefilling: list[Request]) -> PendingCycle:
        """Dispatch one wide (``chunk_size``) admission cycle: a chunk
        of every prefilling row, batched in one bucket. Returns the
        un-harvested cycle record (handles only — no sync here)."""
        c = self.chunk_size
        tokens = np.zeros((self.num_slots, c), np.int32)
        valid = np.zeros(self.num_slots, np.int32)
        for r in prefilling:
            v = min(c, len(r.tokens) - r.pos)
            tokens[r.slot, :v] = r.tokens[r.pos:r.pos + v]
            valid[r.slot] = v
            if self.paged:
                self._grow_blocks(r, r.pos + v)
        self._push_host_state()
        t0 = time.perf_counter()
        last, self.cache, stats = self._chunk(self.params, self.cache,
                                              jnp.asarray(tokens),
                                              jnp.asarray(valid))
        return PendingCycle(kind="chunk", plan=None,
                            prefilling=list(prefilling), valid=valid,
                            res=None, last=last, clock=self.clock,
                            t0=t0, t_dispatch=time.perf_counter(),
                            stats=stats)

    def _harvest_wide(self, p: PendingCycle) -> None:
        """Fold one wide admission cycle's materialized logits into host
        state (row advance, prefix indexing, prefill completion)."""
        if self.expert_layers:
            last, stats = jax.device_get((p.last, p.stats))
            self._count_experts(stats)
        else:
            last = jax.device_get(p.last)
        for r in p.prefilling:
            v = int(p.valid[r.slot])
            r.pos += v
            self.lengths[r.slot] += v
            self.metrics.inc("prefill_tokens", v)
            self.tracer.emit(TM.PREFILL_CHUNK, rid=r.rid, slot=r.slot,
                             cycle=p.clock, args=(v, r.pos))
            self._index_prefix(r)
            if r.pos >= len(r.tokens):
                self._finish_prefill(r, last[r.slot], cycle=p.clock)
        self.metrics.inc("prefill_cycles")

    def _prefill_cycle(self, prefilling: list[Request]) -> None:
        """One chunk of every prefilling row — the synchronous shape:
        dispatch, block, harvest in place (alternating mode and the
        ``overlap=False`` fused wide path)."""
        with TM.span("sched.dispatch"):
            p = self._dispatch_wide(prefilling)
        with TM.span("sched.wait"):
            # speclint: disable=sync-block(the one sanctioned per-cycle sync)
            jax.block_until_ready(p.last)
        self._stamp_wall("chunk", p.t0)
        with TM.span("sched.harvest"):
            self._harvest_wide(p)

    def _finish_prefill(self, req: Request, last_logits: np.ndarray,
                        cycle: float | None = None) -> None:
        """Prompt exhausted: its last-position logits yield the first
        generated token; the row becomes a decode row next cycle."""
        first = int(np.argmax(last_logits))
        req.prefill_done = True
        req.output = [first]
        self._record_tokens(req, 1, cycle=cycle)
        self.cur[req.slot, 0] = first
        self._maybe_retire(req, cycle=cycle)

    # -- planner (fused mode) ----------------------------------------------

    def _plan_cycle(self) -> CyclePlan | None:
        """Build the cycle's work descriptor: every resident row gets a
        role (PREFILL chunk / DRAFT+VERIFY / IDLE). Prefill rows consume
        up to γ+1 prompt tokens each, capped across rows by
        ``max_prefill_tokens_per_step`` (rows past the budget idle one
        cycle — admission can never monopolise a cycle's compute).
        Returns None when no resident row has work."""
        width = self.ecfg.gamma + 1
        chunk = np.zeros((self.num_slots, width), np.int32)
        valid = np.zeros(self.num_slots, np.int32)
        dmask = np.zeros(self.num_slots, bool)
        prefilling: list[Request] = []
        decoding: list[Request] = []
        budget = self.max_prefill_tokens_per_step
        budget = budget if budget is not None else self.num_slots * width
        for slot, r in enumerate(self.slots):
            if r is None:
                continue
            if r.prefill_done:
                dmask[slot] = True
                decoding.append(r)
            elif budget > 0:
                v = min(width, len(r.tokens) - r.pos, budget)
                chunk[slot, :v] = r.tokens[r.pos:r.pos + v]
                valid[slot] = v
                budget -= v
                prefilling.append(r)
        if not prefilling and not decoding:
            return None
        return CyclePlan(chunk_tokens=chunk, prefill_valid=valid,
                         decode_mask=dmask, prefilling=prefilling,
                         decoding=decoding)

    def _plan_wide_cycle(self, plan: CyclePlan) -> bool:
        """Should this cycle run the wide admission bucket instead of the
        fused step?

        With an empty decode pool, always (γ+1-wide prefill would only
        throttle admission, and there is nobody to stall). With decode
        rows resident, compare token costs: riding fused cycles keeps
        each prefilling row's slot busy ``ceil(R/(γ+1))`` cycles instead
        of ``ceil(R/chunk)`` (extra row-cycles of lost occupancy), while
        one wide stall cycle delays every decode row by one cycle
        (``n_decode`` row-cycles). Stall only when riding is strictly
        dearer — short prompts ride (no admission stall, flat inter-token
        latency), long prompts against few decode rows take the stall the
        alternating scheduler would have paid anyway.

        Goodput mode (``_slo_active``): deadlines vote first. A
        prefilling row whose TTFT deadline the wide bucket meets but
        riding blows votes to stall; a decode row whose ITL target one
        stall cycle blows votes to ride. Majority wins; on a tie the
        token-cost comparison re-runs in MEASURED milliseconds (the
        online cost model's per-bucket means — at the cold-start nominal
        rate it reduces to exactly the legacy cycle-count comparison)."""
        if not plan.decoding:
            return True
        if not plan.prefilling:
            return False
        w, c = self.ecfg.gamma + 1, self.chunk_size
        ride_extra = sum(
            -(-(len(r.tokens) - r.pos) // w)
            - -(-(len(r.tokens) - r.pos) // c)
            for r in plan.prefilling)
        if not self._slo_active:
            return ride_extra > len(plan.decoding)
        stall_votes = ride_votes = 0
        for r in plan.prefilling:
            dl = self._next_event_deadline_cycles(r)
            if dl is None:
                continue
            rem = len(r.tokens) - r.pos
            wide_first = self.clock + -(-rem // c) + 1
            ride_first = self.clock + -(-rem // w) + 1
            if wide_first <= dl < ride_first:
                stall_votes += 1            # the wide bucket saves its TTFT
        for r in plan.decoding:
            dl = self._next_event_deadline_cycles(r)
            if dl is None:
                continue
            if self.clock + 1 <= dl < self.clock + 2:
                ride_votes += 1             # one stall cycle blows its ITL
        if stall_votes != ride_votes:
            return stall_votes > ride_votes
        ride_ms = ride_extra * self.cost.bucket_ms("unified")
        stall_ms = len(plan.decoding) * self.cost.bucket_ms("chunk")
        return ride_ms > stall_ms

    def _dispatch_unified(self, plan: CyclePlan,
                          stale: bool = False) -> PendingCycle:
        """Dispatch one planned mixed-role cycle via ``unified_step``
        and return its un-harvested record (no sync — result handles
        only).

        ``stale=True`` is the free-run dispatch: host state is one
        un-harvested cycle behind, so ``cur`` chains device-side off the
        pending cycle's ``next_token`` handle (same (B,1) int32 aval —
        same compile bucket), ``cache["length"]`` is left untouched
        (``engine.commit`` already advanced it in-step: the device is
        authoritative), and decode rows grow blocks conservatively — the
        stale length plus TWO decode horizons covers the in-flight
        commit (≤ γ+1) plus the next verify, capped at the row's
        worst-case reservation so allocation can never fail."""
        horizon = self.ecfg.gamma + 1
        if self.paged:
            for r in plan.prefilling:
                self._grow_blocks(r, r.pos + int(plan.prefill_valid[r.slot]))
            for r in plan.decoding:
                need = (min(int(self.lengths[r.slot]) + 2 * horizon,
                            self._worst_case_tokens(len(r.tokens),
                                                    r.max_new))
                        if stale else
                        int(self.lengths[r.slot]) + horizon)
                self._grow_blocks(r, need)
        if stale:
            # push only the table; length stays device-authoritative
            if self.paged:
                self.cache["block_table"] = jnp.asarray(self.table)
            cur = self._pending.res.next_token[:, None]
        else:
            self._push_host_state()
            cur = jnp.asarray(self.cur)
        self.key, sub = jax.random.split(self.key)
        chunk_dev, valid_dev = self._take_prefetch(plan)
        t0 = time.perf_counter()
        res, last, self.cache, stats = self._unified(
            self.params, self.cache, cur, chunk_dev, valid_dev,
            jnp.asarray(plan.decode_mask), sub)
        pending = PendingCycle(kind="unified", plan=plan, prefilling=[],
                               valid=None, res=res, last=last,
                               clock=self.clock, t0=t0,
                               t_dispatch=time.perf_counter(), stats=stats)
        self._prefetch_next_chunk(plan)
        return pending

    def _harvest_unified(self, p: PendingCycle, cycle: float) -> None:
        """Fold one fused cycle's materialized results into host state.
        ``cycle`` is the harvested cycle's dispatch-time clock (== the
        live clock on the synchronous path). A row that retired between
        the cycle's dispatch and its harvest (pipelined free-run: the
        retire decision arrived one cycle late) is a *zombie* — its
        extra cycle's results are discarded here, never delivered, and
        it contributes nothing to acceptance accounting. The pass's
        expert-layer statistics ride in the cycle's one batched fetch."""
        plan, res = p.plan, p.res
        stats = p.stats if self.expert_layers else None
        # harvest prefill rows
        if plan.prefilling:
            last, stats = jax.device_get((p.last, stats))
            for r in plan.prefilling:
                v = int(plan.prefill_valid[r.slot])
                r.pos += v
                self.lengths[r.slot] += v
                self.metrics.inc("prefill_tokens", v)
                self.tracer.emit(TM.PREFILL_CHUNK, rid=r.rid, slot=r.slot,
                                 cycle=cycle, args=(v, r.pos))
                self._index_prefix(r)
                if r.pos >= len(r.tokens):
                    self._finish_prefill(r, last[r.slot], cycle=cycle)
            self.metrics.inc("prefill_cycles")
            self.metrics.inc("mixed_cycles")
            self.metrics.gauge_max("peak_prefill_tokens_per_cycle",
                                   int(plan.prefill_valid.sum()))
        # harvest decode rows — ONE batched transfer for the cycle's
        # results, not four implicit per-array syncs
        live = [r for r in plan.decoding if r.state != FINISHED]
        if len(live) < len(plan.decoding):
            # zombie rows: retired at the previous harvest AFTER this
            # cycle was already dispatched (free-run) — their results
            # are discarded, the rollback the late-retire test pins
            self.metrics.inc("zombie_rows", len(plan.decoding) - len(live))
        if live:
            tokens, valid, n, nxt, stats = jax.device_get(
                (res.tokens, res.valid, res.n_accepted, res.next_token,
                 stats))
            for r in live:
                self._harvest_decode_row(r, tokens, valid, n, nxt,
                                         cycle=cycle)
            lmask = np.zeros(self.num_slots, bool)
            lmask[[r.slot for r in live]] = True
            self.metrics.inc("accepted", int(n[lmask].sum()))
            self.metrics.inc("drafted", self.ecfg.gamma * len(live))
        if self.expert_layers and (plan.prefilling or live):
            self._count_experts(stats)

    def _count_experts(self, stats: dict) -> None:
        """Fold one target pass's fetched expert-layer statistics into
        the counters: ``moe_expert_tokens`` (top-k assignments that land
        on a held expert, every token of the pass counted),
        ``moe_expert_slots`` (held experts × expert layers: one read of
        each held expert's weights) and ``moe_dropped`` (always 0: the
        layer is dropless)."""
        self.metrics.inc("moe_expert_tokens", int(stats["expert_tokens"]))
        self.metrics.inc("moe_expert_slots",
                         self.cfg.n_held * self.expert_layers)
        self.metrics.inc("moe_dropped", int(stats["dropped"]))

    def _fused_step(self) -> bool:
        """Execute one planned mixed-role cycle via ``unified_step`` —
        the synchronous shape: dispatch, block, harvest in place."""
        with TM.span("sched.plan"):
            plan = self._plan_cycle()
            wide = plan is not None and self._plan_wide_cycle(plan)
        if plan is None:
            return self._fast_forward()
        if wide:
            # wide ``chunk_size``-bucket cycle: either the decode pool is
            # empty (cold start — nothing to piggyback on or stall), or
            # the cost model says riding is dearer than one stall (long
            # prompts × few decode rows). Both buckets compile once at
            # warmup; zero recompiles after.
            self._prefill_cycle([r for r in self.slots
                                 if r is not None and not r.prefill_done])
            self._track_residency()
            self.metrics.inc("cycles")
            self.clock += 1.0
            return True
        with TM.span("sched.dispatch"):
            p = self._dispatch_unified(plan)
        # the cycle's one sanctioned sync: bound the step-wall stamp at
        # the step's completion, before the host-side harvest
        with TM.span("sched.wait"):
            # speclint: disable=sync-block(the one sanctioned per-cycle sync)
            jax.block_until_ready(p.res.tokens)
        self._stamp_wall("unified", p.t0)
        with TM.span("sched.harvest"):
            self._harvest_unified(p, self.clock)
            self._track_residency()
        self.metrics.inc("cycles")
        self.clock += 1.0
        return True

    # -- pipelined dispatch/harvest (async overlap) --------------------------

    def _free_run_ok(self) -> bool:
        """May this call dispatch BEFORE harvesting the pending cycle
        (the regime with real overlap: planning from one-cycle-stale
        host state, chaining ``cur`` device-side)? Only on pure-decode
        stretches where stale planning is provably schedule-neutral: no
        queued request (no admission or preemption decision could read
        stale state), every resident row past prefill, the pending cycle
        itself pure decode, no copy-on-write owed, and greedy sampling
        (a late retire costs one zombie cycle and therefore one extra
        key split; greedy outputs are key-independent, non-greedy ones
        are not, so non-greedy always drains). Rows within γ+1 tokens of
        their ``max_new`` cap drain too: the pending harvest may retire
        them, and dispatching first would waste the retired row's cycle
        — predictable (cap-driven) retires are anticipated, so zombies
        only arise from retires no stale planner could foresee (EOS or
        a per-request stop token landing mid-stretch). Everything else
        drains first — still pipelined across the call boundary, but
        every scheduling decision sees exactly the synchronous state."""
        p = self._pending
        horizon = self.ecfg.gamma + 1
        return (p is not None and p.kind == "unified"
                and not p.plan.prefilling
                and not self.queue
                and not self._pending_cow
                and self.ecfg.greedy
                and all(r is None or (r.prefill_done
                                      and len(r.output) + horizon
                                      < r.max_new)
                        for r in self.slots))

    def _harvest_pending(self) -> None:
        """Land the pending cycle: block on one result handle (the
        pipeline's one sanctioned sync, one cycle late — the device has
        been working on it since dispatch), split the wall stamps into
        dispatch / effective-step / overlapped-host components, fold the
        results into host state, and finalize in-flight spill/restore
        transfers. No-op when nothing is pending."""
        p, self._pending = self._pending, None
        # speclint: disable=sync-truthy(None-check on the PendingCycle record itself, no device value is read)
        if p is None:
            return
        out = p.res.tokens if p.kind == "unified" else p.last
        t_h = time.perf_counter()
        with TM.span("sched.wait"):
            # speclint: disable=sync-block(the one sanctioned per-cycle sync, deferred to harvest)
            jax.block_until_ready(out)
        now = time.perf_counter()
        dispatch_dt = p.t_dispatch - p.t0
        name = p.kind                   # wall bucket: "unified" | "chunk"
        # effective device cost = dispatch + the non-overlapped wait.
        # The overlapped host window is reported BESIDE the step bucket
        # (".overlap"), never added to it, so the CostModel's per-bucket
        # fits keep pricing real device cost, not pipeline bookkeeping.
        self._stamp_wall_at(name + ".dispatch", dispatch_dt, p.clock)
        self._stamp_wall_at(name, dispatch_dt + (now - t_h), p.clock)
        self._stamp_wall_at(name + ".overlap", t_h - p.t_dispatch, p.clock)
        with TM.span("sched.harvest"):
            # speclint: disable=sync-truthy(kind is a host string field of the pending record)
            if p.kind == "unified":
                self._harvest_unified(p, p.clock)
            else:
                self._harvest_wide(p)
            self._finalize_inflight()
            self._track_residency(cycle=p.clock)

    def _finalize_inflight(self) -> None:
        """Land deferred spill/restore transfers at the harvest point
        and stamp their effective walls (dispatch + residual wait — the
        copies have overlapped the fused step since dispatch, so the
        residual is ~zero; Perfetto shows their spans under the adjacent
        fused-step span)."""
        inflight, self._inflight = self._inflight, []
        for kind, handle, dispatch_dt, cycle in inflight:
            t0 = time.perf_counter()
            # speclint: disable=sync-truthy(kind is the host string tag of the inflight tuple)
            if kind == "spill":
                self.spill.finalize(handle)
            else:
                # speclint: disable=sync-block(restore completion marker — narrow, not a cache sync)
                jax.block_until_ready(handle)
            self._stamp_wall_at(
                kind, dispatch_dt + time.perf_counter() - t0, cycle)

    def _take_prefetch(self, plan: CyclePlan):
        """The fused step's chunk operands: the prefetched device
        buffers when the staged prediction matches this plan exactly
        (host-side numpy compare — never a correctness input), a fresh
        H2D transfer otherwise."""
        pf, self._prefetch = self._prefetch, None
        # speclint: disable=sync-asarray(pf[0] is the host numpy copy staged beside the device buffers), sync-truthy(the match decision reads host numpy, never the device staging)
        if (pf is not None and np.array_equal(pf[0], plan.chunk_tokens)
                # speclint: disable=sync-asarray(pf[1] is the host numpy copy staged beside the device buffers)
                and np.array_equal(pf[1], plan.prefill_valid)):
            return pf[2], pf[3]
        return (jnp.asarray(plan.chunk_tokens),
                jnp.asarray(plan.prefill_valid))

    def _prefetch_next_chunk(self, plan: CyclePlan) -> None:
        """Stage the next cycle's predicted prefill-chunk operands on
        device (async H2D) while the just-dispatched step runs. The
        prediction replays the planner's budget walk one chunk ahead;
        any plan change (admission, wide flip, retirement) simply fails
        the match at the next dispatch and the buffers drop."""
        if not self.overlap or not plan.prefilling:
            self._prefetch = None
            return
        width = self.ecfg.gamma + 1
        chunk = np.zeros((self.num_slots, width), np.int32)
        valid = np.zeros(self.num_slots, np.int32)
        budget = self.max_prefill_tokens_per_step
        budget = budget if budget is not None else self.num_slots * width
        staged = False
        for slot, r in enumerate(self.slots):
            if r is None or r.prefill_done or budget <= 0:
                continue
            pos = r.pos + (int(plan.prefill_valid[slot])
                           if r in plan.prefilling else 0)
            v = min(width, len(r.tokens) - pos, budget)
            if v <= 0:
                continue
            chunk[slot, :v] = r.tokens[pos:pos + v]
            valid[slot] = v
            budget -= v
            staged = True
        self._prefetch = (chunk, valid, jnp.asarray(chunk),
                          jnp.asarray(valid)) if staged else None

    def _fused_step_pipelined(self) -> bool:
        """One pipelined serving call. Drain regime: harvest the pending
        cycle, admit, plan, dispatch — decisions bitwise match the
        synchronous path, and the dispatch still overlaps the host work
        up to the NEXT call's harvest. Free-run regime (pure decode):
        plan from (one-cycle-stale) host state, dispatch first, then
        harvest the previous cycle while the device runs the new one —
        the real overlap window."""
        free_run = self._free_run_ok()
        if not free_run:
            self._harvest_pending()
            with TM.span("sched.admit"):
                self._admit_ready()
        with TM.span("sched.plan"):
            plan = self._plan_cycle()
            wide = plan is not None and self._plan_wide_cycle(plan)
        if plan is None:
            # nothing to dispatch: drain whatever is still pending (a
            # trailing zombie-only cycle after the last live row retired
            # one harvest ago) before idling or fast-forwarding
            self._harvest_pending()
            return self._fast_forward()
        with TM.span("sched.dispatch"):
            if wide:
                nxt = self._dispatch_wide(
                    [r for r in self.slots
                     if r is not None and not r.prefill_done])
            else:
                nxt = self._dispatch_unified(plan, stale=free_run)
        self.metrics.inc("cycles")
        self.clock += 1.0
        if free_run:
            self._harvest_pending()
        self._pending = nxt
        return True

    # -- invariants --------------------------------------------------------

    def check_invariants(self) -> None:
        """Cross-registry structural sanity: allocator refcounts and
        reservations, prefix trie <-> pool sync, and spill store <->
        swapped-key sync. Cheap (host-side dict scans) — ``step()``
        runs it every ``debug_invariants`` cycles when the knob is on
        (the test suite enables it via REPRO_DEBUG_INVARIANTS)."""
        if not self.paged:
            return
        self.pool.check_invariants()
        # Host block tables must only hold physical block ids — the device
        # side (gather_block_leaf, the paged-attention kernels) routes any
        # out-of-range entry through the trash block, so an OOB id here
        # means scheduler state corruption, not a recoverable condition.
        assert self.table.min() >= 0 and self.table.max() < self.num_blocks, \
            "host block table entry outside [0, num_blocks)"
        if self.prefix is not None:
            self.prefix.check_invariants()
        if self.spill is not None:
            assert set(self.pool.swapped_keys()) == \
                set(self.spill.keys()), \
                "spill store out of sync with allocator swapped keys"

    # -- decode ------------------------------------------------------------

    def step(self) -> bool:
        """Admit what's ready, then run one serving cycle — the
        pipelined fused step (default: dispatch this cycle, harvest the
        previous one), the synchronous fused step (``overlap=False``),
        or the alternating prefill-chunk / decode cycle (``fused=False``
        and the autoregressive baseline). Returns False when there was
        nothing to do (idle or all arrivals in the future)."""
        if self.debug_invariants > 0 and self.paged:
            self._steps_since_check += 1
            if self._steps_since_check >= self.debug_invariants:
                self._steps_since_check = 0
                self.check_invariants()
        if self.overlap:
            return self._fused_step_pipelined()
        with TM.span("sched.admit"):
            self._admit_ready()
        if self.fused:
            return self._fused_step()
        prefilling = [r for r in self.slots
                      if r is not None and not r.prefill_done]
        if prefilling:
            self._prefill_cycle(prefilling)
            self._track_residency()
            self.metrics.inc("cycles")
            self.clock += 1.0
            return True
        active = np.array([r is not None for r in self.slots])
        if not active.any():
            return self._fast_forward()
        horizon = (self.ecfg.gamma + 1) if self.speculative else 1
        if self.paged:
            for slot in np.flatnonzero(active):
                self._grow_blocks(self.slots[slot],
                                  int(self.lengths[slot]) + horizon)
        self._push_host_state()
        self.key, sub = jax.random.split(self.key)
        cur = jnp.asarray(self.cur)
        act = jnp.asarray(active)
        t0 = time.perf_counter()
        if self.speculative:
            res, self.cache = self._spec(self.params, self.cache, cur,
                                         sub, act)
            tokens, valid, n, nxt = jax.device_get(
                (res.tokens, res.valid, res.n_accepted, res.next_token))
            self.metrics.inc("accepted", int(n[active].sum()))
            self.metrics.inc("drafted", self.ecfg.gamma * int(active.sum()))
            self._stamp_wall("spec", t0)
        else:
            nxt_dev, self.cache = self._auto(self.params, self.cache, cur,
                                             sub, act)
            nxt = jax.device_get(nxt_dev)
            tokens = nxt[:, None]
            valid = np.ones_like(tokens, bool)
            n = np.zeros(self.num_slots, np.int64)
            self._stamp_wall("auto", t0)
        for slot in np.flatnonzero(active):
            self._harvest_decode_row(self.slots[slot], tokens, valid, n,
                                     nxt)
        self._track_residency()
        self.metrics.inc("cycles")
        self.clock += 1.0
        return True

    def run(self, max_cycles: int = 100_000) -> list[Request]:
        """Drive until every submitted request finishes."""
        for _ in range(max_cycles):
            if not self.step():
                break
        if not self.idle:
            raise RuntimeError(f"scheduler not idle after {max_cycles} "
                               "cycles")
        return self.finished

    def latency_summary(self) -> dict:
        """TTFT and inter-token latency percentiles over finished requests.

        Cycle units are deterministic (the unit the λ arrival clock runs
        in) and are what the benchmark gate compares; the wall-clock ITL
        percentiles (ms) sit beside them for operator-facing numbers. A
        speculative burst delivers its run in one cycle/commit, so
        in-burst gaps are 0; stall cycles (alternating-mode admissions)
        surface as gaps ≥ 2 cycles. TTFT has no wall counterpart —
        arrivals are virtual cycle timestamps, not wall times.

        Every key is always present; a percentile whose sample list is
        empty (nothing finished, or single-token outputs with no gaps)
        reports ``None`` rather than raising — callers that format the
        numbers should treat ``None`` as "no data"."""
        ttft = [r.ttft_cycles for r in self.finished
                if r.ttft_cycles is not None]
        gaps = np.concatenate(
            [r.itl_cycles for r in self.finished] or [np.zeros(0)])
        wall_gaps = np.concatenate(
            [np.diff(np.asarray(r.token_walls, np.float64))
             for r in self.finished] or [np.zeros(0)])
        out: dict = {k: None for k in (
            "ttft_cycles_mean", "ttft_cycles_p50", "ttft_cycles_p95",
            "itl_cycles_mean", "itl_cycles_p50", "itl_cycles_p95",
            "itl_ms_p50", "itl_ms_p95")}
        if ttft:
            out["ttft_cycles_mean"] = float(np.mean(ttft))
            out["ttft_cycles_p50"] = float(np.percentile(ttft, 50))
            out["ttft_cycles_p95"] = float(np.percentile(ttft, 95))
        if gaps.size:
            out["itl_cycles_mean"] = float(np.mean(gaps))
            out["itl_cycles_p50"] = float(np.percentile(gaps, 50))
            out["itl_cycles_p95"] = float(np.percentile(gaps, 95))
        if wall_gaps.size:
            out["itl_ms_p50"] = float(np.percentile(wall_gaps, 50) * 1e3)
            out["itl_ms_p95"] = float(np.percentile(wall_gaps, 95) * 1e3)
        return out

    def _request_slo_hit(self, req: Request) -> bool:
        """Did a finished request meet every SLO it declared? Judged in
        cycle space through the cost model's exchange rate — the same
        units the planner's decisions were made in."""
        dl = self._ttft_deadline_cycles(req)
        if dl is not None:
            if req.ttft_cycles is None:
                return False
            if req.arrival + req.ttft_cycles > dl:
                return False
        if req.itl_target_ms is not None and len(req.token_cycles) > 1:
            tgt = self.cost.ms_to_cycles(req.itl_target_ms)
            if float(req.itl_cycles.max()) > tgt:
                return False
        return True

    def goodput_summary(self) -> dict:
        """Deadline-hit goodput over finished SLO-carrying requests."""
        slo = [r for r in self.finished if r.has_slo]
        hits = sum(self._request_slo_hit(r) for r in slo)
        return {"slo_finished": len(slo), "slo_hits": hits,
                "slo_hit_rate": hits / len(slo) if slo else None}

    def summary(self) -> dict:
        """One-stop run report, sourced from the metrics registry: the
        full counter/gauge set (legacy spellings), derived ratios,
        per-bucket wall means next to the cost model (same keys by
        construction — both views come off ``observe_wall``), latency
        and goodput percentiles, compile ``trace_counts`` and the
        tracer's own health (events kept/dropped)."""
        m = self.metrics
        if self.paged:
            m.gauge("pool_blocks", self.pool.capacity)
            m.gauge("pool_high_water_blocks", self.pool.high_water)
            m.gauge("block_size", self.block_size)
        if self.prefix is not None:
            for k, v in self.prefix.snapshot().items():
                m.gauge(k, v)
        if self.swap:
            m.gauge("swapped_now", self.pool.swapped_total)
            for k, v in self.spill.snapshot().items():
                m.gauge(k, v)
        s = m.snapshot()
        if self.finished:
            lat = [r.finished_at - r.arrival for r in self.finished]
            s["mean_latency_cycles"] = float(np.mean(lat))
        s.update(self.latency_summary())
        s.update(self.goodput_summary())
        s["trace_counts"] = dict(self.trace_counts)
        s["telemetry"] = {"trace_enabled": self.tracer.enabled,
                          "trace_events": len(self.tracer.ring),
                          "trace_dropped": self.tracer.dropped}
        return s
