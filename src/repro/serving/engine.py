"""Speculative serving engine — the paper's draft→verify cycle (Fig. 4b/c).

One ``spec_decode_step`` per cycle, fully under jit:

1. γ draft steps with ``view="draft"``: only speculation data is read
   (packed weights' draft reconstruction + draft view of the packed KV
   cache). Draft tokens' K/V live in a γ-slot scratch, SSM draft state in a
   scratch copy.
2. One batched verify pass with ``view="target"`` over the γ+1 tokens:
   speculation + verification data reconstruct the exact model (bit-exact
   for Cassandra-1), and the pass recomputes exact K/V / SSM states for the
   drafted positions.
3. Acceptance (greedy exact-match or paper Eq. 1 rejection sampling) —
   per-sequence accepted counts ``n``.
4. Commit: the *target's* K/V for the accepted prefix are encoded online
   (paper's encoder, Fig. 8b) and appended at per-row offsets; SSM states
   roll back to position n via the returned state history. Rejected-slot
   data stays as masked stale garbage until overwritten.

The same machinery with γ=0 is the autoregressive baseline.

``unified_step`` fuses this cycle with chunked prefill admission: one
mixed-role batch where each row is PREFILL (committing prompt chunk
tokens), DRAFT+VERIFY (the cycle above) or IDLE, driven by per-row
role/plan vectors. The serving scheduler plans one such step per cycle,
so admission piggybacks on decode instead of stalling it
(``scheduler.Scheduler`` in fused mode); ``spec_decode_step`` /
``chunk_prefill_step`` remain the single-role reference paths the
regression tests compare against.

Both speculative steps decode a packed cache once per cycle, before the
drafts (``model.materialize_cache_view``): for Cassandra-1 one per-layer
target decode of the pool feeds the verify pass, and the draft view is
derived from it bit-exactly. They name their phases with
``jax.named_scope`` — ``cass.view`` (the cycle's KV views),
``cass.draft`` (the γ draft passes), ``cass.verify`` (the target pass
and acceptance) and ``cass.commit`` (the KV encode and append) — and
``kvcache`` adds ``cass.kv_decode``, ``cass.kv_derive`` and
``cass.kv_gather`` wherever the packed KV is decoded, derived or paged
in, and ``ffn.moe`` ``cass.moe`` around each expert layer. The scopes change HLO metadata only: a device trace can put each op
down to a phase (``telemetry.hlo_op_scopes``), and the computation is
the same.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_groups
from repro.core import speculative as SP
from repro.core.format import CassandraConfig
from repro.models import model as M
from repro.models.layers import Runtime
from repro.serving import kvcache as KC
from repro.serving.blockpool import blocks_needed


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    gamma: int = 5
    greedy: bool = True
    temperature: float = 1.0
    # shape-stable draft: run every draft step at the verify pass's q=γ+1
    # width over the growing token prefix (no scratch). Identical operand
    # shapes mean identical XLA reduction orders, so with an identity
    # format the q=1-style draft/verify near-tie argmax flips disappear
    # and identity-draft acceptance is exactly 1.0. Costs (γ+1)× draft
    # FLOPs; the memory-bound weight reads (the edge bottleneck) are
    # unchanged.
    stable_draft: bool = False
    # greedy near-tie acceptance margin (see speculative.greedy_accept);
    # 0.0 is the strict lossless rule.
    tie_margin: float = 0.0


def validate_serving_knobs(cfg: ModelConfig, *, gamma: int, num_slots: int,
                           s_max: int, chunk_size: int, fused: bool,
                           speculative: bool, paged: bool, block_size: int,
                           num_blocks: int | None, prefix_cache: bool,
                           prefix_cache_blocks: int | None,
                           max_prefill_tokens_per_step: int | None,
                           swap: bool = False,
                           swap_store_blocks: int | None = None,
                           ttft_deadline_ms: float | None = None,
                           itl_target_ms: float | None = None,
                           attn_kernel: str = "off") -> None:
    """Fail fast on inconsistent serving knobs.

    Every check here used to surface as a jit-time shape error, a silent
    perf inversion, or a mid-flight allocator assert; the scheduler (and
    ``launch.serve``) call this once at startup so misconfiguration reads
    as a one-line ``ValueError`` instead. The SLO kwargs cover callers
    that apply one default SLO to every request (``launch.serve``) —
    per-request values go through ``validate_request_slos`` at
    ``submit()`` time."""
    validate_request_slos(ttft_deadline_ms=ttft_deadline_ms,
                          itl_target_ms=itl_target_ms)
    if num_slots < 1:
        raise ValueError(f"num_slots must be >= 1 (got {num_slots})")
    if s_max < gamma + 2:
        raise ValueError(
            f"s_max={s_max} cannot hold even a 1-token prompt plus the "
            f"γ+1={gamma + 1} speculative horizon")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1 (got {chunk_size})")
    if fused and speculative and chunk_size < gamma + 1:
        raise ValueError(
            f"chunk_size={chunk_size} < γ+1={gamma + 1}: the wide "
            "admission bucket would prefill *slower* than riding fused "
            "cycles, inverting the planner's cost model — raise "
            "chunk_size or lower gamma")
    if (max_prefill_tokens_per_step is not None
            and max_prefill_tokens_per_step < 1):
        raise ValueError(
            "max_prefill_tokens_per_step must be >= 1 (or None): a "
            "zero budget would strand prefilling rows forever")
    if paged:
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1 (got {block_size})")
        if num_blocks is not None and num_blocks < 2:
            raise ValueError(
                f"num_blocks={num_blocks}: the pool needs at least one "
                "allocatable block besides the reserved trash block")
    if attn_kernel not in ("off", "jnp", "interpret", "pallas"):
        raise ValueError(
            f"attn_kernel={attn_kernel!r}: expected one of "
            "off|jnp|interpret|pallas")
    if attn_kernel != "off" and not paged:
        raise ValueError(
            "attn_kernel walks the (B,MB) block table in-kernel — it "
            "requires the paged layout (paged=True)")
    if prefix_cache_blocks is not None and not prefix_cache:
        raise ValueError("prefix_cache_blocks is set but the prefix "
                         "cache is off")
    if prefix_cache:
        if not paged:
            raise ValueError(
                "the prefix cache shares physical pool blocks through "
                "block tables — it requires the paged layout (paged=True)")
        if any(e[0] != "a" for g in layer_groups(cfg) for e in g.entries):
            raise ValueError(
                f"{cfg.name}: prefix caching requires pure-attention "
                "archs — SSM recurrent state is per-request and cannot "
                "be reconstructed from shared KV blocks")
        if chunk_size % block_size != 0:
            raise ValueError(
                f"chunk_size={chunk_size} must be a multiple of "
                f"block_size={block_size} when the prefix cache is on: "
                "cache hits seed prefill at block boundaries, and "
                "aligned chunks keep warm-start pass boundaries a subset "
                "of the cold run's (the bitwise-identity condition)")
        if num_blocks is not None and prefix_cache_blocks is not None \
                and prefix_cache_blocks > num_blocks - 1:
            raise ValueError(
                f"prefix_cache_blocks={prefix_cache_blocks} exceeds the "
                f"pool's {num_blocks - 1} allocatable blocks")
    if swap_store_blocks is not None and not swap:
        raise ValueError("swap_store_blocks is set but preemption/swap "
                         "is off")
    if swap:
        if not paged:
            raise ValueError(
                "preemption/swap spills and restores pool blocks through "
                "block tables — it requires the paged layout (paged=True)")
        if any(e[0] != "a" for g in layer_groups(cfg) for e in g.entries):
            raise ValueError(
                f"{cfg.name}: preemption requires pure-attention archs — "
                "SSM recurrent state lives per-slot (no pool axis), and a "
                "recycled slot would clobber the victim's state")
        if swap_store_blocks is not None and block_size >= 1:
            row_blocks = blocks_needed(s_max, block_size)
            if swap_store_blocks < row_blocks:
                raise ValueError(
                    f"swap_store_blocks={swap_store_blocks} cannot hold "
                    f"even one full row chain ({row_blocks} blocks at "
                    f"s_max={s_max}, block_size={block_size}) — no victim "
                    "would ever be eligible")


def validate_request_slos(*, ttft_deadline_ms: float | None = None,
                          itl_target_ms: float | None = None) -> None:
    """Fail fast on malformed per-request SLOs (``Scheduler.submit``).

    Each SLO is either None (unconstrained) or a strictly positive,
    finite number of milliseconds — a zero or negative deadline is
    unmeetable by construction and would silently class the request as
    hopeless at admission, so it reads as a ValueError instead."""
    for name, val in (("ttft_deadline_ms", ttft_deadline_ms),
                      ("itl_target_ms", itl_target_ms)):
        if val is None:
            continue
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            raise ValueError(f"{name} must be a number in ms or None "
                             f"(got {val!r})")
        if not math.isfinite(val) or val <= 0:
            raise ValueError(f"{name} must be finite and > 0 ms "
                             f"(got {val})")


# ---------------------------------------------------------------------------
# Scratch (draft-side transient state)
# ---------------------------------------------------------------------------

def make_scratch(cfg: ModelConfig, cache: dict, gamma: int) -> list:
    """γ-slot KV scratch per attn entry + SSM draft-state copies."""
    groups = []
    for gi, g in enumerate(layer_groups(cfg)):
        gdict = {}
        for j, entry in enumerate(g.entries):
            ekey = f"e{j}"
            centry = cache["dec"][gi][ekey]
            if entry[0] == "a":
                leaf = jax.tree_util.tree_leaves(centry)[0]
                # paged pools are (R,NB,BS,…): batch comes from `length`
                r, b = leaf.shape[0], cache["length"].shape[0]
                if cfg.mla:
                    gdict[ekey] = {
                        "c": jnp.zeros((r, b, gamma, cfg.kv_lora_rank),
                                       jnp.bfloat16),
                        "kr": jnp.zeros((r, b, gamma, cfg.qk_rope_dim),
                                        jnp.bfloat16)}
                else:
                    gdict[ekey] = {
                        "k": jnp.zeros((r, b, gamma, cfg.n_kv_heads, cfg.hd),
                                       jnp.bfloat16),
                        "v": jnp.zeros((r, b, gamma, cfg.n_kv_heads, cfg.hd),
                                       jnp.bfloat16)}
            else:
                gdict[ekey] = {"conv": centry["conv"], "h": centry["h"]}
        groups.append(gdict)
    return groups


def _scratch_write(scratch: list, updates: list, slot: int) -> list:
    """Place draft-step updates into scratch slot ``slot`` (static)."""
    out = []
    for gdict, gupd in zip(scratch, updates):
        godict = dict(gdict)
        for ekey, upd in gupd.items():
            se = dict(godict[ekey])
            if "k" in upd:
                for nm in ("k", "v"):
                    se[nm] = jax.lax.dynamic_update_slice_in_dim(
                        se[nm], upd[nm].astype(se[nm].dtype), slot, axis=2)
            elif "c" in upd:
                for nm in ("c", "kr"):
                    se[nm] = jax.lax.dynamic_update_slice_in_dim(
                        se[nm], upd[nm].astype(se[nm].dtype), slot, axis=2)
            elif "conv" in upd:
                se["conv"] = upd["conv"].astype(se["conv"].dtype)
                se["h"] = upd["h"]
            godict[ekey] = se
        out.append(godict)
    return out


# ---------------------------------------------------------------------------
# Commit (target-side cache update with rollback)
# ---------------------------------------------------------------------------

def commit(rt: Runtime, cache: dict, updates: list, n: jax.Array) -> dict:
    """Append target-recomputed state for n+1 accepted tokens per row.

    Slot caches append at per-row offsets inside each row's (S_max,)
    region; paged caches scatter the same token runs into the block pool
    through the (traced) block table."""
    cfg, cass = rt.cfg, rt.cass
    book = KC.cache_codebook(cache)
    packed = book is not None
    length = cache["length"]                          # (B,)
    table = cache.get("block_table")
    new_dec = []
    for gi, gupd in enumerate(updates):
        gcache = dict(cache["dec"][gi])
        for ekey, upd in gupd.items():
            centry = dict(gcache[ekey])
            if "k" in upd or "c" in upd:
                items = (("k", cfg.hd), ("v", cfg.hd)) if "k" in upd else \
                    (("c", cfg.kv_lora_rank), ("kr", cfg.qk_rope_dim))
                for nm, d in items:
                    new = upd[nm]                     # (R,B,q,…)
                    if packed:
                        new = jax.vmap(
                            lambda x, d=d: KC.encode_store(cass, x, d, book)
                        )(new)
                    centry[nm] = jax.vmap(
                        lambda c, nw: KC.append_batched(c, nw, length,
                                                        table)
                    )(centry[nm], new)
            elif "h_all" in upd:
                # SSM rollback: state after accepting n+1 tokens
                h_all = upd["h_all"]                  # (R,B,q,di,ns)
                idx = n.reshape(1, -1, 1, 1, 1)
                centry["h"] = jnp.take_along_axis(
                    h_all, idx, axis=2)[:, :, 0]
                win = upd["conv_win"]                 # (R,B,dc-1+q,di)
                dc = cfg.ssm_conv
                widx = (n.reshape(1, -1, 1, 1) + 1
                        + jnp.arange(dc - 1).reshape(1, 1, -1, 1))
                centry["conv"] = jnp.take_along_axis(
                    win, jnp.broadcast_to(
                        widx, (win.shape[0], win.shape[1], dc - 1,
                               win.shape[3])), axis=2
                ).astype(centry["conv"].dtype)
            gcache[ekey] = centry
        new_dec.append(gcache)
    out = dict(cache)
    out["dec"] = new_dec
    out["length"] = length + n.astype(length.dtype) + 1
    return out


# ---------------------------------------------------------------------------
# Decode steps
# ---------------------------------------------------------------------------

def _run_drafts(rt: Runtime, params, cache: dict, cur_tokens: jax.Array,
                key: jax.Array, ecfg: EngineConfig, draft_view: list | None
                ) -> tuple[jax.Array, list, jax.Array]:
    """γ draft steps with ``view="draft"``. Reads the cache, never writes
    it (scratch/cache-view only), so rows whose draft inputs are garbage
    (prefill/idle rows riding through a fused cycle) are harmless.
    ``draft_view`` is the cycle's dense draft view of a packed cache
    (``model.materialize_cache_view``), None where the passes read the
    cache themselves. Returns (draft_tokens (B,γ), per-step draft logits,
    key)."""
    cfg = rt.cfg
    gamma = ecfg.gamma
    rt_d = dataclasses.replace(rt, view="draft" if rt.cass else "plain")

    def sample(lg, key):
        if ecfg.greedy:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return jax.random.categorical(
            sub, lg / ecfg.temperature).astype(jnp.int32), key

    draft_tokens = []
    draft_logits = []
    if ecfg.stable_draft:
        # every draft step re-feeds the growing prefix at the verify
        # width q=γ+1 (garbage tail is causally masked), so draft and
        # verify logits at shared positions see identical shapes and
        # reduction orders — no scratch, no q=1 pass.
        toks = jnp.concatenate(
            [cur_tokens, jnp.zeros((cur_tokens.shape[0], gamma),
                                   cur_tokens.dtype)], axis=1)
        for i in range(gamma):
            logits, _, _ = M.forward_decode(rt_d, params, toks, cache,
                                            cache_view=draft_view)
            lg = logits[:, i]
            nxt, key = sample(lg, key)
            draft_tokens.append(nxt)
            draft_logits.append(lg)
            toks = toks.at[:, i + 1].set(nxt)
    else:
        scratch = make_scratch(cfg, cache, gamma)
        tok = cur_tokens
        for i in range(gamma):
            logits, upd, _ = M.forward_decode(rt_d, params, tok, cache,
                                              scratch=scratch,
                                              scratch_len=jnp.int32(i),
                                              cache_view=draft_view)
            scratch = _scratch_write(scratch, upd, i)
            lg = logits[:, -1]
            nxt, key = sample(lg, key)
            draft_tokens.append(nxt)
            draft_logits.append(lg)
            tok = nxt[:, None]
    return jnp.stack(draft_tokens, axis=1), draft_logits, key    # (B,γ)


def _accept(draft_tokens: jax.Array, draft_logits: list,
            t_logits: jax.Array, key: jax.Array,
            ecfg: EngineConfig) -> SP.AcceptResult:
    if ecfg.greedy:
        return SP.greedy_accept(draft_tokens, t_logits[:, :ecfg.gamma + 1],
                                tie_margin=ecfg.tie_margin)
    dprobs = jax.nn.softmax(
        jnp.stack(draft_logits, axis=1) / ecfg.temperature, axis=-1)
    tprobs = jax.nn.softmax(
        t_logits[:, :ecfg.gamma + 1] / ecfg.temperature, axis=-1)
    _, sub = jax.random.split(key)
    return SP.rejection_sample(draft_tokens, dprobs, tprobs, sub)


def spec_decode_step(rt: Runtime, params, cache: dict, cur_tokens: jax.Array,
                     key: jax.Array, ecfg: EngineConfig
                     ) -> tuple[SP.AcceptResult, dict]:
    """One speculative cycle. cur_tokens (B,1) = last committed token.

    This is the pure decode-only step (the fixed-batch ``Engine`` path and
    the alternating scheduler's reference). ``unified_step`` runs the same
    per-row math for decode rows of a mixed-role batch — the regression
    tests in tests/test_scheduler.py hold them bit-identical."""
    rt_t = dataclasses.replace(rt, view="target" if rt.cass else "plain")
    with jax.named_scope("cass.view"):
        draft_view, target_view = M.materialize_cache_view(rt, cache)
    with jax.named_scope("cass.draft"):
        draft_tokens, draft_logits, key = _run_drafts(
            rt, params, cache, cur_tokens, key, ecfg, draft_view)
    # batched verification over [cur ++ drafts]
    ver_tokens = jnp.concatenate([cur_tokens, draft_tokens], axis=1)
    with jax.named_scope("cass.verify"):
        t_logits, t_upd, _ = M.forward_decode(rt_t, params, ver_tokens,
                                              cache, cache_view=target_view)
        res = _accept(draft_tokens, draft_logits, t_logits, key, ecfg)
    with jax.named_scope("cass.commit"):
        cache = commit(rt, cache, t_upd, res.n_accepted)
    return res, cache


def unified_step(rt: Runtime, params, cache: dict, cur_tokens: jax.Array,
                 chunk_tokens: jax.Array, prefill_valid: jax.Array,
                 decode_mask: jax.Array, key: jax.Array, ecfg: EngineConfig
                 ) -> tuple[SP.AcceptResult, jax.Array, dict, dict]:
    """One fused serving cycle over a mixed-role batch.

    Per-row roles, all traced operands (any role mix hits ONE compile):

    * **PREFILL** (``prefill_valid[b] > 0``) — commit the next
      ``prefill_valid[b]`` prompt tokens from ``chunk_tokens[b]``; the
      returned ``last_logits[b]`` row holds the logits at the chunk's
      last real token (the first generated token once the prompt is
      exhausted).
    * **DRAFT+VERIFY** (``decode_mask[b]``) — one speculative cycle on
      ``cur_tokens[b]``; results land in the returned ``AcceptResult``.
    * **IDLE** (neither) — commits one garbage token into its masked
      stale region / the trash block; the caller freezes its length and
      recurrent state (``scheduler._freeze_rows``).

    ``chunk_tokens`` is (B, γ+1): the fused pass width IS the verify
    width, so decode rows see exactly the shapes (and therefore XLA
    reduction orders) of ``spec_decode_step`` — mixed-role admission is
    lossless for them — and prefill chunks ride the decode compile bucket
    instead of stalling it. The γ draft passes run for every row; prefill
    and idle rows' draft outputs are garbage that never touches the cache
    (drafts write scratch only). One target pass then serves as verify
    for decode rows and as the chunk-prefill forward for prefill rows.

    Every op here is per-row, the expert layer included (it is
    dropless), so a row's outputs are bit-identical whatever rides
    beside it. The fourth output is the target pass's expert-layer
    statistics (``model.forward_decode``'s ``aux``: ``expert_tokens``,
    ``dropped``), device scalars the scheduler reads at harvest.

    **Deferred-harvest contract** (the scheduler's dispatch/harvest
    pipeline leans on it): of this step's jitted outputs only the cache
    is donated, so the ``res``/``last`` handles a dispatch returns stay
    valid across the NEXT cycle's dispatch — the scheduler may hold
    them a full cycle and ``device_get`` late. All outputs of one
    executable materialize together, so blocking on any single handle
    (``res.tokens``) at harvest proves the whole cycle — KV commits and
    the ``length`` advance included — has landed. ``commit`` advancing
    ``length`` by ``n+1`` in-step is what makes the device cache
    self-sufficient: a free-running dispatch can chain ``cur`` off the
    previous ``res.next_token`` handle with NO host push of lengths,
    and the verify still reads exactly the committed prefix.
    """
    rt_t = dataclasses.replace(rt, view="target" if rt.cass else "plain")
    with jax.named_scope("cass.view"):
        draft_view, target_view = M.materialize_cache_view(rt, cache)
    with jax.named_scope("cass.draft"):
        draft_tokens, draft_logits, key = _run_drafts(
            rt, params, cache, cur_tokens, key, ecfg, draft_view)
    is_prefill = prefill_valid > 0
    ver_tokens = jnp.concatenate([cur_tokens, draft_tokens], axis=1)
    tokens = jnp.where(is_prefill[:, None], chunk_tokens, ver_tokens)
    with jax.named_scope("cass.verify"):
        t_logits, t_upd, aux = M.forward_decode(rt_t, params, tokens, cache,
                                                cache_view=target_view)
        res = _accept(draft_tokens, draft_logits, t_logits, key, ecfg)
    # role-masked commit width: prefill rows commit their chunk's real
    # tokens, decode rows their accepted run + bonus, idle rows one
    # masked garbage token
    n = jnp.where(is_prefill,
                  jnp.maximum(prefill_valid.astype(jnp.int32), 1) - 1,
                  jnp.where(decode_mask, res.n_accepted, 0))
    with jax.named_scope("cass.commit"):
        cache = commit(rt, cache, t_upd, n)
        last = jnp.take_along_axis(t_logits, n[:, None, None], axis=1)[:, 0]
    return res, last, cache, _pass_stats(aux)


def _pass_stats(aux: dict) -> dict:
    """What the scheduler counts of a target pass's expert layers."""
    return {"expert_tokens": aux["expert_tokens"], "dropped": aux["dropped"]}


def chunk_prefill_step(rt: Runtime, params, cache: dict,
                       tokens: jax.Array, valid: jax.Array
                       ) -> tuple[jax.Array, dict, dict]:
    """One batched prefill chunk: q=C prompt tokens per row, appended at
    each row's current ``length``.

    ``tokens`` (B,C) holds each prefilling row's next chunk (zero-padded);
    ``valid`` (B,) is the per-row count of real tokens (0 for rows riding
    along). All rows share ONE compile bucket regardless of prompt length
    or how many requests are prefilling — admission no longer compiles one
    prefill per prompt length. Commits ``valid[b]`` tokens per row and
    returns the logits at each row's last real token (the first generated
    token once its prompt is exhausted). Rows with valid=0 commit one
    garbage token into their masked stale region (paged: the trash block)
    — the caller freezes their length and recurrent state. The third
    output is the pass's expert-layer statistics, as ``unified_step``'s.
    """
    rt_t = dataclasses.replace(rt, view="target" if rt.cass else "plain")
    logits, upd, aux = M.forward_decode(rt_t, params, tokens, cache)
    n = jnp.maximum(valid.astype(jnp.int32), 1) - 1
    cache = commit(rt, cache, upd, n)
    last = jnp.take_along_axis(logits, n[:, None, None], axis=1)[:, 0]
    return last, cache, _pass_stats(aux)


def autoregressive_step(rt: Runtime, params, cache: dict,
                        cur_tokens: jax.Array, key: jax.Array,
                        greedy: bool = True, temperature: float = 1.0
                        ) -> tuple[jax.Array, dict]:
    """bf16-baseline decode: one token per full-model read."""
    rt_t = dataclasses.replace(rt, view="target" if rt.cass else "plain")
    logits, upd, _ = M.forward_decode(rt_t, params, cur_tokens, cache)
    lg = logits[:, -1]
    if greedy:
        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    else:
        nxt = jax.random.categorical(key, lg / temperature).astype(jnp.int32)
    cache = commit(rt, cache, upd, jnp.zeros(lg.shape[0], jnp.int32))
    return nxt, cache


# ---------------------------------------------------------------------------
# Device-side output harvest
# ---------------------------------------------------------------------------

def scatter_tokens(buf: jax.Array, count: jax.Array, tokens: jax.Array,
                   valid: jax.Array, adv: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Scatter a cycle's accepted tokens into a (B, cap) output buffer.

    Each row writes its ``valid`` tokens at its own offset ``count[b]``;
    invalid slots are routed past ``cap`` where the scatter drops them, so
    no host-side -1 bookkeeping is needed. ``adv`` is the per-row count
    advance (n_accepted+1 for speculative cycles, 1 for autoregressive).
    """
    b, q = tokens.shape
    cap = buf.shape[1]
    pos = count[:, None] + jnp.arange(q)[None, :]
    pos = jnp.where(valid, pos, cap)
    buf = buf.at[jnp.arange(b)[:, None], pos].set(
        tokens.astype(buf.dtype), mode="drop")
    return buf, jnp.minimum(count + adv.astype(count.dtype), cap)


# ---------------------------------------------------------------------------
# Host-side generation loop (examples / tests / benches)
# ---------------------------------------------------------------------------

class Engine:
    """Convenience wrapper: prefill once, then speculative cycles."""

    def __init__(self, cfg: ModelConfig, params,
                 cass: CassandraConfig | None = None,
                 ecfg: EngineConfig = EngineConfig(), rt_extra: dict = {}):
        self.cfg, self.cass, self.ecfg = cfg, cass, ecfg
        self.params = params
        self.rt = Runtime(cfg=cfg, cass=cass,
                          view="target" if cass else "plain", **rt_extra)
        self._prefill = jax.jit(
            lambda p, b, c: M.forward_prefill(self.rt, p, b, c))
        self._spec = jax.jit(partial(spec_decode_step, self.rt,
                                     ecfg=self.ecfg), donate_argnums=(1,))
        self._auto = jax.jit(partial(autoregressive_step, self.rt),
                             donate_argnums=(1,))
        self._scatter = jax.jit(scatter_tokens, donate_argnums=(0,))

    def generate(self, batch: dict, max_new: int, key=None,
                 speculative: bool = True, telemetry=None):
        """Returns (tokens (B, max_new+γ+1) int32, -1 beyond each row's
        output, every row holding ≥ max_new committed tokens), stats.

        ``telemetry`` is an optional ``serving.telemetry.Telemetry``
        bundle: per-cycle CYCLE events (γ proposed, k accepted) and the
        cycle/accepted/drafted counters land there, fed only from the
        host-side values this loop already harvests — no extra syncs."""
        import numpy as np
        from repro.serving import telemetry as TM
        key = key if key is not None else jax.random.PRNGKey(0)
        b, s = batch["tokens"].shape
        pad = self.ecfg.gamma + 1
        s_total = batch["tokens"].shape[1] + (
            self.cfg.frontend_tokens if self.cfg.frontend == "vision" else 0)
        s_max = s_total + max_new + pad
        cache = KC.init_cache(self.cfg, self.cass, b, s_max,
                              packed=self.cass is not None)
        logits, cache = self._prefill(self.params, batch, cache)
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        # device-side output buffer; rows past max_new spill into the γ+1
        # slack and anything beyond is dropped by the scatter
        buf = jnp.full((b, max_new + pad), -1, jnp.int32)
        count = jnp.zeros((b,), jnp.int32)
        ones_b = jnp.ones((b,), jnp.int32)
        buf, count = self._scatter(buf, count, cur,
                                   jnp.ones((b, 1), bool), ones_b)
        committed = np.ones(b, np.int64)    # the prefill-argmax token
        cycles = accepted = drafted = 0
        while committed.min() < max_new:
            key, sub = jax.random.split(key)
            active = committed < max_new    # rows still owing tokens
            if speculative:
                res, cache = self._spec(self.params, cache, cur, sub)
                buf, count = self._scatter(buf, count, res.tokens,
                                           res.valid, res.n_accepted + 1)
                n = jax.device_get(res.n_accepted)
                committed += n + 1
                accepted += int(n[active].sum())
                drafted += self.ecfg.gamma * int(active.sum())
                cycles += 1
                cur = res.next_token[:, None]
                if telemetry is not None:
                    for row in np.flatnonzero(active):
                        telemetry.metrics.observe("acceptance_len",
                                                  int(n[row]))
                        telemetry.tracer.emit(
                            TM.CYCLE, rid=int(row), slot=int(row),
                            cycle=float(cycles),
                            args=(self.ecfg.gamma, int(n[row]),
                                  int(n[row]) + 1))
            else:
                nxt, cache = self._auto(self.params, cache, cur, sub)
                buf, count = self._scatter(buf, count, nxt[:, None],
                                           jnp.ones((b, 1), bool), ones_b)
                committed += 1
                cycles += 1
                cur = nxt[:, None]
                if telemetry is not None:
                    for row in np.flatnonzero(active):
                        telemetry.tracer.emit(
                            TM.CYCLE, rid=int(row), slot=int(row),
                            cycle=float(cycles), args=(0, 0, 1))
        # delivered tokens (device count, capped at the buffer) — fast rows
        # overshoot max_new while slow rows catch up, and those dropped
        # tokens must not inflate throughput; prefill-argmax token is not a
        # decode-cycle product either
        delivered = jax.device_get(count).astype(np.int64)
        stats = {"cycles": cycles,
                 "tokens_per_cycle": float(delivered.mean() - 1)
                 / max(cycles, 1),
                 "acceptance": accepted / drafted if drafted else None}
        if telemetry is not None:
            telemetry.metrics.inc("cycles", cycles)
            telemetry.metrics.inc("accepted", accepted)
            telemetry.metrics.inc("drafted", drafted)
            telemetry.metrics.inc("committed",
                                  int(delivered.sum()) - b)
        return buf, stats
