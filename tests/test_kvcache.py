"""Paged KV cache: block-allocator invariants (including prefix-sharing
refcounts / copy-on-write / eviction, and the preemption SWAPPED state),
bit-exact packed-store round-trips through a block table, device
spill→restore swap round-trips, and the radix prefix index.

Each property has a shared checker driven two ways: hypothesis explores
arbitrary traffic when it is installed (CI), and a deterministic seeded
sweep always runs so the invariants are exercised even without it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitops
from repro.core.format import CassandraConfig
from repro.serving import kvcache as KC
from repro.serving.blockpool import (BlockAllocator, TRASH_BLOCK,
                                     blocks_needed)
from repro.serving.prefixcache import PrefixCache
from repro.serving.swapstore import SpillStore

jax.config.update("jax_platform_name", "cpu")

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                      reason="hypothesis not installed")
SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# Allocator properties
# ---------------------------------------------------------------------------

def _random_ops(rng, n):
    kinds = ["admit", "grow", "retire"]
    return [(kinds[rng.integers(3)], int(rng.integers(8)))
            for _ in range(n)]


def _check_alloc_trace(num_blocks, ops):
    """Arbitrary admit/grow/retire traffic: no block is ever live twice,
    the free list conserves blocks, reservations bound allocations, and
    the trash block is never handed out."""
    pool = BlockAllocator(num_blocks)
    live: list[int] = []
    reserved: dict[int, int] = {}
    next_owner = 0
    for kind, v in ops:
        if kind == "admit":
            need = v % 4 + 1
            if pool.can_reserve(need):
                pool.reserve(next_owner, need)
                reserved[next_owner] = need
                live.append(next_owner)
                next_owner += 1
            else:
                with pytest.raises(ValueError):
                    pool.reserve(next_owner, need)
        elif kind == "grow" and live:
            owner = live[v % len(live)]
            if len(pool.blocks_of(owner)) < reserved[owner]:
                blk = pool.alloc(owner)
                assert blk != TRASH_BLOCK
        elif kind == "retire" and live:
            owner = live.pop(v % len(live))
            blocks = pool.release(owner)
            assert len(set(blocks)) == len(blocks)
            del reserved[owner]
        pool.check_invariants()
    # full drain returns the pool to pristine capacity
    for owner in list(live):
        pool.release(owner)
    pool.check_invariants()
    assert pool.allocated_total == 0 and pool.reserved_total == 0


@pytest.mark.parametrize("seed", range(8))
def test_allocator_trace_seeded(seed):
    rng = np.random.default_rng(seed)
    _check_alloc_trace(int(rng.integers(2, 25)), _random_ops(rng, 60))


if HAVE_HYPOTHESIS:
    OPS = st.lists(
        st.tuples(st.sampled_from(["admit", "grow", "retire"]),
                  st.integers(0, 7)),
        min_size=1, max_size=60)

    @needs_hypothesis
    @given(st.integers(2, 24), OPS)
    @settings(**SETTINGS)
    def test_allocator_trace_property(num_blocks, ops):
        _check_alloc_trace(num_blocks, ops)

    @needs_hypothesis
    @given(st.integers(1, 65), st.integers(1, 32))
    @settings(**SETTINGS)
    def test_blocks_needed_covers_tokens(n_tokens, block_size):
        n = blocks_needed(n_tokens, block_size)
        assert n * block_size >= n_tokens
        assert (n - 1) * block_size < n_tokens


def _random_share_ops(rng, n):
    kinds = ["admit", "grow", "share", "cow", "cache", "retire"]
    return [(kinds[rng.integers(len(kinds))], int(rng.integers(8)),
             int(rng.integers(8))) for _ in range(n)]


def _check_share_trace(num_blocks, ops):
    """Arbitrary admit/grow/share/CoW/cache/retire traffic over the
    refcounted allocator: no block is ever freed (or parked) while its
    refcount is > 0, CoW always diverges into a fresh block without
    touching the source's refcount, free-list conservation holds with the
    parked set included, and the reservation guarantee never breaks."""
    pool = BlockAllocator(num_blocks)
    # stand-in for the prefix cache's eviction policy: surrender the
    # oldest parked block when an allocation finds the free list empty
    pool.evictor = lambda: pool.drop_cached(next(iter(pool._parked)))
    live: list[int] = []
    reserved: dict[int, int] = {}
    next_owner = 0
    for kind, v, w in ops:
        if kind == "admit":
            need = v % 4 + 1
            if pool.can_reserve(need):
                pool.reserve(next_owner, need)
                reserved[next_owner] = need
                live.append(next_owner)
                next_owner += 1
            else:
                with pytest.raises(ValueError):
                    pool.reserve(next_owner, need)
        elif kind == "grow" and live:
            owner = live[v % len(live)]
            if len(pool.blocks_of(owner)) < reserved[owner]:
                blk = pool.alloc(owner)
                assert blk != TRASH_BLOCK
                assert pool.refcount(blk) == 1
        elif kind == "share" and live:
            owner = live[v % len(live)]
            cands = sorted(set(pool._refs) | set(pool._parked))
            if cands:
                blk = cands[w % len(cands)]
                before = pool.refcount(blk)
                overcommit = (pool.is_parked(blk)
                              and not pool.can_reserve(0, extra_pins=1))
                if overcommit:
                    with pytest.raises(ValueError):
                        pool.share(owner, blk)
                else:
                    pool.share(owner, blk)
                    assert pool.refcount(blk) == before + 1
                    assert not pool.is_parked(blk)
        elif kind == "cow" and live:
            owner = live[v % len(live)]
            cands = sorted(set(pool._refs) | set(pool._parked))
            if cands and len(pool.blocks_of(owner)) < reserved[owner]:
                src = cands[w % len(cands)]
                if pool.is_parked(src) and not pool._free:
                    continue    # a real caller pins src first (the
                                # alloc's eviction could pick it)
                before = pool.refcount(src)
                dst = pool.cow(owner, src)
                # CoW diverges into a fresh private block; the shared
                # source is untouched (its refcount does not change)
                assert dst != src and pool.refcount(dst) == 1
                assert pool.refcount(src) == before
        elif kind == "cache" and (pool._refs or pool._parked):
            cands = sorted(set(pool._refs) | set(pool._parked))
            pool.mark_cacheable(cands[v % len(cands)])
        elif kind == "retire" and live:
            owner = live.pop(v % len(live))
            held = (list(pool.blocks_of(owner))
                    + list(pool._shared[owner]))
            dropped = pool.release(owner)
            del reserved[owner]
            # only blocks whose refcount really hit zero were surrendered
            for blk in dropped:
                assert pool.refcount(blk) == 0
            for blk in held:
                if blk not in dropped:
                    assert pool.refcount(blk) >= 1
        pool.check_invariants()
    # full drain: every block refcount reaches zero; the pool conserves
    # capacity across the parked/free split
    for owner in list(live):
        pool.release(owner)
    pool.check_invariants()
    assert pool.allocated_total == 0 and pool.reserved_total == 0
    assert pool.parked_total + len(pool._free) == pool.capacity


@pytest.mark.parametrize("seed", range(8))
def test_share_trace_seeded(seed):
    rng = np.random.default_rng(seed + 100)
    _check_share_trace(int(rng.integers(2, 25)),
                       _random_share_ops(rng, 80))


if HAVE_HYPOTHESIS:
    SHARE_OPS = st.lists(
        st.tuples(st.sampled_from(["admit", "grow", "share", "cow",
                                   "cache", "retire"]),
                  st.integers(0, 7), st.integers(0, 7)),
        min_size=1, max_size=80)

    @needs_hypothesis
    @given(st.integers(2, 24), SHARE_OPS)
    @settings(**SETTINGS)
    def test_share_trace_property(num_blocks, ops):
        _check_share_trace(num_blocks, ops)


def test_share_refcount_lifecycle():
    """A shared block survives its charging owner's release and frees
    only when the last sharer retires; cacheable blocks park instead."""
    pool = BlockAllocator(6)
    pool.reserve("a", 2)
    pool.reserve("b", 1)
    blk = pool.alloc("a")
    pool.share("b", blk)
    assert pool.refcount(blk) == 2
    assert pool.release("a") == []           # b still holds blk
    assert pool.refcount(blk) == 1
    assert pool.uncharged_total == 1         # live but reservation-free
    pool.check_invariants()
    pool.mark_cacheable(blk)
    assert pool.release("b") == [blk]
    assert pool.is_parked(blk)               # cached, evictable — not free
    pool.check_invariants()
    pool.drop_cached(blk)
    assert not pool.is_parked(blk) and pool.refcount(blk) == 0
    pool.check_invariants()


def _random_swap_ops(rng, n):
    kinds = ["admit", "grow", "share", "cache", "retire", "swap_out",
             "swap_in"]
    return [(kinds[rng.integers(len(kinds))], int(rng.integers(8)),
             int(rng.integers(8))) for _ in range(n)]


def _check_swap_trace(num_blocks, ops):
    """Arbitrary admit/grow/share/cache/retire/swap traffic over the
    SWAPPED state: swap_out surrenders blocks + reservation exactly like
    release (shared survivors stay live, cacheable blocks park), a
    swapped key holds zero gate capacity while its logical chain stays
    recorded, swap_in is an ordinary gated reservation, and double
    swap_out / unknown swap_in always raise."""
    pool = BlockAllocator(num_blocks)
    pool.evictor = lambda: pool.drop_cached(next(iter(pool._parked)))
    live: list[int] = []
    reserved: dict[int, int] = {}
    swapped: dict[int, int] = {}        # key -> logical blocks
    next_owner = 0
    next_key = 0
    for kind, v, w in ops:
        if kind == "admit":
            need = v % 4 + 1
            if pool.can_reserve(need):
                pool.reserve(next_owner, need)
                reserved[next_owner] = need
                live.append(next_owner)
                next_owner += 1
        elif kind == "grow" and live:
            owner = live[v % len(live)]
            if len(pool.blocks_of(owner)) < reserved[owner]:
                pool.alloc(owner)
        elif kind == "share" and live:
            owner = live[v % len(live)]
            cands = sorted(pool._refs)
            if cands:
                pool.share(owner, cands[w % len(cands)])
        elif kind == "cache" and pool._refs:
            cands = sorted(pool._refs)
            pool.mark_cacheable(cands[v % len(cands)])
        elif kind == "retire" and live:
            owner = live.pop(v % len(live))
            pool.release(owner)
            del reserved[owner]
        elif kind == "swap_out" and live:
            owner = live.pop(v % len(live))
            charged = list(pool.blocks_of(owner))
            held = charged + list(pool._shared[owner])
            gate_before = pool.reserved_total + pool.uncharged_total
            # swap keys live apart from owner ids, as the scheduler's
            # ("swap", n) keys do: an int key could name a live owner
            key = ("swap", next_key)
            dropped = pool.swap_out(owner, key, len(held))
            # the swapped key holds ZERO gate capacity: the whole
            # reservation left the gate; the only additions are the
            # owner's charged blocks that sharers kept live (each now
            # uncharged, exactly as a plain release would leave them),
            # minus uncharged blocks whose last pin the victim held
            survivors = sum(1 for b in charged if pool.refcount(b) >= 1)
            dead_uncharged = sum(1 for b in dropped if b not in charged)
            assert (pool.reserved_total + pool.uncharged_total
                    == gate_before - reserved[owner] + survivors
                    - dead_uncharged)
            for blk in dropped:
                assert pool.refcount(blk) == 0
            assert pool.is_swapped(key)
            with pytest.raises(ValueError):
                pool.swap_out(owner, key, 0)        # double swap / gone
            swapped[key] = len(held)
            del reserved[owner]
            next_key += 1
        elif kind == "swap_in" and swapped:
            keys = sorted(swapped)
            key = keys[v % len(keys)]
            need = w % 4 + 1
            if pool.can_reserve(need):
                pool.swap_in(key, next_owner, need)
                assert not pool.is_swapped(key)
                del swapped[key]
                reserved[next_owner] = need
                live.append(next_owner)
                next_owner += 1
            else:
                with pytest.raises(ValueError):
                    pool.reserve(next_owner, need)
        assert pool.swapped_total == len(swapped)
        assert pool.swapped_blocks_total == sum(swapped.values())
        pool.check_invariants()
    with pytest.raises(ValueError):
        pool.swap_in(object(), "nobody", 1)         # unknown key
    for key in list(swapped):
        pool.drop_swapped(key)
    for owner in list(live):
        pool.release(owner)
    pool.check_invariants()
    assert pool.swapped_total == 0
    assert pool.allocated_total == 0 and pool.reserved_total == 0


@pytest.mark.parametrize("seed", range(8))
def test_swap_trace_seeded(seed):
    rng = np.random.default_rng(seed + 200)
    _check_swap_trace(int(rng.integers(3, 25)), _random_swap_ops(rng, 80))


if HAVE_HYPOTHESIS:
    SWAP_OPS = st.lists(
        st.tuples(st.sampled_from(["admit", "grow", "share", "cache",
                                   "retire", "swap_out", "swap_in"]),
                  st.integers(0, 7), st.integers(0, 7)),
        min_size=1, max_size=80)

    @needs_hypothesis
    @given(st.integers(3, 24), SWAP_OPS)
    @settings(**SETTINGS)
    def test_swap_trace_property(num_blocks, ops):
        _check_swap_trace(num_blocks, ops)


def test_swap_state_machine():
    """SWAPPED lifecycle basics: swap_out releases like release, the key
    retains the logical chain length, swap_in re-reserves through the
    gate, and misuse raises."""
    pool = BlockAllocator(6)
    pool.reserve("a", 3)
    blocks = [pool.alloc("a") for _ in range(3)]
    assert pool.swap_out("a", "k", 3) == sorted(blocks, reverse=True)
    assert pool.is_swapped("k") and pool.swapped_blocks_total == 3
    assert pool.reserved_total == 0 and pool.allocated_total == 0
    pool.check_invariants()
    # the freed capacity is genuinely reusable while "a" is out
    pool.reserve("b", 5)
    with pytest.raises(ValueError):
        pool.swap_in("k", "a", 1)       # gate: no room to come back
    pool.release("b")
    pool.swap_in("k", "a", 3)
    assert not pool.is_swapped("k") and pool.reserved_total == 3
    with pytest.raises(ValueError):
        pool.swap_in("k", "a2", 1)      # key consumed
    with pytest.raises(ValueError):
        pool.drop_swapped("k")
    pool.check_invariants()


def test_allocator_basics():
    pool = BlockAllocator(5)
    assert pool.capacity == 4
    pool.reserve("a", 2)
    pool.reserve("b", 2)
    assert not pool.can_reserve(1)
    b1, b2 = pool.alloc("a"), pool.alloc("a")
    assert b1 != b2 and TRASH_BLOCK not in (b1, b2)
    with pytest.raises(ValueError):
        pool.alloc("a")                       # reservation exhausted
    assert pool.high_water == 2
    assert set(pool.release("a")) == {b1, b2}
    pool.check_invariants()
    assert pool.can_reserve(2)


# ---------------------------------------------------------------------------
# Paged store round-trips
# ---------------------------------------------------------------------------

D, HKV, BS, NB, MB, B = 32, 2, 4, 9, 3, 2
CASS = CassandraConfig(variant=1, gamma=3)
BOOK = KC.default_kv_codebook()
# disjoint tables: row b owns blocks [1+b*MB, 1+(b+1)*MB)
TABLE = jnp.asarray(
    [[1 + b * MB + i for i in range(MB)] for b in range(B)], jnp.int32)


def _encode(x):
    return KC.encode_store(CASS, x, D, BOOK)


def _empty_pool():
    return jax.tree.map(
        lambda l: jnp.zeros(l.shape, l.dtype),
        jax.eval_shape(_encode, jax.ShapeDtypeStruct(
            (NB, BS, HKV, D), jnp.bfloat16)))


def _check_packed_roundtrip(seed, offset):
    """Tokens scattered into a packed pool through a block table and
    gathered back reconstruct bit-exactly what a direct encode/decode
    yields — paging is lossless by construction."""
    key = jax.random.PRNGKey(seed)
    q = 3
    x = jax.random.normal(key, (B, q, HKV, D), jnp.float32) \
        .astype(jnp.bfloat16)
    at = jnp.full((B,), offset, jnp.int32)
    pool = KC.append_paged_batched(_empty_pool(), _encode(x), TABLE, at)
    view = KC.gather_store(pool, TABLE)          # (B, MB*BS, HKV, …)
    for v in ("target", "draft"):
        got = KC.read_store(CASS, view, D, v, BOOK)
        want = KC.read_store(CASS, _encode(x), D, v, BOOK)
        for b in range(B):
            np.testing.assert_array_equal(
                np.asarray(got[b, offset:offset + q], np.float32),
                np.asarray(want[b], np.float32))


@pytest.mark.parametrize("offset", range(BS))
def test_packed_roundtrip_through_block_table(offset):
    _check_packed_roundtrip(7 * offset + 1, offset)


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(st.integers(0, 2 ** 31 - 1), st.integers(0, BS - 1))
    @settings(**SETTINGS)
    def test_packed_roundtrip_property(seed, offset):
        _check_packed_roundtrip(seed, offset)


# ---------------------------------------------------------------------------
# Draft view derived from the target view
# ---------------------------------------------------------------------------

def _kv_values(kind: str, shape, seed: int) -> jax.Array:
    """bf16 KV vectors of one kind: ``unary`` N(0, 0.25) (every block
    unary-coded), ``delta`` magnitudes spread over 2^-40..2^20 (every
    block delta-coded), ``zeros`` mostly +0, -0 and subnormals, so the
    kept set holds them too."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, shape, jnp.float32) * 0.5
    if kind in ("delta", "zeros"):
        e = jax.random.uniform(k2, shape, minval=-40.0, maxval=20.0)
        x = jnp.sign(x) * 2.0 ** e
    x = x.astype(jnp.bfloat16)
    if kind == "zeros":
        pick = jax.random.randint(k3, shape, 0, 5)
        bits = bitops.bf16_to_bits(x)
        sub = (bits & 0x8000) | (bits & 0x7F | 1)      # exponent 0
        bits = jnp.select([pick == 0, pick == 1, pick == 2],
                          [jnp.zeros_like(bits), jnp.full_like(bits, 0x8000),
                           sub], bits)
        x = bitops.bits_to_bf16(bits)
    return x


@pytest.mark.parametrize("kind, shape, prune, modes", [
    ("unary", (NB, BS, HKV, 128), 0.4, "unary"),
    ("delta", (NB, BS, HKV, 128), 0.4, "delta"),
    ("zeros", (NB, BS, HKV, D), 0.4, "delta"),
    ("unary", (NB, BS, HKV, D), 0.0, "both"),        # keep == block
    ("delta", (2, NB, BS, HKV, D), 0.4, "delta"),    # pool (R,NB,BS,Hkv,d)
])
def test_draft_from_target_bit_exact(kind, shape, prune, modes):
    """``draft_from_target`` on the target view reproduces
    ``read_store(view="draft")`` bit for bit, in unary and delta blocks,
    at pruned positions, for ±0 and subnormals, with nothing pruned, and
    at the pool's stacked shape."""
    cass = CassandraConfig(variant=1, gamma=3, kv_prune=prune)
    d = shape[-1]
    x = _kv_values(kind, shape, seed=d + len(shape))
    store = KC.encode_store(cass, x, d, BOOK)
    delta = np.asarray(store["spec"]["exp_mode"]) == 1
    assert {"unary": not delta.any(), "delta": delta.all(),
            "both": delta.any() and not delta.all()}[modes]
    target = KC.read_store(cass, store, d, "target", BOOK)
    np.testing.assert_array_equal(np.asarray(bitops.bf16_to_bits(target)),
                                  np.asarray(bitops.bf16_to_bits(x)))
    want = KC.read_store(cass, store, d, "draft", BOOK)
    got = jax.jit(KC.draft_from_target, static_argnums=(0, 3))(
        cass, store["spec"], target, d)
    np.testing.assert_array_equal(np.asarray(bitops.bf16_to_bits(got)),
                                  np.asarray(bitops.bf16_to_bits(want)))
    if kind == "zeros":                      # the planted values were kept
        kept = np.asarray(bitops.unpack_bits(store["spec"]["bitmap"], d)
                          ).reshape(shape)
        bits = np.asarray(bitops.bf16_to_bits(x))
        assert (kept & (bits == 0x8000)).any()
        assert (kept & (bits & 0x7F80 == 0) & (bits & 0x7F != 0)).any()
    if prune == 0.0:
        assert cass.kv_keep(d) == d


@pytest.mark.parametrize("seed", range(3))
def test_plain_roundtrip_and_trash_isolation(seed):
    """Plain bf16 pool: a row writing past its table (trash-routed) must
    not disturb another row's blocks."""
    key = jax.random.PRNGKey(seed)
    q = BS * MB  # row 1 writes its whole capacity … and then some
    x = jax.random.normal(key, (B, q, HKV, D), jnp.bfloat16)
    pool = jnp.zeros((NB, BS, HKV, D), jnp.bfloat16)
    pool = KC.append_paged_batched(pool, x, TABLE, jnp.zeros(B, jnp.int32))
    # row 1 overflows: positions beyond MB*BS go to the trash block
    over = KC.append_paged_batched(
        pool, x, TABLE, jnp.asarray([0, BS], jnp.int32))
    view = KC.gather_store(over, TABLE)
    # row 0 rewrote [0,q); row 1 wrote [BS, MB*BS) in-range, rest trashed
    np.testing.assert_array_equal(np.asarray(view[0], np.float32),
                                  np.asarray(x[0], np.float32))
    np.testing.assert_array_equal(
        np.asarray(view[1, BS:], np.float32),
        np.asarray(x[1, :q - BS], np.float32))
    # row 0's blocks were never touched by row 1's overflow
    np.testing.assert_array_equal(
        np.asarray(KC.gather_store(pool, TABLE)[0], np.float32),
        np.asarray(view[0], np.float32))


# ---------------------------------------------------------------------------
# Device-side copy-on-write
# ---------------------------------------------------------------------------


def _leaves(store):
    return [np.asarray(x) for x in jax.tree.leaves(store)]


@pytest.mark.parametrize("packed", [False, True])
def test_copy_pool_blocks_cow_never_mutates_source(packed):
    """``copy_pool_blocks`` duplicates a block bit-exactly (plain and
    packed streams), trash->trash pad pairs are no-ops, and diverging in
    the copy never mutates the shared source block."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, BS, HKV, D), jnp.float32) \
        .astype(jnp.bfloat16)
    if packed:
        pool = _empty_pool()
    else:
        pool = jnp.zeros((NB, BS, HKV, D), jnp.bfloat16)
    src_blk, dst_blk = 2, 5
    table = jnp.asarray([[src_blk]], jnp.int32)
    pool = KC.append_paged_batched(
        pool, _encode(x) if packed else x, table, jnp.zeros(1, jnp.int32))
    # wrap as a minimal (R, NB, BS, …) cache so copy_pool_blocks applies
    cache = {"dec": [{"e0": jax.tree.map(lambda c: c[None], pool)}]}
    src = jnp.asarray([src_blk, TRASH_BLOCK], jnp.int32)
    dst = jnp.asarray([dst_blk, TRASH_BLOCK], jnp.int32)
    out = KC.copy_pool_blocks(cache, src, dst)["dec"][0]["e0"]
    out = jax.tree.map(lambda c: c[0], out)
    for a, b in zip(_leaves(out), _leaves(pool)):
        np.testing.assert_array_equal(a[dst_blk], a[src_blk])   # copied
        np.testing.assert_array_equal(a[src_blk], b[src_blk])   # intact
        np.testing.assert_array_equal(a[TRASH_BLOCK], b[TRASH_BLOCK])
    # diverge in the copy: overwrite the copied block's tail through a
    # table pointing at dst — the shared source must not change
    y = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, HKV, D),
                          jnp.float32).astype(jnp.bfloat16)
    dtable = jnp.asarray([[dst_blk]], jnp.int32)
    after = KC.append_paged_batched(
        out, _encode(y) if packed else y, dtable,
        jnp.full(1, BS - 2, jnp.int32))
    for a, b in zip(_leaves(after), _leaves(pool)):
        np.testing.assert_array_equal(a[src_blk], b[src_blk])
    got = KC.gather_store(after, dtable)
    want = KC.gather_store(out, table)
    if packed:
        got = KC.read_store(CASS, got, D, "target", BOOK)
        want = KC.read_store(CASS, want, D, "target", BOOK)
        yd = KC.read_store(CASS, _encode(y), D, "target", BOOK)
    else:
        yd = y
    # copied head survives, divergence point onward holds the new tokens
    np.testing.assert_array_equal(np.asarray(got[0, :BS - 2], np.float32),
                                  np.asarray(want[0, :BS - 2], np.float32))
    np.testing.assert_array_equal(np.asarray(got[0, BS - 2:], np.float32),
                                  np.asarray(yd[0], np.float32))


# ---------------------------------------------------------------------------
# Device spill -> host -> restore (preemption swap round-trip)
# ---------------------------------------------------------------------------


def _as_cache(pool_store):
    """Wrap a store as the minimal (R, NB, BS, …) cache tree the
    spill/restore steps operate on."""
    return {"dec": [{"e0": jax.tree.map(lambda c: c[None], pool_store)}]}


def _check_swap_roundtrip(seed, packed, offset):
    """``spill``→``restore`` is identity: a row's blocks gathered to host
    and scattered back into freshly allocated blocks reconstruct its
    view bit-exactly (plain and packed streams), trash-padded entries
    are no-ops, and rows that were never swapped are untouched."""
    key = jax.random.PRNGKey(seed)
    q = BS * MB
    x = jax.random.normal(key, (B, q, HKV, D), jnp.float32) \
        .astype(jnp.bfloat16)
    pool = _empty_pool() if packed else jnp.zeros((NB, BS, HKV, D),
                                                  jnp.bfloat16)
    pool = KC.append_paged_batched(
        pool, _encode(x) if packed else x, TABLE, jnp.zeros(B, jnp.int32))
    cache = _as_cache(pool)
    victim_blocks = np.asarray(TABLE[0])            # spill row 0's chain
    pad = MB + 2                                    # fixed compile bucket
    vec = np.full(pad, TRASH_BLOCK, np.int32)
    vec[:MB] = victim_blocks
    spilled = KC.spill_pool_blocks(cache, jnp.asarray(vec))
    store = SpillStore()
    store.put("r0", spilled[0]["e0"], MB, length=q, pos=4, cur=7)
    # the victim's blocks are freed and clobbered by another request
    clobber = jax.random.normal(jax.random.fold_in(key, 1),
                                (B, q, HKV, D), jnp.float32) \
        .astype(jnp.bfloat16)
    pool2 = KC.append_paged_batched(
        pool, _encode(clobber) if packed else clobber,
        jnp.tile(TABLE[:1], (B, 1)), jnp.zeros(B, jnp.int32))
    cache = _as_cache(pool2)
    # resume into a different set of physical blocks, restoring a
    # sub-range [offset, MB) as a partial prefix re-alias would
    new_blocks = np.asarray(TABLE[1])               # row 1's blocks
    rvec = np.full(pad, TRASH_BLOCK, np.int32)
    rvec[:MB - offset] = new_blocks[offset:]
    chain = store.get("r0")
    data = [{"e0": jax.tree.map(jnp.asarray,
                                chain.slice_blocks(offset, MB, pad))}]
    restored = KC.restore_pool_blocks(cache, jnp.asarray(rvec), data)
    got = KC.gather_store(
        jax.tree.map(lambda c: c[0], restored["dec"][0]["e0"]),
        jnp.asarray(new_blocks)[None, :])
    want = KC.gather_store(pool, TABLE)
    if packed:
        got = KC.read_store(CASS, got, D, "target", BOOK)
        want = KC.read_store(CASS, want, D, "target", BOOK)
    # restored range is bit-identical to the pre-preemption bytes
    np.testing.assert_array_equal(
        np.asarray(got[0, offset * BS:], np.float32),
        np.asarray(want[0, offset * BS:], np.float32))
    assert store.pop("r0").n_blocks == MB
    assert store.blocks == 0 and store.total_restored_blocks == MB


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("offset", [0, 1])
def test_swap_roundtrip_bit_exact(packed, offset):
    _check_swap_roundtrip(11 * offset + 3, packed, offset)


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(st.integers(0, 2 ** 31 - 1), st.booleans(),
           st.integers(0, MB - 1))
    @settings(max_examples=10, deadline=None)
    def test_swap_roundtrip_property(seed, packed, offset):
        _check_swap_roundtrip(seed, packed, offset)


def test_swap_roundtrip_other_rows_untouched():
    """Restoring one row's chain must not disturb blocks it does not
    own — the trash-padded scatter only lands on the target blocks (and
    the trash block, which holds garbage by contract)."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (B, BS * MB, HKV, D), jnp.float32) \
        .astype(jnp.bfloat16)
    pool = jnp.zeros((NB, BS, HKV, D), jnp.bfloat16)
    pool = KC.append_paged_batched(pool, x, TABLE, jnp.zeros(B, jnp.int32))
    cache = _as_cache(pool)
    vec = np.full(MB, TRASH_BLOCK, np.int32)
    vec[:MB] = np.asarray(TABLE[0])
    spilled = KC.spill_pool_blocks(cache, jnp.asarray(vec))
    store = SpillStore()
    store.put("r", spilled[0]["e0"], MB, length=BS * MB, pos=0, cur=0)
    data = [{"e0": jax.tree.map(
        jnp.asarray, store.get("r").slice_blocks(0, MB, MB))}]
    restored = KC.restore_pool_blocks(cache, jnp.asarray(vec), data)
    # row 1's blocks are bit-identical before and after
    np.testing.assert_array_equal(
        np.asarray(restored["dec"][0]["e0"][0][np.asarray(TABLE[1])],
                   np.float32),
        np.asarray(pool[np.asarray(TABLE[1])], np.float32))


def test_spill_store_cap_and_accounting():
    """SpillStore: byte/block accounting, the ``can_hold`` victim-policy
    gate (a full store refuses new chains, never drops one), duplicate
    keys and out-of-range restores raise."""
    x = jnp.ones((1, 2, BS, HKV, D), jnp.bfloat16)      # (R,K,BS,…) leaf
    store = SpillStore(max_blocks=3)
    store.put("a", [{"e0": {"k": x, "v": x}}], 2, length=8, pos=8, cur=1)
    assert store.blocks == 2 and store.nbytes > 0
    assert store.can_hold(1) and not store.can_hold(2)
    with pytest.raises(ValueError):
        store.put("a", [{"e0": {"k": x, "v": x}}], 1,
                  length=1, pos=1, cur=0)               # duplicate key
    with pytest.raises(ValueError):
        store.put("b", [{"e0": {"k": x, "v": x}}], 2,
                  length=1, pos=1, cur=0)               # over cap
    chain = store.get("a")
    assert chain.length == 8 and chain.cur == 1
    with pytest.raises(ValueError):
        chain.slice_blocks(1, 3, 4)                     # past n_blocks
    with pytest.raises(ValueError):
        chain.slice_blocks(0, 2, 1)                     # bucket too small
    out = chain.slice_blocks(1, 2, 3)
    assert jax.tree.leaves(out)[0].shape[1] == 3        # padded to bucket
    store.pop("a")
    assert store.blocks == 0 and store.peak_blocks == 2
    assert store.total_spilled_blocks == 2
    assert store.total_restored_blocks == 2


# ---------------------------------------------------------------------------
# Radix prefix index
# ---------------------------------------------------------------------------

PBS = 2          # prefix-cache tests use tiny 2-token blocks


def _prefixed_pool(num_blocks=10, cap=None):
    pool = BlockAllocator(num_blocks)
    return pool, PrefixCache(pool, PBS, max_blocks=cap)


def _admit_chain(pool, cache, owner, tokens, n_blocks):
    """Reserve + allocate + index ``n_blocks`` full blocks of ``tokens``
    the way scheduler admission/prefill does."""
    m = cache.match(tokens)
    pool.reserve(owner, n_blocks - len(m.nodes))
    blocks = []
    for node in m.nodes:
        pool.share(owner, node.block)
        blocks.append(node.block)
    while len(blocks) < n_blocks:
        blocks.append(pool.alloc(owner))
    cache.insert(tokens, blocks, n_blocks * PBS)
    return m, blocks


def test_prefix_match_insert_and_dedup():
    pool, cache = _prefixed_pool()
    toks = np.arange(1, 9)                        # 4 full blocks
    m0, blocks = _admit_chain(pool, cache, "a", toks, 3)
    assert m0.tokens == 0 and len(cache) == 3
    cache.check_invariants()
    # same prompt again: full-block match, capped at len(prompt)-1
    m1 = cache.match(toks)
    assert [n.block for n in m1.nodes] == blocks
    assert m1.full_tokens == 6 and m1.partial is None
    # shorter query: cap at len-1 turns the last block into a partial hit
    m2 = cache.match(toks[:4])
    assert m2.full_tokens == 2
    assert m2.partial is not None and m2.partial_len == 1
    # diverging mid-block yields a partial (copy-on-write) candidate
    div = np.array([1, 2, 3, 99, 5])
    m3 = cache.match(div)
    assert m3.full_tokens == 2 and m3.partial_len == 1
    # a duplicate insert with different physical blocks keeps the
    # existing nodes (the duplicate stays private, never indexed)
    pool.reserve("b", 3)
    dup = [pool.alloc("b") for _ in range(3)]
    assert cache.insert(toks, dup, 6)[1] == 0
    assert len(cache) == 3
    cache.check_invariants()
    pool.check_invariants()


def test_prefix_incremental_insert_watermark():
    """insert() resumes from a (node, start) watermark — the scheduler
    indexes each prefill chunk without re-walking committed blocks —
    and a stale hint (node evicted since) restarts from the root."""
    pool, cache = _prefixed_pool()
    toks = np.arange(1, 11)                        # 5 full blocks
    pool.reserve("a", 5)
    blocks = [pool.alloc("a") for _ in range(5)]
    node, added = cache.insert(toks, blocks, 4)
    assert added == 2
    node2, added2 = cache.insert(toks, blocks, 10, node=node, start=2)
    assert added2 == 3 and len(cache) == 5
    cache.check_invariants()
    # stale hint: park the chain, evict the deepest node, resume from it
    pool.release("a")
    cache.evict_lru()
    assert node2.detached and len(cache) == 4
    pool.reserve("b", 5)
    blocks_b = [pool.alloc("b") for _ in range(5)]
    node3, added3 = cache.insert(toks, blocks_b, 10, node=node2, start=5)
    # restart walks from the root and STOPS at the first identical run
    # held by someone else's block: b's copies stay private — indexing
    # them under a chain b does not pin would break the monotone
    # refcount property leaf-first eviction relies on
    assert added3 == 0 and len(cache) == 4
    assert node3 is cache.root
    cache.check_invariants()
    pool.check_invariants()


def test_prefix_park_evict_lru_leaf_first():
    pool, cache = _prefixed_pool(num_blocks=8)
    toks_a = np.arange(1, 9)
    _, blocks_a = _admit_chain(pool, cache, "a", toks_a, 3)
    pool.release("a")
    assert pool.parked_total == 3                 # parked, not freed
    # a new owner needing the whole pool forces eviction: leaves go
    # first (deepest block), roots last
    pool.reserve("b", 7)
    got = [pool.alloc("b") for _ in range(7)]
    assert len(set(got)) == 7
    assert pool.parked_total == 0 and len(cache) == 0
    pool.check_invariants()
    cache.check_invariants()


def test_prefix_pinned_chain_not_evictable():
    pool, cache = _prefixed_pool(num_blocks=6)
    toks = np.arange(1, 9)
    _, blocks = _admit_chain(pool, cache, "a", toks, 3)
    pool.release("a")
    m, _ = _admit_chain(pool, cache, "b", toks, 3)  # re-pins the chain
    assert m.full_tokens == 6
    # nothing is evictable while b pins the chain: draining the free
    # list then over-allocating must fail, not evict pinned blocks
    pool.reserve("c", pool.capacity - pool.allocated_total)
    for _ in range(pool.capacity - pool.allocated_total):
        pool.alloc("c")
    with pytest.raises(ValueError):
        pool.alloc("c")
    assert len(cache) == 3
    pool.check_invariants()


def test_prefix_cache_cap_enforced_on_park():
    pool, cache = _prefixed_pool(num_blocks=10, cap=2)
    toks = np.arange(1, 11)
    _admit_chain(pool, cache, "a", toks, 4)
    pool.release("a")                      # parks 4, cap 2 -> evict 2 LRU
    assert pool.parked_total == 2 and len(cache) == 2
    cache.check_invariants()
    pool.check_invariants()
