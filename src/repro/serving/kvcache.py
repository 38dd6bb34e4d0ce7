"""KV cache: plain bf16 or Cassandra-packed (speculation + verification).

The packed cache is the paper's §IV-B applied per (token, head) vector:
magnitude top-k pruning (Mustafar-style), mantissa truncation, and exponent
compression (unary for Cassandra-1 / MX for Cassandra-2). Draft decode reads
only the speculation leaves; verification reads both and reconstructs the
target KV **bit-exactly** (corr_bits=8 online guarantees exactness for any
per-token dynamic range).

The exponent codebook is cache-global and stationary — per the hardware
design, the encoder keeps the frequency-ranked book in registers. It is
computed offline per model from calibration KV (the distribution is narrow
and stable, paper Fig. 6); losslessness never depends on the book (a bad
book only shifts blocks into delta mode, which corr_bits=8 corrects).

Cache layout (pytree; R = scan repeats of the layer group):

  attn  (GQA)   {"k": store, "v": store}           store leaf (R,B,S,Hkv,1,*)
  attn  (MLA)   {"c": store, "kr": store}          latent + rope, (R,B,S,1,*)
  ssm           {"conv": (R,B,dc-1,di), "h": (R,B,di,n)}    never packed
  cross (enc-dec) {"ck": (R,B,Senc,H,hd), "cv": …}  plain bf16 (computed once)

plain store = bf16 array; packed store = {"spec": {...}, "verif": {...}}.

A packed cache whose passes read dense KV also keeps its two dense views,
``cache["view"] = {"target": groups, "draft": groups}``: per attention
entry bf16 stores shaped like the packed ones (``{"k", "v"}`` or
``{"c", "kr"}``), so no serving step decodes the pool. Every write to the
packed stores writes the views too: ``engine.commit`` appends the new
tokens' views through the same ``append_batched``, ``copy_pool_blocks``
copies their blocks, and ``restore_pool_blocks`` decodes only the
restored blocks into them (``store_views``). The packed stores then serve
capacity and swap; the per-cycle passes read the views. The paged-kernel
path, which decodes packed KV in-kernel, keeps no views.

Two cache layouts share the store codecs:

* **slot** (``init_cache``) — every request owns a contiguous ``(S_max,)``
  row: leaves are (R,B,S_max,…). Short requests strand the tail of their
  row and S_max is a hard cap.
* **paged** (``init_paged_cache``) — stores hold a global pool of
  fixed-size token blocks, leaves (R,NB,BS,…), addressed through a
  per-request ``block_table`` (B,MB) int32. The table is a *traced*
  operand: admission, growth and recycling re-point rows with zero
  recompiles. Physical block 0 is the trash block (see
  ``serving.blockpool``); reads gather pool→per-request views, writes
  scatter token positions through the table.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_groups
from repro.core import bitops, format as fmt
from repro.core.format import CassandraConfig
from repro.serving.blockpool import TRASH_BLOCK

ONLINE_CORR_BITS = 8


# ---------------------------------------------------------------------------
# Codebook
# ---------------------------------------------------------------------------

def default_kv_codebook() -> tuple[jax.Array, jax.Array]:
    """Generic frequency ranking: exponents ordered by distance from 125.

    Real KV magnitudes cluster below 1.0 (exp ≈ 120–127); ranking by
    |e - 125| with the smaller exponent first on ties matches the measured
    distribution closely enough that mode-0 dominates.
    """
    import numpy as np
    center = 125
    order = sorted(range(256), key=lambda e: (abs(e - center), e))
    exp_of_rank = np.array(order, dtype=np.uint8)
    rank_of_exp = np.zeros(256, dtype=np.uint8)
    for r, e in enumerate(order):
        rank_of_exp[e] = min(r, 255)
    return jnp.asarray(exp_of_rank), jnp.asarray(rank_of_exp)


def calibrate_kv_codebook(kv_samples: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Frequency-ranked codebook from calibration K/V tensors."""
    from repro.core import coding
    _, exps, _ = bitops.split_fields(kv_samples.astype(jnp.bfloat16))
    exp_of_rank, rank_of_exp = coding.build_codebook(exps)
    return exp_of_rank.astype(jnp.uint8), rank_of_exp


def cache_codebook(cache: dict) -> tuple[jax.Array, jax.Array] | None:
    if "book_exp_of_rank" not in cache:
        return None
    return cache["book_exp_of_rank"], cache["book_rank_of_exp"]


# ---------------------------------------------------------------------------
# Per-vector codec (block = vector dim)
# ---------------------------------------------------------------------------

def is_packed(store) -> bool:
    return isinstance(store, dict) and "spec" in store


def _keep(cass: CassandraConfig, d: int) -> int:
    return cass.kv_keep(d)


@partial(jax.jit, static_argnames=("cass", "d"))
def encode_store(cass: CassandraConfig, x: jax.Array, d: int,
                 codebook: tuple[jax.Array, jax.Array]) -> dict:
    """Pack (..., d) bf16 vectors into a {"spec", "verif"} store."""
    scores = jnp.abs(x.astype(jnp.float32))
    spec, verif = fmt.format_tensor(
        x, scores, cass, d, _keep(cass, d), fmt.kv_group(cass, d),
        cass.kv_trunc, codebook=codebook, corr_bits=ONLINE_CORR_BITS,
        pruned_raw=True)
    return {"spec": spec, "verif": verif}


@partial(jax.jit, static_argnames=("cass", "d", "view"))
def read_store(cass: CassandraConfig, store, d: int, view: str,
               codebook: tuple[jax.Array, jax.Array] | None) -> jax.Array:
    """Materialise dense (..., d) bf16 from a store per the runtime view."""
    if not is_packed(store):
        return store
    with jax.named_scope("cass.kv_decode"):
        if view == "draft":
            out = fmt.draft_tensor(store["spec"], cass, d, _keep(cass, d),
                                   fmt.kv_group(cass, d), cass.kv_trunc, d,
                                   codebook=codebook,
                                   corr_bits=ONLINE_CORR_BITS)
        else:
            out = fmt.target_tensor(store["spec"], store["verif"], cass, d,
                                    _keep(cass, d), fmt.kv_group(cass, d),
                                    cass.kv_trunc, d, codebook=codebook,
                                    corr_bits=ONLINE_CORR_BITS)
        # format_tensor blocks the last dim: (..., NB=1, d) -> (..., d)
        return out.reshape(*store["spec"]["bitmap"].shape[:-2], d)


def draft_from_target(cass: CassandraConfig, spec: dict, target: jax.Array,
                      d: int) -> jax.Array:
    """The draft view of a Cassandra-1 store, derived from its target view.

    Bit-exact against ``read_store(view="draft")`` and elementwise, so
    the draft view of tokens whose target view is known costs no decode
    (``store_views``). ``target`` (..., d) is the store's target view and
    ``spec`` its speculation leaves. Pruned positions (bitmap 0) read
    +0; kept ones keep their sign and lose the low ``kv_trunc`` mantissa
    bits. The exponent is exact in unary blocks (``exp_mode`` 0); in
    delta blocks the draft codes ``emax - e`` clamped to
    ``2**exp_bits - 2``, with the escape for ``e == 0``
    (``coding.delta_encode_block``), so it reads
    ``0 if e == 0 else max(e, emax - (2**exp_bits - 2))``.
    """
    if cass.variant != 1:
        raise ValueError("draft_from_target covers Cassandra-1 only: the "
                         "Cassandra-2 draft is not a function of its "
                         "container-exact target")
    with jax.named_scope("cass.kv_derive"):
        bits = bitops.bf16_to_bits(target).astype(jnp.int32)
        kept = bitops.unpack_bits(spec["bitmap"], d).reshape(target.shape)
        exp = (bits >> 7) & 0xFF
        floor = spec["exp_emax"].astype(jnp.int32) - ((1 << cass.exp_bits)
                                                      - 2)
        delta_exp = jnp.where(exp == 0, 0, jnp.maximum(exp, floor))
        exp = jnp.where(spec["exp_mode"] == 0, exp, delta_exp)
        mant = bits & (0x7F & ~((1 << cass.kv_trunc) - 1))
        out = (bits & 0x8000) | (exp << 7) | mant
        return bitops.bits_to_bf16(jnp.where(kept, out, 0))


def store_views(cass: CassandraConfig, store: dict, d: int,
                book: tuple[jax.Array, jax.Array],
                target: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """The dense ``(target, draft)`` views of a packed store, bit for bit
    what ``read_store`` gives for each view.

    ``target`` is the bf16 the store was just encoded from. Cassandra-1's
    target view is exactly that, and its draft view is derived from it
    elementwise (``draft_from_target``), so nothing is decoded. The
    Cassandra-2 target is exact only to its containers, so both views are
    decoded from the store, as they are without ``target``."""
    if target is None or cass.variant != 1:
        target = read_store(cass, store, d, "target", book)
    target = target.astype(jnp.bfloat16)
    if cass.variant == 1:
        return target, draft_from_target(cass, store["spec"], target, d)
    return target, read_store(cass, store, d, "draft", book)


def store_dims(cfg: ModelConfig) -> dict[str, int]:
    """An attention entry's stores and their vector widths."""
    if cfg.mla:
        return {"c": cfg.kv_lora_rank, "kr": cfg.qk_rope_dim}
    return {"k": cfg.hd, "v": cfg.hd}


def has_views(cache: dict) -> bool:
    """Whether the cache keeps dense views of its packed stores."""
    return "view" in cache


def append_store(store, new_store, at) -> dict:
    """dynamic_update_slice every leaf along the S axis (axis 1 of B,S,…)."""
    def upd(c, n):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), at,
                                                   axis=1)
    if not is_packed(store):
        return upd(store, new_store)
    return jax.tree.map(upd, store, new_store)


def append_store_batched(store, new_store, at: jax.Array) -> dict:
    """Per-batch append: leaf (B,S,…) gets new (B,q,…) at row offsets ``at``.

    Batched speculative decoding accepts a different count per sequence, so
    each row writes at its own cache offset. Slots beyond a row's committed
    length hold stale data masked out by the validity mask until
    overwritten.
    """
    def upd(c, n):
        b, q = n.shape[0], n.shape[1]
        pos = at[:, None] + jnp.arange(q)[None, :]
        return c.at[jnp.arange(b)[:, None], pos].set(n.astype(c.dtype))
    if not is_packed(store):
        return upd(store, new_store)
    return jax.tree.map(upd, store, new_store)


def is_paged(cache: dict) -> bool:
    return "block_table" in cache


def gather_block_leaf(pool: jax.Array, table: jax.Array) -> jax.Array:
    """(NB,BS,…) pool + (B,MB) table -> (B,MB*BS,…) request-major view.

    The single paging-address primitive. ``gather_store`` applies it
    leaf-wise to (possibly packed) stores; ``model._attn_entry`` applies
    it to materialised dense prefixes so both the GQA (k/v) and MLA
    (latent + rope) decode paths address the pool through the table. The
    table is a *traced* operand — allocation, growth and recycling never
    trigger a recompile; positions past a row's committed ``length`` are
    stale pool data masked out by the attention validity prefix.

    Out-of-range table entries route through the trash block (index 0,
    the ``append_paged_batched`` convention) — NOT through
    ``mode="clip"``'s silent alias to the *last* pool block — so the
    gather path and the paged-attention kernel (which sanitises the same
    way) agree on what a garbage slot reads. ``Scheduler.
    check_invariants`` asserts host-side tables never exceed
    ``num_blocks``; this is the belt-and-braces for traced values.
    """
    nb = pool.shape[0]
    with jax.named_scope("cass.kv_gather"):
        table = jnp.where((table >= 0) & (table < nb), table, TRASH_BLOCK)
        out = jnp.take(pool, table, axis=0, mode="clip")
        return out.reshape(table.shape[0],
                           table.shape[1] * pool.shape[1], *pool.shape[2:])


def gather_store(store, table: jax.Array):
    """Pool store (NB,BS,…) + table (B,MB) -> per-request store (B,MB*BS,…).

    Works leaf-wise, so packed stores gather their spec/verif streams
    without decoding; ``read_store`` on the result then reconstructs only
    the requests' resident tokens.
    """
    if not is_packed(store):
        return gather_block_leaf(store, table)
    return jax.tree.map(lambda c: gather_block_leaf(c, table), store)


def append_batched(store, new_store, at: jax.Array,
                   table: jax.Array | None = None):
    """THE append path: per-row token runs into either cache layout.

    ``new_store`` leaves are (B,q,…) token runs; row ``b`` writes at its
    own logical offset ``at[b]``. With ``table=None`` the run scatters
    into the row's contiguous (B,S,…) slot region; with a (B,MB) block
    table it scatters through the table into the (NB,BS,…) pool. Rows in
    the same batch may carry different real run lengths (mixed prefill
    chunks riding with speculative commits): callers write the full q
    width and advance ``length`` by the per-row real count, leaving the
    tail as masked stale data (slot) or trash-block writes (paged).
    """
    if table is None:
        return append_store_batched(store, new_store, at)
    return append_paged_batched(store, new_store, table, at)


def copy_pool_blocks(cache: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Device-side block copy ``pool[dst[i]] = pool[src[i]]`` in every
    attention store leaf — the copy-on-write primitive.

    A request that diverges inside a shared block receives a fresh block
    from its own reservation and a copy of the shared block's contents
    (plain or packed — the copy is leaf-wise and never decodes), then
    overwrites from the divergence point. ``src``/``dst`` are *traced*
    (K,) int32 operands padded with ``TRASH_BLOCK`` -> ``TRASH_BLOCK``
    no-op pairs, so any number of copies per cycle hits one compile.
    SSM entries are per-row recurrent state with no token axis — nothing
    to copy (prefix caching is validated off for SSM archs). Dense views
    copy the same blocks."""
    def cp(leaf):                                  # (R, NB, BS, …)
        return leaf.at[:, dst].set(leaf[:, src])
    new_dec = []
    for g in cache["dec"]:
        gd = {}
        for ekey, e in g.items():
            gd[ekey] = e if "conv" in e else jax.tree.map(cp, e)
        new_dec.append(gd)
    out = dict(cache)
    out["dec"] = new_dec
    if has_views(cache):
        out["view"] = jax.tree.map(cp, cache["view"])
    return out


def spill_pool_blocks(cache: dict, blocks: jax.Array) -> list:
    """Gather ``pool[blocks]`` out of every attention store leaf — the
    device→host half of KV swap-out.

    ``blocks`` is a *traced* (K,) int32 operand padded with
    ``TRASH_BLOCK`` entries, so any spill size up to K hits one compile
    (the scheduler's K is the per-row block-table width — one bucket
    serves every preemption). Returns a pytree mirroring ``cache["dec"]``
    with attention leaves (R, K, BS, …): a bit-copy of the spilled
    blocks' contents, plain or packed alike (the gather never decodes).
    The caller ``device_get``s the result into a ``SpillStore`` BEFORE
    the allocator frees the blocks for reuse. SSM entries are skipped —
    recurrent state has no pool axis (swap is validated off for SSM
    archs)."""
    out = []
    for g in cache["dec"]:
        gd = {}
        for ekey, e in g.items():
            if "conv" in e:
                continue
            gd[ekey] = jax.tree.map(lambda leaf: leaf[:, blocks], e)
        out.append(gd)
    return out


def restore_pool_blocks(cache: dict, blocks: jax.Array, data: list,
                        cass: CassandraConfig | None = None) -> dict:
    """Scatter spilled block contents back: ``pool[blocks[i]] = data[i]``
    in every attention store leaf — the host→device half of KV swap-in.

    ``data`` is the (R, K, BS, …) pytree ``spill_pool_blocks`` produced
    (host-padded with zeros past the real blocks); ``blocks`` is again a
    traced trash-padded (K,) int32 vector, so every restore reuses the
    one compiled step. Padded entries scatter into the trash block,
    which holds garbage by contract. Restored bytes are bit-identical to
    the spilled ones, so a resumed row's attention sees exactly the
    cache it had when preempted. A cache with dense views (``cass`` then
    names its format) gets the K restored blocks decoded into both, one
    layer at a time; no other block is decoded."""
    def put(leaf, d):
        return leaf.at[:, blocks].set(d.astype(leaf.dtype))
    new_dec = []
    for g, gd in zip(cache["dec"], data):
        gout = {}
        for ekey, e in g.items():
            if "conv" in e:
                gout[ekey] = e
            else:
                gout[ekey] = jax.tree.map(put, e, gd[ekey])
        new_dec.append(gout)
    out = dict(cache)
    out["dec"] = new_dec
    if has_views(cache):
        if cass is None:
            raise ValueError("restoring into a cache with dense views "
                             "needs its CassandraConfig")
        book = cache_codebook(cache)
        view = {nm: [dict(g) for g in groups]
                for nm, groups in cache["view"].items()}
        with jax.named_scope("cass.kv_view_write"):
            for gi, gd in enumerate(data):
                for ekey, vt in cache["view"]["target"][gi].items():
                    vt_new, vd_new = {}, {}
                    for nm, leaf in vt.items():
                        t, dr = jax.lax.map(
                            lambda s, d=leaf.shape[-1]: store_views(
                                cass, s, d, book),
                            jax.tree.map(jnp.asarray, gd[ekey][nm]))
                        vt_new[nm] = put(leaf, t)
                        vd_new[nm] = put(
                            cache["view"]["draft"][gi][ekey][nm], dr)
                    view["target"][gi][ekey] = vt_new
                    view["draft"][gi][ekey] = vd_new
        out["view"] = view
    return out


def restore_pool_blocks_marked(cache: dict, blocks: jax.Array, data: list,
                               cass: CassandraConfig | None = None
                               ) -> tuple[dict, jax.Array]:
    """``restore_pool_blocks`` plus a scalar completion *marker*.

    The marker (count of real, non-trash restore entries) is a tiny
    output of the SAME jit executable as the scatter: one XLA
    computation's results all become ready together, so
    ``block_until_ready(marker)`` proves the whole restore — H2D
    transfer included — has landed without touching (or transferring)
    any cache leaf. The synchronous scheduler blocks on it immediately
    to stamp a truthful restore wall; the pipelined scheduler defers
    that block to its next harvest, by which time the restore has
    overlapped the following fused step and the wait is ~zero."""
    out = restore_pool_blocks(cache, blocks, data, cass)
    return out, jnp.sum(blocks != TRASH_BLOCK)


def append_paged_batched(store, new_store, table: jax.Array,
                         at: jax.Array) -> dict:
    """Scatter per-row token runs into the block pool through the table.

    ``store`` leaves (NB,BS,…); ``new_store`` leaves (B,q,…); row ``b``
    writes its q tokens at logical positions ``at[b]+i``, resolved to
    physical slots ``table[b, pos//BS]*BS + pos%BS``. Positions past a
    row's table (masked rows riding along, chunk padding) are routed to
    the trash block so they can never corrupt another request's blocks.
    """
    def upd(c, n):
        nb, bs = c.shape[0], c.shape[1]
        b, q = n.shape[0], n.shape[1]
        mb = table.shape[1]
        pos = at[:, None] + jnp.arange(q)[None, :]            # (B,q)
        lblk = pos // bs
        phys = jnp.take_along_axis(table, jnp.minimum(lblk, mb - 1),
                                   axis=1)
        phys = jnp.where(lblk < mb, phys, TRASH_BLOCK)
        idx = phys * bs + pos % bs                            # (B,q)
        flat = c.reshape(nb * bs, *c.shape[2:])
        flat = flat.at[idx.reshape(-1)].set(
            n.astype(c.dtype).reshape(b * q, *n.shape[2:]), mode="drop")
        return flat.reshape(c.shape)
    if not is_packed(store):
        return upd(store, new_store)
    return jax.tree.map(upd, store, new_store)


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def _entry_kind(cfg: ModelConfig, entry: str) -> str:
    if entry[0] == "a":
        return "mla" if cfg.mla else "gqa"
    return "ssm"


def _entry_struct(cfg: ModelConfig, cass: CassandraConfig | None,
                  kind: str, b: int, s_max: int, packed: bool,
                  book) -> dict:
    """ShapeDtypeStruct tree of one cache entry (no allocation)."""

    def store_struct(shape, d):
        if not packed:
            return jax.ShapeDtypeStruct((*shape, d), jnp.bfloat16)
        dummy = jax.ShapeDtypeStruct((*shape, d), jnp.bfloat16)
        return jax.eval_shape(
            lambda x, bk: encode_store(cass, x, d, bk), dummy, book)

    if kind == "gqa":
        return {"k": store_struct((b, s_max, cfg.n_kv_heads), cfg.hd),
                "v": store_struct((b, s_max, cfg.n_kv_heads), cfg.hd)}
    if kind == "mla":
        return {"c": store_struct((b, s_max), cfg.kv_lora_rank),
                "kr": store_struct((b, s_max), cfg.qk_rope_dim)}
    if kind == "ssm":
        return {"conv": jax.ShapeDtypeStruct(
                    (b, cfg.ssm_conv - 1, cfg.d_inner), jnp.bfloat16),
                "h": jax.ShapeDtypeStruct(
                    (b, cfg.d_inner, cfg.ssm_state), jnp.float32)}
    raise ValueError(kind)


def _view_specs(cfg: ModelConfig, b: int, s: int) -> dict:
    """ShapeDtypeStructs of a packed cache's dense views: per layer group
    the attention entries' bf16 stores, once for each view."""
    groups = []
    for g in layer_groups(cfg):
        gdict = {f"e{j}": _entry_struct(cfg, None, _entry_kind(cfg, entry),
                                        b, s, False, None)
                 for j, entry in enumerate(g.entries) if entry[0] == "a"}
        groups.append(jax.tree.map(
            lambda x, r=g.repeats: jax.ShapeDtypeStruct((r, *x.shape),
                                                        x.dtype), gdict))
    return {"target": groups, "draft": groups}


def cache_specs(cfg: ModelConfig, cass: CassandraConfig | None,
                b: int, s_max: int, packed: bool) -> dict:
    """ShapeDtypeStruct pytree of the full cache (dry-run input specs).
    A packed cache keeps its dense views."""
    book = (jax.ShapeDtypeStruct((256,), jnp.uint8),
            jax.ShapeDtypeStruct((256,), jnp.uint8))

    def stack(tree, r):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((r, *x.shape), x.dtype), tree)

    cache: dict = {"dec": [],
                   "length": jax.ShapeDtypeStruct((b,), jnp.int32)}
    for g in layer_groups(cfg):
        gdict = {}
        for j, entry in enumerate(g.entries):
            kind = _entry_kind(cfg, entry)
            gdict[f"e{j}"] = _entry_struct(cfg, cass, kind, b, s_max,
                                           packed and kind != "ssm", book)
        cache["dec"].append(stack(gdict, g.repeats))
    if cfg.cross_attention:
        senc = cfg.frontend_tokens
        cache["cross"] = []
        for g in layer_groups(cfg):
            gdict = {}
            for j, entry in enumerate(g.entries):
                if entry[0] == "a":
                    gdict[f"e{j}"] = {
                        "ck": jax.ShapeDtypeStruct(
                            (b, senc, cfg.n_heads, cfg.hd), jnp.bfloat16),
                        "cv": jax.ShapeDtypeStruct(
                            (b, senc, cfg.n_heads, cfg.hd), jnp.bfloat16)}
            cache["cross"].append(stack(gdict, g.repeats))
    if packed:
        cache["book_exp_of_rank"] = book[0]
        cache["book_rank_of_exp"] = book[1]
        cache["view"] = _view_specs(cfg, b, s_max)
    return cache


def _install_book(cache: dict, codebook) -> dict:
    book = codebook or default_kv_codebook()
    # pad exp_of_rank to 256 so specs stay shape-stable
    eor = jnp.zeros(256, jnp.uint8).at[:book[0].shape[0]].set(book[0])
    cache["book_exp_of_rank"] = eor
    cache["book_rank_of_exp"] = book[1]
    return cache


def init_cache(cfg: ModelConfig, cass: CassandraConfig | None,
               b: int, s_max: int, packed: bool,
               codebook: tuple[jax.Array, jax.Array] | None = None) -> dict:
    """Allocate a zeroed cache (smoke/bench scale only)."""
    specs = cache_specs(cfg, cass, b, s_max, packed)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    if packed:
        cache = _install_book(cache, codebook)
    return cache


def paged_cache_specs(cfg: ModelConfig, cass: CassandraConfig | None,
                      b: int, num_blocks: int, block_size: int,
                      max_blocks: int, packed: bool,
                      views: bool = True) -> dict:
    """ShapeDtypeStruct pytree of a paged cache.

    Attention stores become block pools (R,NB,BS,…) shared by all rows;
    SSM state stays per-row (token-recurrent state has no token axis to
    page). ``block_table`` (B,MB) maps each row's logical blocks to pool
    blocks; ``length`` stays (B,). A packed pool keeps dense views
    (R,NB,BS,…) unless ``views`` is False, as on the paged-kernel path.
    """
    if cfg.cross_attention:
        raise NotImplementedError(
            "paged caches do not support cross-attention stores yet")
    book = (jax.ShapeDtypeStruct((256,), jnp.uint8),
            jax.ShapeDtypeStruct((256,), jnp.uint8))

    def stack(tree, r):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((r, *x.shape), x.dtype), tree)

    cache: dict = {
        "dec": [],
        "length": jax.ShapeDtypeStruct((b,), jnp.int32),
        "block_table": jax.ShapeDtypeStruct((b, max_blocks), jnp.int32),
    }
    for g in layer_groups(cfg):
        gdict = {}
        for j, entry in enumerate(g.entries):
            kind = _entry_kind(cfg, entry)
            if kind == "ssm":
                gdict[f"e{j}"] = _entry_struct(cfg, cass, kind, b, 0,
                                               False, book)
            else:
                # pool: "batch"=NB blocks, "seq"=BS slots per block
                gdict[f"e{j}"] = _entry_struct(cfg, cass, kind, num_blocks,
                                               block_size, packed, book)
        cache["dec"].append(stack(gdict, g.repeats))
    if packed:
        cache["book_exp_of_rank"] = book[0]
        cache["book_rank_of_exp"] = book[1]
        if views:
            cache["view"] = _view_specs(cfg, num_blocks, block_size)
    return cache


def init_paged_cache(cfg: ModelConfig, cass: CassandraConfig | None,
                     b: int, num_blocks: int, block_size: int,
                     max_blocks: int, packed: bool,
                     codebook: tuple[jax.Array, jax.Array] | None = None,
                     views: bool = True) -> dict:
    """Allocate a zeroed paged cache; all table entries start at the trash
    block (0)."""
    specs = paged_cache_specs(cfg, cass, b, num_blocks, block_size,
                              max_blocks, packed, views)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    if packed:
        cache = _install_book(cache, codebook)
    return cache
