"""Model assembly: init + forward in three modes, scan-over-layer-groups.

``forward_train``   — full-sequence causal (or enc-dec) pass; no cache.
``forward_prefill`` — full-sequence pass that *writes* the KV cache (packed
                      Cassandra encode inside the layer scan — the online
                      encoder of paper Fig. 8b) and returns last-position
                      logits.
``forward_decode``  — q new tokens (1 for autoregressive / draft, γ+1 for
                      verification) against the cache; returns per-layer
                      updates for the serving engine to commit (rollback on
                      rejection is a slice of the returned states).

All layer stacks run as ``lax.scan`` over stacked parameters so HLO size is
O(block-pattern), not O(depth) — 61–88-layer models compile on one CPU core
and the 512-device dry-run stays tractable.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_groups
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.layers import Runtime
from repro.serving import kvcache as KC

Params = dict


# ---------------------------------------------------------------------------
# Initialisation
# ---------------------------------------------------------------------------

def _dense_init(key, n_in, n_out, dtype, bias=False, std=None):
    std = std if std is not None else (n_in ** -0.5)
    p = {"w": (jax.random.normal(key, (n_in, n_out), jnp.float32)
               * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def _norm_init(cfg: ModelConfig, d):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.family == "audio":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def _init_gqa(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    hd = cfg.hd
    bias = cfg.qkv_bias or cfg.family == "audio"
    p = {
        "wq": _dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype, bias),
        "wk": _dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype, bias),
        "wv": _dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype, bias),
        "wo": _dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype,
                          cfg.family == "audio",
                          std=(cfg.n_heads * hd) ** -0.5
                          / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _init_mla(key, cfg: ModelConfig, dtype):
    """MLA weights. Without q-LoRA (``q_lora_rank`` 0) the query is one
    projection ``wq`` drawn from the key ``q_a`` would have had."""
    ks = jax.random.split(key, 5)
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = {"q_a": _dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
             "q_a_norm": {"scale": jnp.ones((cfg.q_lora_rank,),
                                            jnp.float32)},
             "q_b": _dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk,
                                dtype)}
    else:
        q = {"wq": _dense_init(ks[0], cfg.d_model, cfg.n_heads * qk, dtype)}
    return {
        **q,
        "kv_a": _dense_init(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_a_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), jnp.float32)},
        "kv_b": _dense_init(ks[3], cfg.kv_lora_rank,
                            cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim),
                            dtype),
        "wo": _dense_init(ks[4], cfg.n_heads * cfg.v_head_dim, cfg.d_model,
                          dtype, std=(cfg.n_heads * cfg.v_head_dim) ** -0.5
                          / (2 * cfg.n_layers) ** 0.5),
    }


def _init_ssm(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    di, n, dtr = cfg.d_inner, cfg.ssm_state, cfg.dt_r
    return {
        "in_proj": _dense_init(ks[0], cfg.d_model, 2 * di, dtype),
        "out_proj": _dense_init(ks[5], di, cfg.d_model, dtype,
                                std=di ** -0.5 / (2 * cfg.n_layers) ** 0.5),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di), jnp.float32)
                   * (cfg.ssm_conv ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _dense_init(ks[2], di, dtr + 2 * n, dtype),
        "dt_proj": _dense_init(ks[3], dtr, di, dtype, std=dtr ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
        "D": jnp.ones((di,), jnp.float32),
    }


def _init_mlp(key, cfg: ModelConfig, d_ff, dtype):
    ks = jax.random.split(key, 3)
    bias = cfg.family == "audio"
    p = {"w_up": _dense_init(ks[0], cfg.d_model, d_ff, dtype, bias),
         "w_down": _dense_init(ks[1], d_ff, cfg.d_model, dtype, bias,
                               std=d_ff ** -0.5 / (2 * cfg.n_layers) ** 0.5)}
    if cfg.ffn_act == "swiglu":
        p["w_gate"] = _dense_init(ks[2], cfg.d_model, d_ff, dtype)
    return p


def _init_moe(key, cfg: ModelConfig, dtype):
    """Router over all ``n_experts``; weights of the held experts only
    (``expert_first ..``), expert ``e`` drawn from ``fold_in(k, e)`` so it
    is the same tensor whichever share holds it."""
    k_router, k_shared, k_experts = jax.random.split(key, 3)
    ids = cfg.expert_first + jnp.arange(cfg.n_held)
    p = {
        "router": {"w": (jax.random.normal(
            k_router, (cfg.d_model, cfg.n_experts), jnp.float32) * 0.02)},
        "experts": jax.vmap(lambda e: _init_mlp(
            jax.random.fold_in(k_experts, e), cfg, cfg.expert_ff, dtype))(
                ids),
    }
    if cfg.n_shared_experts:
        p["shared"] = _init_mlp(k_shared, cfg, cfg.expert_ff
                                * cfg.n_shared_experts, dtype)
    return p


def _init_entry(key, cfg: ModelConfig, entry: str, cross: bool, dtype):
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": _norm_init(cfg, cfg.d_model)}
    if entry[0] == "a":
        p["attn"] = (_init_mla(ks[0], cfg, dtype) if cfg.mla
                     else _init_gqa(ks[0], cfg, dtype))
    else:
        p["ssm"] = _init_ssm(ks[0], cfg, dtype)
    if cross and entry[0] == "a":
        p["xattn"] = _init_gqa(ks[2], cfg, dtype)
        p["norm_x"] = _norm_init(cfg, cfg.d_model)
    if entry[1] == "m":
        p["ffn"] = _init_mlp(ks[1], cfg, cfg.d_ff, dtype)
        p["norm2"] = _norm_init(cfg, cfg.d_model)
    elif entry[1] == "M":
        p["moe"] = _init_moe(ks[1], cfg, dtype)
        p["norm2"] = _norm_init(cfg, cfg.d_model)
    return p


def _init_groups(key, cfg: ModelConfig, cross: bool, dtype):
    groups = []
    for g in layer_groups(cfg):
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, g.repeats)
        gdict = {}
        for j, entry in enumerate(g.entries):
            ekeys = jax.vmap(lambda k, j=j: jax.random.fold_in(k, j))(keys)
            gdict[f"e{j}"] = jax.vmap(
                lambda k, e=entry: _init_entry(k, cfg, e, cross, dtype)
            )(ekeys)
        groups.append(gdict)
    return groups


@partial(jax.jit, static_argnames=("cfg", "dtype"))
def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random parameters, built as one compiled program: run op by
    op, every leaf shape compiles programs of its own, which on a TPU
    takes about a minute for Qwen3-1.7B."""
    k_emb, k_dec, k_enc, k_head, k_mtp = jax.random.split(key, 5)
    params: Params = {
        "embed": {"table": (jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype)},
        "final_norm": _norm_init(cfg, cfg.d_model),
        "dec": _init_groups(k_dec, cfg, cfg.cross_attention, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                        dtype, std=0.02)
    if cfg.is_encdec:
        enc_cfg = cfg  # same dims
        params["enc"] = _init_groups(k_enc, enc_cfg, False, dtype)
        params["enc_norm"] = _norm_init(cfg, cfg.d_model)
    if cfg.family == "audio":
        params["pos_embed"] = {"table": (jax.random.normal(
            jax.random.fold_in(k_emb, 1),
            (cfg.max_wavelength_pos + 128, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype)}
    if cfg.mtp_depth > 0:
        params["mtp"] = {
            "norm_h": _norm_init(cfg, cfg.d_model),
            "norm_e": _norm_init(cfg, cfg.d_model),
            "proj": _dense_init(k_mtp, 2 * cfg.d_model, cfg.d_model, dtype),
            "block": _init_entry(jax.random.fold_in(k_mtp, 1), cfg,
                                 "am", False, dtype),
            "final_norm": _norm_init(cfg, cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _attn_entry(rt: Runtime, bp: dict, x, positions, *, causal, centry,
                scratch, length, scratch_len, book, s_max, ventry=None,
                table=None):
    """Attention sub-block in any mode. Returns (out, upd).

    ``ventry`` — optional dense view of the packed cache entry, decoded
    once per speculative cycle by ``materialize_cache_view``: the γ draft
    passes read its draft view and, for Cassandra-1, the verify pass its
    target view, so neither decodes the pool here. Without it the entry
    is decoded in place (``read_store``).
    ``table`` — paged caches only: (B,MB) block table; the cache entry (or
    its ventry) is a block pool decoded pool-wide, and the per-request
    prefix is assembled by ``kvcache.gather_block_leaf``. ``s_max`` is
    then the virtual per-request capacity MB*BS. When
    ``rt.attn_kernel != "off"`` the gather never happens: the
    paged-attention kernel walks the table in-kernel instead (and for
    packed GQA draft passes, runs the Cassandra decode in-kernel too).
    """
    cfg = rt.cfg
    cass = rt.cass
    view = "draft" if rt.view == "draft" else "target"
    if centry is None:                       # train / prefill full-seq
        if cfg.mla:
            out, kv = A.mla_attention(rt, bp["attn"], x, positions,
                                      causal=causal)
            return out, {"c": kv[0], "kr": kv[1]}
        out, kv = A.gqa_attention(rt, bp["attn"], x, positions, causal=causal)
        return out, {"k": kv[0], "v": kv[1]}

    if rt.attn_kernel != "off" and table is not None:
        return _attn_entry_paged(rt, bp, x, positions, centry=centry,
                                 scratch=scratch, length=length,
                                 scratch_len=scratch_len, book=book,
                                 ventry=ventry, table=table)

    # cached decode: assemble prefix = cache view ++ scratch
    if jnp.ndim(length) == 1:                # per-batch lengths (B,)
        smax_valid = jnp.arange(s_max)[None, :] < length[:, None]
    else:
        smax_valid = jnp.arange(s_max) < length
    def cat_valid(valid, g):
        gv = jnp.arange(g) < scratch_len
        if valid.ndim == 2:
            gv = jnp.broadcast_to(gv[None, :], (valid.shape[0], g))
        return jnp.concatenate([valid, gv], axis=-1)

    if cfg.mla:
        if ventry is not None:
            pc, pkr = ventry["c"], ventry["kr"]
        else:
            pc = KC.read_store(cass, centry["c"], cfg.kv_lora_rank, view,
                               book)
            pkr = KC.read_store(cass, centry["kr"], cfg.qk_rope_dim, view,
                                book)
        if table is not None:
            pc = KC.gather_block_leaf(pc, table)
            pkr = KC.gather_block_leaf(pkr, table)
        valid = smax_valid
        if scratch is not None:
            pc = jnp.concatenate([pc, scratch["c"].astype(pc.dtype)], axis=1)
            pkr = jnp.concatenate([pkr, scratch["kr"].astype(pkr.dtype)],
                                  axis=1)
            valid = cat_valid(valid, scratch["c"].shape[1])
        out, (nc, nkr) = A.mla_attention(rt, bp["attn"], x, positions,
                                         prefix_latent=(pc, pkr),
                                         prefix_valid=valid)
        return out, {"c": nc, "kr": nkr}
    if ventry is not None:
        pk, pv = ventry["k"], ventry["v"]
    else:
        pk = KC.read_store(cass, centry["k"], cfg.hd, view, book)
        pv = KC.read_store(cass, centry["v"], cfg.hd, view, book)
    if table is not None:
        pk = KC.gather_block_leaf(pk, table)
        pv = KC.gather_block_leaf(pv, table)
    valid = smax_valid
    if scratch is not None:
        pk = jnp.concatenate([pk, scratch["k"].astype(pk.dtype)], axis=1)
        pv = jnp.concatenate([pv, scratch["v"].astype(pv.dtype)], axis=1)
        valid = cat_valid(valid, scratch["k"].shape[1])
    out, (nk, nv) = A.gqa_attention(rt, bp["attn"], x, positions,
                                    prefix_kv=(pk, pv), prefix_valid=valid)
    return out, {"k": nk, "v": nv}


def _attn_entry_paged(rt: Runtime, bp: dict, x, positions, *, centry,
                      scratch, length, scratch_len, book, ventry, table):
    """Cached decode through kernels/paged_attention (attn_kernel knob).

    The pool stays in pool layout; the per-request prefix is never
    gathered. Packed GQA caches feed the draft pass their *spec leaves*
    directly — the Cassandra decode runs inside the kernel, so draft KV
    never exists densely in HBM. The verify pass (target view) and all
    MLA paths, packed latent pools included, read a dense pool
    (``ventry``/``read_store``) through the plain kernel variant.
    """
    cfg = rt.cfg
    cass = rt.cass
    view = "draft" if rt.view == "draft" else "target"
    if cfg.mla:
        if ventry is not None:
            pc, pkr = ventry["c"], ventry["kr"]
        else:
            pc = KC.read_store(cass, centry["c"], cfg.kv_lora_rank, view,
                               book)
            pkr = KC.read_store(cass, centry["kr"], cfg.qk_rope_dim, view,
                                book)
        out, (nc, nkr) = A.mla_attention_paged(
            rt, bp["attn"], x, positions, c_pool=pc, kr_pool=pkr,
            table=table, length=length, scratch=scratch,
            scratch_len=scratch_len)
        return out, {"c": nc, "kr": nkr}
    if ventry is None and KC.is_packed(centry["k"]) and view == "draft":
        kv_pools = ("packed", centry["k"]["spec"], centry["v"]["spec"],
                    book[0], cass.kv_keep(cfg.hd))
    else:
        if ventry is not None:
            pk, pv = ventry["k"], ventry["v"]
        else:
            pk = KC.read_store(cass, centry["k"], cfg.hd, view, book)
            pv = KC.read_store(cass, centry["v"], cfg.hd, view, book)
        kv_pools = ("plain", pk, pv)
    out, (nk, nv) = A.gqa_attention_paged(
        rt, bp["attn"], x, positions, kv_pools=kv_pools, table=table,
        length=length, scratch=scratch, scratch_len=scratch_len)
    return out, {"k": nk, "v": nv}


def _block(rt: Runtime, bp: dict, entry: str, x, positions, *, mode,
           causal=True, centry=None, scratch=None, length=None,
           scratch_len=None, book=None, s_max=0, cross_entry=None,
           enc_out=None, valid_len=None, ventry=None, table=None):
    """One transformer block. Returns (x, cache_update, moe_aux)."""
    cfg = rt.cfg
    upd: dict = {}
    h = L.norm(rt, bp["norm1"], x)
    if entry[0] == "a":
        out, kv_upd = _attn_entry(rt, bp, h, positions, causal=causal,
                                  centry=centry, scratch=scratch,
                                  length=length, scratch_len=scratch_len,
                                  book=book, s_max=s_max, ventry=ventry,
                                  table=table)
        if kv_upd is not None and mode in ("decode", "prefill"):
            upd = dict(kv_upd)
    else:
        state = None
        if mode == "decode":
            src = scratch if scratch is not None else centry
            state = (src["conv"], src["h"])
        out, new_state, extras = S.mamba(
            rt, bp["ssm"], h, state=state, valid_len=valid_len,
            with_states=(mode == "decode"))
        if mode in ("decode", "prefill"):
            upd = {"conv": new_state[0], "h": new_state[1]}
            if extras is not None:
                upd.update(extras)
    x = x + out

    if cross_entry is not None or (enc_out is not None and entry[0] == "a"):
        hx = L.norm(rt, bp["norm_x"], x)
        if enc_out is not None:           # train/prefill: project enc_out
            ck, cv = A.gqa_project_kv(rt, bp["xattn"], enc_out, None)
            if mode == "prefill":
                upd["ck"], upd["cv"] = ck, cv
        else:
            ck, cv = cross_entry["ck"], cross_entry["cv"]
        xo, _ = A.gqa_attention(rt, bp["xattn"], hx, None,
                                cross_kv=(ck, cv))
        x = x + xo

    aux = _aux0()
    if entry[1] == "m":
        h = L.norm(rt, bp["norm2"], x)
        x = x + F.mlp(rt, bp["ffn"], h)
    elif entry[1] == "M":
        h = L.norm(rt, bp["norm2"], x)
        out, maux = F.moe(rt, bp["moe"], h)
        x = x + out
        aux = {k: maux[k].astype(v.dtype) for k, v in aux.items()}
    x = rt.shard_act(x, ("batch", None, None))
    return x, upd, aux


def _aux0() -> dict:
    """A pass's expert-layer statistics, summed over its layers
    (``ffn.moe``'s ``aux``); zero where a layer has no experts."""
    return {"balance_loss": jnp.float32(0.0), "dropped": jnp.int32(0),
            "expert_tokens": jnp.int32(0)}


# ---------------------------------------------------------------------------
# Group scan driver
# ---------------------------------------------------------------------------

def _scan_groups(rt: Runtime, groups_params, entries_per_group, x, positions,
                 *, mode, causal=True, cache_groups=None, scratch_groups=None,
                 cross_groups=None, length=None, scratch_len=None, book=None,
                 s_max=0, enc_out=None, valid_len=None, view_groups=None,
                 table=None):
    """Run all layer groups; scan over repeats within each group."""
    aux0 = _aux0()
    updates_groups = []
    for gi, entries in enumerate(entries_per_group):
        gp = groups_params[gi]
        xs = [gp]
        if cache_groups is not None:
            xs.append(cache_groups[gi])
        if scratch_groups is not None:
            xs.append(scratch_groups[gi])
        if cross_groups is not None:
            xs.append(cross_groups[gi])
        if view_groups is not None:
            xs.append(view_groups[gi])

        def body(carry, sl, entries=entries, has_cache=cache_groups is not None,
                 has_scr=scratch_groups is not None,
                 has_cross=cross_groups is not None,
                 has_view=view_groups is not None):
            xx, aux = carry
            idx = 0
            bp = sl[idx]; idx += 1
            gcache = sl[idx] if has_cache else None
            idx += int(has_cache)
            gscr = sl[idx] if has_scr else None
            idx += int(has_scr)
            gcross = sl[idx] if has_cross else None
            idx += int(has_cross)
            gview = sl[idx] if has_view else None
            g_upd = {}
            for j, entry in enumerate(entries):
                ekey = f"e{j}"
                centry = gcache[ekey] if gcache is not None else None
                scr = gscr[ekey] if gscr is not None else None
                xen = (gcross or {}).get(ekey) if gcross is not None else None
                ven = (gview or {}).get(ekey) if gview is not None else None
                xx, upd, baux = _block(
                    rt, bp[ekey], entry, xx, positions, mode=mode,
                    causal=causal, centry=centry, scratch=scr, length=length,
                    scratch_len=scratch_len, book=book, s_max=s_max,
                    cross_entry=xen, enc_out=enc_out, valid_len=valid_len,
                    ventry=ven, table=table)
                if upd:
                    g_upd[ekey] = upd
                aux = jax.tree.map(jnp.add, aux, baux)
            return (xx, aux), g_upd

        if rt.remat:
            if rt.remat_policy == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            else:
                body = jax.checkpoint(body)
        if rt.unroll:
            # python loop (HLO grows with depth — roofline cost extraction;
            # lax.scan bodies are counted once by XLA cost analysis)
            repeats = jax.tree.leaves(xs[0])[0].shape[0]
            carry, ys = (x, aux0), []
            for r in range(repeats):
                carry, y = body(carry, jax.tree.map(lambda a: a[r],
                                                    tuple(xs)))
                ys.append(y)
            (x, aux0) = carry
            g_updates = (jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
                         if ys and jax.tree.leaves(ys[0]) else ys[0]
                         if ys else {})
        else:
            (x, aux0), g_updates = jax.lax.scan(body, (x, aux0), tuple(xs))
        updates_groups.append(g_updates)
    return x, aux0, updates_groups


def _entries(cfg: ModelConfig):
    return [g.entries for g in layer_groups(cfg)]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed_inputs(rt: Runtime, params, tokens, patch_embeds=None,
                  positions=None):
    cfg = rt.cfg
    x = L.embed(params["embed"], tokens)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    if cfg.family == "audio":
        pos = positions if positions is not None else jnp.arange(x.shape[1])
        x = x + jnp.take(params["pos_embed"]["table"], pos, axis=0
                         ).astype(x.dtype)
    return x


def _rope_positions(cfg: ModelConfig, x, offset=0):
    if cfg.family == "audio":
        return None                        # learned positions, no rope
    return offset + jnp.arange(x.shape[1])


def _run_encoder(rt: Runtime, params, frame_embeds):
    cfg = rt.cfg
    x = frame_embeds.astype(jnp.bfloat16)
    x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model
                                   ).astype(x.dtype)[None]
    x, _, _ = _scan_groups(rt, params["enc"], _entries(cfg), x, None,
                           mode="train", causal=cfg.causal_encoder)
    return L.norm(rt, params["enc_norm"], x)


# ---------------------------------------------------------------------------
# Public forwards
# ---------------------------------------------------------------------------

def forward_train(rt: Runtime, params: Params, batch: dict,
                  return_hidden: bool = False):
    """Full-sequence pass. Returns (logits|hidden, aux)."""
    cfg = rt.cfg
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(rt, params, batch["frame_embeds"])
    x = _embed_inputs(rt, params, batch["tokens"],
                      batch.get("patch_embeds"))
    x = rt.shard_act(x, ("batch", None, None))
    positions = _rope_positions(cfg, x)
    x, aux, _ = _scan_groups(rt, params["dec"], _entries(cfg), x, positions,
                             mode="train", enc_out=enc_out)
    x = L.norm(rt, params["final_norm"], x)
    aux = dict(aux)
    if cfg.mtp_depth > 0:
        aux["mtp_hidden"] = _mtp_hidden(rt, params, x, batch["tokens"])
    if return_hidden:
        return x, aux
    return L.unembed(rt, params, x), aux


def _mtp_hidden(rt: Runtime, params, h, tokens):
    """Deepseek MTP: hidden for predicting t+2 from (h_t, emb(t+1))."""
    cfg = rt.cfg
    mp = params["mtp"]
    h_in = L.norm(rt, mp["norm_h"], h[:, :-1])
    e_in = L.norm(rt, mp["norm_e"], L.embed(params["embed"], tokens[:, 1:]))
    z = L.dense(rt, mp["proj"], jnp.concatenate([h_in, e_in], axis=-1))
    positions = _rope_positions(cfg, z)
    z, _, _ = _block(rt, mp["block"], "am", z, positions, mode="train")
    return L.norm(rt, mp["final_norm"], z)


def forward_prefill(rt: Runtime, params: Params, batch: dict, cache: dict):
    """Process the prompt, write the cache. Returns (last_logits, cache)."""
    cfg = rt.cfg
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(rt, params, batch["frame_embeds"])
    x = _embed_inputs(rt, params, batch["tokens"],
                      batch.get("patch_embeds"))
    x = rt.shard_act(x, ("batch", None, None))
    s = x.shape[1]
    positions = _rope_positions(cfg, x)
    book = KC.cache_codebook(cache)
    x, aux, upd = _scan_groups(rt, params["dec"], _entries(cfg), x, positions,
                               mode="prefill", enc_out=enc_out)
    # commit: encode K/V (packed path) and place at offset 0
    cache = _commit_prefill(rt, cache, upd, s, book)
    x = L.norm(rt, params["final_norm"], x[:, -1:])
    return L.unembed(rt, params, x), cache


def _commit_prefill(rt: Runtime, cache, updates_groups, s, book):
    cfg = rt.cfg
    cass = rt.cass
    if KC.is_paged(cache):
        raise NotImplementedError(
            "paged caches are filled by chunked prefill "
            "(engine.chunk_prefill_step), not forward_prefill")
    packed = book is not None
    new_dec = []
    new_cross = [] if "cross" in cache else None
    for gi, g_upd in enumerate(updates_groups):
        gcache = cache["dec"][gi]
        gout = dict(gcache)
        gx = dict(cache["cross"][gi]) if new_cross is not None else None

        def commit_entry(centry, upd):
            out = dict(centry)
            if "k" in upd:      # gqa
                for name in ("k", "v"):
                    enc = (KC.encode_store(cass, upd[name], cfg.hd, book)
                           if packed else upd[name])
                    out[name] = KC.append_store(centry[name], enc, 0)
            elif "c" in upd:    # mla
                enc_c = (KC.encode_store(cass, upd["c"], cfg.kv_lora_rank,
                                         book) if packed else upd["c"])
                enc_r = (KC.encode_store(cass, upd["kr"], cfg.qk_rope_dim,
                                         book) if packed else upd["kr"])
                out["c"] = KC.append_store(centry["c"], enc_c, 0)
                out["kr"] = KC.append_store(centry["kr"], enc_r, 0)
            elif "conv" in upd:  # ssm
                out["conv"] = upd["conv"].astype(centry["conv"].dtype)
                out["h"] = upd["h"]
            return out

        for ekey, upd in g_upd.items():
            core = {k: v for k, v in upd.items() if k not in ("ck", "cv")}
            if core:
                gout[ekey] = jax.vmap(commit_entry)(gcache[ekey], core)
            if "ck" in upd and gx is not None:
                gx[ekey] = {"ck": upd["ck"].astype(jnp.bfloat16),
                            "cv": upd["cv"].astype(jnp.bfloat16)}
        new_dec.append(gout)
        if new_cross is not None:
            new_cross.append(gx)
    out = dict(cache)
    out["dec"] = new_dec
    if new_cross is not None:
        out["cross"] = new_cross
    out["length"] = jnp.full_like(cache["length"], s)
    return out


def _kernel_reads_pool(rt: Runtime, cache: dict) -> bool:
    """The paged-attention kernel path: draft passes decode packed KV
    inside the kernel and verify decodes per layer, so no dense pool view
    is built for them."""
    return rt.attn_kernel != "off" and KC.is_paged(cache)


def materialize_cache_view(rt: Runtime, cache: dict
                           ) -> tuple[list | None, list | None]:
    """Dense views of a packed cache for one speculative cycle:
    ``(draft, target)``, each a list over layer groups of dense stores
    (``forward_decode``'s ``cache_view``), or None where the passes read
    the cache themselves.

    One decode of the pool per cycle: for Cassandra-1 each layer's
    target view is decoded once (``read_store(view="target")``) and the
    draft view is derived from it elementwise
    (``kvcache.draft_from_target``, bit-exact); the γ draft passes read
    the draft view and the verify pass the target view. Cassandra-2's
    draft is not a function of its container-exact target, so its draft
    view is decoded apart and verify decodes the target per layer
    itself (target None). Layers are decoded one at a time (``lax.map``
    over each group's repeats), so a decode has the shapes and
    temporaries of one layer's. Plain caches, and the paged-kernel path
    (``attn_kernel != "off"``), get ``(None, None)``.
    ``kv_pool_decodes`` counts the pool decodes each case leaves in a
    cycle.
    """
    cfg, cass = rt.cfg, rt.cass
    book = KC.cache_codebook(cache)
    if book is None or _kernel_reads_pool(rt, cache):
        return None, None
    dims = ({"c": cfg.kv_lora_rank, "kr": cfg.qk_rope_dim} if cfg.mla
            else {"k": cfg.hd, "v": cfg.hd})
    shared = cass.variant == 1

    def layer(entries):
        draft, target = {}, {}
        for ekey, centry in entries.items():
            draft[ekey], target[ekey] = {}, {}
            for nm, d in dims.items():
                if shared:
                    t = KC.read_store(cass, centry[nm], d, "target", book)
                    target[ekey][nm] = t
                    draft[ekey][nm] = KC.draft_from_target(
                        cass, centry[nm]["spec"], t, d)
                else:
                    draft[ekey][nm] = KC.read_store(cass, centry[nm], d,
                                                    "draft", book)
        return draft, target

    drafts, targets = [], []
    for gi, g in enumerate(layer_groups(cfg)):
        attn = {f"e{j}": cache["dec"][gi][f"e{j}"]
                for j, entry in enumerate(g.entries) if entry[0] == "a"}
        draft, target = jax.lax.map(layer, attn) if attn else ({}, {})
        drafts.append(draft)
        targets.append(target)
    return drafts, (targets if shared else None)


def kv_pool_decodes(rt: Runtime, cache: dict, drafts: bool = True) -> int:
    """Full-pool decodes of the packed KV in one serving step: 0 for a
    plain cache; 1 when the step does not draft, when one target decode
    feeds both views (Cassandra-1) or when the paged kernel decodes the
    draft passes' KV in-kernel; 2 when the draft view is decoded apart
    from verify's target (Cassandra-2). See ``materialize_cache_view``."""
    if KC.cache_codebook(cache) is None:
        return 0
    if not drafts or rt.cass.variant == 1 or _kernel_reads_pool(rt, cache):
        return 1
    return 2


def forward_decode(rt: Runtime, params: Params, tokens: jax.Array,
                   cache: dict, scratch: dict | None = None,
                   scratch_len=None, cache_view: list | None = None):
    """q new tokens against the cache. Returns (logits, updates, aux).

    ``updates`` mirrors the cache groups: per attn entry the new tokens'
    K/V (B,q,…), per ssm entry {"h_all", "conv_win", "conv", "h"} for
    commit/rollback by the serving engine. ``aux`` sums the expert
    layers' statistics over the pass (``ffn.moe``). ``cache_view`` optionally
    provides dense stores decoded beforehand (one view of
    ``materialize_cache_view``); attention entries then read them and
    decode nothing.

    Rows are fully independent here — per-row ``length`` offsets the
    positions, the attention prefix mask is per-row, and the slot/paged
    prefixes are per-row regions/tables — so one pass can carry rows at
    *different serving phases* (a prompt chunk landing at length L_a
    beside a γ+1 verify run at L_b beside an idle row): the fused
    mixed-role serving step (``engine.unified_step``) is just this pass
    with per-row token selection, and a row's outputs are bit-identical
    whatever the other rows carry (the expert layer is dropless, so it
    couples no rows either).
    """
    cfg = rt.cfg
    length = cache["length"]
    slen = scratch_len if scratch_len is not None else jnp.int32(0)
    q = tokens.shape[1]
    if jnp.ndim(length) == 1:                    # per-batch lengths
        pos = length[:, None] + slen + jnp.arange(q)[None, :]
    else:
        pos = length + slen + jnp.arange(q)
    x = L.embed(params["embed"], tokens)
    if cfg.family == "audio":
        x = x + jnp.take(params["pos_embed"]["table"], pos, axis=0
                         ).astype(x.dtype)
        positions = None
    else:
        positions = pos
    book = KC.cache_codebook(cache)
    s_max = _cache_s_max(cfg, cache)
    x, aux, upd = _scan_groups(
        rt, params["dec"], _entries(cfg), x, positions, mode="decode",
        cache_groups=cache["dec"], scratch_groups=scratch,
        cross_groups=cache.get("cross"), length=length, scratch_len=slen,
        book=book, s_max=s_max, view_groups=cache_view,
        table=cache.get("block_table"))
    x = L.norm(rt, params["final_norm"], x)
    return L.unembed(rt, params, x), upd, aux


def _cache_s_max(cfg: ModelConfig, cache: dict) -> int:
    """Virtual per-request token capacity of the cache (static).

    Slot layout: the S axis of the stores. Paged layout: the stores hold
    (R,NB,BS,…) pool blocks, so capacity is table-width MB × BS.
    """
    mb = cache["block_table"].shape[1] if KC.is_paged(cache) else 1
    for g in cache["dec"]:
        for e in g.values():
            if "k" in e:
                leaf = jax.tree_util.tree_leaves(e["k"])[0]
                return mb * leaf.shape[2]       # (R,B,S,…) | (R,NB,BS,…)
            if "c" in e:
                leaf = jax.tree_util.tree_leaves(e["c"])[0]
                return mb * leaf.shape[2]
    return 0


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(rt: Runtime, params: Params, batch: dict,
            z_loss: float = 1e-4, balance_coef: float = 1e-2,
            mtp_coef: float = 0.3, vocab_chunk: int = 0):
    """Causal LM loss (+ optional MoE balance and MTP terms).

    The unembed+CE is fused and (optionally) computed in sequence chunks so
    full fp32 logits are never materialised (big-vocab memory).
    """
    hidden, aux = forward_train(rt, params, batch, return_hidden=True)
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:      # vlm: patches prepended
        hidden = hidden[:, hidden.shape[1] - labels.shape[1]:]
    ce, z = _chunked_ce(rt, params, hidden[:, :-1], labels[:, 1:])
    loss = ce + z_loss * z
    metrics = {"ce": ce, "z": z}
    if cfg_has_moe(rt.cfg):
        loss = loss + balance_coef * aux["balance_loss"]
        metrics["balance"] = aux["balance_loss"]
        metrics["dropped"] = aux["dropped"]
    if rt.cfg.mtp_depth > 0:
        mtp_h = aux["mtp_hidden"]                # predicts t+2 at index t
        mce, _ = _chunked_ce(rt, params, mtp_h[:, :-1], labels[:, 2:])
        loss = loss + mtp_coef * mce
        metrics["mtp_ce"] = mce
    metrics["loss"] = loss
    return loss, metrics


def cfg_has_moe(cfg: ModelConfig) -> bool:
    return any(e[1] == "M" for e in cfg.block_pattern)


def _chunked_ce(rt: Runtime, params, hidden, labels, chunk: int = 512):
    """Fused unembed + cross-entropy over sequence chunks (fp32).

    The chunk body is rematerialised in the backward pass (checkpoint) so
    the fp32 logits of a chunk are never part of the residual set — the
    big-vocab memory killer. Logits stay vocab-sharded over ``model``.
    """
    b, s, d = hidden.shape
    ch = min(chunk, s)
    while s % ch:                                # largest divisor <= chunk
        ch -= 1
    nc = s // ch
    hc = jnp.moveaxis(hidden.reshape(b, nc, ch, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, ch), 1, 0)

    @jax.checkpoint
    def step(carry, xs):
        h, lab = xs
        logits = L.unembed(rt, params, h)        # (B,ch,V) fp32
        logits = rt.shard_act(logits, ("batch", None, "ffn"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        ce_sum, z_sum = carry
        return (ce_sum + jnp.sum(lse - gold), z_sum + jnp.sum(lse ** 2)), None

    carry = (jnp.float32(0.0), jnp.float32(0.0))
    if rt.unroll:                                # roofline cost extraction
        for i in range(nc):
            carry, _ = step(carry, (hc[i], lc[i]))
        ce_sum, z_sum = carry
    else:
        (ce_sum, z_sum), _ = jax.lax.scan(step, carry, (hc, lc))
    n = b * s
    return ce_sum / n, z_sum / n
