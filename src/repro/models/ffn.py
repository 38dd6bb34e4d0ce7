"""Feed-forward blocks: dense MLP (SwiGLU / squared-ReLU / GELU) and a
dropless top-k mixture of experts that computes the experts this chip
holds.

The expert layer routes every token over all ``n_experts`` and computes
the part of the result that its held experts (``cfg.expert_first ..``,
``cfg.n_held`` of them) give: the chip's share under expert parallelism,
whose absent experts' part is left out. No token is ever dropped: every
held expert runs over every token of the pass, and a token's combine
weight for an expert it did not pick is 0. At serving widths (a few
tokens a row a pass) reading each held expert's weights once is the cost
whatever the routing, so this costs what a capacity of one slot per
token would, with no sort, gather or scatter; and since each token's
output depends on that token alone, rows of one batch stay independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import Runtime


def mlp(rt: Runtime, p: dict, x: jax.Array, path: str = "ffn") -> jax.Array:
    """Dense FFN. SwiGLU has a gate; relu2/gelu are single-branch."""
    if rt.cfg.ffn_act == "swiglu" or "w_gate" in p:
        g = L.dense(rt, p["w_gate"], x, f"{path}.gate")
        u = L.dense(rt, p["w_up"], x, f"{path}.up")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = L.dense(rt, p["w_up"], x, f"{path}.up")
        h = L.act_fn(rt.cfg.ffn_act)(h.astype(jnp.float32)).astype(x.dtype)
    h = rt.shard_act(h, ("batch", None, "ffn"))
    return L.dense(rt, p["w_down"], h, f"{path}.down")


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def route(rt: Runtime, p: dict, xt: jax.Array
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router over all ``n_experts``, in float32 (router weights stay full
    precision): (probs (T,E), top_p (T,k), top_e (T,k)). ``top_p`` holds
    the top-k probabilities, renormalised over the k when
    ``norm_topk_prob``."""
    cfg = rt.cfg
    rlogits = jnp.dot(xt.astype(jnp.float32),
                      p["router"]["w"].astype(jnp.float32))      # (T,E)
    rprobs = jax.nn.softmax(rlogits, axis=-1)
    top_p, top_e = jax.lax.top_k(rprobs, cfg.n_experts_per_tok)  # (T,k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return rprobs, top_p, top_e


def moe(rt: Runtime, p: dict, x: jax.Array) -> tuple[jax.Array, dict]:
    """Dropless top-k MoE over x (B,S,d), the held experts' part (module
    doc). Returns (out, aux).

    p = {"router": {"w"} (d, n_experts), "experts": {w_gate/w_up/w_down
    stacked (n_held,…)}, optional "shared": dense-FFN params (deepseek's
    shared experts, which every chip computes)}. ``aux``: ``balance_loss``
    (switch-style, over all experts), ``expert_tokens`` (top-k assignments
    that land on a held expert) and ``dropped`` (assignments to a held
    expert left uncomputed: none, by construction).
    """
    cfg = rt.cfg
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope("cass.moe"):
        rprobs, top_p, top_e = route(rt, p, xt)
        # each held expert's combine weight per token, 0 where not picked
        held = cfg.expert_first + jnp.arange(cfg.n_held)
        hit = top_e[:, :, None] == held[None, None, :]            # (T,k,H)
        w = jnp.sum(jnp.where(hit, top_p[..., None], 0.0), axis=1)
        ys = jax.vmap(lambda pw: mlp(rt, pw, xt, "moe.expert"))(
            p["experts"])                                         # (H,T,d)
        ys = rt.shard_act(ys, ("experts", None, None))
        out = jnp.sum(ys.astype(jnp.float32) * w.T[..., None], axis=0)
        out = out.astype(x.dtype)
        if "shared" in p:
            out = out + mlp(rt, p["shared"], xt, "moe.shared")
        load = jnp.bincount(top_e.reshape(-1), length=e)
        aux = {
            "dropped": jnp.int32(0),
            "expert_tokens": jnp.sum(hit, dtype=jnp.int32),
            "balance_loss": jnp.sum(jnp.mean(rprobs, axis=0) * load
                                    / jnp.maximum(t * k, 1)) * e,
        }
    return out.reshape(b, s, d), aux


def moe_reference(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    """Oracle: a loop over the held experts, each weighted by the
    probability of the tokens that picked it (tests only)."""
    cfg = rt.cfg
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    _, top_p, top_e = route(rt, p, xt)
    out = jnp.zeros((t, d), jnp.float32)
    for i in range(cfg.n_held):
        pw = jax.tree.map(lambda a: a[i], p["experts"])
        y = mlp(rt, pw, xt).astype(jnp.float32)
        wgt = jnp.sum(jnp.where(top_e == cfg.expert_first + i, top_p, 0.0),
                      axis=-1)
        out = out + y * wgt[:, None]
    out = out.astype(x.dtype)
    if "shared" in p:
        out = out + mlp(rt, p["shared"], xt)
    return out.reshape(b, s, d)
