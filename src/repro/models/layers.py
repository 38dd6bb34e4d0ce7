"""Primitive layers shared by every architecture.

The central abstraction is :func:`dense`: every matmul weight in the model is
either a plain bf16 array **or** a Cassandra-packed ``{"spec": …, "verif": …}``
pytree. The packed form is resolved per the runtime ``view``:

* ``plain``  — weight is a plain array (training / bf16-baseline serving)
* ``draft``  — reconstruct the zero-padded draft weight from speculation data
  only (models the draft pass reading only the compressed stream)
* ``target`` — reconstruct the exact weight from speculation + verification
  data (bit-exact for Cassandra-1)

On TPU the reconstruction is the fused Pallas decode-matmul
(:mod:`repro.kernels.draft_matmul`); the jnp path here is its oracle and the
backend the 512-device dry-run lowers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.format import (
    CassandraConfig,
    draft_weight,
    target_weight,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Static per-call context threaded through all layer functions."""
    cfg: ModelConfig
    cass: CassandraConfig | None = None
    view: str = "plain"                 # plain | draft | target
    shard: Callable | None = None       # logical activation-sharding hook
    collector: Any = None               # calibration stats collector (non-jit)
    kernels: str = "jnp"                # jnp | interpret | pallas
    attn_kernel: str = "off"            # off | jnp | interpret | pallas —
    # paged-attention decode kernel (kernels/paged_attention): "off" keeps
    # the gather_block_leaf path; "jnp" the gather-free scan reference;
    # "interpret"/"pallas" the Pallas kernel (interpret = CPU CI).
    attn_chunk_q: int = 1024
    attn_chunk_k: int = 1024
    ssm_chunk: int = 64
    remat: bool = False                 # checkpoint each scanned layer block
    remat_policy: str = "full"          # full | dots (save matmul outputs)
    unroll: bool = False                # python-loop layer groups (roofline)
    moe_capacity_factor: float = 1.25   # per-expert slots vs perfect balance

    def shard_act(self, x: jax.Array, spec: tuple) -> jax.Array:
        if self.shard is None:
            return x
        return self.shard(x, spec)


def is_packed(w) -> bool:
    return isinstance(w, dict) and "spec" in w


def packed_shape(w: dict) -> tuple[int, int]:
    """Recover the (in, out) shape of a packed weight from its bitmap."""
    bitmap = w["spec"]["bitmap"]          # (out, NB, block//32)
    out, nb, bw = bitmap.shape[-3:]
    return nb * bw * 32, out


def resolve_weight(rt: Runtime, w, path: str = "") -> jax.Array:
    """Materialise a weight leaf per the runtime view."""
    if isinstance(w, dict) and "target" in w:   # packing.resolve_views
        if rt.view not in ("draft", "target"):
            raise ValueError(f"decoded weight {path} under view={rt.view!r}")
        return w[rt.view]
    if not is_packed(w):
        return w
    if rt.cass is None:
        raise ValueError(f"packed weight {path} but no CassandraConfig")
    shape = packed_shape(w)
    if rt.view == "draft":
        if rt.kernels != "jnp":
            from repro.kernels import ops as kops
            return kops.draft_weight_dense(w["spec"], rt.cass, shape,
                                           interpret=rt.kernels == "interpret")
        return draft_weight(w["spec"], rt.cass, shape)
    if rt.view == "target":
        return target_weight(w["spec"], w["verif"], rt.cass, shape)
    raise ValueError(f"packed weight {path} under view={rt.view!r}")


def dense(rt: Runtime, p: dict, x: jax.Array, path: str = "") -> jax.Array:
    """x @ W (+ b). ``p`` = {"w": array-or-packed, optional "b"}."""
    if rt.collector is not None:
        rt.collector.observe(path, x)
    w = p["w"]
    if is_packed(w) and rt.view == "draft" and rt.kernels != "jnp":
        from repro.kernels import ops as kops
        y = kops.draft_matmul(x, w["spec"], rt.cass, packed_shape(w),
                              interpret=rt.kernels == "interpret")
    else:
        wm = resolve_weight(rt, w, path)
        y = jnp.dot(x, wm.astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    if "bias" in p:
        return layernorm(p, x, rt.cfg.norm_eps)
    return rmsnorm(p, x, rt.cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B,S,H,D) with positions (B,S) or (S,). Half-split convention."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                         # (D/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B,S,D/2)
    cos = jnp.cos(ang)[..., None, :]                     # (B,S,1,D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(rt: Runtime, params: dict, x: jax.Array) -> jax.Array:
    """Final projection to vocab logits (fp32)."""
    if rt.cfg.tie_embeddings:
        w = params["embed"]["table"].T
        if rt.collector is not None:
            rt.collector.observe("lm_head", x)
        return jnp.dot(x, w.astype(x.dtype)).astype(jnp.float32)
    return dense(rt, params["lm_head"], x, "lm_head").astype(jnp.float32)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal table (n, d)."""
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(d // 2, dtype=jnp.float32)
                  / max(d // 2 - 1, 1))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def act_fn(name: str):
    if name == "swiglu":  # handled by ffn (gated)
        return jax.nn.silu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name}")
