"""Offline model-to-Cassandra-format transformation (paper Fig. 4a).

``format_params`` walks a parameter pytree and replaces every large matmul
weight with its packed ``{"spec", "verif"}`` partition. Small / accuracy-
critical leaves stay full precision: embeddings (row lookups — no bandwidth
win), MoE routers (paper keeps them exact), norms, biases, convs, SSM
A_log/D/dt. Stacked (scan) weights of shape (R, in, out) are packed per
layer (``lax.map`` over the stack).

Wanda calibration: ``Calibrator`` records per-input-channel activation L2
norms during an (unjitted) calibration forward; ``format_params`` consumes
its stats keyed by the layer path. Without calibration the score falls back
to |W| (magnitude pruning) — acceptance is a little lower but nothing
breaks (measured in benchmarks/acceptance.py).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import format as fmt
from repro.core.format import CassandraConfig

# parent-dict keys whose "w" leaves must stay full precision
_SKIP_PARENTS = {"router"}
# leaf names that are never packed
_SKIP_LEAVES = {"conv_w", "conv_b", "A_log", "D", "dt_bias", "table",
                "scale", "bias", "b"}


class Calibrator:
    """Collects per-path activation L2 norms (Wanda's ||act||_2).

    Used as ``Runtime(collector=Calibrator())`` on an **unjitted** forward
    over ~128 calibration samples; traced observations (e.g. inside vmap)
    are skipped silently.
    """

    def __init__(self):
        self.sq_sums: dict[str, Any] = {}
        self.counts: dict[str, int] = {}

    def observe(self, path: str, x) -> None:
        if isinstance(x, jax.core.Tracer):
            return
        flat = jnp.reshape(x, (-1, x.shape[-1])).astype(jnp.float32)
        sq = jnp.sum(jnp.square(flat), axis=0)
        if path in self.sq_sums and self.sq_sums[path].shape == sq.shape:
            self.sq_sums[path] = self.sq_sums[path] + sq
        else:
            self.sq_sums[path] = sq
        self.counts[path] = self.counts.get(path, 0) + flat.shape[0]

    def act_norm(self, path: str):
        if path not in self.sq_sums:
            return None
        return jnp.sqrt(self.sq_sums[path])


def _should_pack(parent_key: str, w: jax.Array) -> bool:
    if parent_key in _SKIP_PARENTS:
        return False
    if w.ndim not in (2, 3):
        return False
    n_in, n_out = w.shape[-2], w.shape[-1]
    if n_in < 64 or n_out < 8:
        return False
    return n_in % 32 == 0


def _format_layer(w: jax.Array, act_norm, cass: CassandraConfig):
    """Format one (in, out) weight (blocked along ``in``)."""
    wt = w.T
    if act_norm is None:
        scores = jnp.abs(wt.astype(jnp.float32))
    else:
        from repro.core import pruning
        scores = pruning.wanda_scores(w, act_norm).T
    block = cass.weight_block(w.shape[0])
    keep = cass.weight_keep(block)
    return fmt.format_tensor(wt, scores, cass, block, keep,
                             cass.mx_group, cass.weight_trunc)


@partial(jax.jit, static_argnames=("cass",))
def _format_stack(w: jax.Array, act_norm, cass: CassandraConfig):
    """Format a stacked (R, in, out) weight one layer at a time.

    ``lax.map`` keeps one layer's formatting temporaries live; a ``vmap``
    over the stack holds all R of them at once, which for a 28-36 layer
    FFN stack exceeds one accelerator's memory."""
    if act_norm is None:
        return jax.lax.map(lambda wl: _format_layer(wl, None, cass), w)
    return jax.lax.map(lambda xs: _format_layer(*xs, cass), (w, act_norm))


def _pack_weight(w: jax.Array, act_norm, cass: CassandraConfig, trim: bool):
    if w.ndim == 2:
        spec, verif = _format_layer(w, act_norm, cass)
    else:
        spec, verif = _format_stack(w, act_norm, cass)
    if trim:  # host sync — concrete values only (offline formatting)
        spec, verif = fmt._trim_lossless(spec, verif, cass.variant)
    return {"spec": spec, "verif": verif}


def format_params(params: Any, cass: CassandraConfig,
                  calib: Calibrator | None = None,
                  trim: bool = True) -> Any:
    """Replace packable weights with Cassandra partitions (see module doc).

    ``trim=False`` keeps the (redundant) correction nibbles so the function
    is trace-safe — used by ``jax.eval_shape`` in the dry-run.
    """

    def walk(node, parent_key: str, path: str):
        if isinstance(node, dict):
            if "w" in node and not isinstance(node["w"], dict):
                w = node["w"]
                if _should_pack(parent_key, w):
                    an = calib.act_norm(path) if calib is not None else None
                    if an is not None and an.shape[-1] != w.shape[-2]:
                        an = None
                    out = dict(node)
                    out["w"] = _pack_weight(w, an, cass, trim)
                    return out
                return node
            return {k: walk(v, k, f"{path}.{k}" if path else k)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, parent_key, f"{path}[{i}]")
                    for i, v in enumerate(node)]
        return node

    return walk(params, "", "")


@partial(jax.jit, static_argnames=("cass", "shape"))
def _decode_views(spec: dict, verif: dict, cass: CassandraConfig,
                  shape: tuple[int, int]) -> dict:
    def one(sv):
        return {"draft": fmt.draft_weight(sv[0], cass, shape),
                "target": fmt.target_weight(sv[0], sv[1], cass, shape)}

    if spec["bitmap"].ndim == 3:                  # one (in, out) weight
        return one((spec, verif))
    return jax.lax.map(one, (spec, verif))        # (R, ...) stack


def resolve_views(params: Any, cass: CassandraConfig) -> Any:
    """Decode every packed weight once into its dense draft and target views.

    Weights do not change while serving, but ``layers.dense`` would
    otherwise rebuild the whole model's dense weights from the packed
    streams on each of the γ draft passes and on the verify pass — a
    decode far dearer than the matmuls it feeds on a TPU. The price is two
    dense bf16 copies of the packed weights. Each ``{"spec", "verif"}``
    leaf becomes ``{"draft": w, "target": w}`` with ``w`` of the weight's
    (..., in, out) shape; ``layers.resolve_weight`` picks the view.
    """
    def walk(node):
        if isinstance(node, dict):
            if "spec" in node and "verif" in node:
                bitmap = node["spec"]["bitmap"]   # (..., out, NB, block//32)
                shape = (bitmap.shape[-2] * bitmap.shape[-1] * 32,
                         bitmap.shape[-3])
                return _decode_views(node["spec"], node["verif"], cass,
                                     shape)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(params)


def params_nbytes(params: Any) -> dict[str, int]:
    """Byte accounting: plain vs spec vs verif (Fig. 14 inputs)."""
    acc = {"plain": 0, "spec": 0, "verif": 0}

    def walk(node, zone):
        if isinstance(node, dict):
            if "spec" in node and "verif" in node:
                walk(node["spec"], "spec")
                walk(node["verif"], "verif")
                return
            for v in node.values():
                walk(v, zone)
        elif isinstance(node, list):
            for v in node:
                walk(v, zone)
        elif hasattr(node, "dtype"):
            acc[zone] += node.size * jnp.dtype(node.dtype).itemsize

    walk(params, "plain")
    return acc
