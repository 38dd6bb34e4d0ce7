"""Offline model-to-Cassandra-format transformation (paper Fig. 4a).

``format_params`` walks a parameter pytree and replaces every large matmul
weight with its packed ``{"spec", "verif"}`` partition. Small / accuracy-
critical leaves stay full precision: embeddings (row lookups — no bandwidth
win), MoE routers (paper keeps them exact), norms, biases, convs, SSM
A_log/D/dt. Stacked (scan) weights of shape (R, in, out), and stacked
expert weights (R, E, in, out), are packed one (in, out) matrix at a time
(``lax.map`` over the flattened stack). A single (in, out) weight of more
than ``_BLOCK_ELEMS`` values, such as an untied LM head over 10^5 ids, is
formatted and decoded a block of output columns at a time, into the same
bits as whole, so that one block's temporaries are live.

Wanda calibration: ``Calibrator`` records per-input-channel activation L2
norms during an (unjitted) calibration forward; ``format_params`` consumes
its stats keyed by the layer path. Without calibration the score falls back
to |W| (magnitude pruning) — acceptance is a little lower but nothing
breaks (measured in benchmarks/acceptance.py).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import bitops, coding, pruning
from repro.core import format as fmt
from repro.core.format import CassandraConfig

# parent-dict keys whose "w" leaves must stay full precision
_SKIP_PARENTS = {"router"}
# a 2-D weight of more values is formatted and decoded in column blocks
# (whole, a 2048 x 102,400 head asks 9.84 GB of formatting temporaries)
_BLOCK_ELEMS = 1 << 24
# the leaves every column block shares: the exponent codebooks
_BOOKS = ("codebook", "pruned_codebook")
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
    if w.ndim not in (2, 3, 4):
        return False
    n_in, n_out = w.shape[-2], w.shape[-1]
    if n_in < 64 or n_out < 8:
        return False
    return n_in % 32 == 0


def _format_layer(w: jax.Array, act_norm, cass: CassandraConfig):
    """Format one (in, out) weight (blocked along ``in``)."""
    block = cass.weight_block(w.shape[0])
    keep = cass.weight_keep(block)
    return fmt.format_tensor(*_scored(w, act_norm), cass, block, keep,
                             cass.mx_group, cass.weight_trunc)


def _scored(w: jax.Array, act_norm):
    """(out, in) weight and its pruning scores (Wanda, else |W|)."""
    wt = w.T
    if act_norm is None:
        return wt, jnp.abs(wt.astype(jnp.float32))
    return wt, pruning.wanda_scores(w, act_norm).T


def _col_blocks(n_in: int, n_out: int) -> int:
    """The fewest blocks of output columns, dividing ``n_out``, in which an
    (n_in, n_out) weight holds at most ``_BLOCK_ELEMS`` values a block."""
    n = -(-n_in * n_out // _BLOCK_ELEMS)
    while n_out % n:
        n += 1
    return n


@partial(jax.jit, static_argnames=("cass", "n"))
def _format_cols(w: jax.Array, act_norm, cass: CassandraConfig, n: int):
    """``_format_layer`` of one (in, out) weight, ``n`` blocks of output
    columns at a time, bit for bit.

    The format couples columns only through a tensor's exponent codebooks,
    built from its kept and pruned exponents' histograms: a first pass
    sums those block by block, a second codes every block against the
    shared books."""
    n_in, n_out = w.shape
    block = cass.weight_block(n_in)
    keep = cass.weight_keep(block)
    blocks = w.reshape(n_in, n, n_out // n).transpose(1, 0, 2)

    def code(wl, **books):
        return fmt.format_tensor(*_scored(wl, act_norm), cass, block, keep,
                                 cass.mx_group, cass.weight_trunc, **books)

    if cass.variant != 1:                  # no codebooks
        return _merge_cols(jax.lax.map(code, blocks))

    def counts(wl):
        wt, scores = _scored(wl, act_norm)
        sel = pruning.select_topk_blocked(wt.astype(jnp.bfloat16), scores,
                                          keep, block)
        return tuple(bitops.histogram256(bitops.split_fields(sel[k])[1])
                     for k in ("kept", "pruned"))

    kept, pruned = (coding.codebook_from_counts(c.sum(0))
                    for c in jax.lax.map(counts, blocks))
    spec, verif = _merge_cols(jax.lax.map(
        lambda wl: code(wl, codebook=kept, pruned_codebook=pruned), blocks))
    spec["codebook"] = coding.trim_codebook(kept[0])
    if keep != block:
        verif["pruned_codebook"] = coding.trim_codebook(pruned[0])
    return spec, verif


def _merge_cols(tree):
    """(n, out/n, ...) leaves of column blocks -> (out, ...)."""
    return jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), tree)


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
        n = _col_blocks(*w.shape)
        spec, verif = (_format_layer(w, act_norm, cass) if n == 1 else
                       _format_cols(w, act_norm, cass, n))
    elif w.ndim == 3:
        spec, verif = _format_stack(w, act_norm, cass)
    else:
        # expert stacks: the experts run under vmap, where the calibrator
        # records nothing, so they are scored by magnitude
        spec, verif = _unflatten(
            _format_stack(w.reshape(-1, *w.shape[-2:]), None, cass),
            w.shape[:-2])
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


def _unflatten(tree, lead: tuple):
    """Split every leaf's leading dim back into the stack dims ``lead``."""
    return jax.tree.map(lambda a: a.reshape(*lead, *a.shape[1:]), tree)


@partial(jax.jit, static_argnames=("cass", "shape"))
def _decode_views(spec: dict, verif: dict, cass: CassandraConfig,
                  shape: tuple[int, int]) -> dict:
    def one(sv):
        return {"draft": fmt.draft_weight(sv[0], cass, shape),
                "target": fmt.target_weight(sv[0], sv[1], cass, shape)}

    lead = spec["bitmap"].shape[:-3]
    if not lead:                                  # one (in, out) weight
        n = _col_blocks(*shape)
        if n == 1:
            return one((spec, verif))
        return _decode_cols(spec, verif, cass, shape, n)
    if len(lead) == 1:                            # (R, ...) stack
        return jax.lax.map(one, (spec, verif))
    # (R, E, ...) expert stack: every leaf's own dims follow the stack's
    sv = jax.tree.map(
        lambda a: a.reshape(math.prod(lead), *a.shape[len(lead):]),
        (spec, verif))
    return _unflatten(jax.lax.map(one, sv), lead)


def _decode_cols(spec: dict, verif: dict, cass: CassandraConfig,
                 shape: tuple[int, int], n: int) -> dict:
    """Both views of one (in, out) weight, ``n`` blocks of output columns
    at a time (the blocks of ``_format_cols``)."""
    n_in, n_out = shape

    def split(part):
        return ({k: v.reshape(n, n_out // n, *v.shape[1:])
                 for k, v in part.items() if k not in _BOOKS},
                {k: v for k, v in part.items() if k in _BOOKS})

    (s_cols, s_books), (v_cols, v_books) = split(spec), split(verif)

    def one(sv):
        s, v = {**sv[0], **s_books}, {**sv[1], **v_books}
        cols = (n_in, n_out // n)
        return {"draft": fmt.draft_weight(s, cass, cols),
                "target": fmt.target_weight(s, v, cass, cols)}

    views = jax.lax.map(one, (s_cols, v_cols))    # (n, in, out/n) each
    return jax.tree.map(
        lambda a: a.transpose(1, 0, 2).reshape(n_in, n_out), views)


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
