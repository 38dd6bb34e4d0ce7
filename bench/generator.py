"""What every traffic kind shares: request specs, stratified draws, prompts.

A mix (``bench/traffic/<mix>.json``) is data only. Its ``kind`` names the
module that turns it into requests and drives the window with them,
``bench/traffic/<kind>.py`` (see ``harness.load_kind``); every kind draws
its sizes and prompts with the helpers here.

Lengths are *stratified*: a set of ``n`` draws is the ``n`` mid-quantiles
``(i + 0.5) / n`` of the distribution, and the seed only permutes them.
So every seed offers the same multiset of sizes in another order: the
seed changes which request meets which state, not how much work the
window holds. Under speculative decoding the prompt's tokens change the
work too (how many drafted tokens are accepted), so a kind draws them
from a fixed key of its mix where every seed has to serve the same
sessions (``closed``).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from scipy import stats


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """One request as offered. ``due_s`` is seconds after the window
    opens for a request on a fixed schedule, or None for one that is sent
    when its client's previous one finishes."""
    index: int
    user: int
    due_s: float | None
    prompt: np.ndarray          # (L,) int32 token ids
    max_new: int


def rng(seed: int, stream: int) -> np.random.Generator:
    # numpy seeds take any non-negative int, 64-bit seeds included
    return np.random.default_rng([int(seed), stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, rounded.

    ``fixed``: ``value``. ``loguniform``: ``lo``..``hi``. ``lognormal``:
    ``median`` and ``sigma`` (of the log), clipped to ``lo``..``hi``.
    """
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, float(dist["value"]))
    if kind == "loguniform":
        lo, hi = math.log(dist["lo"]), math.log(dist["hi"])
        return np.round(np.exp(lo + u * (hi - lo)))
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * stats.norm.ppf(u))
        return np.round(np.clip(x, dist["lo"], dist["hi"]))
    raise ValueError(f"unknown distribution {kind!r}")


def upper(dist: dict) -> int:
    """The largest length a distribution can draw."""
    return int(dist["value"] if dist["dist"] == "fixed" else dist["hi"])


def prompt(gen: np.random.Generator, length: int, vocab: int) -> np.ndarray:
    """``length`` token ids drawn uniformly from the vocabulary."""
    return gen.integers(0, vocab, int(length), dtype=np.int64
                        ).astype(np.int32)
