"""Traffic kind ``closed``: a fixed number of clients, each with one
request in flight; a client whose request finishes sends its next one at
once (no think time).

Mix keys: ``users`` (= ``slots``), ``prompt_len`` and ``max_new`` (length
distributions of ``generator.quantiles``), and ``prompt_key``, the fixed
key of the sessions' prompt tokens. Each client's first request is
admitted and prefilled during set-up, so the window opens on sessions
whose cache already holds their whole prompt.

Every seed offers the same sessions. How many tokens a draft gets
accepted depends on what the prompt is, so prompt tokens drawn from the
seed would change the work from seed to seed; here each block of
``users`` requests is one fixed set (lengths, output budgets and tokens
from ``prompt_key``), and the seed only decides which client sends which
member of the set, and so the slot it is served in.
"""
from __future__ import annotations

import time

import numpy as np

import generator as G
import harness as H


def max_context(mix: dict) -> int:
    """Longest prompt + longest output: sizes each slot's cache."""
    return G.upper(mix["prompt_len"]) + G.upper(mix["max_new"])


class Stream:
    """Endless request stream for ``users`` clients. Request ``k`` goes to
    client ``k % users``; each block of ``users`` consecutive requests is
    the block's fixed set of sessions, in an order drawn from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.seed, self.vocab = seed, vocab
        self.key = int(mix["prompt_key"])
        self.users = int(mix["users"])
        self.plen = G.quantiles(mix["prompt_len"], self.users)
        self.mnew = G.quantiles(mix["max_new"], self.users)
        self._made = 0

    def member(self, block: int, j: int) -> tuple[np.ndarray, int]:
        """Session ``j`` of block ``block``: the same for every seed. The
        pairing of prompt lengths with output budgets is drawn from the
        fixed key too."""
        pair = G.rng(self.key, 200 + block).permutation(self.users)
        prompt = G.prompt(G.rng(self.key, 10_000 + block * self.users + j),
                          self.plen[j], self.vocab)
        return prompt, int(self.mnew[pair[j]])

    def next(self, user: int) -> G.RequestSpec:
        k = self._made
        block, i = divmod(k, self.users)
        order = G.rng(self.seed, 100 + block).permutation(self.users)
        prompt, max_new = self.member(block, int(order[i]))
        self._made += 1
        return G.RequestSpec(index=k, user=user, due_s=None, prompt=prompt,
                             max_new=max_new)


def start(sched, mix: dict, cell: dict, seed: int, vocab: int):
    """Set-up: each client's first request, prefilled until it has its
    first token."""
    stream = Stream(mix, seed, vocab)
    records = []
    for u in range(stream.users):
        spec = stream.next(u)
        rec = H.RequestRecord.of(spec, time.perf_counter())
        H.submit(sched, rec, spec)
        records.append(rec)
    while any(r.req is not None and not r.req.token_walls for r in records):
        sched.step()
    return stream, records


def window(sched, state, seconds: float, tr) -> H.Window:
    """Every client keeps one request in flight for ``seconds``."""
    stream, records = state
    tr.start()
    t0 = time.perf_counter()
    active = {r.user: r for r in records}
    while time.perf_counter() - t0 < seconds:
        tr.tick()
        with H.span("bench.step"):
            sched.step()
        with H.span("bench.book"):
            for u, rec in list(active.items()):
                if rec.req is not None and not rec.req.done:
                    continue
                spec = stream.next(u)
                new = H.RequestRecord.of(spec, time.perf_counter())
                H.submit(sched, new, spec)
                records.append(new)
                active[u] = new
    t1 = time.perf_counter()
    tr.stop()
    return H.Window(records=records, t0=t0, t1=t1, t_end=t1,
                    at_close=H.snapshot(sched))
