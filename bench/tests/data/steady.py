"""Traffic kind ``steady``, added by a test as a later change would add
one: requests due at a fixed interval, one over the cell's
``rate_per_s`` (``bench/cells/<cell>.json``), whatever the server does;
after the window, followed until each has its first token."""
import time

import generator as G
import harness as H


def max_context(mix):
    return G.upper(mix["prompt_len"]) + G.upper(mix["max_new"])


def start(sched, mix, cell, seed, vocab):
    return mix, cell["rate_per_s"], seed, vocab


def window(sched, state, seconds, tr):
    mix, rate, seed, vocab = state
    n = round(rate * seconds)
    plen = G.rng(seed, 1).permutation(G.quantiles(mix["prompt_len"], n))
    specs = [G.RequestSpec(index=i, user=i, due_s=i / rate,
                           prompt=G.prompt(G.rng(seed, 10 + i), plen[i],
                                           vocab),
                           max_new=G.upper(mix["max_new"]))
             for i in range(n)]
    tr.start()
    t0 = time.perf_counter()
    records = [H.RequestRecord.of(s, t0 + s.due_s) for s in specs]
    nxt = 0
    while time.perf_counter() - t0 < seconds:
        tr.tick()
        while nxt < n and records[nxt].due <= time.perf_counter():
            H.submit(sched, records[nxt], specs[nxt])
            nxt += 1
        if sched.idle:
            time.sleep(0.001)
        else:
            sched.step()
    t1 = time.perf_counter()
    tr.stop()
    at_close = H.snapshot(sched)
    while any(r.req is not None and not r.req.token_walls for r in records):
        sched.step()
    return H.Window(records=records, t0=t0, t1=t1,
                    t_end=time.perf_counter(), at_close=at_close)
