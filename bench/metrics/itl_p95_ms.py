"""95th percentile of the gaps between a request's output tokens, ms.

One sample per output token delivered in the window, except a request's
first token: the wall gap since that request's previous token (or since
the window opened, when that token came before it). Tokens handed out in
one harvest get 0 after the first. Each request still running when the
window closes adds its open gap (close minus its last token) once, so a
stall that spans the close still shows.
"""
import numpy as np


def read(r):
    gaps = []
    for rec in r.records:
        st = r.stamps(rec)
        for prev, cur in zip(st, st[1:]):
            if r.t0 <= cur <= r.t1:
                gaps.append(cur - max(prev, r.t0))
        seen = [w for w in st if w <= r.t1]
        finished = rec.req is not None and rec.req.done \
            and len(seen) == len(st)
        if seen and not finished:
            gaps.append(r.t1 - max(seen[-1], r.t0))
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
