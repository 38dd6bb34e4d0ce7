"""Scheduler dispatch: mean host milliseconds per fused-step dispatch in
the window (the scheduler's ``unified.dispatch`` wall bucket, differenced
over the window: its total over its calls)."""

BUCKET = "unified.dispatch"


def read(r):
    c0, s0 = r.walls0.get(BUCKET, (0, 0.0))
    c1, s1 = r.walls1.get(BUCKET, (0, 0.0))
    return (s1 - s0) / (c1 - c0) * 1e3 if c1 > c0 else None
