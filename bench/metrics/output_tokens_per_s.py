"""Output tokens handed out in the window over the window's seconds: every
token whose stamp (``Request.token_walls``, taken at harvest) falls in
the window, over all requests."""


def read(r):
    n = sum(1 for rec in r.records for w in r.stamps(rec)
            if r.t0 <= w <= r.t1)
    return n / r.window_s if r.window_s > 0 else None
