"""Model step: device milliseconds per execution of the fused serving
program (``serve_unified``) in the traced window, from the profiler's
module events."""

PROGRAM = "serve_unified"


def read(r):
    if r.trace is None:
        return None
    p = r.trace["programs"].get(PROGRAM)
    return p["seconds"] / p["calls"] * 1e3 if p and p["calls"] else None
