"""Seconds from process start to the window's opening: imports, device
start, weight init, formatting, view decode, compiles (or loads from the
persistent cache), warm-up and, closed loop, the sessions' prefill."""


def read(r):
    return r.setup_s
