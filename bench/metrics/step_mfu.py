"""Model step: useful work over the device's busy time at peak, %.

Useful work is the bfloat16 target model's FLOPs (``bench/work.py``) for
the output tokens handed out while the profiler traced, each at
the position of the forward that produced it (a request's first token is
its prefill's), and for the prompt tokens prefilled in it (the lifecycle
tracer's PREFILL_CHUNK events). It is divided by the seconds in which an
operation ran on the chip times the chip's peak bf16 rate. A speculative
step does more than this (draft passes, rejected positions), so the share
cannot pass what the device did.
"""
import work


def read(r):
    if r.trace is None or r.trace["busy_s"] <= 0 or not r.events:
        return None
    lo, hi = r.traced
    flops = 0.0
    for rec in r.records:
        st = r.stamps(rec)
        pos = [rec.n_prompt + i - 1 for i, w in enumerate(st)
               if i > 0 and lo <= w <= hi]
        flops += work.token_flops(r.hf, pos)
    for ts, _cycle, kind, _rid, _slot, args in r.events:
        if kind == "prefill" and lo <= ts <= hi:
            n, pos_after = args
            flops += work.span_flops(r.hf, pos_after - n, pos_after)
    peak = r.peaks["bf16_flops_per_s"] * r.trace["busy_s"]
    return flops / peak * 100.0
