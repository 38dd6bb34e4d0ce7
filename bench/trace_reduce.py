"""Profiler trace -> device busy time, per-program time, top ops, idle gaps.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three kinds of events, as ``(name, start_ns, end_ns)``:

* per device plane (``/device:TPU:<n>``), the executions of compiled
  programs (line ``XLA Modules``) and of their operations (``XLA Ops``);
* on the host planes, the benchmark's own spans (names ``bench.*``, from
  ``jax.profiler.TraceAnnotation`` in ``bench/``).

``reduce`` then works on that :class:`Trace` alone, so tests can feed it
a small recorded one (``Trace.to_json``/``from_json``):

* busy time = the union of operation intervals on a device, clipped to
  the window (the ``bench.window`` span); idle = the window minus busy;
* per program: device seconds and executions (module events, by program
  name without the ``jit_`` prefix and the ``(id)`` suffix);
* top operations by summed self time (device seconds less those of the
  operations nested inside, such as a loop's body);
* idle gaps, each attributed to the innermost benchmark span that holds
  its midpoint, summed per span name.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"

Event = tuple  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    modules: dict      # device name -> [Event] program executions
    ops: dict          # device name -> [Event] operations
    spans: list        # [Event] benchmark host spans

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(modules={k: [tuple(e) for e in v]
                            for k, v in d["modules"].items()},
                   ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   spans=[tuple(e) for e in d["spans"]])


def _label(ev) -> str:
    """An operation's name for the breakdown: its framework op path
    (``tf_op`` stat, e.g. ``jit(serve_unified)/while/body/...``) when the
    trace has one, else the HLO instruction name."""
    for key, value in ev.stats:
        if key == "tf_op" and value:
            return str(value)
    return ev.name.split(" = ")[0]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(path))
    modules, ops, spans = defaultdict(list), defaultdict(list), []
    for plane in prof.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if dest is None:
                    continue
                labels: dict = {}
                for ev in line.events:
                    name = ev.name
                    if dest is ops:
                        if name not in labels:
                            labels[name] = _label(ev)
                        name = labels[name]
                    dest[plane.name].append(
                        (name, int(ev.start_ns), int(ev.end_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
    return Trace(modules=dict(modules), ops=dict(ops), spans=spans)


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def program_name(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def window(trace: Trace) -> tuple[int, int]:
    w = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not w:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in w), max(e for _, e in w)


def _span_at(spans, t: int) -> str:
    """Innermost (shortest) benchmark span holding instant ``t``."""
    best, best_len = "(no span)", None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e and (
                best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(trace: Trace, top: int = 10) -> dict:
    lo, hi = window(trace)
    devices = sorted(set(trace.ops) | set(trace.modules))
    busy_ns, gaps_by_span = [], defaultdict(float)
    programs = defaultdict(lambda: [0.0, 0])
    op_time = defaultdict(float)
    for dev in devices:
        evs = trace.ops.get(dev) or trace.modules.get(dev, [])
        busy = clip(union((s, e) for _, s, e in evs), lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps_by_span[_span_at(trace.spans, (g0 + g1) // 2)] += \
                    (g1 - g0) / 1e9 / len(devices)
        for name, s, e in clip_events(trace.modules.get(dev, []), lo, hi):
            p = programs[program_name(name)]
            p[0] += (e - s) / 1e9
            p[1] += 1
        for name, secs in self_times(
                clip_events(trace.ops.get(dev, []), lo, hi)):
            op_time[name] += secs
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / 1e9 / max(len(devices), 1)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(devices),
        "programs": {k: {"seconds": v[0], "calls": v[1]}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by_span.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def self_times(events):
    """(name, seconds) of each event less the events nested inside it (a
    loop's body operations run inside the loop's own event)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1][3]][1] -= (e - s) / 1e9
        stack.append((name, s, e, len(out)))
        out.append([name, (e - s) / 1e9])
    return out


def clip_events(events, lo: int, hi: int):
    """Events whose start lies in the window, clipped to its end."""
    return [(n, s, min(e, hi)) for n, s, e in events if lo <= s < hi]
