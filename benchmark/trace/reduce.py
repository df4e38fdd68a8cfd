"""From a loaded trace (``xplane.load``) to the quantities the readers
use. All times are seconds on the trace's own clock.

Device time is taken from the ``XLA Ops`` line of each device plane.
Events on that line can nest (a loop holds its body's operations), so
busy time is the union of the intervals and an operation's time is its
self time: its duration less what its children cover. The sum of self
times equals the union, so no share built on them can count a nanosecond
twice.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .xplane import DEVICE_PLANE, HOST_PLANE

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_window"
REQUEST_SPAN = "bench_request"

NAME_CHARS = 160       # an operation's name is its HLO text: cut for the line
Interval = Tuple[float, float]


@dataclass
class Span:
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Device:
    name: str
    busy: List[Interval]                  # merged, inside the window
    op_self_s: Dict[str, float]           # self seconds by operation name
    launches: List[Interval]              # program executions


@dataclass
class Reduced:
    window: Interval
    devices: List[Device]
    requests: List[Span]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return sum(length(d.busy) for d in self.devices) / len(self.devices)

    def op_seconds(self) -> float:
        """Self seconds of all operations, averaged over devices."""
        total = sum(s for d in self.devices for s in d.op_self_s.values())
        return total / len(self.devices)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    return length(clip(merged, lo, hi))


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Self seconds by name of events [(name, start, end)] that nest."""
    out: Dict[str, float] = {}
    stack: List[List] = []     # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            # a child takes its part (clipped to the parent) from the parent
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return None


def reduce_trace(trace) -> Reduced:
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    spans: Dict[str, List[Span]] = {}
    for plane in host:
        for line in plane["lines"]:
            for name, start, dur, stats in line["events"]:
                spans.setdefault(name, []).append(
                    Span(start / 1e9, (start + dur) / 1e9, dict(stats)))
    if len(spans.get(WINDOW_SPAN, ())) != 1:
        raise RuntimeError(f"the trace holds {len(spans.get(WINDOW_SPAN, ()))}"
                           f" {WINDOW_SPAN} spans, expected 1")
    w = spans[WINDOW_SPAN][0]
    lo, hi = w.start, w.end
    devices = []
    for plane in sorted((p for p in trace["planes"]
                         if DEVICE_PLANE.match(p["name"])),
                        key=lambda p: int(DEVICE_PLANE.match(
                            p["name"]).group(1))):
        ops = _line(plane, OPS_LINE)
        if ops is None:
            raise RuntimeError(
                f"{plane['name']} has no {OPS_LINE!r} line; it has "
                f"{[ln['name'] for ln in plane['lines']]}")
        evs = [(n, max(s / 1e9, lo), min((s + d) / 1e9, hi))
               for n, s, d, _st in ops]
        evs = [ev for ev in evs if ev[2] > ev[1]]
        mods = _line(plane, MODULES_LINE) or []
        devices.append(Device(
            name=plane["name"],
            busy=merge([(s, e) for _n, s, e in evs]),
            op_self_s=self_times(evs),
            launches=clip([(s / 1e9, (s + d) / 1e9) for _n, s, d, _st in mods],
                          lo, hi)))
    if not devices:
        raise RuntimeError("the trace holds no device plane: "
                           f"{[p['name'] for p in trace['planes']]}")
    requests = sorted((r for r in spans.get(REQUEST_SPAN, ())
                       if r.start >= lo and r.start < hi),
                      key=lambda r: r.start)
    return Reduced(window=(lo, hi), devices=devices, requests=requests)


def breakdown(red: Reduced, top: int = 10) -> Dict[str, List]:
    """The device operations that took most (self) time, and the idle
    gaps of the first device by which of the benchmark's spans covered
    them, longest total first."""
    ops: Dict[str, float] = {}
    for d in red.devices:
        for n, s in d.op_self_s.items():
            ops[n] = ops.get(n, 0.0) + s / len(red.devices)
    gaps: Dict[str, float] = {}
    busy = red.devices[0].busy
    edges = [red.window[0]] + [t for iv in busy for t in iv] + [red.window[1]]
    starts = [r.start for r in red.requests]
    longest = max((r.end - r.start for r in red.requests), default=0.0)
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        near = red.requests[bisect.bisect_left(starts, g0 - longest):
                            bisect.bisect_right(starts, g1)]
        for name, secs in _attribute(near, g0, g1).items():
            gaps[name] = gaps.get(name, 0.0) + secs
    rank = lambda d: [[n[:NAME_CHARS], s] for n, s in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}


def _attribute(requests: Sequence[Span], g0: float, g1: float
               ) -> Dict[str, float]:
    """Split the gap [g0, g1] by which requests were open in it."""
    cuts = sorted({g0, g1} | {t for r in requests for t in (r.start, r.end)
                              if g0 < t < g1})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        live = [r for r in requests if r.start <= mid < r.end]
        if not live:
            name = "no_request_open"
        elif len(live) == 1:
            name = f"in_request:{live[0].stats.get('shape', '?')}"
        else:
            name = f"in_requests:{len(live)}_open"
        out[name] = out.get(name, 0.0) + (b - a)
    return out
