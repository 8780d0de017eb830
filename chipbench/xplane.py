"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations and programs per chip, the benchmark's own
host spans, busy time as the union of operation intervals, and idle gaps.

Times are in seconds on the trace's own clock, which the profiler shares
between host and device planes."""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import List, Tuple

Interval = Tuple[float, float]          # (start, end)


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    name: str
    ops: List[Event] = field(default_factory=list)       # "XLA Ops"
    modules: List[Event] = field(default_factory=list)   # "XLA Modules"


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Event]                  # host spans named by the benchmark


def short(name: str) -> str:
    """An operation's event carries its whole HLO instruction; keep the
    instruction's name (``%fusion.12 = bf16[...] ...`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(events: List[Event]) -> List[Event]:
    """Events that contain no other event (a ``while`` op contains the
    operations of its body); ``events`` sorted by start."""
    out = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start >= e.end:
            out.append(e)
    return out


def find(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str, span_prefix: str = "chipbench.",
         device_prefix: str = "/device:") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            dev = Device(plane.name)
            for line in plane.lines:
                target = {"XLA Ops": dev.ops,
                          "XLA Modules": dev.modules}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    target.append(Event(short(e.name), s,
                                        s + e.duration_ns * 1e-9))
            if dev.ops or dev.modules:
                devices.append(dev)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        s = e.start_ns * 1e-9
                        spans.append(Event(e.name, s,
                                           s + e.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    for d in devices:
        d.ops.sort(key=lambda e: e.start)
        d.modules.sort(key=lambda e: e.start)
    return Trace(devices, spans)


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(events: List[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union([(x.start, x.end) for x in events],
                                       lo, hi))


def gaps(events: List[Event], lo: float, hi: float) -> List[Interval]:
    """Idle intervals in [lo, hi] between the union of ``events``."""
    out, t = [], lo
    for s, e in union([(x.start, x.end) for x in events], lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def window(trace: Trace, name: str) -> Interval:
    """The interval of the host span ``name`` (the traced window)."""
    for s in trace.spans:
        if s.name == name:
            return s.start, s.end
    raise KeyError(f"no span {name!r} in the trace")


def innermost(spans: List[Event], t: float, default: str) -> str:
    """Name of the shortest span that covers time ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else default
