"""Reduce a profiler trace of the window to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX, into flat events.  The reductions work on those
events, so they are tested on synthetic ones:

* busy seconds: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  within the traced window, averaged over the devices;
* a kernel's device seconds: the summed durations of the op events
  whose name matches it;
* ``breakdown``: the device ops that took most time, and the longest
  idle gaps, each named by the host event that overlapped it most.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"          # host span around the measured window


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    """Every timed event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    """(start, end) ns of the host span around the measured window; a
    trace without it is an error, since no other span means the same."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: Sequence[Event]) -> Dict[str, List[Event]]:
    """Op events per device plane."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE \
                and e.dur_ns > 0:
            out[e.plane].append(e)
    return dict(out)


def _clip(evs, lo, hi) -> List[Tuple[float, float]]:
    return sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs
                  if e.end_ns > lo and e.start_ns < hi)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclass
class Reduced:
    window_s: float
    busy_s: Optional[float]           # None: no device op was traced
    ops: Dict[str, List[Event]]       # per device, clipped to the window
    lo: float
    hi: float
    host: List[Event]

    def kernel_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of ops whose name matches ``pattern``, summed
        over devices and averaged; None when none matched."""
        rx = re.compile(pattern)
        per = [sum(min(e.end_ns, self.hi) - max(e.start_ns, self.lo)
                   for e in evs if rx.search(e.name))
               for evs in self.ops.values()]
        total = sum(per)
        return total / len(per) / 1e9 if per and total > 0 else None

    def breakdown(self, n: int = 10) -> dict:
        """Top device ops by time, and the longest idle gaps named by
        the host event that overlapped each most (first device)."""
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for e in evs:
                tot[e.name] += (min(e.end_ns, self.hi)
                                - max(e.start_ns, self.lo)) / 1e9
        k = max(1, len(self.ops))
        top = sorted(((name, s / k) for name, s in tot.items()),
                     key=lambda t: -t[1])[:n]
        gaps = []
        if self.ops:
            busy = _union(_clip(next(iter(self.ops.values())),
                                self.lo, self.hi))
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            gaps = sorted(((edges[i], edges[i + 1])
                           for i in range(0, len(edges), 2)
                           if edges[i + 1] > edges[i]),
                          key=lambda g: g[0] - g[1])[:n]
        named = []
        for a, b in gaps:
            named.append([host_cause(self.host, a, b), (b - a) / 1e9])
        return {"device_ops": [[a, b] for a, b in top], "idle_gaps": named}


def host_cause(host: Sequence[Event], a: float, b: float) -> str:
    """The host event that best explains the device gap [a, b): the
    shortest one covering at least half of it, else the one covering
    most of it."""
    cover = [(min(b, e.end_ns) - max(a, e.start_ns), e) for e in host]
    cover = [(o, e) for o, e in cover if o > 0]
    if not cover:
        return "no host event"
    half = [e for o, e in cover if o >= 0.5 * (b - a)]
    if half:
        return min(half, key=lambda e: e.dur_ns).name
    return max(cover, key=lambda t: t[0])[1].name


def reduce(events: Sequence[Event]) -> Reduced:
    """Busy and window seconds of the traced window, the host span
    ``bench.window``."""
    lo, hi = window_of(events)
    ops = {p: [e for e in evs if e.end_ns > lo and e.start_ns < hi]
           for p, evs in device_ops(events).items()}
    busy = None
    if ops:
        busy = sum(sum(b - a for a, b in _union(_clip(evs, lo, hi)))
                   for evs in ops.values()) / len(ops) / 1e9
    host = [e for e in events
            if not DEVICE_PLANE.match(e.plane) and e.name != WINDOW_SPAN
            and e.dur_ns > 0 and e.end_ns > lo and e.start_ns < hi]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy, ops=ops,
                   lo=lo, hi=hi, host=host)
