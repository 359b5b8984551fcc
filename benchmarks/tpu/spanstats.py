"""The serving program's own host spans in a traced window, shared by
the span readers in ``metrics/``.

``repro.serving`` opens these spans on the profiler's clock (see
docs/architecture.md, "Spans and counters"); ``run.reduced.host`` holds
them as host events of the window, taken here by exact name.  A program
without them opens no ``engine.step`` span, and every reader then
returns None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from trace_reduce import _clip, _union

SCHED_STEP = "sched.step"            # Scheduler.step
ENGINE_STEP = "engine.step"          # Engine.step, one per iteration
DISPATCH = "engine.dispatch"         # building and calling one jitted program
FETCH = "engine.fetch"               # one blocking device-to-host read

Intervals = List[Tuple[float, float]]


def spans(run, name: str) -> list:
    r = run.reduced
    return [] if r is None else [e for e in r.host if e.name == name]


def n_iter(run) -> int:
    """Engine iterations in the traced window."""
    return len(spans(run, ENGINE_STEP))


def per_iter_ms(run, name: str) -> Optional[float]:
    """Summed duration of the ``name`` spans per engine iteration."""
    n = n_iter(run)
    if not n:
        return None
    return sum(e.dur_ns for e in spans(run, name)) / n / 1e6


def length(iv: Intervals) -> float:
    return sum(b - a for a, b in iv)


def intersect(x: Intervals, y: Intervals) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x: Intervals, y: Intervals) -> Intervals:
    """``x`` less ``y``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > a:
                out.append((a, y[k][0]))
            a = max(a, y[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def covered(run, name: str, lo: float, hi: float) -> Intervals:
    """Union of the ``name`` spans, clipped to [lo, hi)."""
    return _union(_clip(spans(run, name), lo, hi))


def step_host_ms(run) -> Optional[float]:
    """Per engine iteration: each ``sched.step``'s duration less the
    part of it inside ``engine.dispatch`` or ``engine.fetch``."""
    n = n_iter(run)
    if not n:
        return None
    inf = float("inf")
    steps = _union(_clip(spans(run, SCHED_STEP), -inf, inf))
    calls = _union(_clip(spans(run, DISPATCH) + spans(run, FETCH), -inf, inf))
    return (length(steps) - length(intersect(steps, calls))) / n / 1e6


def idle_host_share(run) -> Optional[float]:
    """Percent of the window in which no op runs on the device while the
    host is inside ``sched.step`` and not inside ``engine.fetch``,
    averaged over the devices."""
    r = run.reduced
    if r is None or not r.ops or r.hi <= r.lo or not n_iter(run):
        return None
    loop = subtract(covered(run, SCHED_STEP, r.lo, r.hi),
                    covered(run, FETCH, r.lo, r.hi))
    shares = []
    for evs in r.ops.values():
        idle = subtract([(r.lo, r.hi)], _union(_clip(evs, r.lo, r.hi)))
        shares.append(length(intersect(idle, loop)) / (r.hi - r.lo))
    return 100.0 * sum(shares) / len(shares)


def program_counts_compiles() -> bool:
    """Whether the served program keeps a compile counter (its engine
    then leaves ``compile`` event records in ``Engine.trace``)."""
    from repro.serving import engine
    return hasattr(engine, "compile_counter")


def step_compile_ms(run) -> Optional[float]:
    """Seconds of the window's ``compile`` trace records, in ms per
    engine iteration of the window."""
    w = run.window
    if not w.iterations or not program_counts_compiles():
        return None
    s = sum(rec["seconds"] for rec in w.trace_records
            if rec.get("event") == "compile")
    return 1e3 * s / w.iterations
