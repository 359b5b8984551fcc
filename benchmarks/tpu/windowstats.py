"""Numbers of one window, shared by the metric readers in ``metrics/``.

Each reader is a file of its own that calls into here, so a later cell
or metric adds a reader without editing one that exists.  A reader
returns None where its window holds nothing to read; a share is never
reported as 0 for want of a measurement.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

import work


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(run, t: float) -> bool:
    w = run.window
    return w.start <= t <= w.end


def output_tokens(run) -> int:
    return sum(1 for s in run.window.served for t in s.stamps
               if in_window(run, t))


def inter_token_gaps(run) -> List[float]:
    """Every gap between consecutive tokens of one request, pooled."""
    out = []
    for s in run.window.served:
        st = [t for t in s.stamps if in_window(run, t)]
        out += [b - a for a, b in zip(st, st[1:])]
    return out


def ttfts(run) -> List[float]:
    """First token - due, for every request due in the window; one with
    no first token when the window closed counts what it has waited."""
    w = run.window
    return [(s.stamps[0] if s.stamps else w.end) - s.due
            for s in w.served if s.due <= w.end]


def queue_waits(run) -> List[float]:
    w = run.window
    return [(s.admitted if s.admitted is not None else w.end) - s.due
            for s in w.served if s.due <= w.end]


def mean_step_ms(run, want) -> Optional[float]:
    d = [s.t1 - s.t0 for s in run.window.steps if want(s)]
    return 1e3 * sum(d) / len(d) if d else None


def experts_by_call(run) -> Dict[Tuple[int, str], List[list]]:
    """Per (engine iteration, phase): the routed-row counts of each MoE
    layer, from the engine's trace records."""
    out: Dict[Tuple[int, str], List[list]] = defaultdict(list)
    for rec in run.window.trace_records:
        if "counts" in rec and "layer" in rec:
            out[(rec["iter"], rec["phase"])].append(list(rec["counts"]))
    return out


def required_step_seconds(run) -> Optional[float]:
    """Sum over the window's steps of the least time of each program
    call's required work (prefill call and decode call apart)."""
    calls = experts_by_call(run)
    if not calls:
        return None
    total = 0.0
    for st in run.window.steps:
        for phase, rows in (("prefill", st.prefill), ("decode", st.decode)):
            if not rows:
                continue
            hit = [sum(1 for c in cnt if c > 0)
                   for cnt in calls.get((st.iteration, phase), [])]
            f, b = work.step_work(run.model, rows, hit)
            total += work.least_seconds(f, b, run.peak)
    return total


def step_mfu(run) -> Optional[float]:
    least = required_step_seconds(run)
    if least is None:
        return None
    return work.share_percent(least, run.window.seconds)


def expert_least_seconds(run) -> Optional[float]:
    calls = experts_by_call(run)
    if not calls:
        return None
    total = 0.0
    for counts in calls.values():
        f, b = work.expert_work(run.model, counts)
        total += work.least_seconds(f, b, run.peak)
    return total
