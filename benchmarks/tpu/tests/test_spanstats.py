"""The span readers (``metrics/step_*_ms.py``, ``idle_host_share.py``)
on a synthetic trace: nested and overlapping spans, device idle outside
the serving loop, and a program without spans or a compile counter."""
from types import SimpleNamespace

import pytest

import run as bench_run
import spanstats
import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, start, dur, plane=HOST, line="python"):
    return tr.Event(plane, line, name, float(start), float(dur))


def op(start, dur):
    return ev("fusion", start, dur, DEV, tr.OPS_LINE)


# window [0, 10000) ns, two iterations.  Iteration 1: dispatch, fetch,
# dispatch, fetch back to back, then boundary work.  Iteration 2: a
# dispatch that overlaps the fetch before it.  After the loop: a fetch
# and a dispatch outside any sched.step, and idle the loop does not own.
SPANS = [ev(tr.WINDOW_SPAN, 0, 10000),
         ev("sched.step", 1000, 3000), ev("engine.step", 1100, 2800),
         ev("engine.dispatch", 1200, 300), ev("engine.fetch", 1500, 1000),
         ev("engine.dispatch", 2500, 100), ev("engine.fetch", 2600, 400),
         ev("engine.boundary", 3000, 200),
         ev("sched.step", 5000, 3000), ev("engine.step", 5100, 2800),
         ev("engine.dispatch", 5200, 200), ev("engine.fetch", 5400, 1600),
         ev("engine.dispatch", 6900, 200),
         ev("engine.fetch", 8500, 200), ev("engine.dispatch", 9000, 100)]
OPS = [op(1500, 900), op(2600, 300), op(5400, 1400), op(8600, 100)]
COMPILE = {"iter": 1, "event": "compile", "count": 3, "seconds": 0.004}
LAYER = {"iter": 1, "layer": 0, "phase": "decode", "counts": [1, 0]}

# dispatch 900 ns and fetch 3200 ns over 2 iterations; the two loop
# steps hold 6000 ns of which dispatch or fetch cover 1800 + 1900; idle
# inside the loop and outside fetches: 500 + 100 + 1000 + 400 + 1000
EXPECTED = {"step_dispatch_ms": 450e-6, "step_fetch_ms": 1600e-6,
            "step_host_ms": 1150e-6, "idle_host_share": 30.0,
            "step_compile_ms": 2.0}


def make_run(events, records=(COMPILE, LAYER), iterations=2):
    window = SimpleNamespace(iterations=iterations,
                             trace_records=list(records))
    return SimpleNamespace(reduced=tr.reduce(events), window=window)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_synthetic_trace(metric):
    got = bench_run.load_reader(metric)(make_run(SPANS + OPS))
    assert got == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", ["step_dispatch_ms", "step_fetch_ms",
                                    "step_host_ms", "idle_host_share"])
def test_span_readers_read_none_without_program_spans(metric):
    old = [e for e in SPANS + OPS if not e.name.startswith(("sched.",
                                                           "engine."))]
    assert bench_run.load_reader(metric)(make_run(old)) is None


def test_idle_share_needs_device_ops():
    assert spanstats.idle_host_share(make_run(SPANS)) is None


def test_compile_ms_reads_zero_in_a_window_without_compiles():
    assert spanstats.step_compile_ms(make_run(SPANS, [LAYER])) == 0.0


def test_compile_ms_reads_none_without_the_counter(monkeypatch):
    monkeypatch.setattr(spanstats, "program_counts_compiles", lambda: False)
    assert spanstats.step_compile_ms(make_run(SPANS)) is None


@pytest.mark.parametrize("x,y,inter,diff", [
    ([(0, 10)], [(2, 3), (5, 12)], [(2, 3), (5, 10)], [(0, 2), (3, 5)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(3, 4), (6, 7)], [(0, 3), (7, 9)]),
    ([(0, 4)], [], [], [(0, 4)]),
    ([(1, 2)], [(0, 5)], [(1, 2)], []),
])
def test_interval_algebra(x, y, inter, diff):
    assert spanstats.intersect(x, y) == inter
    assert spanstats.subtract(x, y) == diff
