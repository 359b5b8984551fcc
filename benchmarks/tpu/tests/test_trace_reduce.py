"""Trace reduction: idle share, kernel time and breakdown on a
synthetic trace, and loading a trace recorded with jax.profiler."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, start, dur, plane=DEV, line=tr.OPS_LINE):
    return tr.Event(plane, line, name, float(start), float(dur))


def synthetic():
    # window [1000, 11000) ns; ops overlap at 2000-3500; idle gaps of
    # 1000 (1000-2000), 2500 (4000-6500) and 3500 (7500-11000)
    return [ev(tr.WINDOW_SPAN, 1000, 10000, HOST, "python"),
            ev("fusion.1", 2000, 1000),
            ev("moe_kernel", 2500, 1000),
            ev("moe_kernel", 3500, 500),
            ev("fusion.2", 6500, 1000),
            ev("before_window", 0, 500),
            ev("bench.step", 1500, 6000, HOST, "python"),
            ev("TransferFromDevice", 6600, 500, HOST, "python"),
            ev("bench.idle", 7500, 3500, HOST, "python"),
            ev("RunStep", 4000, 2000, HOST, "python"),
            ev("other", 0, 1, "/device:TPU:0", "XLA Modules")]


def test_busy_and_window():
    r = tr.reduce(synthetic())
    assert r.window_s == pytest.approx(10000e-9)
    assert r.busy_s == pytest.approx(3000e-9)            # 2000-4000 + 6500-7500


def test_kernel_seconds_by_name():
    r = tr.reduce(synthetic())
    assert r.kernel_seconds("moe_kernel") == pytest.approx(1500e-9)
    assert r.kernel_seconds("no_such_op") is None


def test_breakdown_names_gaps_by_host_work():
    b = tr.reduce(synthetic()).breakdown()
    assert [n for n, _ in b["device_ops"]] == ["moe_kernel", "fusion.1",
                                               "fusion.2"]
    assert b["device_ops"][0][1] == pytest.approx(1500e-9)
    assert b["idle_gaps"] == [["bench.idle", pytest.approx(3500e-9)],
                              ["RunStep", pytest.approx(2500e-9)],
                              ["bench.step", pytest.approx(1000e-9)]]


def test_no_device_ops_reads_none():
    r = tr.reduce([e for e in synthetic() if e.plane != DEV])
    assert r.busy_s is None and r.kernel_seconds(".") is None
    assert r.breakdown()["idle_gaps"] == []


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([e for e in synthetic() if e.name != tr.WINDOW_SPAN])


def test_recorded_cpu_trace_loads(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load(str(tmp_path))
    assert any(e.name == tr.WINDOW_SPAN for e in events)
    r = tr.reduce(events)
    assert r.window_s > 0 and r.busy_s is None           # no TPU plane
