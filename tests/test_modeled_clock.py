"""Modeled wall clock: the engine's closed-form chiplet-array seconds
(autotune.ServingCostModel) vs the sim.modes event-loop referee, and the
scheduler's modeled TTFT/TPOT plumbing."""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.autotune import (HardwareProfile, ServingCostModel,
                                 streaming_layer_cost)
from repro.models import api
from repro.serving import (Engine, ServeConfig, Scheduler, SchedulerConfig,
                           TrafficConfig, make_traffic, run_closed_loop)
from repro.sim.hardware import PROTOTYPE_2X2, spec_from_config
from repro.sim.modes import replay_trace, simulate_trajectory

# stated agreement tolerances, model vs referee (measured headroom on
# the reduced granite workload: <=1.5% per record, <=0.5% aggregate)
PER_RECORD_TOL = 0.05
AGGREGATE_TOL = 0.02


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _traced_run(cfg, params, schedule=None):
    spec = {"strategy": "capacity"}
    if schedule:
        spec["schedule"] = schedule
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                          chunk_tokens=4, spec=spec))
    for p in ((1, 2, 3, 4), (9, 8, 7), (5, 5, 5, 5, 5)):
        eng.submit_chunked(list(p), max_new=6)
    eng.run()
    return eng


@pytest.mark.parametrize("schedule", [None, "dynamic"],
                         ids=["static", "dynamic"])
def test_model_agrees_with_referee(setup, schedule):
    """Every trace record's closed-form modeled_s must agree with the
    discrete expert-flow event loop (sim.modes.simulate_trajectory)
    within PER_RECORD_TOL, and the trace total within AGGREGATE_TOL —
    the two are deliberately different constructions, so this is a real
    cross-check, not an identity."""
    cfg, params = setup
    eng = _traced_run(cfg, params, schedule)
    spec = spec_from_config(eng.cfg)
    cf = eng.cfg.moe.capacity_factor
    assert eng.trace, "no workload trace"
    checked = 0
    records = [rec for rec in eng.trace if "counts" in rec]
    for rec in records:
        counts = np.asarray(rec["counts"], np.float64)
        if counts.sum() <= 0:
            continue
        assert rec["modeled_s"] > 0
        if rec["schedule"] == "dynamic":
            ref = simulate_trajectory(
                PROTOTYPE_2X2, spec, counts,
                order=rec.get("trajectory") or rec["order"],
                capacity_factor=cf)
        else:
            ref = simulate_trajectory(PROTOTYPE_2X2, spec, counts,
                                      padded=True, capacity_factor=cf)
        assert abs(rec["modeled_s"] - ref) <= PER_RECORD_TOL * ref, \
            (rec["layer"], rec["phase"], rec["modeled_s"], ref)
        checked += 1
    assert checked > 0
    total_m = sum(rec["modeled_s"] for rec in records)
    total_r = replay_trace(PROTOTYPE_2X2, spec, eng.trace,
                           capacity_factor=cf)
    assert abs(total_m - total_r) <= AGGREGATE_TOL * total_r


def test_streaming_cost_exact_at_extremes():
    """The closed form is exact against the event loop's structure at
    both regimes: compute-bound => fill + compute chain; DDR-bound =>
    active serial weight loads."""
    E, C, d, de, n_mats = 8, 4, 64, 128, 2
    eb = float(n_mats * d * de * 2)

    def profile(flops, bw):
        return HardwareProfile(name="synthetic", peak_flops=flops,
                               mem_bw=bw, link_bw=bw, link_latency=0.0,
                               vmem_bytes=1 << 20)

    ddr_bound = profile(1e18, 1e9)
    c = streaming_layer_cost(E, C, d, de, n_mats, E * C, ddr_bound)
    assert c["total_s"] == pytest.approx(E * eb / 1e9, rel=1e-12)
    comp_bound = profile(1e9, 1e18)
    c = streaming_layer_cost(E, C, d, de, n_mats, E * C, comp_bound)
    assert c["total_s"] == pytest.approx(c["t_fill_s"] + c["t_comp_s"],
                                         rel=1e-12)


def test_dynamic_never_costs_more_than_static():
    """For any observed gating, pricing the observed load (dynamic) can
    only shed padded rows and idle weight loads vs the shape-only plan."""
    cfg = reduced_config("granite-moe-1b-a400m")
    cm = ServingCostModel.from_config(cfg)
    rng = np.random.default_rng(0)
    for _ in range(20):
        counts = rng.integers(0, 6, size=cfg.moe.num_experts)
        if counts.sum() == 0:
            continue
        dyn = cm.layer_s(counts, dynamic=True)
        stat = cm.layer_s(counts, dynamic=False)
        assert dyn <= stat + 1e-18, (counts, dyn, stat)


def _closed_loop(cfg, params, clock):
    traffic = make_traffic(TrafficConfig(
        num_requests=6, rate=0.8, avg_prompt=8, max_prompt=16, min_new=2,
        max_new=4, vocab=cfg.vocab_size, seed=0))
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=32,
                                          chunk_tokens=4))
    sched = Scheduler(eng, SchedulerConfig(queue_capacity=16), clock=clock)
    res = run_closed_loop(sched, traffic)
    return eng, sched, res


def test_scheduler_modeled_metrics_always_on(setup):
    """Whatever the primary clock, ServingMetrics carries the secondary
    modeled-seconds TTFT/TPOT/queue-delay, and elapsed_modeled equals
    the trace's modeled_s total."""
    cfg, params = setup
    eng, sched, res = _closed_loop(cfg, params, clock=None)
    m = res["metrics"]
    assert m.completed == 6
    assert m.elapsed_modeled == pytest.approx(
        sum(rec.get("modeled_s", 0.0) for rec in eng.trace), rel=1e-9)
    for pct in (m.ttft_modeled, m.tpot_modeled, m.queue_delay_modeled):
        assert np.isfinite(pct["p50"])
        assert pct["p50"] >= 0
    assert m.ttft_modeled["p50"] > 0
    assert m.throughput_modeled > 0
    d = m.to_dict()
    assert d["elapsed_modeled"] == m.elapsed_modeled
    assert d["ttft_modeled"] == m.ttft_modeled
    # the primary (iteration) metrics are untouched by the modeled clock
    assert m.elapsed == m.iterations


def test_modeled_primary_clock_drains(setup):
    """clock="modeled" advances scheduler.now by the engine's modeled
    seconds; the closed loop still drains and stamps finite latencies."""
    cfg, params = setup
    eng, sched, res = _closed_loop(cfg, params, clock="modeled")
    m = res["metrics"]
    assert m.completed == 6
    assert sched.modeled_now > 0
    assert np.isfinite(m.ttft["p50"])


def test_unknown_clock_string_rejected(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32))
    with pytest.raises(ValueError, match="clock"):
        Scheduler(eng, SchedulerConfig(), clock="wall")
