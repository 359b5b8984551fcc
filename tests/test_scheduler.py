"""Scheduler conformance: bounded queue, FIFO fairness, starvation
bound, slot-recycling complexity, engine<->simulator load agreement."""
from collections import deque

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.serving import (Engine, Scheduler, SchedulerConfig, ServeConfig)
from repro.sim import workload as sim_workload


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _sched(cfg, params, *, max_batch=2, policy="fcfs", capacity=4,
           starvation_limit=8, chunk_tokens=4):
    eng = Engine(params, cfg, ServeConfig(max_batch=max_batch, max_ctx=32,
                                          chunk_tokens=chunk_tokens))
    return Scheduler(eng, SchedulerConfig(queue_capacity=capacity,
                                          policy=policy,
                                          starvation_limit=starvation_limit))


# ---------------------------------------------------------------------------
# queue behavior (no engine compute needed until step())
# ---------------------------------------------------------------------------


def test_queue_never_exceeds_bound(setup):
    cfg, params = setup
    s = _sched(cfg, params, capacity=3)
    rids = [s.offer([1, 2], 2) for _ in range(6)]
    assert sum(r is not None for r in rids) == 3
    assert rids[3:] == [None, None, None]
    assert s.queue_depth() == 3 and s.rejected == 3
    # backpressure clears as the queue drains into slots
    s.admit_ready()
    assert s.queue_depth() == 1                      # 2 slots filled
    assert s.offer([3, 4], 2) is not None


def test_fifo_order_preserved_under_equal_lengths(setup):
    cfg, params = setup
    s = _sched(cfg, params, max_batch=1, capacity=16)
    rids = [s.offer([1, 2, 3], 2) for _ in range(5)]
    admitted = []
    for _ in range(60):
        admitted += s.admit_ready()
        if len(admitted) == 5:
            break
        s.step()
    assert admitted == rids, "equal-length requests must admit in FIFO order"


def test_spf_prefers_short_prompts(setup):
    cfg, params = setup
    s = _sched(cfg, params, max_batch=1, policy="spf", capacity=16,
               starvation_limit=1000)
    r_long = s.offer(list(range(1, 13)), 2)
    r_short = s.offer([5, 5], 2)
    first = []
    while not first:
        first = s.admit_ready()
        s.step()
    # both requests were queued before any admission: spf must admit
    # the short one into the single slot first, despite arrival order
    assert first == [r_short] and r_long is not None


def test_no_starvation_under_spf_aging(setup):
    """A long prompt at the queue head is admitted within
    starvation_limit iterations once slots free, even while shorter
    prompts keep arriving (the aging guard)."""
    cfg, params = setup
    lim = 6
    s = _sched(cfg, params, max_batch=1, policy="spf", capacity=64,
               starvation_limit=lim)
    r_long = s.offer(list(range(1, 15)), 2)          # queue head, longest
    admitted_at = None
    for it in range(120):
        s.offer([7, 8], 2)                           # fresh short each iter
        s.step()
        t = s.tickets[r_long]
        if t.admitted_iter is not None:
            admitted_at = t
            break
    assert admitted_at is not None, "long request starved"
    # admitted at the first slot-free event after the aging bound trips:
    # bounded by starvation_limit + one short-request service time
    waited = admitted_at.admitted_iter - admitted_at.arrival_iter
    assert waited <= lim + 8, f"waited {waited} > aging bound {lim}+8"


def test_slot_recycling_is_o1(setup):
    """Satellite regression: free-slot recycling must be a deque
    (popleft/append are O(1); the old list.pop(0) was O(max_batch))."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=3, max_ctx=16))
    assert isinstance(eng.free_slots, deque)
    a = eng.free_slots.popleft()
    eng.free_slots.append(a)
    assert list(eng.free_slots) == [1, 2, 0]         # FIFO slot rotation


def test_offer_validates_at_the_door(setup):
    cfg, params = setup
    s = _sched(cfg, params)
    with pytest.raises(ValueError, match="max_ctx"):
        s.offer(list(range(40)), 10)                 # 40 + 10 > 32
    assert s.queue_depth() == 0


# ---------------------------------------------------------------------------
# metrics + engine<->simulator conformance
# ---------------------------------------------------------------------------


def test_metrics_lifecycle(setup):
    cfg, params = setup
    s = _sched(cfg, params, max_batch=2, capacity=8)
    s.offer([1, 2, 3, 4, 5], 3)
    s.offer([9, 8], 2)
    s.drain()
    m = s.metrics()
    assert m.completed == 2 and m.rejected == 0
    assert m.tokens_emitted == 5
    for pct in (m.ttft, m.queue_delay):
        assert pct["p50"] <= pct["p95"] <= pct["p99"]
    # queue delay cannot exceed TTFT (admission precedes the first token)
    assert m.queue_delay["p50"] <= m.ttft["p50"]
    assert m.throughput > 0


def test_engine_vs_simulator_load_agreement(setup):
    """Conformance: replaying the engine's workload trace through
    sim.workload reproduces the engine's per-expert loads exactly, and
    the replayed workloads run through the chiplet simulator."""
    from repro.sim.engine import simulate_layer
    from repro.sim.hardware import PROTOTYPE_2X2, spec_from_config

    cfg, params = setup
    s = _sched(cfg, params, max_batch=2, chunk_tokens=3)
    s.offer([1, 2, 3, 4, 5, 6, 7], 3)
    s.offer([9, 8, 7], 2)
    s.drain()
    trace = [r for r in s.engine.trace if "counts" in r]
    assert trace and {"prefill", "decode"} == {r["phase"] for r in trace}

    P = PROTOTYPE_2X2.num_chiplets
    replayed = sim_workload.workloads_from_trace(trace, P)
    assert len(replayed) == len(trace)
    # exact per-record agreement: chiplet-striped counts sum back
    for rec, (it, layer, wl) in zip(trace, replayed):
        assert (it, layer) == (rec["iter"], rec["layer"])
        np.testing.assert_array_equal(wl.expert_totals,
                                      np.asarray(rec["counts"]))
    # aggregate per-layer agreement
    totals = sim_workload.trace_expert_totals(trace)
    agg = {}
    for _, layer, wl in replayed:
        agg[layer] = agg.get(layer, 0) + wl.expert_totals
    for layer, t in totals.items():
        np.testing.assert_array_equal(agg[layer], t)
        assert t.sum() > 0
    # the replayed workload drives the cycle-level simulator
    spec = spec_from_config(s.engine.cfg)
    busiest = max((wl for _, _, wl in replayed),
                  key=lambda w: w.expert_totals.sum())
    res = simulate_layer(PROTOTYPE_2X2, spec, busiest, "fse_dp_paired")
    assert res.latency > 0 and 0 <= res.utilization <= 1
    np.testing.assert_array_equal(
        sorted(np.nonzero(busiest.expert_totals)[0]),
        sorted(set(range(spec.num_experts))
               - set(res.dropped_experts)
               - set(np.where(busiest.expert_totals == 0)[0])))


def test_streaming_emission_callback(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32,
                                          chunk_tokens=4))
    seen = []
    s = Scheduler(eng, SchedulerConfig(queue_capacity=8),
                  on_token=lambda rid, tok: seen.append((rid, tok)))
    rid = s.offer([1, 2, 3], 3)
    s.drain()
    assert [t for r, t in seen if r == rid] == s.outputs()[rid]
    assert len(seen) == 3
