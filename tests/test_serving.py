"""Serving engine: deferral output-invariance, continuous batching,
slot lifecycle, trace export."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.serving import Engine, ServeConfig


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(cfg, params, slack=0.0, n_threshold=None, prompts=((1, 2, 3, 4), (9, 8, 7))):
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                          buffering_slack=slack, theta_min=3))
    if n_threshold:
        eng.policy.n_threshold = n_threshold
    rids = [eng.submit(list(p), max_new=6) for p in prompts]
    outs = eng.run()
    return eng, [outs[r] for r in rids]


def test_deferral_output_invariance(setup):
    """Algorithm 2 must never change generated tokens — only latency."""
    cfg, params = setup
    eng0, outs0 = _run(cfg, params, slack=0.0)
    eng1, outs1 = _run(cfg, params, slack=0.5, n_threshold=2)
    assert outs0 == outs1
    assert eng1.stats["deferrals"] > 0
    assert eng1.stats["iterations"] >= eng0.stats["iterations"]


def test_deferral_saves_expert_loads(setup):
    cfg, params = setup
    eng, _ = _run(cfg, params, slack=0.5, n_threshold=1)
    assert eng.stats["expert_loads_saved"] > 0


def test_continuous_batching_matches_sequential(setup):
    """Batched decoding == one-at-a-time decoding, token for token."""
    cfg, params = setup
    _, batched = _run(cfg, params, prompts=((1, 2, 3), (4, 5, 6, 7)))
    _, solo_a = _run(cfg, params, prompts=((1, 2, 3),))
    _, solo_b = _run(cfg, params, prompts=((4, 5, 6, 7),))
    assert batched[0] == solo_a[0]
    assert batched[1] == solo_b[0]


def test_submit_rejects_oversized_requests(setup):
    """Satellite regression: len(prompt) + max_new > max_ctx raises a
    clear ValueError up front instead of silently truncating generation
    at the max_ctx - 1 boundary — on both admission paths."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=16))
    with pytest.raises(ValueError, match="max_ctx"):
        eng.submit(list(range(1, 13)), max_new=5)        # 12 + 5 > 16
    with pytest.raises(ValueError, match="max_ctx"):
        eng.submit_chunked(list(range(1, 13)), max_new=5)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new=2)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=0)
    assert len(eng.free_slots) == 2, "rejected requests hold no slot"
    # the boundary case fits (and is not truncated): 11 + 5 == 16
    rid = eng.submit(list(range(1, 12)), max_new=5)
    outs = eng.run()
    assert len(outs[rid]) == 5


def test_slot_recycling_constant_time(setup):
    """Satellite regression: slots recycle through a deque —
    admission pops left, completion appends right, both O(1)."""
    from collections import deque
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=3, max_ctx=32))
    assert isinstance(eng.free_slots, deque)
    r0 = eng.submit([1, 2], max_new=2)
    assert eng.requests[r0].slot == 0
    eng.run()
    assert list(eng.free_slots) == [1, 2, 0]             # recycled to tail
    r1 = eng.submit([3, 4], max_new=2)
    assert eng.requests[r1].slot == 1                    # FIFO slot reuse


def test_slot_lifecycle(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32))
    eng.submit([1, 2], max_new=3)
    eng.submit([3, 4], max_new=3)
    with pytest.raises(RuntimeError):
        eng.submit([5], max_new=2)
    eng.run()
    assert len(eng.free_slots) == 2          # slots reclaimed
    eng.submit([5, 6], max_new=2)            # reusable
    eng.run()


def test_trace_export(setup):
    cfg, params = setup
    eng, _ = _run(cfg, params)
    assert eng.trace, "per-layer expert counts exported for the simulator"
    rec = eng.trace[0]
    assert {"iter", "layer", "counts", "order"} <= set(rec)
    assert rec["counts"].sum() > 0
    assert sorted(rec["order"]) == list(range(cfg.moe.num_experts))


def test_mixed_length_prompts(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48))
    r1 = eng.submit([1], max_new=4)
    r2 = eng.submit(list(range(1, 20)), max_new=4)
    outs = eng.run()
    assert len(outs[r1]) == 4 and len(outs[r2]) == 4


# ---------------------------------------------------------------------------
# route-once pipeline + dynamic trajectory scheduling
# ---------------------------------------------------------------------------


def test_engine_routes_each_moe_layer_once(setup, monkeypatch):
    """The engine's gate pass IS the route stage: one gating.route call
    per MoE layer per iteration, threaded into both deferral and expert
    execution (no re-route inside moe_block).

    Pinned to the eager path: on the fused path gating.route only runs
    at trace time inside a cached compiled segment, so monkeypatch
    counting can't see it — tests/test_megastep.py has the fused
    structural counterpart."""
    from repro.core import gating
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32,
                                          fused=False))
    eng.submit([1, 2, 3], max_new=4)

    calls = []
    real_route = gating.route

    def counting_route(*a, **kw):
        calls.append(1)
        return real_route(*a, **kw)

    monkeypatch.setattr(gating, "route", counting_route)
    eng.step()
    n_moe = sum(1 for _, f in (eng._layer_kind(l) for l in range(eng.L))
                if f == "moe")
    assert n_moe > 0
    assert len(calls) == n_moe, (len(calls), n_moe)


def test_dynamic_schedule_output_invariant(setup):
    """schedule=dynamic re-orders expert execution along the EMA
    trajectory but never changes emitted tokens (the virtualization
    argument, engine-level)."""
    from repro.core.strategy import ExecutionSpec
    cfg, params = setup

    def run(spec):
        eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                              spec=spec))
        rids = [eng.submit(list(p), max_new=6) for p in ((1, 2, 3, 4),
                                                         (9, 8, 7))]
        outs = eng.run()
        return eng, [outs[r] for r in rids]

    e_s, o_s = run(ExecutionSpec(strategy="capacity"))
    e_d, o_d = run(ExecutionSpec(strategy="capacity", schedule="dynamic"))
    assert o_s == o_d
    assert e_d.stats["dynamic_schedules"] > 0
    assert e_s.stats["dynamic_schedules"] == 0
    # trace carries the executed trajectory under dynamic scheduling
    rec = [r for r in e_d.trace if "counts" in r][-1]
    assert rec["schedule"] == "dynamic"
    assert sorted(rec["trajectory"]) == list(range(cfg.moe.num_experts))
    assert [r for r in e_s.trace if "counts" in r][-1]["schedule"] == "static"
    # EMA trackers observed every MoE layer
    assert e_d.load_trackers and all(
        t.steps > 0 for t in e_d.load_trackers.values())


def test_trace_counts_use_gating_helper(setup):
    """Engine counts == gating.expert_token_counts over the active
    slots (the hand-rolled numpy loop is gone)."""
    import jax.numpy as jnp
    from repro.core import gating
    cfg, params = setup
    eng, _ = _run(cfg, params)
    rec = eng.trace[0]
    assert rec["counts"].dtype == np.int64
    assert rec["counts"].sum() > 0
    # a masked row contributes nothing
    x2d = jax.random.normal(jax.random.PRNGKey(0), (4, cfg.d_model))
    routing = gating.route(
        jax.tree.map(lambda a: a[0], params["periods"][0])["moe"]["router"],
        x2d, top_k=cfg.moe.top_k)
    m = jnp.asarray([True, False, False, False])
    assert int(gating.expert_token_counts(routing, m).sum()) == cfg.moe.top_k


def test_serveconfig_deprecated_aliases_warn_once():
    """Satellite: moe_impl / autotune aliases emit a one-shot
    DeprecationWarning and still merge into the spec."""
    import warnings as _w
    from repro.serving import engine as engine_mod
    engine_mod._ALIAS_WARNED.clear()
    with pytest.warns(DeprecationWarning, match="moe_impl"):
        sc = ServeConfig(moe_impl="dense")
    assert sc.spec.strategy == "dense"
    with pytest.warns(DeprecationWarning, match="autotune"):
        sc = ServeConfig(autotune="off")
    assert sc.spec.autotune == "off"
    with _w.catch_warnings():
        _w.simplefilter("error")               # second use is silent
        ServeConfig(moe_impl="dense", autotune="off")
    # spec-based configuration never warns
    with _w.catch_warnings():
        _w.simplefilter("error")
        ServeConfig(spec="capacity")
