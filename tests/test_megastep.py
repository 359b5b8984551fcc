"""Fused mega-step engine: bit-identity vs the legacy eager loop,
recompile guard, host-sync budget, route-once structure."""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels.ops import use_kernels
from repro.models import api
from repro.serving import Engine, ServeConfig
from repro.serving import megastep


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = ((1, 2, 3, 4), (9, 8, 7))


def _run(cfg, params, *, fused, chunked, schedule, slack=0.0, nthr=None,
         kernels=False, strategy="capacity", switch=None, **scfg):
    """Serve PROMPTS to completion; ``switch`` = (iteration, threshold)
    sets the deferral threshold after that many iterations."""
    spec = {"strategy": strategy}
    if schedule:
        spec["schedule"] = schedule
    with use_kernels(kernels):
        eng = Engine(params, cfg, ServeConfig(
            max_batch=4, max_ctx=48, fused=fused, chunk_tokens=4,
            buffering_slack=slack, theta_min=3, spec=spec, **scfg))
        if nthr:
            eng.policy.n_threshold = nthr
        sub = eng.submit_chunked if chunked else eng.submit
        rids = [sub(list(p), max_new=6) for p in PROMPTS]
        if switch is not None:
            for _ in range(switch[0]):
                eng.step()
            eng.policy.n_threshold = switch[1]
        outs = eng.run()
    return eng, [outs[r] for r in rids]


def _workload(eng):
    """The trace less its ``compile`` records, which depend on what the
    process compiled before (the two paths compile different programs)."""
    return [r for r in eng.trace if r.get("event") != "compile"]


def _assert_same(e0, o0, e1, o1):
    """Tokens AND the full workload trace must match record for record
    (counts, order, EMA trajectory, modeled seconds — everything)."""
    assert o0 == o1
    t0, t1 = _workload(e0), _workload(e1)
    assert len(t0) == len(t1)
    for a, b in zip(t0, t1):
        assert set(a) == set(b)
        for k in a:
            if isinstance(a[k], np.ndarray):
                assert (a[k] == b[k]).all(), k
            else:
                assert a[k] == b[k], k
    for k in ("deferrals", "dynamic_schedules", "tokens_emitted",
              "iterations", "expert_loads", "expert_loads_saved"):
        assert e0.stats[k] == e1.stats[k], k


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["submit", "chunked"])
@pytest.mark.parametrize("schedule", [None, "dynamic"],
                         ids=["static", "dynamic"])
def test_fused_matches_legacy(setup, chunked, schedule):
    """Same seed => bit-identical tokens and trace between the fused
    jitted path and the legacy per-layer loop (the fused segments are
    built from the very same transformer.decode_* entry points)."""
    cfg, params = setup
    e0, o0 = _run(cfg, params, fused=False, chunked=chunked,
                  schedule=schedule)
    e1, o1 = _run(cfg, params, fused=True, chunked=chunked,
                  schedule=schedule)
    _assert_same(e0, o0, e1, o1)


@pytest.mark.parametrize("schedule", [None, "dynamic"],
                         ids=["static", "dynamic"])
def test_fused_matches_legacy_kernels(setup, schedule):
    """The identity must also hold with the Pallas kernel path enabled
    (the megastep cache keys on the ambient kernel flag)."""
    cfg, params = setup
    e0, o0 = _run(cfg, params, fused=False, chunked=True,
                  schedule=schedule, kernels=True)
    e1, o1 = _run(cfg, params, fused=True, chunked=True,
                  schedule=schedule, kernels=True)
    _assert_same(e0, o0, e1, o1)


def test_fused_matches_legacy_with_deferral(setup):
    """Algorithm-2 deferral churn (changing masks every iteration) on
    the fused path still reproduces the legacy loop exactly."""
    cfg, params = setup
    e0, o0 = _run(cfg, params, fused=False, chunked=True, schedule=None,
                  slack=0.5, nthr=2)
    e1, o1 = _run(cfg, params, fused=True, chunked=True, schedule=None,
                  slack=0.5, nthr=2)
    assert e1.stats["deferrals"] > 0
    _assert_same(e0, o0, e1, o1)


def test_fused_matches_legacy_tiers_sync_free(setup):
    """The sync-free pass writes its records after the pass; those that
    read the EMA (``resident``, the hybrid ``hot`` partition, their
    modeled seconds) still match the legacy loop record for record."""
    cfg, params = setup
    kw = dict(chunked=True, schedule=None, strategy="hybrid",
              resident_budget_mb=1.0)
    e0, o0 = _run(cfg, params, fused=False, **kw)
    e1, o1 = _run(cfg, params, fused=True, **kw)
    recs = [r for r in _workload(e1) if "counts" in r]
    assert recs and all("resident" in r and "hot" in r for r in recs)
    assert e1.stats["sync_free_passes"] > 0
    _assert_same(e0, o0, e1, o1)


@pytest.mark.parametrize("switch", [(2, 2), (3, 1 << 30)],
                         ids=["deferral_on", "deferral_off"])
def test_fused_matches_legacy_deferral_switched(setup, switch):
    """The regime is read every step: lowering the threshold mid-run
    leaves the sync-free path, raising it mid-run (rows deferred
    mid-pass) enters it, and both match the legacy loop exactly."""
    cfg, params = setup
    slack = 0.0 if switch[1] < (1 << 29) else 0.5
    nthr = None if slack == 0.0 else 2
    e0, o0 = _run(cfg, params, fused=False, chunked=True, schedule=None,
                  slack=slack, nthr=nthr, switch=switch)
    e1, o1 = _run(cfg, params, fused=True, chunked=True, schedule=None,
                  slack=slack, nthr=nthr, switch=switch)
    assert e1.stats["deferrals"] > 0
    # every iteration decodes: the passes are those on the sync-free side
    assert e1.stats["sync_free_passes"] == (
        switch[0] if slack == 0.0 else e1.stats["iterations"] - switch[0])
    _assert_same(e0, o0, e1, o1)


SYNC_CASES = {
    # deferral off, static schedule: one read per decode iteration
    "sync_free": dict(),
    "dynamic": dict(spec={"strategy": "capacity", "schedule": "dynamic"}),
    # deferral armed, no row deferred yet: counts and routing indices at
    # every boundary, plus the logits
    "deferral": dict(n_threshold=1000),
}


@pytest.mark.parametrize("case", list(SYNC_CASES))
def test_steady_state_no_retrace_and_sync_budget(setup, case):
    """Steady-state decode triggers ZERO retraces in every regime, and
    the host reads once per iteration when no boundary needs a host
    decision, else once per MoE boundary plus the logits batch."""
    cfg, params = setup
    megastep._CACHE.clear()
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                          chunk_tokens=4, **SYNC_CASES[case]))
    for p in PROMPTS:
        eng.submit(list(p), max_new=12)
    eng.step()
    eng.step()                          # warmup: every segment traced
    ms = megastep.get_megastep(eng.cfg, eng.scfg)
    assert ms.traces > 0
    t0, s0 = ms.traces, eng.stats["host_syncs"]
    p0 = eng.stats["sync_free_passes"]
    n = 3
    for _ in range(n):
        eng.step()
    nb = len(ms.boundaries)
    assert nb > 0
    assert ms.traces == t0, "steady-state decode retraced a segment"
    sync_free = case == "sync_free"
    assert eng.stats["host_syncs"] - s0 == n * (1 if sync_free else nb + 1)
    assert eng.stats["sync_free_passes"] - p0 == (n if sync_free else 0)


def test_sync_free_leaves_when_threshold_lowered(setup):
    """Lowering ``policy.n_threshold`` between steps arms deferral: the
    next pass reads at every boundary and is not counted sync-free."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                          chunk_tokens=4))
    for p in PROMPTS:
        eng.submit(list(p), max_new=12)
    eng.step()
    s0, p0 = eng.stats["host_syncs"], eng.stats["sync_free_passes"]
    eng.step()
    assert eng.stats["host_syncs"] - s0 == 1
    assert eng.stats["sync_free_passes"] - p0 == 1
    eng.policy.n_threshold = 1000
    s0, p0 = eng.stats["host_syncs"], eng.stats["sync_free_passes"]
    eng.step()
    nb = len(megastep.get_megastep(eng.cfg, eng.scfg).boundaries)
    assert eng.stats["host_syncs"] - s0 == nb + 1
    assert eng.stats["sync_free_passes"] == p0


def test_fused_routes_each_moe_layer_once(setup, monkeypatch):
    """Structural route-once check for the fused path: tracing one
    decode iteration calls gating.route exactly once per MoE boundary
    (seg_first routes b0, each seg_mid routes its ending boundary,
    seg_last routes nothing) — the same Routing then drives deferral,
    the trace, and the expert execution."""
    from repro.core import gating
    cfg, params = setup
    megastep._CACHE.clear()
    calls = []
    real_route = gating.route

    def counting_route(*a, **kw):
        calls.append(1)
        return real_route(*a, **kw)

    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32))
    eng.submit([1, 2, 3], max_new=4)    # admission prefill routes too —
    monkeypatch.setattr(gating, "route", counting_route)  # count after
    eng.step()                          # traces seg_first/mid/last
    ms = megastep.get_megastep(eng.cfg, eng.scfg)
    assert len(ms.boundaries) > 0
    assert len(calls) == len(ms.boundaries), (len(calls), ms.boundaries)
    monkeypatch.undo()
    megastep._CACHE.clear()             # drop the counting-traced segments


def test_mesh_falls_back_to_legacy(setup, monkeypatch):
    """Under a distributed mesh the engine must take the eager path (a
    precomputed Routing only matches the single-process layout) even
    with fused=True — dispatch check only."""
    from repro.parallel import meshctx
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32))
    eng.submit([1, 2, 3], max_new=2)
    called = {}
    eng._step_legacy = lambda: called.setdefault("legacy", True) and []
    eng._step_fused = lambda: called.setdefault("fused", True) and []
    monkeypatch.setattr(meshctx, "get_mesh", lambda: object())
    eng.step()
    assert called == {"legacy": True}
