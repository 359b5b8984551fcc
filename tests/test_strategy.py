"""Execution-strategy registry + ExecutionSpec: JSON round-trip, plan
identity, forced-spec bit-parity with the pre-refactor entry points for
all five families, cross-family auto planner vs the chiplet simulator,
and the no-direct-calls acceptance grep."""
import json
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import autotune, gating
from repro.core import strategy as strat
from repro.core.strategy import (FAMILIES, FAMILY_SWEEP, ExecutionSpec,
                                 StrategyContext)
from repro.models import moe as moe_mod

D_MODEL = 16


def _setup(E=8, k=2, de=32, cf=4.0, act="swiglu", shared=0):
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, capacity_factor=cf,
                    num_shared_experts=shared)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), D_MODEL, moe, act,
                              jnp.float32)
    return moe, params


# ---------------------------------------------------------------------------
# ExecutionSpec: round-trip + resolution
# ---------------------------------------------------------------------------


def test_spec_json_roundtrip_identical_plan():
    spec = ExecutionSpec(strategy="auto", prefill="fse_dp", decode="ep",
                         layer_overrides={0: "fse_dp", 3: "tp"},
                         autotune="analytic", sorted_dispatch=True)
    spec2 = ExecutionSpec.from_json(spec.to_json())
    assert spec2 == spec
    # layer override keys survive the str-keyed JSON mapping
    assert dict(spec2.layer_overrides) == {0: "fse_dp", 3: "tp"}
    # the round-tripped spec plans identically for every call site
    moe, _ = _setup()
    ctx = StrategyContext(B=2, S=16, d_model=D_MODEL, moe=moe,
                          activation="swiglu", P=4)
    for phase in (None, "prefill", "decode"):
        for layer in (None, 0, 1, 3):
            n1 = spec.resolve(phase=phase, layer=layer)
            n2 = spec2.resolve(phase=phase, layer=layer)
            assert n1 == n2
            assert strat.get_strategy(n1).plan(ctx) == \
                strat.get_strategy(n2).plan(ctx)


def test_spec_resolution_precedence():
    spec = ExecutionSpec(strategy="capacity", decode="ep",
                         layer_overrides={1: "tp"})
    assert spec.resolve() == "capacity"
    assert spec.resolve(phase="decode") == "ep"
    assert spec.resolve(phase="decode", layer=1) == "tp"
    assert spec.resolve(phase="prefill", layer=0) == "capacity"
    assert spec.strategies_used() == ("capacity", "ep", "tp")
    with pytest.raises(ValueError):
        spec.resolve(phase="warmup")


def test_spec_coerce_and_validation():
    assert ExecutionSpec.coerce(None, default="dense").strategy == "dense"
    assert ExecutionSpec.coerce("ep").strategy == "ep"
    assert ExecutionSpec.coerce({"strategy": "tp"}).strategy == "tp"
    # a partial dict keeps the caller's configured default strategy
    partial = ExecutionSpec.coerce({"autotune": "off"}, default="fse_dp")
    assert partial.strategy == "fse_dp" and partial.autotune == "off"
    spec = ExecutionSpec.coerce("fse_dp")
    assert ExecutionSpec.coerce(spec) is spec
    with pytest.raises(ValueError):
        ExecutionSpec(strategy="auto", autotune="turbo")
    with pytest.raises(ValueError):
        ExecutionSpec.from_dict({"strategy": "auto", "impl": "x"})
    with pytest.raises(KeyError):
        ExecutionSpec(strategy="warp_drive").validate()


def test_registry_contents():
    for name in ("fse_dp", "ep", "tp", "capacity", "dense", "auto"):
        s = strat.get_strategy(name)
        assert s.name == name
        assert isinstance(s, strat.MoEStrategy)
    with pytest.raises(KeyError):
        strat.get_strategy("nope")


# ---------------------------------------------------------------------------
# forced-spec execution == the pre-refactor entry points (bit-identical)
# ---------------------------------------------------------------------------


def _old_single_device(params, x, moe, act, impl):
    """The pre-refactor moe_block body for one impl (single device)."""
    shape = x.shape
    if x.ndim == 2:
        x = x[None]
    x2d = x.reshape(-1, shape[-1])
    routing = gating.route(params["router"], x2d, top_k=moe.top_k)
    if impl == "dense":
        y = moe_mod.moe_dense(params, x2d, routing, act)
    else:
        y = moe_mod.moe_capacity(params, x2d, routing, moe, act)
    y = y.reshape(x.shape)
    aux = gating.aux_load_balance_loss(routing, moe.num_experts)
    if moe.num_shared_experts:
        from repro.models.mlp import ffn
        y = y + ffn(params["shared"], x, act)
    return y.reshape(shape), aux


@pytest.mark.parametrize("family", ["dense", "capacity", "fse_dp", "ep",
                                    "tp"])
def test_forced_spec_bit_identical(family):
    """moe_block(spec=<family>) reproduces the old entry point exactly.

    Single device: fse_dp / ep / tp all take their P=1 capacity
    fallback, which the deprecated ``*_moe_3d`` shims still expose."""
    moe, params = _setup(shared=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, D_MODEL),
                          jnp.float32)
    y, aux = moe_mod.moe_block(params, x, moe, "swiglu", spec=family,
                               return_aux=True)
    if family in ("dense", "capacity"):
        y_ref, aux_ref = _old_single_device(params, x, moe, "swiglu", family)
    else:
        from repro.core import baselines, fse_dp
        old = {"fse_dp": fse_dp.fse_dp_moe_3d, "ep": baselines.ep_moe_3d,
               "tp": baselines.tp_moe_3d}[family]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            y_ref, aux_ref = old(params, x, moe, "swiglu")
        from repro.models.mlp import ffn
        y_ref = y_ref + ffn(params["shared"], x, "swiglu")
    assert np.array_equal(np.asarray(y), np.asarray(y_ref)), family
    assert np.array_equal(np.asarray(aux), np.asarray(aux_ref)), family


def test_auto_single_device_equals_capacity():
    moe, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 4, D_MODEL), jnp.float32)
    y_auto = moe_mod.moe_block(params, x, moe, "swiglu", spec="auto")
    y_cap = moe_mod.moe_block(params, x, moe, "swiglu", spec="capacity")
    assert np.array_equal(np.asarray(y_auto), np.asarray(y_cap))


def test_deprecated_shims_warn_once(monkeypatch):
    from repro.core import baselines, fse_dp
    moe, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 4, D_MODEL), jnp.float32)
    monkeypatch.setattr(strat, "_ENTRY_WARNED", set())
    for fn in (fse_dp.fse_dp_moe_3d, baselines.ep_moe_3d,
               baselines.tp_moe_3d):
        with pytest.warns(DeprecationWarning):
            fn(params, x, moe, "swiglu")
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # second call is silent
            fn(params, x, moe, "swiglu")


# ---------------------------------------------------------------------------
# spec-scoped toggles
# ---------------------------------------------------------------------------


def test_spec_scopes_kernels_and_dispatch():
    moe, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, D_MODEL), jnp.float32)
    y_ref = moe_mod.moe_block(
        params, x, moe, "swiglu",
        spec=ExecutionSpec(strategy="capacity", use_kernels=False))
    from repro.kernels import ops as kops
    with kops.use_kernels(False):
        y_plain = moe_mod.moe_block(params, x, moe, "swiglu", spec="capacity")
    assert np.array_equal(np.asarray(y_ref), np.asarray(y_plain))
    # sorted dispatch through the spec == the explicit context toggle (on
    # a padded-layout path: capacity always sorts into the ragged layout)
    y_sorted = moe_mod.moe_block(
        params, x, moe, "swiglu",
        spec=ExecutionSpec(strategy="hybrid", sorted_dispatch=True))
    with moe_mod.use_sorted_dispatch(True):
        y_ctx = moe_mod.moe_block(params, x, moe, "swiglu", spec="hybrid")
    assert np.array_equal(np.asarray(y_sorted), np.asarray(y_ctx))


def test_spec_autotune_level_scoped():
    spec = ExecutionSpec(strategy="capacity", autotune="off")
    with spec.scope():
        assert autotune.autotune_level() == "off"


# ---------------------------------------------------------------------------
# cross-family auto planner vs the chiplet simulator (acceptance gate)
# ---------------------------------------------------------------------------


def _hw(P):
    from repro.sim.hardware import scaled
    return {2: scaled(1, 2), 4: scaled(2, 2), 8: scaled(2, 4)}[P]


def test_family_ranking_agrees_with_simulator():
    from repro.sim import modes as sim_modes
    from repro.sim.hardware import ModelSpec
    assert len(FAMILY_SWEEP) >= 12
    agree, rows = 0, []
    for (B, S, E, de, P) in FAMILY_SWEEP:
        hw = _hw(P)
        profile = autotune.HardwareProfile.from_chiplet(hw)
        moe = MoEConfig(num_experts=E, top_k=2, d_expert=de)
        costs = strat.family_costs(B, S, 512, moe, "swiglu", P,
                                   profile=profile)
        chosen = strat.pick_family(costs)
        sim = sim_modes.rank_families(hw, ModelSpec("s", 512, de, E, 2),
                                      B * S, B=B, S=S)
        best = min((f for f in FAMILIES if f in sim), key=lambda f: sim[f])
        agree += chosen == best
        rows.append((B, S, E, de, P, chosen, best))
    frac = agree / len(FAMILY_SWEEP)
    assert frac >= 0.8, f"family rank agreement {frac:.2f} < 0.8: {rows}"


def test_family_sweep_exercises_all_families():
    """The referee must not be degenerate: each family wins somewhere.

    On homogeneous hardware only the BASE_FAMILIES race (``hybrid``
    needs a near-memory tier — its own sweep lives in
    tests/test_hybrid.py)."""
    from repro.sim import modes as sim_modes
    from repro.sim.hardware import ModelSpec
    winners = set()
    for (B, S, E, de, P) in FAMILY_SWEEP:
        sim = sim_modes.rank_families(_hw(P), ModelSpec("s", 512, de, E, 2),
                                      B * S, B=B, S=S)
        assert "hybrid" not in sim          # no NDP tier on this hardware
        winners.add(min((f for f in FAMILIES if f in sim),
                        key=lambda f: sim[f]))
    assert winners == set(strat.BASE_FAMILIES)


def test_plan_family_off_level_routes_through_registry():
    moe, _ = _setup()
    plan = strat.plan_family(4, 16, 512, moe, "swiglu", 4, level="off")
    assert plan.family == "fse_dp" and plan.source == "fallback"
    assert plan.mode == "stream"            # the legacy static heuristic
    # P=1 resolves to the capacity fallback family
    plan1 = strat.plan_family(4, 16, 512, moe, "swiglu", 1)
    assert plan1.family == "capacity"


def test_auto_plan_carries_family_breakdown():
    moe = MoEConfig(num_experts=16, top_k=2, d_expert=512)
    profile = autotune.HardwareProfile.from_chiplet(_hw(4))
    ctx = StrategyContext(B=8, S=1, d_model=512, moe=moe,
                          activation="swiglu", P=4, profile=profile)
    plan = strat.get_strategy("auto").plan(ctx)
    assert plan.family in FAMILIES
    assert plan.family in dict(plan.per_mode_s)   # cost breakdown attached
    assert plan.predicted_s > 0


def test_ep_feasibility_rules():
    assert strat.ep_feasible(B=8, S=1, E=16, P=4)     # batch-shardable
    assert strat.ep_feasible(B=1, S=8, E=16, P=4)     # seq-shardable
    assert not strat.ep_feasible(B=3, S=1, E=16, P=4)  # neither divides
    assert not strat.ep_feasible(B=8, S=8, E=12, P=8)  # experts don't split
    assert not strat.ep_feasible(B=8, S=8, E=16, P=1)  # no model axis


# ---------------------------------------------------------------------------
# acceptance grep: the five families are reachable only via the registry
# ---------------------------------------------------------------------------


def test_no_direct_moe3d_calls_outside_shims():
    """`grep` gate from the issue: no ``*_moe_3d(`` call sites outside the
    one-line deprecation shims (defs + shim bodies in core/fse_dp.py and
    core/baselines.py; this test calls them via getattr only)."""
    root = os.path.join(os.path.dirname(__file__), "..")
    allowed = {os.path.normpath(p) for p in
               ("src/repro/core/fse_dp.py", "src/repro/core/baselines.py",
                "src/repro/core/__init__.py", "tests/test_strategy.py")}
    pat = re.compile(r"\b(?:fse_dp|ep|tp)_moe_3d\s*\(")
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.normpath(os.path.relpath(path, root))
                if rel in allowed:
                    continue
                with open(path) as f:
                    for i, line in enumerate(f, 1):
                        if pat.search(line):
                            offenders.append(f"{rel}:{i}: {line.strip()}")
    assert not offenders, "direct *_moe_3d calls outside the shims:\n" + \
        "\n".join(offenders)
