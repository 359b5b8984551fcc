"""Cost-model autotuner: rank agreement vs the chiplet simulator, VMEM
budget discipline, feasibility, fallback parity, and the measured cache."""
import json
import os

import pytest

from _hyp import given, settings, st

from repro.core import autotune as at
from repro.core.autotune import (HardwareProfile, Plan, VALIDATION_SWEEP,
                                 feasible_modes, plan_kernel_tiles, plan_moe)
from repro.configs.base import MoEConfig
from repro.sim import modes as sim_modes
from repro.sim.hardware import ModelSpec, scaled

D_MODEL = 512


def _hw(P):
    return {2: scaled(1, 2), 4: scaled(2, 2), 8: scaled(2, 4)}[P]


def _moe(E, de, micro=4):
    return MoEConfig(num_experts=E, top_k=2, d_expert=de, micro_slices=micro)


# ---------------------------------------------------------------------------
# acceptance criterion: >=80% top-choice agreement with the simulator on a
# >=12-point (B, S, E, d_expert, P) sweep
# ---------------------------------------------------------------------------


def test_mode_ranking_agrees_with_simulator():
    assert len(VALIDATION_SWEEP) >= 12
    agree, rows = 0, []
    for (B, S, E, de, P) in VALIDATION_SWEEP:
        hw = _hw(P)
        profile = HardwareProfile.from_chiplet(hw)
        spec = ModelSpec("sweep", D_MODEL, de, E, 2)
        plan = plan_moe(B, S, D_MODEL, _moe(E, de), "swiglu", P,
                        profile=profile, level="analytic")
        sim = sim_modes.rank_modes(hw, spec, B * S, B=B, S=S)
        best = min(sim, key=sim.get)
        agree += plan.mode == best
        rows.append((B, S, E, de, P, plan.mode, best))
    frac = agree / len(VALIDATION_SWEEP)
    assert frac >= 0.8, f"rank agreement {frac:.2f} < 0.8: {rows}"


def test_sweep_exercises_all_three_modes():
    """The referee itself must not be degenerate: each mode wins somewhere."""
    winners = set()
    for (B, S, E, de, P) in VALIDATION_SWEEP:
        sim = sim_modes.rank_modes(_hw(P), ModelSpec("s", D_MODEL, de, E, 2),
                                   B * S, B=B, S=S)
        winners.add(min(sim, key=sim.get))
    assert winners == {"stream", "index", "slice"}


# ---------------------------------------------------------------------------
# VMEM budget + feasibility discipline
# ---------------------------------------------------------------------------


def _check_plan_invariants(B, S, E, de, P, profile):
    plan = plan_moe(B, S, D_MODEL, _moe(E, de), "swiglu", P,
                    profile=profile, level="analytic")
    assert plan.mode in feasible_modes(B, S, P)
    assert plan.vmem_bytes <= profile.vmem_bytes, \
        f"plan {plan} exceeds VMEM budget {profile.vmem_bytes}"
    de_loc = max(1, de // P)
    assert 1 <= plan.micro_slices <= de_loc
    assert de_loc % plan.micro_slices == 0
    return plan


def test_plan_respects_vmem_budget_sweep():
    profile = HardwareProfile.from_chiplet(_hw(4))
    for (B, S, E, de, P) in VALIDATION_SWEEP:
        _check_plan_invariants(B, S, E, de, P,
                               HardwareProfile.from_chiplet(_hw(P)))
    # a deliberately tiny budget still yields a fitting plan
    tight = HardwareProfile(name="tight", peak_flops=profile.peak_flops,
                            mem_bw=profile.mem_bw, link_bw=profile.link_bw,
                            link_latency=profile.link_latency,
                            vmem_bytes=256 * 1024)
    for (B, S, E, de, P) in VALIDATION_SWEEP[:6]:
        _check_plan_invariants(B, S, E, de, P, tight)


@given(B=st.integers(1, 64), S=st.integers(1, 512),
       E=st.sampled_from([4, 8, 16, 32, 64]),
       de=st.sampled_from([64, 128, 256, 512, 1024]),
       P=st.sampled_from([2, 4, 8]))
@settings(max_examples=60, deadline=None)
def test_plan_invariants_property(B, S, E, de, P):
    _check_plan_invariants(B, S, E, de, P, HardwareProfile.from_chiplet(_hw(P)))


def test_infeasible_modes_never_selected():
    profile = HardwareProfile.from_chiplet(_hw(4))
    # S=3 < P and B*S % P != 0 -> only slice lowers
    plan = plan_moe(5, 3, D_MODEL, _moe(16, 512), "swiglu", 4,
                    profile=profile, level="analytic")
    assert plan.mode == "slice"
    with pytest.raises(ValueError):
        plan_moe(5, 3, D_MODEL, _moe(16, 512), "swiglu", 4,
                 profile=profile, mode="stream")


# ---------------------------------------------------------------------------
# fallback ('off') == the legacy pick_mode heuristic
# ---------------------------------------------------------------------------


def test_level_off_matches_legacy_heuristic():
    for (B, S, E, de, P) in VALIDATION_SWEEP:
        moe = _moe(E, de)
        plan = plan_moe(B, S, D_MODEL, moe, "swiglu", P, level="off")
        if S % P == 0 and S >= P:
            legacy = "stream"
        elif (B * S) % P == 0:
            legacy = "index"
        else:
            legacy = "slice"
        assert plan.mode == legacy
        assert plan.micro_slices == moe.micro_slices
        assert plan.source == "fallback"
        assert plan.kernel_opts() == {}


def test_pick_mode_deprecated_one_shot(monkeypatch):
    """pick_mode warns exactly once per process and is gone from the
    repro.core namespace (the registry is the API)."""
    import warnings

    from repro.core import fse_dp
    import repro.core as core_pkg
    assert not hasattr(core_pkg, "pick_mode")
    monkeypatch.setattr(at, "_PICK_MODE_WARNED", False)
    with pytest.warns(DeprecationWarning):
        assert fse_dp.pick_mode(4, 16, 4) == "stream"
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second call must be silent
        assert at.pick_mode(5, 3, 4) == "slice"


def test_kernel_opts_off_is_empty():
    assert at.kernel_opts_for(8, 16, 64, 32, "swiglu", level="off") == {}


# ---------------------------------------------------------------------------
# tile planner
# ---------------------------------------------------------------------------


def test_tpu_profile_reads_published_peaks_and_refuses_unknown_kinds():
    v5e = HardwareProfile.from_tpu("TPU v5 lite")
    assert (v5e.peak_flops, v5e.mem_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        HardwareProfile.from_tpu("TPU v99")


def test_tile_planner_prefers_defaults_when_they_fit():
    profile = HardwareProfile.from_tpu("TPU v5 lite")
    tiles = plan_kernel_tiles(8, 64, 256, 128, "swiglu", profile)
    assert tiles["fits"]
    assert tiles["dmodel_tile"] is None          # d_model kept whole
    assert tiles["vmem_bytes"] <= profile.vmem_bytes


def test_tile_planner_shrinks_under_tiny_budget():
    profile = HardwareProfile(name="tiny", peak_flops=1e12, mem_bw=1e11,
                              link_bw=1e11, link_latency=1e-8,
                              vmem_bytes=2 * 2 ** 20)
    tiles = plan_kernel_tiles(8, 256, 1024, 1024, "swiglu", profile)
    big = plan_kernel_tiles(8, 256, 1024, 1024, "swiglu",
                            HardwareProfile.from_tpu("TPU v5 lite"))
    assert tiles["vmem_bytes"] < big["vmem_bytes"]


# ---------------------------------------------------------------------------
# measured autotune cache
# ---------------------------------------------------------------------------


def test_measured_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    monkeypatch.setattr(at, "_MEASURED", {})
    monkeypatch.setattr(at, "_CACHE_LOADED", False)
    entry = at.measured_kernel_tiles(2, 8, 32, 16, "swiglu",
                                     dtype_bytes=4, reps=1)
    assert entry["ms"] > 0
    assert "candidates" in entry and len(entry["candidates"]) >= 1
    path = os.path.join(str(tmp_path), "kernel_tiles.json")
    assert os.path.exists(path)
    with open(path) as f:
        disk = json.load(f)
    assert len(disk) == 1
    # second call is a pure cache hit (no re-timing): identical object
    again = at.measured_kernel_tiles(2, 8, 32, 16, "swiglu",
                                     dtype_bytes=4, reps=1)
    assert again is entry


# ---------------------------------------------------------------------------
# plan plumbing
# ---------------------------------------------------------------------------


def test_plan_kernel_opts_roundtrip():
    p = Plan(mode="stream", micro_slices=2, token_tile=64, dexpert_tile=16)
    assert p.kernel_opts() == {"token_tile": 64, "dexpert_tile": 16}
    p = Plan(mode="slice", micro_slices=1)
    assert p.kernel_opts() == {}


def test_forced_mode_plans_cover_all_modes():
    profile = HardwareProfile.from_chiplet(_hw(4))
    for mode in ("stream", "index", "slice"):
        plan = plan_moe(2, 16, D_MODEL, _moe(8, 64), "swiglu", 4,
                        profile=profile, mode=mode)
        assert plan.mode == mode
        assert plan.source == "forced"
