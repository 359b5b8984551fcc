"""Quantized expert streaming (kernels.quant) + EMA-hot weight tiering.

Tolerance contract (docs/quantization.md): the Pallas kernel must match
the *quantized* jnp oracle (`ref.streamed_moe_quant_ref` — the identical
quantize→dequantize round-trip) tightly for int8/fp8/fp32 and within a
looser bf16 bound (the kernel's h-cast before the down GEMM); the
quantized oracle itself sits within a documented relative-Frobenius
error of the fp32 reference.  Tiering is accounting-only: tokens and
trace counts are bit-identical with the tier on or off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategy import ExecutionSpec
from repro.kernels import ops, quant, ref
from repro.kernels.streamed_moe import streamed_moe_kernel

# kernel vs quantized-oracle tolerance per streamed format
KERNEL_TOL = {"fp32": 2e-5, "int8": 2e-5, "fp8": 2e-5, "bf16": 2e-2}
# quantized-oracle vs fp32-oracle relative Frobenius error ceiling
ORACLE_REL = {"fp32": 0.0, "bf16": 0.01, "int8": 0.02, "fp8": 0.06}


def _shapes(E=3, C=37, d=32, m=24, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    xe = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, m), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (E, d, m), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (E, m, d), jnp.float32) * 0.1
    return xe, wg, wu, wd


# ---------------------------------------------------------------------------
# the quant module itself
# ---------------------------------------------------------------------------


def test_weight_bytes_table():
    assert quant.weight_bytes("fp32") == 4
    assert quant.weight_bytes("bf16") == 2
    assert quant.weight_bytes("int8") == 1
    assert quant.weight_bytes("fp8") == 1
    assert quant.weight_bytes() is None
    assert quant.weight_bytes(default=2) == 2
    with quant.use_weight_dtype("int8"):
        assert quant.weight_dtype() == "int8"
        assert quant.weight_bytes(default=2) == 1
    assert quant.weight_dtype() is None


def test_unknown_weight_dtype_rejected():
    with pytest.raises(ValueError):
        quant.check_weight_dtype("int4")
    with pytest.raises(ValueError):
        ExecutionSpec(strategy="capacity", weight_dtype="e5m2")


@pytest.mark.parametrize("wd", ["int8", "fp8"])
def test_quantize_shapes_and_roundtrip(wd):
    _, wg, _, wdn = _shapes()
    q, s = quant.quantize(wg, wd)                 # (E,d,m) -> scales (E,1,m)
    assert q.shape == wg.shape and s.shape == (wg.shape[0], 1, wg.shape[2])
    assert jnp.dtype(q.dtype).itemsize == 1
    back = quant.dequantize(q, s)
    # int8 rounds to the nearest scale step (error <= scale/2); fp8 e4m3
    # carries 3 mantissa bits, so error is *relative*: <= 2^-4 of the value
    err = np.abs(np.asarray(back - wg))
    if wd == "int8":
        bound = np.asarray(s) * 0.51
    else:
        bound = np.abs(np.asarray(wg)) * 2.0 ** -4 + np.asarray(s) * 0.01
    assert (err <= bound + 1e-7).all()
    q2, s2 = quant.quantize(wdn, wd)              # (E,m,d) -> scales (E,1,d)
    assert s2.shape == (wdn.shape[0], 1, wdn.shape[2])


# ---------------------------------------------------------------------------
# kernel vs quantized oracle: activations x tilings x formats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wd", ["fp32", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("act", ["swiglu", "relu2", "gelu"])
def test_kernel_matches_quant_oracle(act, wd):
    xe, wg, wu, wd_ = _shapes()
    wg = wg if act == "swiglu" else None
    with ops.use_kernels(True):
        got = ops.streamed_moe(xe, wg, wu, wd_, act, weight_dtype=wd,
                               token_tile=16, interpret=True)
    want = ref.streamed_moe_quant_ref(xe, wg, wu, wd_, act, wd)
    tol = KERNEL_TOL[wd]
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("wd", ["int8", "fp8"])
@pytest.mark.parametrize("dm_tile,de_tile", [(128, 128), (128, 256),
                                              (256, 128)])
def test_kernel_quant_tiled_matches_oracle(wd, dm_tile, de_tile):
    """Scale side-operands must block-index correctly under d_model and
    d_expert tiling (LANE-aligned tiles; C=37 with token_tile=16 also
    covers row masking)."""
    xe, wg, wu, wd_ = _shapes(E=2, d=256, m=512)
    with ops.use_kernels(True):
        got = ops.streamed_moe(xe, wg, wu, wd_, "swiglu", weight_dtype=wd,
                               token_tile=16, dmodel_tile=dm_tile,
                               dexpert_tile=de_tile, interpret=True)
    want = ref.streamed_moe_quant_ref(xe, wg, wu, wd_, "swiglu", wd)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wd", ["bf16", "int8", "fp8"])
def test_quant_oracle_near_fp32_oracle(wd):
    """The streamed format's information loss stays within the documented
    relative-Frobenius ceiling of the exact fp32 reference."""
    xe, wg, wu, wd_ = _shapes()
    exact = np.asarray(ref.streamed_moe_ref(xe, wg, wu, wd_, "swiglu"))
    qq = np.asarray(ref.streamed_moe_quant_ref(xe, wg, wu, wd_, "swiglu", wd))
    rel = np.linalg.norm(qq - exact) / np.linalg.norm(exact)
    assert rel <= ORACLE_REL[wd], f"{wd}: rel error {rel:.4f}"


@pytest.mark.parametrize("wd", ["int8", "fp8"])
def test_ambient_dispatch_and_oracle_parity(wd):
    """ExecutionSpec.scope() threads the format ambiently: the kernel
    branch and the use_kernels(False) oracle branch agree at the kernel
    tolerance, with no explicit weight_dtype kwarg anywhere."""
    xe, wg, wu, wd_ = _shapes()
    sp = ExecutionSpec(strategy="capacity", weight_dtype=wd)
    with sp.scope(), ops.use_kernels(True):
        y_k = ops.streamed_moe(xe, wg, wu, wd_, "swiglu", interpret=True)
    with sp.scope(), ops.use_kernels(False):
        y_r = ops.streamed_moe(xe, wg, wu, wd_, "swiglu")
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-5)


def test_quantized_kernel_ships_scale_operands():
    """Gateless quantized lowering carries exactly x, w_u, w_d + 2 scale
    rows — and the weight operands enter the pallas_call at 1 byte."""
    xe, _, wu, wd_ = _shapes()

    def f(xe, wu, wd_):
        with ops.use_kernels(True):
            return ops.streamed_moe(xe, None, wu, wd_, "gelu",
                                    weight_dtype="int8", interpret=True)

    jaxpr = jax.make_jaxpr(f)(xe, wu, wd_)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    if not calls:  # custom_vjp wraps the call one level down
        for e in jaxpr.jaxpr.eqns:
            for sub in (e.params.get("call_jaxpr"), e.params.get("fun_jaxpr")):
                if sub is None:
                    continue
                sub = getattr(sub, "jaxpr", sub)
                calls += [q for q in sub.eqns
                          if q.primitive.name == "pallas_call"]
    assert calls, "expected a pallas_call in the jaxpr"
    avals = [v.aval for v in calls[0].invars]
    # tile map, used-tile count; xe, w_u, w_d, s_u, s_d
    assert len(avals) == 2 + 5
    assert sum(jnp.dtype(a.dtype).itemsize == 1 for a in avals) == 2


def test_quantized_gradients_are_straight_through():
    """The custom VJP differentiates the fp32 oracle of the *original*
    weights (STE), so grads are finite and match the unquantized ones."""
    xe, wg, wu, wd_ = _shapes()

    def loss(wu, wg, wdt):
        return jnp.sum(ops.streamed_moe(xe, wg, wu, wd_, "swiglu",
                                        weight_dtype=wdt,
                                        interpret=True) ** 2)

    with ops.use_kernels(True):
        g_q = jax.grad(loss)(wu, wg, "int8")
        g_f = jax.grad(loss)(wu, wg, None)
    assert np.isfinite(np.asarray(g_q)).all()
    # STE: same backward function, different forward residual — the only
    # difference is the cotangent from the (slightly different) output,
    # so grads track the unquantized ones loosely but globally
    g_q, g_f = np.asarray(g_q), np.asarray(g_f)
    rel = np.linalg.norm(g_q - g_f) / np.linalg.norm(g_f)
    assert rel <= 0.05, f"STE grad drifted {rel:.3f} from fp32 grad"


# ---------------------------------------------------------------------------
# planner: quantized weight bytes re-validate rank agreement
# ---------------------------------------------------------------------------


def test_mode_ranking_agrees_with_simulator_quantized():
    """Acceptance: >=80% top-choice agreement with the discrete referee
    when the streamed expert weights are 1 byte/param (int8/fp8)."""
    from repro.core.autotune import (HardwareProfile, VALIDATION_SWEEP,
                                     plan_moe)
    from repro.configs.base import MoEConfig
    from repro.sim import modes as sim_modes
    from repro.sim.hardware import ModelSpec, scaled
    hw_of = {2: scaled(1, 2), 4: scaled(2, 2), 8: scaled(2, 4)}
    agree, rows = 0, []
    for (B, S, E, de, P) in VALIDATION_SWEEP:
        hw = hw_of[P]
        profile = HardwareProfile.from_chiplet(hw)
        spec = ModelSpec("sweep", 512, de, E, 2, bytes_per_param=1)
        plan = plan_moe(B, S, 512, MoEConfig(num_experts=E, top_k=2,
                                             d_expert=de, micro_slices=4),
                        "swiglu", P, profile=profile, level="analytic",
                        weight_bytes=1)
        sim = sim_modes.rank_modes(hw, spec, B * S, B=B, S=S)
        best = min(sim, key=sim.get)
        agree += plan.mode == best
        rows.append((B, S, E, de, P, plan.mode, best))
    frac = agree / len(VALIDATION_SWEEP)
    assert frac >= 0.8, f"quantized rank agreement {frac:.2f} < 0.8: {rows}"


def test_plan_cost_drops_with_weight_bytes():
    """Halving streamed bytes must never raise the planned layer cost."""
    from repro.core.autotune import plan_moe
    from repro.configs.base import MoEConfig
    moe = MoEConfig(num_experts=16, top_k=2, d_expert=512, micro_slices=4)
    c2 = plan_moe(4, 64, 512, moe, "swiglu", 4, level="analytic",
                  weight_bytes=2).predicted_s
    c1 = plan_moe(4, 64, 512, moe, "swiglu", 4, level="analytic",
                  weight_bytes=1).predicted_s
    assert 0 < c1 <= c2


# ---------------------------------------------------------------------------
# EMA-hot expert weight tiering (serving engine accounting)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from repro.configs import reduced_config
    from repro.models import api
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, budget_mb, wd=None, schedule="dynamic"):
    from repro.serving import Engine, ServeConfig
    sp = ExecutionSpec(strategy="capacity", schedule=schedule,
                       weight_dtype=wd)
    eng = Engine(params, cfg, ServeConfig(max_batch=4, max_ctx=48, spec=sp,
                                          resident_budget_mb=budget_mb))
    rids = [eng.submit(list(p), max_new=5) for p in ((1, 2, 3, 4), (9, 8, 7))]
    outs = eng.run()
    return eng, [outs[r] for r in rids]


def test_tiering_is_bit_identical(served):
    """The tier is pure accounting: tokens, trace counts and trajectories
    are unchanged; only residency/DDR bookkeeping differs."""
    cfg, params = served
    e0, o0 = _serve(cfg, params, 0.0, wd="int8")
    e1, o1 = _serve(cfg, params, 0.05, wd="int8")
    assert o0 == o1
    r0 = [r for r in e0.trace if "counts" in r]
    r1 = [r for r in e1.trace if "counts" in r]
    assert len(r0) == len(r1)
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(a["counts"], b["counts"])
        assert a.get("trajectory") == b.get("trajectory")
        assert "resident" not in a and "resident" in b
    assert e0.stats["ddr_bytes_saved"] == 0
    assert e1.stats["ddr_bytes_saved"] > 0
    assert e1.stats["resident_weight_bytes"] > 0
    m0 = sum(r["modeled_s"] for r in r0)
    m1 = sum(r["modeled_s"] for r in r1)
    assert m1 < m0                    # resident experts skip DDR terms


def test_quantized_clock_halves_ddr(served):
    """int8 weights halve the modeled expert-weight stream vs the bf16
    default clock (DDR-bound regime, so modeled seconds drop)."""
    cfg, params = served
    e_bf, _ = _serve(cfg, params, 0.0, wd=None)
    e_q, _ = _serve(cfg, params, 0.0, wd="int8")
    assert e_q.cost_model.expert_bytes * 2 == e_bf.cost_model.expert_bytes
    m_bf = sum(r["modeled_s"] for r in e_bf.trace if "modeled_s" in r)
    m_q = sum(r["modeled_s"] for r in e_q.trace if "modeled_s" in r)
    assert m_q < m_bf


@pytest.mark.parametrize("schedule", ["dynamic", "static"])
def test_modeled_clock_agrees_with_referee_under_tiering(served, schedule):
    """Closed-form residency accounting vs the discrete replay referee at
    *partial* residency (resident < active — the regime the tier is
    for): aggregate agreement within 5%, and both sides agree the tier
    saves time."""
    from repro.sim import hardware, modes
    cfg, params = served
    e0, _ = _serve(cfg, params, 0.0, wd="int8", schedule=schedule)
    e1, _ = _serve(cfg, params, 0.05, wd="int8", schedule=schedule)
    assert 0 < e1._n_resident < cfg.moe.num_experts
    spec = hardware.spec_from_config(cfg, weight_bytes=1)
    for eng in (e0, e1):
        modeled = sum(r["modeled_s"] for r in eng.trace if "modeled_s" in r)
        referee = modes.replay_trace(hardware.PROTOTYPE_2X2, spec, eng.trace)
        assert abs(modeled - referee) / referee <= 0.05, \
            f"{schedule}: modeled {modeled:.3e} vs referee {referee:.3e}"
    ref0 = modes.replay_trace(hardware.PROTOTYPE_2X2, spec, e0.trace)
    ref1 = modes.replay_trace(hardware.PROTOTYPE_2X2, spec, e1.trace)
    assert ref1 < ref0


def test_negative_resident_budget_rejected(served):
    from repro.serving import ServeConfig
    with pytest.raises(ValueError):
        ServeConfig(resident_budget_mb=-1.0)
