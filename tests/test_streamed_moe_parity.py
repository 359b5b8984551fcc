"""CPU-interpret parity for the streamed-MoE Pallas kernel and its
``kernels.ops`` dispatch layer: all three activations vs the jnp oracle,
native gateless lowering, d_model/d_expert tiling, capacity-row padding,
gradients, the ragged layout (empty groups, one expert taking every
row, a trajectory order, int8/fp8 scales, unrouted experts never read),
and the single-device model paths under use_kernels on/off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import gating
from repro.kernels import ops, ref
from repro.kernels.streamed_moe import streamed_moe_kernel
from repro.models import moe as moe_mod


def _shapes(E=3, C=37, d=32, m=24, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    xe = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, m), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (E, d, m), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (E, m, d), jnp.float32) * 0.1
    return xe, wg, wu, wd


@pytest.mark.parametrize("act", ["swiglu", "relu2", "gelu"])
def test_kernel_matches_ref_all_activations(act):
    """Satellite: gateless activations pass w_g=None natively."""
    xe, wg, wu, wd = _shapes()
    wg = wg if act == "swiglu" else None
    got = streamed_moe_kernel(xe, wg, wu, wd, activation=act, token_tile=16,
                              interpret=True)
    want = ref.streamed_moe_ref(xe, wg, wu, wd, act)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("act", ["swiglu", "relu2", "gelu"])
@pytest.mark.parametrize("dm_tile,de_tile", [(128, 128), (128, 256),
                                              (256, 128)])
def test_kernel_tiled_matches_ref(act, dm_tile, de_tile):
    """Micro-slices larger than one VMEM block lower via d_model/m tiling
    (LANE-aligned tiles, so the widths are multiples of 128); C=37 with
    token_tile=16 also exercises padded-row masking."""
    xe, wg, wu, wd = _shapes(E=2, d=256, m=512)
    wg = wg if act == "swiglu" else None
    got = streamed_moe_kernel(xe, wg, wu, wd, activation=act, token_tile=16,
                              dmodel_tile=dm_tile, dexpert_tile=de_tile,
                              interpret=True)
    want = ref.streamed_moe_ref(xe, wg, wu, wd, act)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_gateless_ships_no_placeholder_operand():
    """relu2/gelu must not lower a w_gate operand at all (the old kernel
    shipped w_u twice as a placeholder, doubling HBM→VMEM traffic)."""
    xe, _, wu, wd = _shapes()
    jaxpr = jax.make_jaxpr(
        lambda xe, wu, wd: streamed_moe_kernel(
            xe, None, wu, wd, activation="gelu", interpret=True))(xe, wu, wd)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert calls, "expected a pallas_call in the jaxpr"
    # the scalar-prefetched tile map and used-tile count, then xe, w_u,
    # w_d — no placeholder
    assert len(calls[0].invars) == 2 + 3


def test_swiglu_requires_gate():
    xe, _, wu, wd = _shapes()
    with pytest.raises(ValueError):
        streamed_moe_kernel(xe, None, wu, wd, activation="swiglu")
    with pytest.raises(ValueError):
        ref.streamed_moe_ref(xe, None, wu, wd, "swiglu")


@pytest.mark.parametrize("act", ["swiglu", "gelu"])
def test_ops_dispatch_parity_and_grads(act):
    """ops.streamed_moe: kernel branch (fwd + custom-VJP bwd) matches the
    use_kernels(False) oracle branch."""
    xe, wg, wu, wd = _shapes()
    wg = wg if act == "swiglu" else None

    def loss(wu, wg):
        return jnp.sum(ops.streamed_moe(xe, wg, wu, wd, act) ** 2)

    with ops.use_kernels(True):
        y_k = ops.streamed_moe(xe, wg, wu, wd, act, interpret=True)
        g_k = jax.grad(loss)(wu, wg)
    with ops.use_kernels(False):
        y_r = ops.streamed_moe(xe, wg, wu, wd, act)
        g_r = jax.grad(loss)(wu, wg)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(g_k, g_r, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("capacity_factor", [1.0, 4.0])
def test_moe_capacity_kernel_parity(act, capacity_factor):
    """Single-device capacity path flows through the dispatch layer and is
    bit-compatible (within fp32 tolerance) across kernel on/off, with
    pairs dropped at finite capacity (cf 1) and none dropped (cf = E)."""
    moe = MoEConfig(num_experts=4, top_k=2, d_expert=24,
                    capacity_factor=capacity_factor)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), 16, moe, act, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (21, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=moe.top_k)
    with ops.use_kernels(True):
        y_k = moe_mod.moe_capacity(params, x, r, moe, act)
    with ops.use_kernels(False):
        y_r = moe_mod.moe_capacity(params, x, r, moe, act)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the ragged layout: rows grouped by expert, tiles of one expert each
# ---------------------------------------------------------------------------


def _ragged(group_rows, Tc=8, E=5, d=32, m=24, key=3, order=None):
    """xs (N,Tc,d) with ``group_rows[g]`` rows in group g (zero-padded to
    tiles, two spare tiles at the end), padded sizes, and weights."""
    from repro.kernels.streamed_moe import SUBLANE
    sizes = np.array([-(-n // Tc) * Tc for n in group_rows], np.int32)
    N = int(sizes.sum()) // Tc + 2
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    x = np.zeros((N * Tc, d), np.float32)
    xr = np.asarray(jax.random.normal(ks[0], (N * Tc, d), jnp.float32))
    at = 0
    for n, sz in zip(group_rows, sizes):
        x[at:at + n] = xr[at:at + n]
        at += int(sz)
    wg = jax.random.normal(ks[1], (E, d, m), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (E, d, m), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (E, m, d), jnp.float32) * 0.1
    assert Tc % SUBLANE == 0
    return (jnp.asarray(x.reshape(N, Tc, d)), jnp.asarray(sizes),
            None if order is None else jnp.asarray(order, jnp.int32),
            wg, wu, wd)


# rows per group (layout order) and the expert of each group
_RAGGED_CASES = {
    "zero_rows": ([3, 0, 11, 0, 1], None),          # experts 1, 3 have none
    "one_expert": ([0, 0, 0, 21, 0], None),         # every row to expert 3
    "trajectory": ([2, 9, 0, 4, 1], [3, 0, 4, 1, 2]),  # groups permuted
}


@pytest.mark.parametrize("act", ["swiglu", "relu2", "gelu"])
@pytest.mark.parametrize("case", sorted(_RAGGED_CASES))
def test_ragged_kernel_matches_ref(act, case):
    """Every group's rows through its own expert, zero rows past the
    groups, against the ragged_dot oracle."""
    from repro.kernels.streamed_moe import ragged_moe_kernel
    rows, order = _RAGGED_CASES[case]
    xs, sizes, order, wg, wu, wd = _ragged(rows, order=order)
    wg = wg if act == "swiglu" else None
    got = ragged_moe_kernel(xs, sizes, order, wg, wu, wd, activation=act,
                            interpret=True)
    want = ref.ragged_moe_ref(xs, sizes, order, wg, wu, wd, act)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    used = int(sizes.sum())
    assert not np.asarray(got).reshape(-1, xs.shape[-1])[used:].any()


@pytest.mark.parametrize("case", sorted(_RAGGED_CASES))
def test_ragged_kernel_tiled_matches_ref(case):
    """d_model and d_expert tiled (two LANE-wide tiles each): unused
    tiles hold every block index at the last used step's."""
    from repro.kernels.streamed_moe import ragged_moe_kernel
    rows, order = _RAGGED_CASES[case]
    xs, sizes, order, wg, wu, wd = _ragged(rows, order=order, d=256, m=256)
    got = ragged_moe_kernel(xs, sizes, order, wg, wu, wd,
                            activation="swiglu", dmodel_tile=128,
                            dexpert_tile=128, interpret=True)
    want = ref.ragged_moe_ref(xs, sizes, order, wg, wu, wd, "swiglu")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wdt", ["int8", "fp8"])
@pytest.mark.parametrize("act", ["swiglu", "gelu"])
def test_ragged_kernel_quantized_matches_quant_ref(wdt, act):
    """int8/fp8 weights with their scale blocks, indexed by the tile
    map like the weights."""
    rows, order = _RAGGED_CASES["trajectory"]
    xs, sizes, order, wg, wu, wd = _ragged(rows, order=order)
    wg = wg if act == "swiglu" else None
    with ops.use_kernels(True):
        got = ops.ragged_moe(xs, sizes, order, wg, wu, wd, act,
                             weight_dtype=wdt, interpret=True)
    want = ref.ragged_moe_quant_ref(xs, sizes, order, wg, wu, wd, act, wdt)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ragged_kernel_never_reads_unrouted_experts():
    """NaN weights in every expert without a group change nothing: their
    blocks are never read (tiles past the used count included)."""
    from repro.kernels.streamed_moe import ragged_moe_kernel
    rows, order = _RAGGED_CASES["zero_rows"]
    xs, sizes, _, wg, wu, wd = _ragged(rows)
    idle = np.array([n == 0 for n in rows])
    poison = lambda w: jnp.where(idle[:, None, None], jnp.nan, w)
    clean = ragged_moe_kernel(xs, sizes, None, wg, wu, wd,
                              activation="swiglu", interpret=True)
    got = ragged_moe_kernel(xs, sizes, None, poison(wg), poison(wu),
                            poison(wd), activation="swiglu", interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("act", ["swiglu", "gelu"])
def test_ragged_ops_parity_and_grads(act):
    """ops.ragged_moe: kernel branch (fwd + custom-VJP bwd through the
    ragged oracle) matches the use_kernels(False) branch."""
    rows, order = _RAGGED_CASES["trajectory"]
    xs, sizes, order, wg, wu, wd = _ragged(rows, order=order)
    wg = wg if act == "swiglu" else None

    def loss(xs, wu, wg):
        return jnp.sum(ops.ragged_moe(xs, sizes, order, wg, wu, wd, act,
                                      interpret=True) ** 2)

    with ops.use_kernels(True):
        y_k = ops.ragged_moe(xs, sizes, order, wg, wu, wd, act,
                             interpret=True)
        g_k = jax.grad(loss, (0, 1, 2))(xs, wu, wg)
    with ops.use_kernels(False):
        y_r = ops.ragged_moe(xs, sizes, order, wg, wu, wd, act)
        g_r = jax.grad(loss, (0, 1, 2))(xs, wu, wg)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-5)
    for a, b in zip(g_k, g_r):
        if a is not None:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("pairs,E,want", [
    (8, 32, 8), (48, 128, 8), (48, 64, 8), (64, 32, 8),   # decode
    (512, 32, 32), (3072, 128, 64), (3072, 64, 128),       # prefill
    (20000, 8, 128),                                       # capped
])
def test_ragged_token_tile_follows_rows(pairs, E, want):
    from repro.kernels.streamed_moe import ragged_tiles, ragged_token_tile
    Tc = ragged_token_tile(pairs, E)
    assert Tc == want
    # N tiles hold the worst case: all but one hit expert take one row
    G = min(E, pairs)
    assert ragged_tiles(pairs, E, Tc) >= G - 1 + -(-(pairs - G + 1) // Tc)


def test_fse_dp_single_device_kernel_parity():
    """fse_dp strategy without a mesh (P=1 capacity fallback), kernels on/off."""
    from repro.core import strategy
    moe = MoEConfig(num_experts=4, top_k=2, d_expert=32, capacity_factor=2.0)
    params = moe_mod.moe_init(jax.random.PRNGKey(2), 16, moe, "swiglu",
                              jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 16), jnp.float32)
    with ops.use_kernels(True):
        y_k, aux_k = strategy.execute("fse_dp", params, x, moe, "swiglu")
    with ops.use_kernels(False):
        y_r, aux_r = strategy.execute("fse_dp", params, x, moe, "swiglu")
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(aux_k, aux_r, rtol=1e-6)


def test_kernel_micro_slice_sum_order_invariant():
    """Σ over permuted d_expert micro-slices == whole-expert FFN through the
    new tiled kernel (FSE-DP virtualization property)."""
    from repro.kernels.streamed_moe import resolve_tiles
    E, C, d, de, M = 2, 19, 256, 48, 4
    xe, wg, wu, wd = _shapes(E=E, C=C, d=d, m=de, key=7)
    full = ref.streamed_moe_ref(xe, wg, wu, wd, "swiglu")
    mic = de // M
    # d_model really is tiled (two LANE-wide tiles), not rounded to whole
    assert resolve_tiles(C, d, mic, gated=True, x_bytes=4, w_bytes=4,
                         token_tile=8, dmodel_tile=128)[1] == 128
    parts = [streamed_moe_kernel(
        xe, wg[..., i * mic:(i + 1) * mic], wu[..., i * mic:(i + 1) * mic],
        wd[:, i * mic:(i + 1) * mic, :], activation="swiglu", token_tile=8,
        dmodel_tile=128, interpret=True)
        for i in np.random.default_rng(0).permutation(M)]
    np.testing.assert_allclose(sum(parts), full, rtol=3e-5, atol=3e-5)


# (E, d_model, d_expert) of the MoE configs whose widths the old
# any-divisor rule and per-block VMEM cap got wrong on v5e
_REAL_WIDTHS = [(32, 1024, 512),       # granite-moe-1b-a400m
                (128, 2048, 768),      # qwen3-30b-a3b
                (64, 2048, 1408)]      # deepseek-moe-16b


@pytest.mark.parametrize("dim,req,want", [
    (1408, 1024, 128),    # 704 divides 1408 but is not LANE-aligned
    (768, 512, 384), (768, 768, 768), (2048, 1000, 512),
    (1408, 64, 128),      # below the smallest aligned tile: round up
    (24, 8, 24),          # no LANE-aligned divisor: the full dim
])
def test_fit_tile_is_lane_aligned_or_full(dim, req, want):
    from repro.kernels.streamed_moe import fit_tile
    assert fit_tile(dim, req) == want


@pytest.mark.parametrize("E,d,m", _REAL_WIDTHS)
@pytest.mark.parametrize("C", [1, 8, 64, 4096])
@pytest.mark.parametrize("w_bytes", [2, 1])
def test_default_tiles_fit_the_vmem_budget(E, d, m, C, w_bytes):
    """Every default tile obeys the Mosaic block rule and the whole
    working set (double-buffered blocks, output, scratch) fits the
    scoped-VMEM budget."""
    from repro.kernels.streamed_moe import (LANE, SUBLANE, VMEM_BUDGET_BYTES,
                                            default_tiles, vmem_bytes)
    Tc, Tj, Tk = default_tiles(C, d, m, gated=True, w_bytes=w_bytes)
    assert Tc == C or Tc % SUBLANE == 0
    for t, dim in ((Tj, d), (Tk, m)):
        assert dim % t == 0 and (t % LANE == 0 or t == dim)
    assert vmem_bytes(Tc, Tj, Tk, True, 2, w_bytes) <= VMEM_BUDGET_BYTES
