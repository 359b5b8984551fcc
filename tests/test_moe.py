"""MoE block: routing properties (hypothesis), dense == per-token loop
oracle, capacity semantics, shared experts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs.base import MoEConfig
from repro.core import gating
from repro.models import moe as moe_mod


def _setup(E=8, k=2, d=16, de=32, cf=4.0, act="swiglu", shared=0):
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, capacity_factor=cf,
                    num_shared_experts=shared)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), d, moe, act, jnp.float32)
    return moe, params


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(2, 16), st.integers(1, 4))
def test_routing_properties(T, E, k):
    k = min(k, E)
    d = 8
    p = gating.router_init(jax.random.PRNGKey(E * 100 + k), d, E, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(T), (T, d), jnp.float32)
    r = gating.route(p, x, top_k=k)
    assert r.indices.shape == (T, k)
    # weights renormalized
    np.testing.assert_allclose(np.asarray(r.weights).sum(-1), 1.0, rtol=1e-4)
    # combine rows sum to 1 and have exactly k nonzeros
    comb = np.asarray(r.combine)
    np.testing.assert_allclose(comb.sum(-1), 1.0, rtol=1e-4)
    assert ((comb > 0).sum(-1) <= k).all()
    # full probs are a distribution
    np.testing.assert_allclose(np.asarray(r.probs).sum(-1), 1.0, rtol=1e-4)


def test_dense_matches_per_token_loop():
    moe, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=moe.top_k)
    y = moe_mod.moe_dense(params, x, r, "swiglu")

    # per-token oracle
    idx = np.asarray(r.indices)
    w = np.asarray(r.weights)
    y_ref = np.zeros_like(np.asarray(y))
    for t in range(10):
        for j in range(moe.top_k):
            e = idx[t, j]
            xe = x[t][None]
            h = jax.nn.silu(xe @ params["w_gate"][e]) * (xe @ params["w_up"][e])
            y_ref[t] += w[t, j] * np.asarray(h @ params["w_down"][e])[0]
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)


def test_capacity_matches_dense_with_ample_capacity():
    moe, params = _setup(cf=8.0 / 2.0)        # C >= T: nothing drops
    x = jax.random.normal(jax.random.PRNGKey(2), (12, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=moe.top_k)
    y_c = moe_mod.moe_capacity(params, x, r, moe, "swiglu")
    y_d = moe_mod.moe_dense(params, x, r, "swiglu")
    np.testing.assert_allclose(y_c, y_d, rtol=2e-4, atol=2e-4)


def test_capacity_drops_overflow():
    """With capacity 1 token per expert, overflow tokens contribute 0."""
    moe, params = _setup(E=2, k=1, cf=2.0 / 16.0)   # C = 1 for T=16
    x = jnp.ones((16, 16), jnp.float32)             # identical tokens -> same expert
    r = gating.route(params["router"], x, top_k=1)
    y = moe_mod.moe_capacity(params, x, r, moe, "swiglu")
    nz = np.abs(np.asarray(y)).sum(-1) > 1e-9
    assert nz.sum() == 1                            # only the first survives


def test_aux_loss_uniform_vs_skewed():
    r_uniform = gating.Routing(
        indices=jnp.arange(8).reshape(8, 1) % 4,
        weights=jnp.ones((8, 1)),
        probs=jnp.full((8, 4), 0.25),
        combine=jax.nn.one_hot(jnp.arange(8) % 4, 4))
    r_skew = gating.Routing(
        indices=jnp.zeros((8, 1), jnp.int32),
        weights=jnp.ones((8, 1)),
        probs=jnp.eye(4)[jnp.zeros(8, jnp.int32)],
        combine=jax.nn.one_hot(jnp.zeros(8, jnp.int32), 4))
    assert float(gating.aux_load_balance_loss(r_skew, 4)) > \
        float(gating.aux_load_balance_loss(r_uniform, 4))


def test_shared_experts_added():
    moe, params = _setup(shared=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 16), jnp.float32)
    y = moe_mod.moe_block(params, x, moe, "swiglu", impl="dense")
    params2 = dict(params)
    params2["shared"] = jax.tree.map(jnp.zeros_like, params["shared"])
    y2 = moe_mod.moe_block(params2, x, moe, "swiglu", impl="dense")
    assert not np.allclose(np.asarray(y), np.asarray(y2))


@pytest.mark.parametrize("impl", ["dense", "capacity"])
@pytest.mark.parametrize("shared", [0, 1])
def test_moe_block_2d_matches_3d(impl, shared):
    """(T,d) input == the (1,T,d) path, exact values and shape — the
    regression test for the old double-reshape around the shared-expert
    add (2-D x reshaped to 3-D and back must change nothing)."""
    moe, params = _setup(shared=shared)
    x2d = jax.random.normal(jax.random.PRNGKey(7), (12, 16), jnp.float32)
    y2d = moe_mod.moe_block(params, x2d, moe, "swiglu", impl=impl)
    y3d = moe_mod.moe_block(params, x2d[None], moe, "swiglu", impl=impl)
    assert y2d.shape == x2d.shape
    np.testing.assert_array_equal(np.asarray(y2d), np.asarray(y3d)[0])


def test_expert_token_counts():
    moe, params = _setup(E=4, k=2)
    x = jax.random.normal(jax.random.PRNGKey(4), (20, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=2)
    counts = np.asarray(gating.expert_token_counts(r))
    assert counts.sum() == 20 * 2


# ---------------------------------------------------------------------------
# ragged dispatch: pairs sorted by expert, groups padded to the token tile
# ---------------------------------------------------------------------------


def _kept_pairs_tables(routing, T, E, C):
    idx, _ = moe_mod.dispatch_tables(routing, T, E, C)
    idx = np.asarray(idx)
    return {(int(t), e) for e in range(E) for t in idx[e] if t < T}


@pytest.mark.parametrize("E,k,T,cf", [(8, 2, 24, 1.0), (8, 2, 24, 0.5),
                                      (4, 1, 16, 0.25), (16, 4, 10, 16.0)])
@pytest.mark.parametrize("dynamic", [False, True])
def test_ragged_dispatch_drops_what_dispatch_tables_drops(E, k, T, cf,
                                                          dynamic):
    """Finite capacity keeps the first C pairs of each expert in token
    order, whatever the trajectory; every kept pair's row holds its
    token, in a tile of its expert."""
    from repro.core import trajectory
    from repro.kernels.streamed_moe import ragged_tiles, tile_map
    moe, params = _setup(E=E, k=k, cf=cf)
    x = jax.random.normal(jax.random.PRNGKey(T), (T, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=k)
    C = moe_mod.capacity_of(T, moe)
    order = None
    if dynamic:
        order = jnp.asarray(trajectory.build_schedule(
            np.asarray(gating.expert_token_counts(r))).order, jnp.int32)
    Tc = 8
    tok, sizes, dest = moe_mod.ragged_dispatch(r, T, E, C, Tc, order)
    N = ragged_tiles(T * k, E, Tc)
    assert tok.shape == (N, Tc)
    tok, dest = np.asarray(tok).reshape(-1), np.asarray(dest)
    ind = np.asarray(r.indices)
    kept = {(t, int(ind[t, j])) for t in range(T) for j in range(k)
            if dest[t, j] < N * Tc}
    assert kept == _kept_pairs_tables(r, T, E, C)
    tile_expert, used = tile_map(sizes, order, Tc, N)
    assert int(used[0]) * Tc == int(np.asarray(sizes).sum())
    for t in range(T):
        for j in range(k):
            if dest[t, j] < N * Tc:
                assert tok[dest[t, j]] == t
                assert int(tile_expert[dest[t, j] // Tc]) == ind[t, j]
    # every row that no pair owns is padding
    assert (np.sort(tok)[len(kept):] == T).all()


@pytest.mark.parametrize("cf", [0.5, 8.0])
def test_capacity_dynamic_order_bit_identical(cf):
    """A host trajectory permutes the groups and their weight streams,
    never the values: bit-identical to the static layout, with and
    without drops, kernel and oracle alike."""
    from repro.core import trajectory
    from repro.kernels import ops
    moe, params = _setup(cf=cf)
    x = jax.random.normal(jax.random.PRNGKey(9), (20, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=moe.top_k)
    sched = trajectory.build_schedule(
        np.random.default_rng(0).integers(0, 9, moe.num_experts))
    for kernels in (True, False):
        with ops.use_kernels(kernels):
            ys = moe_mod.moe_capacity(params, x, r, moe, "swiglu")
            yd = moe_mod.moe_capacity(params, x, r, moe, "swiglu",
                                      schedule=sched)
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yd))


def test_capacity_row_alone_bit_identical():
    """Drop-free, a row's output does not depend on the rows beside it,
    though the token tile follows the row count (8 rows alone, 16 for
    32 rows)."""
    from repro.kernels.streamed_moe import ragged_token_tile
    moe, params = _setup(cf=8.0)
    x = jax.random.normal(jax.random.PRNGKey(10), (32, 16), jnp.float32)
    assert (ragged_token_tile(2, 8), ragged_token_tile(64, 8)) == (8, 16)
    r = gating.route(params["router"], x, top_k=moe.top_k)
    y = np.asarray(moe_mod.moe_capacity(params, x, r, moe, "swiglu"))
    for t in (0, 13, 31):
        r1 = gating.route(params["router"], x[t:t + 1], top_k=moe.top_k)
        y1 = moe_mod.moe_capacity(params, x[t:t + 1], r1, moe, "swiglu")
        np.testing.assert_array_equal(np.asarray(y1)[0], y[t])


def test_capacity_one_expert_takes_every_row():
    """Every row routed to one expert, drop-free: one group, one expert
    streamed, the dense oracle's output."""
    moe, params = _setup(E=2, k=1, cf=2.0)          # C = T
    x = jnp.ones((16, 16), jnp.float32)
    r = gating.route(params["router"], x, top_k=1)
    assert len(set(np.asarray(r.indices).ravel().tolist())) == 1
    y = moe_mod.moe_capacity(params, x, r, moe, "swiglu")
    np.testing.assert_allclose(y, moe_mod.moe_dense(params, x, r, "swiglu"),
                               rtol=2e-4, atol=2e-4)
