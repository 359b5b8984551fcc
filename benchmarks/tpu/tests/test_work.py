"""Required-work counts: hand counts for both configurations, counts
that follow the routing and not the dispatch capacity, and shares."""
import json
import os

import jax
import numpy as np
import pytest

import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return work.Model.from_config(json.load(f)["model"])


def test_granite_decode_token_by_hand():
    m = model("granite-moe-1b-a400m")
    attn = 1024 * 16 * 64 * 2 + 1024 * 8 * 64 * 2        # q,o + k,v
    layer = attn + 1024 * 32 + 2 * 1024                   # + router, norms
    expert = 3 * 1024 * 512
    flops_active = 2 * 24 * (layer + 8 * expert)
    ctx = 100                                             # keys: 101
    flops_attn = 24 * 4 * 16 * 64 * 101
    flops_head = 2 * 1024 * 49155
    f, b = work.step_work(m, [work.Rows(ctx=ctx, tokens=1, emit=True)],
                          experts_hit=[8] * 24)
    assert f == flops_active + flops_attn + flops_head
    weights = 24 * layer + 24 * 8 * expert + 1024 * 49155 + 1024
    kv = 24 * 2 * 8 * 64 * 101
    assert b == 2 * weights + 2 * kv


def test_deepseek_prefill_chunk_by_hand():
    m = model("deepseek-moe-16b-l6")
    attn = 4 * 2048 * 16 * 128
    shared = 2 * 3 * 2048 * 1408
    layer = attn + 2048 * 64 + shared + 2 * 2048
    expert = 3 * 2048 * 1408
    rows = [work.Rows(ctx=64, tokens=64, emit=False),
            work.Rows(ctx=0, tokens=10, emit=True)]
    f, b = work.step_work(m, rows, experts_hit=[40] * 6)
    keys = (64 * 64 + 64 * 65 // 2) + 10 * 11 // 2
    assert f == (2 * 6 * (layer + 6 * expert) * 74
                 + 6 * 4 * 16 * 128 * keys + 2 * 2048 * 102400)
    kv = 6 * 2 * 16 * 128 * (128 + 10)
    assert b == 2 * (6 * layer + 6 * 40 * expert + 2048 * 102400
                     + 2048 * 74 + kv)
    assert m.active_matmul_params() == 6 * (layer + 6 * expert)


def test_no_rows_no_work():
    assert work.step_work(model("granite-moe-1b-a400m"), [], []) == (0, 0)


def test_expert_work_follows_routing_not_capacity():
    """One routing through the program's capacity dispatch at a finite
    capacity and at the drop-free one: the implementation pads every
    expert to C rows, 32-fold more at drop-free, and computes the same
    routed rows; the required work is the routing's and is the same."""
    from repro.configs.base import MoEConfig
    from repro.core import gating
    from repro.kernels import ops as kops
    from repro.models import moe as moe_mod
    E, k, d, m, T = 32, 8, 64, 32, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (T, d))
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    params = {"router": {"w_router": jax.random.normal(ks[0], (d, E))},
              "w_gate": jax.random.normal(ks[1], (E, d, m)) / 8,
              "w_up": jax.random.normal(ks[2], (E, d, m)) / 8,
              "w_down": jax.random.normal(ks[3], (E, m, d)) / 6}
    routing = gating.route(params["router"], x, top_k=k)
    counts = np.asarray(gating.expert_token_counts(routing))
    mdl = work.Model(num_layers=1, d_model=d, num_heads=1, num_kv_heads=1,
                     head_dim=d, vocab_size=8, num_experts=E, top_k=k,
                     d_expert=m)
    seen = {}
    # C = 8 (>= T, so nothing drops: a token takes an expert once) and
    # the engine's drop-free C = T * k
    for cf in (float(k), float(E)):
        cfg = MoEConfig(num_experts=E, top_k=k, d_expert=m,
                        capacity_factor=cf)
        with kops.use_kernels(False):
            y = moe_mod.moe_capacity(params, x, routing, cfg, "swiglu")
        seen[cf] = (E * moe_mod.capacity_of(T, cfg), np.asarray(y),
                    work.expert_work(mdl, [counts]))
    (rows_a, y_a, w_a), (rows_b, y_b, w_b) = seen.values()
    assert (rows_a, rows_b) == (E * 8, E * T * k)
    np.testing.assert_allclose(y_a, y_b, rtol=1e-5, atol=1e-5)
    assert w_a == w_b
    assert w_a[0] == 6 * d * m * T * k
    assert w_a[1] == 2 * (3 * d * m * int((counts > 0).sum())
                          + 2 * d * T * k)


def test_share_is_one_at_least_time():
    peak = work.peaks("TPU v5 lite")
    f, b = 197e12 * 0.002, 819e9 * 0.003                  # bytes bound
    least = work.least_seconds(f, b, peak)
    assert least == pytest.approx(0.003)
    assert work.share_percent(least, least) == pytest.approx(100.0)
    assert work.share_percent(least, 2 * least) == pytest.approx(50.0)
    assert work.share_percent(least, 0.0) is None


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        work.peaks("TPU v9 imaginary")
