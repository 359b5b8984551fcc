"""Per-kernel allclose vs ref.py oracles — shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.ssd import ssd_intra_chunk_kernel
from repro.kernels.streamed_moe import streamed_moe_kernel


# ---------------------------------------------------------------------------
# streamed_moe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,d,m", [(2, 8, 16, 8), (4, 100, 64, 24),
                                     (8, 128, 128, 32), (1, 1, 8, 8)])
@pytest.mark.parametrize("act", ["swiglu", "relu2", "gelu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streamed_moe(E, C, d, m, act, dtype):
    ks = jax.random.split(jax.random.PRNGKey(E * 10 + C), 4)
    xe = jax.random.normal(ks[0], (E, C, d), dtype)
    wg = (jax.random.normal(ks[1], (E, d, m), jnp.float32) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, d, m), jnp.float32) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, m, d), jnp.float32) * 0.1).astype(dtype)
    got = streamed_moe_kernel(xe, wg, wu, wd, activation=act, token_tile=32)
    want = ref.streamed_moe_ref(xe, wg, wu, wd, act)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(1, 50), st.sampled_from([16, 32]),
       st.sampled_from([8, 16]))
def test_streamed_moe_property(E, C, d, m):
    ks = jax.random.split(jax.random.PRNGKey(E * 1000 + C), 4)
    xe = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, m), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (E, d, m), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (E, m, d), jnp.float32) * 0.1
    got = streamed_moe_kernel(xe, wg, wu, wd, activation="swiglu", token_tile=16)
    want = ref.streamed_moe_ref(xe, wg, wu, wd, "swiglu")
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_streamed_moe_slice_sum_equals_full():
    """Σ over d_expert micro-slices == whole-expert FFN — the FSE-DP
    order-invariance (virtualization) property at kernel level."""
    E, C, d, de, M = 2, 16, 32, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    xe = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, de), jnp.float32) * 0.1
    wu = jax.random.normal(ks[2], (E, d, de), jnp.float32) * 0.1
    wd = jax.random.normal(ks[3], (E, de, d), jnp.float32) * 0.1
    full = ref.streamed_moe_ref(xe, wg, wu, wd, "swiglu")
    mic = de // M
    parts = [streamed_moe_kernel(xe, wg[..., i*mic:(i+1)*mic],
                                 wu[..., i*mic:(i+1)*mic],
                                 wd[:, i*mic:(i+1)*mic, :], activation="swiglu")
             for i in np.random.permutation(M)]          # any order
    np.testing.assert_allclose(sum(parts), full, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd", [(1, 64, 2, 16), (2, 100, 4, 32),
                                      (1, 256, 1, 64), (1, 17, 2, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + S), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    got = flash_attention_kernel(q, k, v, q_tile=32, k_tile=32)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_rectangular_kv():
    """Sk > Sq (cached prefix) aligns causality to the right edge."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.float32)
    got = flash_attention_kernel(q, k, v, q_tile=16, k_tile=16)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# SSD intra-chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,nc,c,h,p,n", [(1, 2, 16, 2, 8, 4),
                                          (2, 3, 32, 4, 16, 8),
                                          (1, 1, 8, 1, 4, 4)])
def test_ssd_intra_chunk(b, nc, c, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(b * 10 + nc), 4)
    xc = jax.random.normal(ks[0], (b, nc, c, h, p), jnp.float32)
    Bc = jax.random.normal(ks[1], (b, nc, c, h, n), jnp.float32)
    Cc = jax.random.normal(ks[2], (b, nc, c, h, n), jnp.float32)
    Ac = -jnp.abs(jax.random.normal(ks[3], (b, h, nc, c), jnp.float32)) * 0.1
    Acum = jnp.cumsum(Ac, -1)
    gy, gs = ssd_intra_chunk_kernel(xc, Bc, Cc, Ac, Acum)
    wy, ws = ref.ssd_intra_chunk_ref(xc, Bc, Cc, Ac, Acum)
    np.testing.assert_allclose(gy, wy, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(gs, ws, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the one kernel-selection rule (kernels.ops)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", False)])
def test_every_kernel_interprets_only_on_cpu(monkeypatch, backend, interpret):
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_default() is interpret


def test_no_pallas_env_switch_is_refused_on_tpu(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_NO_PALLAS", "1")
    assert ops.kernels_enabled() is False            # the CPU oracle path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="REPRO_NO_PALLAS"):
        ops.kernels_enabled()
    with ops.use_kernels(False):                     # explicit: still allowed
        assert ops.kernels_enabled() is False
