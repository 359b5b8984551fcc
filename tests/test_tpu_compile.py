"""The main path's Pallas kernels compile for a described TPU v5e at real
widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  This catches what
interpret mode cannot — a block not aligned to the (8, 128) tiling, a
kernel that asks for more VMEM than the chip's scoped limit — and
checks that each program really holds the kernel (``tpu_custom_call``).

The topology is built in a module fixture, never at import: only one
process at a time may load the TPU library, so every test that needs it
lives in this one file.  The persistent compilation cache is off around
these compiles (an entry written for a described device cannot be read
back without one).
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.ssd import ssd_intra_chunk_kernel
from repro.kernels.streamed_moe import (ragged_moe_kernel, ragged_tiles,
                                        ragged_token_tile,
                                        streamed_moe_kernel)

# (E, d_model, d_expert) at the published widths
MOE_WIDTHS = {
    "granite-moe-1b-a400m": (32, 1024, 512),
    "qwen3-30b-a3b": (128, 2048, 768),
    "deepseek-moe-16b": (64, 2048, 1408),
    "kanana-2-30b-a3b": (128, 2048, 768),
}
TOP_K = {"granite-moe-1b-a400m": 8, "deepseek-moe-16b": 6,
         "kanana-2-30b-a3b": 6}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("C", [8, 64])
@pytest.mark.parametrize("arch,weights", [
    ("granite-moe-1b-a400m", "bf16"),
    ("granite-moe-1b-a400m", "int8"),
    ("qwen3-30b-a3b", "bf16"),
    ("deepseek-moe-16b", "bf16"),
])
def test_streamed_moe_compiles(one_chip, arch, weights, C):
    E, d, m = MOE_WIDTHS[arch]
    wdt = jnp.int8 if weights == "int8" else jnp.bfloat16
    shapes = [((E, C, d), jnp.bfloat16), ((E, d, m), wdt), ((E, d, m), wdt),
              ((E, m, d), wdt)]
    if weights == "int8":
        shapes += [((E, 1, m), jnp.float32), ((E, 1, m), jnp.float32),
                   ((E, 1, d), jnp.float32)]

        def fn(xe, wg, wu, wd, sg, su, sd):
            return streamed_moe_kernel(xe, wg, wu, wd, activation="swiglu",
                                       s_g=sg, s_u=su, s_d=sd,
                                       interpret=False)
    else:
        def fn(xe, wg, wu, wd):
            return streamed_moe_kernel(xe, wg, wu, wd, activation="swiglu",
                                       interpret=False)
    assert "tpu_custom_call" in _compiled_hlo(fn, one_chip, *shapes)


@pytest.mark.parametrize("rows", [1, 8, 512])
@pytest.mark.parametrize("arch,weights", [
    ("granite-moe-1b-a400m", "bf16"),
    ("deepseek-moe-16b", "bf16"),
    ("kanana-2-30b-a3b", "bf16"),
    ("kanana-2-30b-a3b", "fp8"),
])
def test_ragged_moe_compiles(one_chip, arch, weights, rows):
    """The capacity path's ragged kernel at the serving cells' call
    shapes: decode at batch 1 and 8 (k and 8k pairs), and a 512-token
    prefill chunk (512k pairs), with the token tile and tile count the
    dispatch picks for them."""
    E, d, m = MOE_WIDTHS[arch]
    pairs = rows * TOP_K[arch]
    Tc = ragged_token_tile(pairs, E)
    N = ragged_tiles(pairs, E, Tc)
    wdt = jnp.float8_e4m3fn if weights == "fp8" else jnp.bfloat16
    shapes = [((N, Tc, d), jnp.bfloat16), ((E,), jnp.int32),
              ((E,), jnp.int32), ((E, d, m), wdt), ((E, d, m), wdt),
              ((E, m, d), wdt)]
    if weights == "fp8":
        shapes += [((E, 1, m), jnp.float32), ((E, 1, m), jnp.float32),
                   ((E, 1, d), jnp.float32)]

    def fn(xs, sizes, order, wg, wu, wd, *scales):
        s_g, s_u, s_d = scales or (None,) * 3
        return ragged_moe_kernel(xs, sizes, order, wg, wu, wd,
                                 activation="swiglu", s_g=s_g, s_u=s_u,
                                 s_d=s_d, interpret=False)
    assert "tpu_custom_call" in _compiled_hlo(fn, one_chip, *shapes)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_attention_compiles(one_chip, head_dim):
    qkv = ((1, 512, 8, head_dim), jnp.bfloat16)
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention_kernel(q, k, v, interpret=False),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_ssd_compiles_at_mamba2_370m_widths(one_chip):
    # mamba2-370m: d_model 1024, expand 2 -> 32 heads of 64, d_state 128,
    # chunk 256; four chunks of one sequence
    b, nc, c, h, p, n = 1, 4, 256, 32, 64, 128
    f32 = jnp.float32
    hlo = _compiled_hlo(
        lambda *a: ssd_intra_chunk_kernel(*a, interpret=False), one_chip,
        ((b, nc, c, h, p), f32), ((b, nc, c, h, n), f32),
        ((b, nc, c, h, n), f32), ((b, h, nc, c), f32), ((b, h, nc, c), f32))
    assert "tpu_custom_call" in hlo
