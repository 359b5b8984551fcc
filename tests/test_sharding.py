"""Sharding rules: divisibility guards, FSE-DP weight layout, cache specs."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced_config
from repro.launch.specs import params_struct, decode_structs
from repro.configs.shapes import SHAPES
from repro.parallel import sharding as shd


class FakeMesh:
    """Shape-only stand-in (never touches jax devices)."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_fit_divisibility():
    assert shd._fit(MESH, "model", 64) == "model"
    assert shd._fit(MESH, "model", 63) is None
    assert shd._fit(MESH, ("pod", "data"), 32) == "data"     # shrinks to data
    assert shd._fit(MESH3, ("pod", "data"), 32) == ("pod", "data")
    assert shd._fit(MESH3, ("pod", "data"), 16) == "data"    # shrinks


def test_moe_weight_layout_is_fse_dp():
    """d_expert must shard over model — one copy of every expert/group."""
    spec = shd.param_spec("periods/0/moe/w_up", (24, 32, 1024, 512), MESH, fsdp=False)
    assert spec == P(None, None, None, "model")
    spec = shd.param_spec("periods/0/moe/w_down", (24, 32, 512, 1024), MESH, fsdp=False)
    assert spec == P(None, None, "model", None)


def test_dense_ffn_tp():
    assert shd.param_spec("periods/0/ffn/w_up", (32, 2048, 8192), MESH, fsdp=False) \
        == P(None, None, "model")
    assert shd.param_spec("periods/0/ffn/w_down", (32, 8192, 2048), MESH, fsdp=True) \
        == P(None, "model", "data")


def test_attention_heads_tp():
    assert shd.param_spec("periods/0/attn/wq", (32, 4096, 4096), MESH, fsdp=False) \
        == P(None, None, "model")
    assert shd.param_spec("periods/0/attn/wo", (32, 4096, 4096), MESH, fsdp=False) \
        == P(None, "model", None)


def test_vocab_sharding():
    # embedding shards d_model (gather-friendly); lm_head shards vocab
    assert shd.param_spec("embed", (256000, 6144), MESH, fsdp=False) \
        == P(None, "model")
    assert shd.param_spec("lm_head", (6144, 256000), MESH, fsdp=False) \
        == P(None, "model")
    # d_model not divisible -> replicate that dim
    assert shd.param_spec("embed", (49155, 1023), MESH, fsdp=False) == P(None, None)


def test_norms_replicated():
    assert shd.param_spec("periods/0/norm1/scale", (32, 1024), MESH, fsdp=False) == P()


def test_cache_specs():
    # KV: (nper, B, S, kv, hd) — batch over dp, seq over model (SP decode)
    spec = shd.cache_spec("caches/0/kv/k", (32, 128, 32768, 8, 128), MESH,
                          batch_axes=("data",))
    assert spec == P(None, "data", "model", None, None)
    # batch=1 long-context: batch replicated, seq still sharded
    spec = shd.cache_spec("caches/0/kv/k", (4, 1, 524288, 8, 128), MESH,
                          batch_axes=("data",))
    assert spec == P(None, None, "model", None, None)
    spec = shd.cache_spec("caches/0/ssm/ssd", (48, 128, 32, 64, 128), MESH,
                          batch_axes=("data",))
    assert spec == P(None, "data", "model", None, None)


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "jamba-v0.1-52b",
                                  "mamba2-370m", "whisper-base"])
def test_param_specs_cover_all_leaves(arch):
    """Every parameter leaf of every family gets a valid spec whose axes
    divide the dims (the divisibility contract)."""
    cfg = get_config(arch)
    ps = params_struct(cfg)
    flat = jax.tree_util.tree_flatten_with_path(ps)[0]
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        spec = shd.param_spec(key, leaf.shape, MESH, fsdp=False)
        assert len(spec) <= len(leaf.shape), (key, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is not None:
                size = MESH.shape[ax] if isinstance(ax, str) else \
                    int(jnp.prod(jnp.asarray([MESH.shape[a] for a in ax])))
                assert dim % size == 0, (key, spec, leaf.shape)


def test_make_mesh_gives_auto_axes():
    """jax.make_mesh defaults to Explicit axes, under which the sharding
    constraints above refuse to name an axis; the repo's helper gives
    every axis the Auto type."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax_cache"])
def test_compile_cache_placed_from_outside(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, where set, stands and nothing is set in
    code; otherwise the cache goes to a fixed path in the checkout."""
    import os
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.configure_compile_cache()
        if env_dir is None:
            assert got == os.path.join(compile_cache.repo_root(),
                                       "artifacts", "jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
