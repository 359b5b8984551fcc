"""ExecutionSpec per-layer overrides on 8 fake devices: a 4-layer MoE
stack with ``layer_overrides`` = {fse_dp on even layers, ep on odd}
must produce exactly the arrays of (a) a hand-built per-layer loop that
forces each layer's strategy directly and (b) per-layer forced
``moe_block`` calls — proving spec resolution + the unrolled period
loop change nothing but the dataflow."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.strategy import ExecutionSpec
from repro.models import moe as moe_mod, transformer
from repro.models.layers import apply_norm
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import constrain_seq_sharded

cfg = ModelConfig(
    name="toy-moe-4l", family="moe", num_layers=4, d_model=32,
    num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=64,
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=64,
                  capacity_factor=4.0, micro_slices=2, impl="fse_dp"),
    dtype="float32")

mesh = make_mesh((2, 4), ("data", "model"))
params = transformer.init_lm(jax.random.PRNGKey(0), cfg)
B, S = 4, 16
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

FORCED = ["fse_dp", "ep", "fse_dp", "ep"]
spec = ExecutionSpec(strategy="fse_dp",
                     layer_overrides={i: n for i, n in enumerate(FORCED)})

p, plan = transformer.period_plan(cfg)
assert p == 1 and cfg.num_layers // p == 4
positions = jnp.arange(S)[None, :]


def fwd_spec(params, tokens):
    return transformer.forward(params, tokens, cfg, spec=spec)


def fwd_forced(params, tokens):
    """Independent per-layer loop forcing each layer's strategy name,
    mirroring forward's SP constraints around each period."""
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for c in range(cfg.num_layers):
        x = constrain_seq_sharded(x)
        slot = jax.tree.map(lambda a: a[c], params["periods"][0])
        x, a = transformer._apply_slot_full(
            slot, x, cfg, "attn", "moe", positions=positions,
            spec=ExecutionSpec(strategy=FORCED[c]), phase="train")
        aux = aux + a
        x = constrain_seq_sharded(x)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return x @ params["lm_head"], aux


with meshctx.with_mesh(mesh):
    y1, aux1 = jax.jit(fwd_spec)(params, tokens)
    y2, aux2 = jax.jit(fwd_forced)(params, tokens)
    assert np.array_equal(np.asarray(y1), np.asarray(y2)), \
        f"spec-override forward != per-layer forced (max diff " \
        f"{np.abs(np.asarray(y1) - np.asarray(y2)).max():.2e})"
    assert np.array_equal(np.asarray(aux1), np.asarray(aux2))
    print(f"forward with layer_overrides == per-layer forced "
          f"(logits {tuple(y1.shape)})")

    # block-level: spec resolution picks the forced strategy per layer
    h = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model),
                          jnp.float32)
    moe_params = jax.tree.map(lambda a: a[0], params["periods"][0])["moe"]
    for i, forced in enumerate(FORCED):
        ya = jax.jit(lambda pp, hh, i=i: moe_mod.moe_block(
            pp, hh, cfg.moe, cfg.activation, spec=spec, layer=i))(moe_params, h)
        yb = jax.jit(lambda pp, hh, n=forced: moe_mod.moe_block(
            pp, hh, cfg.moe, cfg.activation, impl=n))(moe_params, h)
        assert np.array_equal(np.asarray(ya), np.asarray(yb)), (i, forced)
    print("moe_block(spec, layer=i) == moe_block(impl=forced[i]) for all i")

print("LAYER OVERRIDES OK")
