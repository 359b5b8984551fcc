"""Kanana-2-30B-A3B — the DeepSeek-V3 block: latent attention (no
q-LoRA), 128 sigmoid-routed experts top-6 with a selection bias, 2
shared experts, one leading dense layer.

[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, model_type
deepseek_v3]
"""
from .base import MLAConfig, ModelConfig, MoEConfig, register

CONFIG = register(ModelConfig(
    name="kanana-2-30b-a3b",
    family="moe",
    num_layers=48,
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    head_dim=128,                      # v_head_dim: the heads' output width
    d_ff=6144,                         # the dense layer's intermediate_size
    vocab_size=128256,
    activation="swiglu",
    norm="rmsnorm",
    rope_theta=1e6,
    moe=MoEConfig(num_experts=128, top_k=6, d_expert=768,
                  num_shared_experts=2, impl="fse_dp", scoring="sigmoid",
                  routed_scaling=2.448),
    moe_every=1,
    first_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True),
    source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    verified="hf",
))


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="kanana-2-30b-a3b-reduced", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=128,
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=32,
                      num_shared_experts=2, impl="dense", scoring="sigmoid",
                      routed_scaling=2.448),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16,
                      rope_interleave=True))
