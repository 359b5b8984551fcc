"""Configuration dataclasses for the repro framework.

Every architecture in ``repro.configs`` is described by a frozen
:class:`ModelConfig`.  Configs are pure data — building parameters or
choosing shardings happens in ``repro.models`` / ``repro.parallel``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


def moe_capacity_rows(tokens: int, top_k: int, num_experts: int,
                      capacity_factor: float) -> int:
    """Per-expert capacity C = max(1, ceil(tokens*top_k/E*cf)).

    The single source of truth for every capacity computation: the
    executed dispatch (``models.moe`` / ``core.baselines``), the cost
    model (``core.autotune``), and the mode simulator (``sim.modes``)
    all delegate here so they can never disagree on C.
    """
    import math
    return max(1, math.ceil(tokens * top_k / num_experts * capacity_factor))


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-Experts FFN block configuration."""

    num_experts: int
    top_k: int
    d_expert: int                      # per-expert FFN hidden dim
    num_shared_experts: int = 0        # DeepSeek-style always-on experts
    capacity_factor: float = 1.25      # EP baseline dispatch capacity
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01
    # FSE-DP knobs (paper §IV)
    micro_slices: int = 4              # micro-slices per per-device slice
    impl: str = "dense"                # default strategy name when no
                                       # ExecutionSpec is given (a
                                       # repro.core.strategy registry key)
    # router (repro.core.gating): "softmax" renormalises the top-k
    # probabilities; "sigmoid" (DeepSeek-V3) chooses by sigmoid score
    # plus a per-expert selection bias that does not enter the weights,
    # renormalises the chosen scores and scales them by routed_scaling
    scoring: str = "softmax"
    routed_scaling: float = 1.0

    def __post_init__(self):
        assert self.top_k <= self.num_experts

    def capacity_rows(self, tokens: int) -> int:
        return moe_capacity_rows(tokens, self.top_k, self.num_experts,
                                 self.capacity_factor)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): keys and values
    are up-projected from one cached latent per token, and a rotary key
    shared by every head is cached beside it."""

    kv_lora_rank: int = 512            # width of the cached latent
    qk_nope_head_dim: int = 128        # per-head query/key width without RoPE
    qk_rope_head_dim: int = 64         # RoPE'd width (shared key)
    v_head_dim: int = 128
    rope_interleave: bool = True       # RoPE on pairs (2i, 2i+1)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values cached per token per layer: the latent and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 / SSD block configuration."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64                 # SSD head dim (P)
    n_groups: int = 1
    chunk_size: int = 256              # SSD chunk length
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class FrontendConfig:
    """Modality frontend stub (audio frames / vision patches).

    The backbone consumes *precomputed* embeddings supplied by
    ``input_specs`` — per the assignment the frontend itself is a stub.
    """

    kind: str                          # "audio" | "vision"
    num_prefix_tokens: int = 256       # vision: patch tokens prepended
    frame_dim: int = 0                 # audio: dim of precomputed frames (=d_model)


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int                          # dense-FFN hidden dim (0 for pure SSM)
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // num_heads
    activation: str = "swiglu"         # swiglu | relu2 | gelu
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    moe_every: int = 1                 # MoE FFN every k-th layer (others dense)
    first_dense: int = 0               # leading layers with a dense FFN of
                                       # width d_ff in place of the MoE
                                       # (first_k_dense_replace)
    mla: Optional[MLAConfig] = None    # latent attention in every
                                       # attention layer
    ssm: Optional[SSMConfig] = None
    attn_every: int = 1                # hybrid: attention every k-th layer (others SSM)
    encoder_layers: int = 0            # enc-dec (whisper): encoder depth
    frontend: Optional[FrontendConfig] = None
    max_seq_len: int = 524_288
    dtype: str = "bfloat16"
    # provenance
    source: str = ""
    verified: str = "unverified"

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_moe(self) -> bool:
        return self.moe is not None

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM/hybrid families only (per assignment)."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decode(self) -> bool:
        """All assigned archs decode (enc-dec decodes with its decoder)."""
        return True

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-decoder-layer mixer kind: 'attn', 'mla' or 'ssm'."""
        kinds = []
        for i in range(self.num_layers):
            if self.family == "ssm":
                kinds.append("ssm")
            elif self.family == "hybrid":
                # paper-listed 1:7 attn:ssm interleave — one attn layer per
                # attn_every block, placed mid-block like Jamba (index 4 of 8;
                # we use the last slot of each period for scan regularity).
                kinds.append("attn" if (i % self.attn_every) == self.attn_every - 1 else "ssm")
            else:
                kinds.append("attn" if self.mla is None else "mla")
        return tuple(kinds)

    def ffn_kinds(self) -> Tuple[str, ...]:
        kinds = []
        for i in range(self.num_layers):
            if i < self.first_dense:
                kinds.append("dense")
            elif self.moe is not None and (i % self.moe_every) == self.moe_every - 1:
                kinds.append("moe")
            elif self.d_ff > 0:
                kinds.append("dense")
            else:
                kinds.append("none")   # pure SSM blocks carry their own mixing
        return tuple(kinds)

    # ---- parameter counting (used by roofline + config tests) --------
    def param_count(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total = embed
        attn = d * (n_q * hd) + 2 * d * (n_kv * hd) + (n_q * hd) * d
        if self.mla is not None:
            m = self.mla
            attn = (d * n_q * m.qk_head_dim + d * m.cache_width
                    + m.kv_lora_rank
                    + m.kv_lora_rank * n_q * (m.qk_nope_head_dim + m.v_head_dim)
                    + n_q * m.v_head_dim * d)
        dense_ffn = (3 if self.activation == "swiglu" else 2) * d * self.d_ff
        moe_ffn = 0
        if self.moe is not None:
            e, de = self.moe.num_experts, self.moe.d_expert
            per_e = (3 if self.activation == "swiglu" else 2) * d * de
            moe_ffn = e * per_e + d * e  # + router
            moe_ffn += self.moe.num_shared_experts * per_e
        ssm_p = 0
        if self.ssm is not None:
            di = self.ssm.expand * d
            nh = di // self.ssm.head_dim
            # in_proj (z,x,B,C,dt) + conv + out_proj + A,D
            ssm_p = d * (2 * di + 2 * self.ssm.n_groups * self.ssm.d_state + nh) \
                + self.ssm.d_conv * (di + 2 * self.ssm.n_groups * self.ssm.d_state) \
                + di * d + 2 * nh
        for i, (mix, ffn) in enumerate(zip(self.layer_kinds(), self.ffn_kinds())):
            total += ssm_p if mix == "ssm" else attn
            if ffn == "dense":
                total += dense_ffn
            elif ffn == "moe":
                total += moe_ffn
            total += 2 * d  # norms
        if self.encoder_layers:
            # encoder self-attn+ffn, decoder cross-attn already excluded above;
            # add encoder stack + decoder cross-attention
            total += self.encoder_layers * (attn + dense_ffn + 2 * d)
            total += self.num_layers * (attn + d)  # cross-attn + norm
        return int(total)

    def active_param_count(self) -> int:
        """Activated params per token (MoE top-k instead of all experts)."""
        if self.moe is None:
            return self.param_count()
        full = self.param_count()
        e, k = self.moe.num_experts, self.moe.top_k
        per_e = (3 if self.activation == "swiglu" else 2) * self.d_model * self.moe.d_expert
        n_moe_layers = sum(1 for f in self.ffn_kinds() if f == "moe")
        return int(full - n_moe_layers * (e - k) * per_e)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list:
    _load_all()
    return sorted(_REGISTRY)


_ARCH_MODULES = [
    "nemotron_4_15b", "yi_6b", "stablelm_1_6b", "nemotron_4_340b",
    "jamba_v0_1_52b", "whisper_base", "granite_moe_1b", "phi3_5_moe",
    "internvl2_2b", "mamba2_370m",
    # paper Table-I workload models (simulator + extra configs)
    "deepseek_moe_16b", "qwen3_30b_a3b", "yuan2_m32",
    # DeepSeek-V3 block: latent attention, sigmoid router, dense layer 0
    "kanana_2_30b_a3b",
]

_loaded = False


def _load_all():
    global _loaded
    if _loaded:
        return
    import importlib
    for m in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{m}")
    _loaded = True
