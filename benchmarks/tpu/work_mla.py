"""Required work of a serving step for a DeepSeek-V3-block model: latent
attention (MLA) with a latent cache, leading dense layers, MoE layers.

``work.py`` models one attention + MoE block per layer with K/V bytes;
this module counts the same quantities for this block, with
``work.py``'s peaks, rows and rules: what the routing and the requests
ask for, not what an implementation computes.

* Latent attention is counted in its absorbed form, the least work at
  a cache: per (query, key) pair ``2·H·(kv_lora_rank + qk_rope_head_dim)``
  FLOPs for the scores and ``2·H·kv_lora_rank`` for the values; the
  projections ``W_q, W_kva, W_kvb, W_o`` are matmul parameters every
  token passes through once.  Bytes: the live latent cache,
  ``(kv_lora_rank + qk_rope_head_dim)`` values per position per layer.
* Layers ``< first_dense`` hold a dense SwiGLU FFN of width ``d_ff``;
  the others a router, the routed experts and the shared experts.

Sizes come from the configuration file's ``model`` section.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import work
import windowstats as ws


@dataclass(frozen=True)
class MlaModel:
    num_layers: int
    d_model: int
    num_heads: int
    vocab_size: int
    num_experts: int
    top_k: int
    d_expert: int
    num_shared_experts: int
    d_ff: int
    first_dense: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    tie_embeddings: bool = False
    weight_bytes: int = 2          # bf16

    @classmethod
    def from_config(cls, model: dict) -> "MlaModel":
        keys = cls.__dataclass_fields__
        return cls(**{k: v for k, v in model.items() if k in keys})

    # -- parameters -----------------------------------------------------
    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kvb_params(self) -> int:
        return self.kv_lora_rank * self.num_heads * (self.qk_nope_head_dim
                                                     + self.v_head_dim)

    @property
    def attn_params(self) -> int:
        d, H = self.d_model, self.num_heads
        return (d * H * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + d * self.cache_width + self.kvb_params
                + H * self.v_head_dim * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert

    @property
    def n_moe(self) -> int:
        return self.num_layers - self.first_dense

    @property
    def dense_ffn_layer_params(self) -> int:
        """Weights of a leading dense layer: attention, FFN, norms (and
        the latent's norm)."""
        return (self.attn_params + 3 * self.d_model * self.d_ff
                + 2 * self.d_model + self.kv_lora_rank)

    @property
    def moe_layer_params(self) -> int:
        """Weights every token of an MoE layer reads: attention, router
        and its bias, shared experts and the norms."""
        return (self.attn_params + (self.d_model + 1) * self.num_experts
                + self.num_shared_experts * self.expert_params
                + 2 * self.d_model + self.kv_lora_rank)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab_size

    def active_matmul_params(self) -> int:
        """Matmul weights one token passes through, head excluded."""
        return (self.first_dense * self.dense_ffn_layer_params
                + self.n_moe * (self.moe_layer_params
                                + self.top_k * self.expert_params))

    @property
    def latent_bytes_per_token_layer(self) -> int:
        return self.cache_width * self.weight_bytes


def attn_pair_flops(m: MlaModel) -> int:
    """Absorbed-form FLOPs of one (query, key) pair in one layer."""
    return 2 * m.num_heads * (m.cache_width + m.kv_lora_rank)


def causal_keys(r: work.Rows) -> int:
    """Keys the new positions ctx .. ctx+tokens-1 attend to, summed."""
    return r.tokens * r.ctx + r.tokens * (r.tokens + 1) // 2


def step_work(m: MlaModel, rows: Sequence[work.Rows],
              experts_hit: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one program call requires, as
    ``work.step_work`` counts them, for this block.  ``experts_hit``:
    per MoE layer, the experts routed at least one row."""
    if not rows:
        return 0.0, 0.0
    L = m.num_layers
    toks = sum(r.tokens for r in rows)
    emits = sum(1 for r in rows if r.emit)
    flops = 2.0 * m.active_matmul_params() * toks
    flops += L * attn_pair_flops(m) * sum(causal_keys(r) for r in rows)
    flops += 2.0 * m.head_params * emits
    wb = m.weight_bytes
    nbytes = wb * (m.first_dense * m.dense_ffn_layer_params
                   + m.n_moe * m.moe_layer_params)
    nbytes += wb * m.expert_params * sum(experts_hit)
    nbytes += wb * m.head_params * (1 if emits else 0)
    nbytes += wb * m.d_model * toks                     # embedding rows
    nbytes += L * m.latent_bytes_per_token_layer * sum(r.ctx + r.tokens
                                                       for r in rows)
    return flops, float(nbytes)


def attn_work(m: MlaModel, rows: Sequence[work.Rows]
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the latent attention of one program call, a
    decode step or a prefill chunk, every layer: per row,
    ``2·H·keys·(cache_width + kv_lora_rank)`` for scores and values and
    ``2·W_kvb`` per new position for absorbing its query and expanding
    its output; bytes of each row's live latent context (new positions
    included) and of ``W_kvb`` once per call."""
    if not rows:
        return 0.0, 0.0
    L = m.num_layers
    keys = sum(causal_keys(r) for r in rows)
    toks = sum(r.tokens for r in rows)
    flops = L * (attn_pair_flops(m) * keys + 2.0 * m.kvb_params * toks)
    nbytes = L * (m.latent_bytes_per_token_layer
                  * sum(r.ctx + r.tokens for r in rows)
                  + m.weight_bytes * m.kvb_params)
    return float(flops), float(nbytes)


def required_step_seconds(run) -> Optional[float]:
    """``windowstats.required_step_seconds`` for this block: the least
    time of each program call's required work, summed over the window."""
    calls = ws.experts_by_call(run)
    if not calls:
        return None
    m = MlaModel.from_config(run.cell.config["model"])
    total = 0.0
    for st in run.window.steps:
        for phase, rows in (("prefill", st.prefill), ("decode", st.decode)):
            if not rows:
                continue
            hit = [sum(1 for c in cnt if c > 0)
                   for cnt in calls.get((st.iteration, phase), [])]
            f, b = step_work(m, rows, hit)
            total += work.least_seconds(f, b, run.peak)
    return total


def attn_least_seconds(run) -> Optional[float]:
    """The least time of the window's latent attention: every decode
    step and every prefill chunk, each a program call of its own."""
    m = MlaModel.from_config(run.cell.config["model"])
    total = sum(work.least_seconds(*attn_work(m, rows), run.peak)
                for st in run.window.steps
                for rows in (st.prefill, st.decode) if rows)
    return total or None
