"""Required work of a serving step, and the chip's published peaks.

The counts here are what the routing and the requests *ask for*, not
what an implementation happens to compute: an expert that no row was
routed to costs nothing, and a routed (token, expert) pair costs one
SwiGLU FFN of the expert's width.  So a dispatch that pads every expert
to a drop-free capacity reads as a low share of its roofline, and one
that skips the padding reads higher; neither can read above 100%.

Sizes come from the configuration file's ``model`` section (the
published widths as run), never from the program's own config objects.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Sequence, Tuple

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; a kind the table lacks is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them with their source"
                         ) from None


@dataclass(frozen=True)
class Model:
    """The widths a step's work depends on (one attention + MoE block
    per layer, SwiGLU experts, untied or tied head)."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    num_experts: int
    top_k: int
    d_expert: int
    num_shared_experts: int = 0
    tie_embeddings: bool = False
    weight_bytes: int = 2          # bf16

    @classmethod
    def from_config(cls, model: dict) -> "Model":
        keys = cls.__dataclass_fields__
        return cls(**{k: v for k, v in model.items() if k in keys})

    # -- parameters -----------------------------------------------------
    @property
    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return (2 * d * self.num_heads * hd
                + 2 * d * self.num_kv_heads * hd)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert

    @property
    def dense_layer_params(self) -> int:
        """Per-layer weights every token reads: attention, router,
        shared experts and the two norms."""
        return (self.attn_params + self.d_model * self.num_experts
                + self.num_shared_experts * self.expert_params
                + 2 * self.d_model)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab_size

    def active_matmul_params(self) -> int:
        """Matmul weights one token passes through, head excluded."""
        return self.num_layers * (self.dense_layer_params
                                  + self.top_k * self.expert_params)

    @property
    def kv_bytes_per_token_layer(self) -> int:
        return 2 * self.num_kv_heads * self.head_dim * self.weight_bytes


@dataclass(frozen=True)
class Rows:
    """What one program call of a step processed for one request:
    ``tokens`` new positions starting at context ``ctx`` (tokens already
    cached), and whether its logits were needed (``emit``)."""
    ctx: int
    tokens: int
    emit: bool


def step_work(m: Model, rows: Sequence[Rows],
              experts_hit: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one program call requires.

    ``rows``: the requests it advanced.  ``experts_hit``: per MoE layer,
    how many experts were routed at least one row in this call.
    FLOPs: 2 x active matmul params per token, attention scores and
    values over each token's causal context, and the head for each
    emitted token.  Bytes: every non-expert weight once, each expert
    routed at least one row once, the head once if any row emits, and
    the live KV of each request read once and its new positions written.
    """
    if not rows:
        return 0.0, 0.0
    L, H, hd = m.num_layers, m.num_heads, m.head_dim
    toks = sum(r.tokens for r in rows)
    emits = sum(1 for r in rows if r.emit)
    flops = 2.0 * m.active_matmul_params() * toks
    for r in rows:
        # sum over new positions p = ctx .. ctx+tokens-1 of (p + 1) keys
        keys = r.tokens * r.ctx + r.tokens * (r.tokens + 1) // 2
        flops += L * 4.0 * H * hd * keys
    flops += 2.0 * m.head_params * emits
    wb = m.weight_bytes
    nbytes = wb * L * m.dense_layer_params
    nbytes += wb * m.expert_params * sum(experts_hit)
    nbytes += wb * m.head_params * (1 if emits else 0)
    nbytes += wb * m.d_model * toks                     # embedding rows
    for r in rows:
        nbytes += L * m.kv_bytes_per_token_layer * (r.ctx + r.tokens)
    return flops, float(nbytes)


def expert_work(m: Model, counts: Iterable[Sequence[int]]
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) the expert FFN requires for one call, given per
    MoE layer the routed rows of each expert: 6·d·d_expert per routed
    (token, expert) pair; the weights of each expert with a row, and
    the routed rows read in and written out (bf16)."""
    flops = nbytes = 0.0
    per_pair = 2.0 * m.expert_params
    for cnt in counts:
        pairs = sum(int(c) for c in cnt)
        hit = sum(1 for c in cnt if int(c) > 0)
        flops += per_pair * pairs
        nbytes += m.weight_bytes * (m.expert_params * hit
                                    + 2 * m.d_model * pairs)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                  ) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    bf16 peak and bytes over the HBM bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bw"])


def share_percent(least_s: float, measured_s: float):
    """``least_s`` as a percentage of ``measured_s``; None where nothing
    was measured (a share is never reported as 0 for want of a time)."""
    if measured_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / measured_s
