"""Plain float32 reference of a DeepSeek-V3-block transformer.

Pre-norm blocks of RMSNorm -> multi-head latent attention -> residual
-> RMSNorm -> FFN -> residual; final RMSNorm and the head.  The FFN is
a dense SwiGLU in the first ``first_dense`` layers, and in the others a
sigmoid router over every expert with a selection bias, SwiGLU experts
and always-on shared experts.

Latent attention, per token ``x`` (the full, not the absorbed, form):
``q = x W_q`` split per head into ``q_nope`` and ``q_pe``;
``[c, k_pe] = x W_kva``; ``c = RMSNorm(c)``; ``[k_nope, v] = c W_kvb``
per head; RoPE on ``q_pe`` and on ``k_pe`` (one key shared by every
head) over interleaved pairs ``(2i, 2i+1)``, written here as a complex
rotation; scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_head_dim)``
under a causal softmax; heads concatenated, times ``W_o``.

Router, per token ``h``: ``s = sigmoid(h W_r)``; the experts are the
top-k of ``s + b`` (``b``: ``e_score_correction_bias``, which moves the
choice only); their weights are ``s`` there, divided by their sum when
``norm_topk``, times ``routed_scaling``.  The shared experts' output is
added unweighted.

Written in ``jax.numpy`` alone: it imports nothing of the program, runs
every matmul at ``highest`` precision in float32, computes every expert
for every token, and has no cache.  It reads the benchmark's weights in
the parameter layout they were made in (``periods/<slot>/...`` stacked
over periods) and runs one layer at a time, casting only that layer to
float32, so it fits beside the bfloat16 weights on one chip.
Departures from the published model: none beyond the configuration
file's cut in depth.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from references import moe_transformer as base

KEYS = ("num_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "rope_interleave", "top_k",
        "routed_scaling", "norm_topk", "rms_norm_eps")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta, interleave):
    """x: (B, S, ..., dr) at positions 0..S-1; pair i = entries
    (2i, 2i+1), rotated by the angle pos * theta**(-2i/dr)."""
    assert interleave, "the configuration names interleaved RoPE"
    dr = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    rot = jnp.exp(1j * ang).reshape((1, x.shape[1])
                                    + (1,) * (x.ndim - 3) + (dr // 2,))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * rot
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def _attention(x, a, m):
    """Causal latent attention on x (B, S, d), one sequence at a time."""
    B, S, _ = x.shape
    H, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = (x @ a["wq"]).reshape(B, S, H, dn + dr)
    kva = x @ a["wkv_a"]
    c = _rms(kva[..., :r], a["kv_norm"]["scale"], m["rms_norm_eps"])
    kv = (c @ a["wkv_b"]).reshape(B, S, H, dn + dv)
    k_pe = _rope(kva[..., r:], m["rope_theta"], m["rope_interleave"])
    q_pe = _rope(q[..., dn:], m["rope_theta"], m["rope_interleave"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (B, S, H, dr))],
        -1)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def one(args):
        qb, kb, vb = args
        s = jnp.einsum("qhd,khd->hqk", qb, kb) / jnp.sqrt(
            jnp.float32(dn + dr))
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vb)

    o = jax.lax.map(one, (q, k, kv[..., dn:]))
    return o.reshape(B, S, H * dv) @ a["wo"]


def _moe(h, e, m):
    """Sigmoid-routed experts plus shared experts on h (T, d)."""
    T = h.shape[0]
    r = e["router"]
    s = jax.nn.sigmoid(h @ r["w_router"])
    _, idx = jax.lax.top_k(s + r["e_score_correction_bias"], m["top_k"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if m["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * m["routed_scaling"]
    gate = jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(w)

    def expert(y, xs):
        g, wg, wu, wd = xs
        return y + g[:, None] * base._swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (gate.T, e["w_gate"], e["w_up"], e["w_down"]))
    sh = e["shared"]
    return y + base._swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])


@partial(jax.jit, static_argnames=("m",))
def _layer(x, slot, i, *, m):
    """One block on x (B, S, d) float32; ``slot`` holds the stacked
    weights of this layer's slot, ``i`` the layer's index in it."""
    m = dict(m)
    w = base._take(slot, i)
    B, S, d = x.shape
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms(x, w["norm1"]["scale"], eps), w["mla"], m)
    h = _rms(x, w["norm2"]["scale"], eps).reshape(B * S, d)
    if "moe" in w:
        y = _moe(h, w["moe"], m)
    else:
        f = w["ffn"]
        y = base._swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
    return x + y.reshape(B, S, d)


class Reference(base.Reference):
    """Final hidden states of ``seqs`` under the reference, computed
    layer by layer; ``read`` (the plain reference's) then reads any
    tokens against them."""

    def __init__(self, params, model: dict, seqs: Sequence[Sequence[int]],
                 rows: int = 0, pad_to: int = 256):
        """``seqs`` padded to ``rows`` sequences and a multiple of
        ``pad_to`` positions (causal attention keeps padding out of the
        real positions), so runs of one cell reuse one compiled layer."""
        self.lens = [len(s) for s in seqs]
        S = -(-max(self.lens) // pad_to) * pad_to
        toks = np.zeros((max(rows, len(seqs)), S), np.int32)
        for b, s in enumerate(seqs):
            toks[b, :len(s)] = s
        m = tuple((k, model[k]) for k in KEYS)
        periods = params["periods"]
        p = len(periods)
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
            for layer in range(model["num_layers"]):
                x = _layer(x, periods[layer % p], layer // p, m=m)
            self.hidden = x
        head = params.get("lm_head")
        self.head = params["embed"].T if head is None else head
        self.scale = params["final_norm"]["scale"].astype(jnp.float32)
