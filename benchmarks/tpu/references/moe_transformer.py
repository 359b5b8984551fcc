"""Plain float32 reference of a decoder-only MoE transformer.

Pre-norm blocks of RMSNorm -> grouped-query attention with rotary
positions (rotate-half, theta from the configuration) -> residual ->
RMSNorm -> softmax router, top-k, weights renormalised over the chosen
k -> SwiGLU experts (plus always-on shared experts) -> residual; final
RMSNorm and the head.  Written in ``jax.numpy`` alone: it imports
nothing of the program, runs every matmul at ``highest`` precision in
float32, and computes every expert for every token (no dispatch, no
capacity, no kernel, no cache).

It reads the benchmark's weights in the parameter layout they were
made in (``periods/<slot>/...`` stacked over periods) and runs one
layer at a time, casting only that layer to float32, so it fits beside
the bfloat16 weights on one chip.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
HEAD_ROWS = 512          # positions per block of the head projection


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    """x: (B, S, H, hd); positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("m",))
def _layer(x, slot, i, *, m):
    """One block on x (B, S, d) float32; ``slot`` holds the stacked
    weights of this layer's slot, ``i`` the layer's index in it."""
    m = dict(m)
    w = _take(slot, i)
    B, S, d = x.shape
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = w["attn"]
    h = _rms(x, w["norm1"]["scale"])
    q = _rope((h @ a["wq"]).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope((h @ a["wk"]).reshape(B, S, KV, hd), m["rope_theta"])
    v = (h @ a["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(B, S, H * hd) @ a["wo"]

    e = w["moe"]
    h = _rms(x, w["norm2"]["scale"]).reshape(B * S, d)
    probs = jax.nn.softmax(h @ e["router"]["w_router"], -1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / top.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(B * S)[:, None], idx].set(top)

    def expert(y, xs):
        g, wg, wu, wd = xs
        return y + g[:, None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (gate.T, e["w_gate"], e["w_up"], e["w_down"]))
    if "shared" in e:
        sh = e["shared"]
        y = y + _swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    return x + y.reshape(B, S, d)


@jax.jit
def _read(hidden, head, scale, targets, keep):
    """For rows of final hidden states: the gap by which each target
    token's logit lies below the row's best, the row's argmax, and the
    row's logits at the vocabulary ids ``keep``."""
    z = _rms(hidden, scale) @ head.astype(jnp.float32)
    tgt = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    return z.max(-1) - tgt, z.argmax(-1), z[:, keep]


class Reference:
    """Final hidden states of ``seqs`` under the reference, computed
    layer by layer; ``read`` then reads any tokens against them."""

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
        m = tuple((k, model[k]) for k in ("num_heads", "num_kv_heads",
                                          "head_dim", "top_k", "rope_theta"))
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

    def read(self, rows: List[tuple], keep) -> tuple:
        """``rows``: (sequence index, position, target token).  Returns
        the gap of each target below the row's best logit, the argmax of
        each row, and each row's logits at the vocabulary ids ``keep``."""
        b = np.array([r[0] for r in rows], np.int32)
        pos = np.array([r[1] for r in rows], np.int32)
        tgt = np.array([r[2] for r in rows], np.int32)
        keep = jnp.asarray(keep, jnp.int32)
        out = ([], [], [])
        with jax.default_matmul_precision("highest"):
            for lo in range(0, len(rows), HEAD_ROWS):
                sl = slice(lo, lo + HEAD_ROWS)
                n = len(b[sl])
                pad = HEAD_ROWS - n
                hb = self.hidden[np.pad(b[sl], (0, pad)),
                                 np.pad(pos[sl], (0, pad))]
                got = _read(hb, self.head, self.scale,
                            jnp.asarray(np.pad(tgt[sl], (0, pad))), keep)
                for acc, a in zip(out, got):
                    acc.append(np.asarray(a)[:n])
        return tuple(np.concatenate(acc) for acc in out)
