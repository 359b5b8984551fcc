"""Pallas TPU kernel: causal flash attention (online softmax).

Grid: (B*H, Sq/Tq, Sk/Tk) with the KV dimension innermost; the running
max / denominator / accumulator live in VMEM scratch and persist across
KV grid steps (Pallas revisiting semantics).  Causal blocks entirely
above the diagonal are masked out; the final KV step normalizes and
writes the output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, tq, tk, sk_total, sq_total):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (Tq, hd)
    k = k_ref[0]                                   # (Tk, hd)
    v = v_ref[0]                                   # (Tk, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # causal mask in global coordinates (supports Sk >= Sq, aligned right)
    qpos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    s = jnp.where(kpos <= qpos + (sk_total - sq_total), s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, q_tile=128, k_tile=128,
                           interpret: bool | None = None):
    """q,k,v: (B,S,H,hd), kv pre-broadcast to H heads. Causal. -> (B,S,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if interpret is None:
        from .ops import interpret_default
        interpret = interpret_default()
    tq = min(q_tile, Sq)
    tk = min(k_tile, Sk)
    padq = (-Sq) % tq
    padk = (-Sk) % tk
    if padq:
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
    if padk:
        k = jnp.pad(k, ((0, 0), (0, padk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padk), (0, 0), (0, 0)))
    Sqp, Skp = Sq + padq, Sk + padk
    # (B,S,H,hd) -> (B*H, S, hd)
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sqp, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * H, Skp, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * H, Skp, hd)

    grid = (B * H, Sqp // tq, Skp // tk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / (hd ** 0.5), tq=tq, tk=tk,
                          sk_total=Sk, sq_total=Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    out = out[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out
