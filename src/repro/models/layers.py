"""Primitive layers: norms, embeddings, rotary, linear init helpers.

All layers are pure functions over parameter pytrees (nested dicts of
``jnp.ndarray``).  Compute-sensitive reductions run in fp32 and cast
back to the working dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(cfg_dtype: str):
    return jnp.dtype(cfg_dtype)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def layernorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def norm_init(kind: str, d):
    return rmsnorm_init(d) if kind == "rmsnorm" else layernorm_init(d)


def apply_norm(kind: str, params, x):
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float, interleave: bool = False):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.

    Pair ``i`` (frequency ``theta**(-2i/hd)``) is the two halves' i-th
    entries ``(i, i + hd/2)`` (rotate-half), or with ``interleave`` the
    adjacent entries ``(2i, 2i+1)`` (DeepSeek-V3's ``rope_interleave``)."""
    hd = x.shape[-1]
    inv = jnp.asarray(rope_freqs(hd, theta))          # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., seq, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(*x.shape[:-1], hd // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, d: int):
    pos = np.arange(length)[:, None]
    dim = np.arange(d)[None, :]
    ang = pos / np.power(10000, 2 * (dim // 2) / d)
    enc = np.where(dim % 2 == 0, np.sin(ang), np.cos(ang))
    return jnp.asarray(enc, jnp.float32)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation_fn(kind: str):
    if kind == "swiglu":  # handled in mlp.py (two projections)
        return jax.nn.silu
    if kind == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if kind == "gelu":
        return jax.nn.gelu
    raise ValueError(kind)
