"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# streamed_moe: grouped expert GEMM over one d_expert micro-slice
# ---------------------------------------------------------------------------

def streamed_moe_ref(xe, w_g, w_u, w_d, activation: str):
    """xe: (E,C,d); w_g/w_u: (E,d,m); w_d: (E,m,d) -> (E,C,d) fp32.

    ``w_g`` may be None for the gateless activations (relu2 / gelu)."""
    if activation == "swiglu":
        if w_g is None:
            raise ValueError("activation='swiglu' requires w_g")
        h = jax.nn.silu(jnp.einsum("ecd,edm->ecm", xe, w_g)) \
            * jnp.einsum("ecd,edm->ecm", xe, w_u)
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edm->ecm", xe, w_u)))
    elif activation == "gelu":
        h = jax.nn.gelu(jnp.einsum("ecd,edm->ecm", xe, w_u))
    else:
        raise ValueError(activation)
    return jnp.einsum("ecm,emd->ecd", h, w_d).astype(jnp.float32)


def streamed_moe_quant_ref(xe, w_g, w_u, w_d, activation: str,
                           weight_dtype: str):
    """Quantized-streaming oracle: round-trip the expert weights through
    the streamed storage format (``kernels.quant.fake_quant`` — the
    identical per-(expert, output-channel) quantize→dequantize the
    Pallas kernel performs in VMEM), then run the exact fp32 einsum
    reference.  This is the ground truth the quantized kernel is tested
    against (tolerance contract: ``docs/quantization.md``)."""
    from . import quant
    return streamed_moe_ref(xe.astype(jnp.float32),
                            quant.fake_quant(w_g, weight_dtype),
                            quant.fake_quant(w_u, weight_dtype),
                            quant.fake_quant(w_d, weight_dtype),
                            activation)


def ragged_moe_ref(xs, sizes, order, w_g, w_u, w_d, activation: str):
    """Oracle of ``streamed_moe.ragged_moe_kernel``: xs (N,Tc,d) rows in
    groups of ``sizes`` rows (layout order), group g run by expert
    ``order[g]`` (None = g) -> (N,Tc,d) fp32, zeros past the groups.
    ``jax.lax.ragged_dot`` per projection, so the work is the routed
    rows' wherever XLA lowers it natively."""
    N, Tc, d = xs.shape
    if order is not None:
        w_g, w_u, w_d = (None if w is None else jnp.take(w, order, axis=0)
                         for w in (w_g, w_u, w_d))
    x = xs.reshape(N * Tc, d)
    sizes = sizes.astype(jnp.int32)
    dot = lambda a, w: jax.lax.ragged_dot(a, w, sizes)
    if activation == "swiglu":
        if w_g is None:
            raise ValueError("activation='swiglu' requires w_g")
        h = jax.nn.silu(dot(x, w_g)) * dot(x, w_u)
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(dot(x, w_u)))
    elif activation == "gelu":
        h = jax.nn.gelu(dot(x, w_u))
    else:
        raise ValueError(activation)
    return dot(h, w_d).astype(jnp.float32).reshape(N, Tc, d)


def ragged_moe_quant_ref(xs, sizes, order, w_g, w_u, w_d, activation: str,
                         weight_dtype: str):
    """:func:`ragged_moe_ref` on weights round-tripped through the
    streamed storage format, in fp32 (as :func:`streamed_moe_quant_ref`)."""
    from . import quant
    return ragged_moe_ref(xs.astype(jnp.float32), sizes, order,
                          quant.fake_quant(w_g, weight_dtype),
                          quant.fake_quant(w_u, weight_dtype),
                          quant.fake_quant(w_d, weight_dtype), activation)


# ---------------------------------------------------------------------------
# flash attention (causal)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v):
    """q,k,v: (B,S,H,hd) (kv already head-broadcast) -> (B,S,H,hd)."""
    hd = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
    Sq, Sk = q.shape[1], k.shape[1]
    mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None] + (Sk - Sq)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# SSD intra-chunk (Mamba-2)
# ---------------------------------------------------------------------------

def _segsum(x):
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    keep = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(keep, out, -jnp.inf)


def ssd_intra_chunk_ref(xc, Bc, Cc, Ac, A_cumsum):
    """Intra-chunk SSD terms.

    xc: (b,nc,c,h,p); Bc/Cc: (b,nc,c,h,n); Ac/A_cumsum: (b,h,nc,c)
    Returns Y_diag (b,nc,c,h,p), states (b,nc,h,p,n)  — both fp32.
    """
    L = jnp.exp(_segsum(Ac))
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)
    decay_states = jnp.exp(A_cumsum[:, :, :, -1:] - A_cumsum)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay_states, xc)
    return Y_diag.astype(jnp.float32), states.astype(jnp.float32)
