"""Pallas TPU kernel: Mamba-2 SSD intra-chunk terms.

For each (batch·chunk, head) grid cell the kernel holds one chunk of
X/B/C plus the per-chunk decay row in VMEM and produces the
intra-chunk output term and the chunk's contribution to the inter-chunk
state.  The O(c²) semiseparable mask L = exp(segsum(A)) is built with
iota inside the kernel (no HBM traffic for the mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _kernel(x_ref, b_ref, c_ref, acum_ref, y_ref, st_ref, *, chunk):
    x = x_ref[0, 0, 0].astype(jnp.float32)              # (c, p)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)             # (c, n)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)             # (c, n)
    acum = acum_ref[0, 0, 0, 0].astype(jnp.float32)     # (c,)

    # L[i, j] = exp(acum[i] - acum[j]) for i >= j else 0
    diff = acum[:, None] - acum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32) * L  # (c,c)
    y_ref[0, 0, 0] = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # acum[-1] as a masked reduction (Mosaic has no dynamic_slice)
    last = jnp.sum(jnp.where(jj[0] == chunk - 1, acum, 0.0))
    decay = jnp.exp(last - acum)                        # (c,)
    st_ref[0, 0, 0] = jnp.dot((Bm * decay[:, None]).T, x,
                              preferred_element_type=jnp.float32).T  # (p,n)


def ssd_intra_chunk_kernel(xc, Bc, Cc, Ac, A_cumsum, *, interpret: bool | None = None):
    """xc: (b,nc,c,h,p); Bc/Cc: (b,nc,c,h,n); Ac/A_cumsum: (b,h,nc,c).

    Returns (Y_diag (b,nc,c,h,p), states (b,nc,h,p,n)) in fp32.

    The head axis moves out of the last two dims before the call, so
    every block's last two dims span whole array dims (the Mosaic block
    rule).  ``Ac`` is part of the SSD interface but the intra-chunk terms
    need only its cumulative sum, so it is never shipped to the kernel.
    """
    b, nc, c, h, p = xc.shape
    n = Bc.shape[-1]
    if interpret is None:
        from .ops import interpret_default
        interpret = interpret_default()
    grid = (b, nc, h)
    heads_major = lambda a: a.transpose(0, 1, 3, 2, 4)   # (b,nc,h,c,·)
    acum = A_cumsum[:, :, :, None, :]                     # (b,h,nc,1,c)

    y, st = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, c, p), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, c, n), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, c, n), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, c), lambda i, j, k: (i, k, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, c, p), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, c, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(heads_major(xc), heads_major(Bc), heads_major(Cc), acum)
    return heads_major(y), st
