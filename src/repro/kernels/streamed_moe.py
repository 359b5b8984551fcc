"""Pallas TPU kernel: grouped expert GEMM over one d_expert micro-slice.

This is the compute hot-spot of FSE-DP's ring step (paper §IV): the
kernel body is the per-chiplet "SRAM" level of the adaptation — it
holds exactly **one weight micro-slice** (w_g/w_u: (d, m), w_d: (m, d))
plus one token tile in VMEM while computing the partial expert output,
mirroring the paper's claim that on-chip residency is one micro-slice
per stream.  HBM→VMEM pipelining across grid steps is Pallas's
automatic double-buffering of the BlockSpec'd operands (the DDR→SRAM
flow of Fig. 6); the D2D hop between chips is the ``ppermute`` in
``repro.core.fse_dp`` one level up.

Grid: (E, C/Tc, d/Tj, m/Tk, d/Ti) — experts outer so weight blocks are
revisited across token tiles of the same expert; the three inner dims
tile the output d_model (j), the micro-slice hidden dim (k) and the
contraction d_model (i) so micro-slices larger than one VMEM block
still lower.  The pre-activation accumulates in a VMEM scratch over
``i``; the second GEMM accumulates into the (revisited) output block
over ``k`` — both reduction dims are grid-minor, which is the Pallas
requirement for accumulate-safe block revisiting.  With the default
tile sizes (full d/m) the grid degenerates to the classic (E, C/Tc)
form.  Gateless activations (relu2 / gelu) lower without a w_gate
operand at all, so no placeholder slice is ever shipped HBM→VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TOKEN_TILE = 128
# Mosaic block rule: the last two dims of every block are multiples of
# (SUBLANE, LANE) or span the whole array dim
LANE, SUBLANE = 128, 8
# TPU v5e's default scoped-VMEM limit (what one kernel may hold in fast
# memory unless it asks for more).  Tiles are planned against 3/4 of it;
# the rest is left to Mosaic's own temporaries (dot results, casts).
VMEM_LIMIT_BYTES = 16 * 2 ** 20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 3 // 4


def _lane_tiles(dim: int):
    """Tiles Mosaic accepts on a lane axis of width ``dim``, largest
    first: the divisors that are multiples of LANE, and ``dim`` itself."""
    return [t for t in range(dim, 0, -1)
            if dim % t == 0 and (t % LANE == 0 or t == dim)]


def fit_tile(dim: int, req: int) -> int:
    """Tile for a d_model or d_expert block axis: the largest divisor of
    ``dim`` that is a multiple of LANE and <= ``req``; the smallest such
    divisor when none is <= ``req``; ``dim`` itself when it has none.

    Every d/m tile is the last (lane) dim of some block (w_up's Tk,
    w_down's and the output's Tj), so LANE alignment covers the sublane
    rule too.  This is the one rounding rule shared by the kernel and
    the ``core.autotune`` planner, so the two cannot drift apart."""
    tiles = _lane_tiles(dim)
    below = [t for t in tiles if t <= req]
    return below[0] if below else tiles[-1]


def token_tile_for(C: int, req: int) -> int:
    """Token-tile rows (a sublane axis): all of ``C`` when it fits in
    ``req``, else ``req`` rounded down to a multiple of SUBLANE (capacity
    rows are padded up to a multiple of the tile)."""
    if C <= req:
        return max(C, 1)
    return max(SUBLANE, req - req % SUBLANE)


def vmem_bytes(Tc: int, Tj: int, Tk: int, gated: bool, x_bytes: int = 2,
               w_bytes: int = 2) -> int:
    """VMEM working set of one grid step (``Ti == Tj``): every streamed
    block (x, the weight blocks, 1-byte formats' fp32 scale rows) and the
    fp32 output block double-buffered by the Pallas pipeline, plus the
    fp32 pre-activation scratch and, for 1-byte weights, the fp32
    dequantized copies the kernel makes in VMEM."""
    n_up = 2 if gated else 1
    streamed = Tc * Tj * x_bytes + (n_up * Tj * Tk + Tk * Tj) * w_bytes
    dequant = 0
    if w_bytes == 1:
        streamed += (n_up * Tk + Tj) * 4
        dequant = (n_up * Tj * Tk + Tk * Tj) * 4
    out = Tc * Tj * 4
    scratch = n_up * Tc * Tk * 4
    return 2 * (streamed + out) + scratch + dequant


def default_tiles(C: int, d: int, m: int, *, gated: bool, x_bytes: int = 2,
                  w_bytes: int = 2, token_tile: int = DEFAULT_TOKEN_TILE):
    """(Tc, Tj, Tk) the kernel lowers with no explicit d/m tiles: d_model
    whole if any hidden tile then fits ``VMEM_BUDGET_BYTES`` (tiling
    d_model makes the up/gate GEMMs recompute once per output tile), with
    the largest fitting hidden tile; else the largest fitting d_model
    tile; else the smallest aligned tiles."""
    Tc = token_tile_for(C, token_tile)
    tks = _lane_tiles(m)
    for Tj in _lane_tiles(d):
        for Tk in tks:
            if vmem_bytes(Tc, Tj, Tk, gated, x_bytes,
                          w_bytes) <= VMEM_BUDGET_BYTES:
                return Tc, Tj, Tk
    return Tc, _lane_tiles(d)[-1], tks[-1]


def resolve_tiles(C: int, d: int, m: int, *, gated: bool, x_bytes: int = 2,
                  w_bytes: int = 2, token_tile: int = DEFAULT_TOKEN_TILE,
                  dmodel_tile: int | None = None,
                  dexpert_tile: int | None = None):
    """(Tc, Tj, Tk) the kernel lowers for these options: the defaults,
    with each requested d/m tile rounded by :func:`fit_tile`."""
    Tc, Tj, Tk = default_tiles(C, d, m, gated=gated, x_bytes=x_bytes,
                               w_bytes=w_bytes, token_tile=token_tile)
    if dmodel_tile is not None:
        Tj = fit_tile(d, dmodel_tile)
    if dexpert_tile is not None:
        Tk = fit_tile(m, dexpert_tile)
    return Tc, Tj, Tk


def _kernel(*refs, activation, quantized, nI, C, Tc):
    gated = activation == "swiglu"
    n_in = (4 if gated else 3) + (quantized * (3 if gated else 2))
    x_ref, *w_refs = refs[:n_in]
    o_ref, hu_ref, *rest = refs[n_in:]
    hg_ref = rest[0] if gated else None
    if gated:
        wg_ref, wu_ref, wd_ref = w_refs[:3]
        sg_ref, su_ref, sd_ref = w_refs[3:] if quantized else (None,) * 3
    else:
        wu_ref, wd_ref = w_refs[:2]
        wg_ref = sg_ref = None
        su_ref, sd_ref = w_refs[2:] if quantized else (None, None)
    c = pl.program_id(1)
    k = pl.program_id(3)
    i = pl.program_id(4)

    @pl.when(i == 0)
    def _init_acc():
        hu_ref[...] = jnp.zeros_like(hu_ref)
        if hg_ref is not None:
            hg_ref[...] = jnp.zeros_like(hg_ref)

    def _load_up(w_ref, s_ref):
        w = w_ref[0]                  # (Ti, Tk) — int8/fp8 when quantized
        if s_ref is not None:
            # dequantize in VMEM: per-output-channel scale row (1,1,Tk)
            w = w.astype(jnp.float32) * s_ref[0, 0][None, :]
        return w

    x = x_ref[0]                      # (Tc, Ti)
    hu_ref[...] += jnp.dot(x, _load_up(wu_ref, su_ref),
                           preferred_element_type=jnp.float32)
    if hg_ref is not None:
        hg_ref[...] += jnp.dot(x, _load_up(wg_ref, sg_ref),
                               preferred_element_type=jnp.float32)

    @pl.when(i == nI - 1)
    def _finalize():
        if activation == "swiglu":
            h = jax.nn.silu(hg_ref[...]) * hu_ref[...]
        elif activation == "relu2":
            h = jnp.square(jnp.maximum(hu_ref[...], 0.0))
        else:  # gelu
            h = jax.nn.gelu(hu_ref[...])
        # mask padded capacity rows instead of computing garbage-then-truncate
        row = c * Tc + jax.lax.broadcasted_iota(jnp.int32, h.shape, 0)
        h = jnp.where(row < C, h, 0.0)
        wd = wd_ref[0]                # (Tk, Tj)
        if sd_ref is not None:
            wd = wd.astype(jnp.float32) * sd_ref[0, 0][None, :]
        contrib = jnp.dot(h.astype(wd.dtype), wd,
                          preferred_element_type=jnp.float32)

        @pl.when(k == 0)
        def _set():
            o_ref[0] = contrib

        @pl.when(k > 0)
        def _acc():
            o_ref[0] += contrib


def streamed_moe_kernel(xe, w_g, w_u, w_d, *, activation: str,
                        s_g=None, s_u=None, s_d=None,
                        token_tile: int = DEFAULT_TOKEN_TILE,
                        dmodel_tile: int | None = None,
                        dexpert_tile: int | None = None,
                        interpret: bool | None = None):
    """xe: (E,C,d); w_g: (E,d,m) or None; w_u: (E,d,m); w_d: (E,m,d).

    Returns (E,C,d) float32.  ``w_g`` is required for swiglu and ignored
    (never lowered as an operand) for the gateless activations.

    Quantized streaming: when ``s_u``/``s_d`` (and ``s_g`` for swiglu)
    are given, the weight operands are int8/fp8 with per-(expert,
    output-channel) fp32 scales — s_g/s_u: (E,1,m), s_d: (E,1,d)
    (``kernels.quant``).  Scale rows ship as (1,1,Tk)/(1,1,Tj) side
    blocks riding the same grid indices as their weight tile and are
    dequantized in VMEM right before each GEMM, so DDR->VMEM traffic is
    one byte per weight plus a ~1/d_in-sized scale stream.

    ``dmodel_tile`` tiles d_model on both sides of the expert FFN
    (contraction of the up-projection and output of the down-projection);
    ``dexpert_tile`` tiles the micro-slice hidden dim.  Defaults
    (:func:`default_tiles`) keep d_model whole where they can and pick
    the largest hidden tile whose whole working set fits
    ``VMEM_BUDGET_BYTES``.  Requested tiles are rounded by
    :func:`fit_tile` to LANE-aligned divisors.

    Trade-off: with ``dmodel_tile < d`` the up/gate GEMMs are recomputed
    once per output-d tile (the activation between the two GEMMs forces
    either that or an (Tc, m) h-scratch).  Keep d_model whole unless the
    weight blocks genuinely overflow VMEM.
    """
    E, C, d = xe.shape
    m = w_u.shape[-1]
    gated = activation == "swiglu"
    quantized = s_u is not None
    if gated and w_g is None:
        raise ValueError("activation='swiglu' requires w_g")
    if quantized and (s_d is None or (gated and s_g is None)):
        raise ValueError("quantized weights need scales for every operand")
    if activation not in ("swiglu", "relu2", "gelu"):
        raise ValueError(f"unknown activation {activation!r}")
    if interpret is None:
        from .ops import interpret_default
        interpret = interpret_default()

    Tc, Tj, Tk = resolve_tiles(C, d, m, gated=gated,
                               x_bytes=jnp.dtype(xe.dtype).itemsize,
                               w_bytes=jnp.dtype(w_u.dtype).itemsize,
                               token_tile=token_tile, dmodel_tile=dmodel_tile,
                               dexpert_tile=dexpert_tile)
    Ti = Tj
    pad = (-C) % Tc
    if pad:
        xe = jnp.pad(xe, ((0, 0), (0, pad), (0, 0)))
    Cp = C + pad
    nI = d // Ti
    grid = (E, Cp // Tc, d // Tj, m // Tk, nI)

    in_specs = [pl.BlockSpec((1, Tc, Ti), lambda e, c, j, k, i: (e, c, i))]
    operands = [xe]
    if gated:
        in_specs.append(pl.BlockSpec((1, Ti, Tk), lambda e, c, j, k, i: (e, i, k)))
        operands.append(w_g)
    in_specs += [
        pl.BlockSpec((1, Ti, Tk), lambda e, c, j, k, i: (e, i, k)),   # w_up
        pl.BlockSpec((1, Tk, Tj), lambda e, c, j, k, i: (e, k, j)),   # w_down
    ]
    operands += [w_u, w_d]
    if quantized:
        # per-output-channel scale rows, block-indexed like their weights
        up_spec = pl.BlockSpec((1, 1, Tk), lambda e, c, j, k, i: (e, 0, k))
        if gated:
            in_specs.append(up_spec)
            operands.append(s_g)
        in_specs += [up_spec,
                     pl.BlockSpec((1, 1, Tj), lambda e, c, j, k, i: (e, 0, j))]
        operands += [s_u, s_d]
    scratch = [pltpu.VMEM((Tc, Tk), jnp.float32)]                     # pre-act up
    if gated:
        scratch.append(pltpu.VMEM((Tc, Tk), jnp.float32))             # pre-act gate

    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation,
                          quantized=quantized, nI=nI, C=C, Tc=Tc),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Tc, Tj), lambda e, c, j, k, i: (e, c, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, d), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[:, :C]
