"""Pallas TPU kernel: ragged grouped expert GEMM over one d_expert
micro-slice.

Rows arrive grouped by expert in tiles of ``Tc`` rows, each tile
holding rows of one expert only: ``xs`` is ``(N, Tc, d)``.  A
scalar-prefetched tile -> expert map, and the count of tiles in use,
tell every grid step whose weight blocks to stream.  Consecutive tiles
of one expert keep the same weight block index, so Pallas fetches that
expert's weights once; an expert with no rows has no tile, so its
weights are never read from HBM.  Tiles past the used count point at
the last used expert's last blocks (no new DMA), skip their compute
with ``pl.when`` and write zeros.  The single-device capacity path
(``repro.models.moe.moe_capacity``) sorts its (token, expert) pairs
into this layout, so one call streams only the experts it hits and
computes about the routed rows — the paper's "stream only what the
trajectory needs".

The kernel body is the per-chiplet "SRAM" level of the adaptation: it
holds exactly **one weight micro-slice** (w_g/w_u: (d, m), w_d: (m, d))
plus one token tile in VMEM while computing the partial expert output.
HBM->VMEM pipelining across grid steps is Pallas's automatic
double-buffering of the BlockSpec'd operands (the DDR->SRAM flow of
Fig. 6); the D2D hop between chips is the ``ppermute`` in
``repro.core.fse_dp`` one level up.

Grid: (N, d/Tj, m/Tk, d/Ti) — tiles outer; the three inner dims tile
the output d_model (j), the micro-slice hidden dim (k) and the
contraction d_model (i) so micro-slices larger than one VMEM block
still lower.  The pre-activation accumulates in a VMEM scratch over
``i``; the second GEMM accumulates into the (revisited) output block
over ``k`` — both reduction dims are grid-minor, which is the Pallas
requirement for accumulate-safe block revisiting.  Gateless activations
(relu2 / gelu) lower without a w_gate operand at all, so no placeholder
slice is ever shipped HBM->VMEM.  Zero rows (a group's padding) come
out as zero rows under all three activations.

:func:`streamed_moe_kernel` keeps the padded ``(E, C, d)`` layout that
FSE-DP's ring and the hybrid tiers dispatch: the same kernel with every
expert given ``C`` rows.
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


def ragged_token_tile(pairs: int, num_experts: int) -> int:
    """Token-tile rows of the ragged layout for ``pairs`` routed (token,
    expert) pairs over ``num_experts``: twice the mean rows of an expert
    that is hit, as a power of two from SUBLANE up to
    DEFAULT_TOKEN_TILE.  Most experts then take one tile (one pass over
    their weights), and a decode call's one or two rows per expert are
    not padded out to a prefill-sized tile."""
    want = -(-2 * pairs // max(1, min(num_experts, pairs)))
    Tc = SUBLANE
    while Tc < min(want, DEFAULT_TOKEN_TILE):
        Tc *= 2
    return Tc


def ragged_tiles(pairs: int, num_experts: int, Tc: int) -> int:
    """Static tile count N that holds ``pairs`` rows grouped by expert,
    each group padded to a multiple of ``Tc``: at most
    ``min(E, pairs)`` groups, each wasting at most ``Tc - 1`` rows."""
    return (pairs + min(num_experts, pairs) * (Tc - 1)) // Tc


def tile_map(sizes, order, Tc: int, N: int):
    """(tile -> expert (N,) int32, tiles in use (1,) int32) for groups of
    ``sizes`` rows (multiples of ``Tc``), laid out in ``order`` (group g
    is expert ``order[g]``; ``None`` = canonical).  Tiles past the used
    count map to the last used group's expert."""
    ends = jnp.cumsum(sizes.astype(jnp.int32)) // Tc      # (G,) tile ends
    used = ends[-1]
    # compare_all: one (N, G) comparison, no loop on the device
    find = lambda v: jnp.searchsorted(ends, v, side="right",
                                      method="compare_all")
    g = jnp.minimum(find(jnp.arange(N, dtype=jnp.int32)), find(used - 1))
    expert = g if order is None else jnp.asarray(order)[g]
    return expert.astype(jnp.int32), used.reshape(1).astype(jnp.int32)


def _kernel(tile_expert_ref, used_ref, *refs, activation, quantized, nI):
    del tile_expert_ref               # consumed by the index maps
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
    live = pl.program_id(0) < used_ref[0]
    k = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_not(live))
    def _unused():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live & (i == 0))
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

    @pl.when(live)
    def _up():
        x = x_ref[0]                  # (Tc, Ti)
        hu_ref[...] += jnp.dot(x, _load_up(wu_ref, su_ref),
                               preferred_element_type=jnp.float32)
        if hg_ref is not None:
            hg_ref[...] += jnp.dot(x, _load_up(wg_ref, sg_ref),
                                   preferred_element_type=jnp.float32)

    @pl.when(live & (i == nI - 1))
    def _finalize():
        if activation == "swiglu":
            h = jax.nn.silu(hg_ref[...]) * hu_ref[...]
        elif activation == "relu2":
            h = jnp.square(jnp.maximum(hu_ref[...], 0.0))
        else:  # gelu
            h = jax.nn.gelu(hu_ref[...])
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


def ragged_moe_kernel(xs, sizes, order, w_g, w_u, w_d, *, activation: str,
                      s_g=None, s_u=None, s_d=None,
                      dmodel_tile: int | None = None,
                      dexpert_tile: int | None = None,
                      interpret: bool | None = None):
    """xs: (N,Tc,d) rows grouped by expert; sizes: (G,) rows of each
    group, multiples of Tc, in layout order; order: (G,) expert of each
    group, or None for groups ``0..G-1``; w_g: (E,d,m) or None; w_u:
    (E,d,m); w_d: (E,m,d).  Returns (N,Tc,d) float32: row r of a group
    is that expert's FFN of ``xs`` row r, and rows past the groups are
    zeros.  ``w_g`` is required for swiglu and ignored (never lowered as
    an operand) for the gateless activations.

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
    N, Tc, d = xs.shape
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

    _, Tj, Tk = resolve_tiles(Tc, d, m, gated=gated,
                              x_bytes=jnp.dtype(xs.dtype).itemsize,
                              w_bytes=jnp.dtype(w_u.dtype).itemsize,
                              token_tile=Tc, dmodel_tile=dmodel_tile,
                              dexpert_tile=dexpert_tile)
    Ti = Tj
    nJ, nK, nI = d // Tj, m // Tk, d // Ti
    tile_expert, used = tile_map(sizes, order, Tc, N)

    def live(t, j, k, i, used):
        """Grid indices with every unused tile held at the last used
        step's blocks, so it starts no DMA."""
        on = t < used[0]
        return (jnp.where(on, t, used[0] - 1), jnp.where(on, j, nJ - 1),
                jnp.where(on, k, nK - 1), jnp.where(on, i, nI - 1))

    def x_map(t, j, k, i, te, used):
        t, _, _, i = live(t, j, k, i, used)
        return t, 0, i

    def up_map(t, j, k, i, te, used):        # w_gate / w_up (E, d, m)
        t, _, k, i = live(t, j, k, i, used)
        return te[t], i, k

    def down_map(t, j, k, i, te, used):      # w_down (E, m, d)
        t, j, k, _ = live(t, j, k, i, used)
        return te[t], k, j

    def up_scale_map(t, j, k, i, te, used):
        t, _, k, _ = live(t, j, k, i, used)
        return te[t], 0, k

    def down_scale_map(t, j, k, i, te, used):
        t, j, _, _ = live(t, j, k, i, used)
        return te[t], 0, j

    in_specs = [pl.BlockSpec((1, Tc, Ti), x_map)]
    operands = [xs]
    if gated:
        in_specs.append(pl.BlockSpec((1, Ti, Tk), up_map))
        operands.append(w_g)
    in_specs += [pl.BlockSpec((1, Ti, Tk), up_map),
                 pl.BlockSpec((1, Tk, Tj), down_map)]
    operands += [w_u, w_d]
    if quantized:
        # per-output-channel scale rows, block-indexed like their weights
        up_spec = pl.BlockSpec((1, 1, Tk), up_scale_map)
        if gated:
            in_specs.append(up_spec)
            operands.append(s_g)
        in_specs += [up_spec, pl.BlockSpec((1, 1, Tj), down_scale_map)]
        operands += [s_u, s_d]
    scratch = [pltpu.VMEM((Tc, Tk), jnp.float32)]                     # pre-act up
    if gated:
        scratch.append(pltpu.VMEM((Tc, Tk), jnp.float32))             # pre-act gate

    return pl.pallas_call(
        functools.partial(_kernel, activation=activation,
                          quantized=quantized, nI=nI),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, nJ, nK, nI),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Tc, Tj),
                                   lambda t, j, k, i, te, used: (t, 0, j)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((N, Tc, d), jnp.float32),
        interpret=interpret,
        name="streamed_moe",
    )(tile_expert, used, *operands)


def streamed_moe_kernel(xe, w_g, w_u, w_d, *, activation: str,
                        s_g=None, s_u=None, s_d=None,
                        token_tile: int = DEFAULT_TOKEN_TILE,
                        dmodel_tile: int | None = None,
                        dexpert_tile: int | None = None,
                        interpret: bool | None = None):
    """xe: (E,C,d) -> (E,C,d) float32: every expert's FFN over its C
    rows (the padded layout FSE-DP's ring and the hybrid tiers
    dispatch).  The ragged kernel with every expert one group of C rows,
    padded to a multiple of the token tile; options as
    :func:`ragged_moe_kernel`, plus ``token_tile`` (rounded by
    :func:`token_tile_for`)."""
    E, C, d = xe.shape
    Tc = token_tile_for(C, token_tile)
    pad = (-C) % Tc
    if pad:
        xe = jnp.pad(xe, ((0, 0), (0, pad), (0, 0)))
    Cp = C + pad
    out = ragged_moe_kernel(xe.reshape(E * Cp // Tc, Tc, d),
                            jnp.full((E,), Cp, jnp.int32), None,
                            w_g, w_u, w_d, activation=activation,
                            s_g=s_g, s_u=s_u, s_d=s_d,
                            dmodel_tile=dmodel_tile,
                            dexpert_tile=dexpert_tile, interpret=interpret)
    return out.reshape(E, Cp, d)[:, :C]
