"""jit'd public wrappers around the Pallas kernels with ref fallbacks.

``use_kernels(False)`` (or the REPRO_NO_PALLAS env var) routes every op
to its pure-jnp oracle — the dry-run path uses this so the 512-device
SPMD compile sees plain XLA ops.  The env var is refused on a TPU, so a
chip run can never measure the oracle in place of the kernels.

:func:`interpret_default` is the one rule for how a kernel lowers: in
the Pallas interpreter on the CPU backend, natively everywhere else.

``streamed_moe`` (the padded ``(E, C, d)`` layout: the FSE-DP ring step
``repro.core.fse_dp._expert_partial``, the hybrid tiers, the EP/TP
baselines) and ``ragged_moe`` (rows grouped by expert: the single-device
capacity path ``repro.models.moe.moe_capacity``) are the dispatch layer
the model code calls, so the paper's micro-slice kernel is the hot path
whenever kernels are enabled.  Each Pallas branch carries a custom VJP
(backward derived from the jnp oracle) so gradients flow through
training and the FSE-DP ring transpose without a hand-written backward
kernel.
"""
from __future__ import annotations

import contextlib
import contextvars
import os

import jax

from . import quant, ref
from .streamed_moe import ragged_moe_kernel, streamed_moe_kernel
from .flash_attention import flash_attention_kernel
from .ssd import ssd_intra_chunk_kernel

def interpret_default() -> bool:
    """Whether a kernel called with ``interpret=None`` runs in the Pallas
    interpreter: only on the CPU backend.  Every kernel resolves its
    default here, so none is quietly interpreted on an accelerator."""
    return jax.default_backend() == "cpu"


# None = not set in this context: the REPRO_NO_PALLAS default applies
_USE = contextvars.ContextVar("repro_use_pallas", default=None)


@contextlib.contextmanager
def use_kernels(enabled: bool):
    tok = _USE.set(enabled)
    try:
        yield
    finally:
        _USE.reset(tok)


def _env_default() -> bool:
    if not os.environ.get("REPRO_NO_PALLAS"):
        return True
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "REPRO_NO_PALLAS is set on a TPU backend: the chip path runs "
            "the Pallas kernels, never the jnp oracle in their place — "
            "unset it (or use kernels.ops.use_kernels(False) explicitly "
            "for a reference computation)")
    return False


def kernels_enabled() -> bool:
    enabled = _USE.get()
    return _env_default() if enabled is None else enabled


# ---------------------------------------------------------------------------
# streamed_moe — differentiable kernel dispatch
# ---------------------------------------------------------------------------

def _streamed_weights(weight_dtype, w_g, w_u, w_d):
    """The expert weights as the kernel streams them, and their scale
    kwargs.  int8/fp8 quantize in-graph at the dispatch layer: params
    keep their original dtype; the kernel streams int8/fp8 blocks plus
    per-(expert, output-channel) scale rows and dequantizes in VMEM."""
    if weight_dtype in quant.QUANTIZED:
        s_g = None
        if w_g is not None:
            w_g, s_g = quant.quantize(w_g, weight_dtype)
        w_u, s_u = quant.quantize(w_u, weight_dtype)
        w_d, s_d = quant.quantize(w_d, weight_dtype)
        return (w_g, w_u, w_d), dict(s_g=s_g, s_u=s_u, s_d=s_d)
    return tuple(quant.storage_cast(w, weight_dtype)
                 for w in (w_g, w_u, w_d)), {}


def _weight_dtype(kw):
    """The streamed weight format: the ``weight_dtype`` kwarg (popped
    from ``kw``), else the ambient ``quant.use_weight_dtype``."""
    wdt = quant.check_weight_dtype(kw.pop("weight_dtype", None))
    return quant.weight_dtype() if wdt is None else wdt


def _streamed_moe_raw(activation, weight_dtype, opts, xe, w_g, w_u, w_d):
    ws, scales = _streamed_weights(weight_dtype, w_g, w_u, w_d)
    return streamed_moe_kernel(xe, *ws, activation=activation, **scales,
                               **dict(opts))


_streamed_moe_diff = jax.custom_vjp(_streamed_moe_raw,
                                    nondiff_argnums=(0, 1, 2))


def _streamed_moe_fwd(activation, weight_dtype, opts, xe, w_g, w_u, w_d):
    out = _streamed_moe_raw(activation, weight_dtype, opts, xe, w_g, w_u, w_d)
    return out, (xe, w_g, w_u, w_d)


def _streamed_moe_bwd(activation, weight_dtype, opts, res, g):
    # straight-through: the backward of the quantized forward is the
    # full-precision oracle VJP on the original weights
    xe, w_g, w_u, w_d = res
    _, vjp = jax.vjp(
        lambda xe, wg, wu, wd: ref.streamed_moe_ref(xe, wg, wu, wd, activation),
        xe, w_g, w_u, w_d)
    return vjp(g)


_streamed_moe_diff.defvjp(_streamed_moe_fwd, _streamed_moe_bwd)


def streamed_moe(xe, w_g, w_u, w_d, activation: str, **kw):
    """Grouped expert FFN over one micro-slice.  ``w_g=None`` selects the
    gateless path natively (no placeholder operand).

    ``weight_dtype`` (kwarg or the ambient ``quant.use_weight_dtype``
    context, entered by ``ExecutionSpec.scope()``) selects the streamed
    storage format for the expert weights: int8/fp8 quantize in-graph
    with per-(expert, output-channel) scales; the oracle fallback runs
    the identical quantize→dequantize round-trip, so ``use_kernels(False)``
    stays the ground truth at any weight dtype."""
    kw = dict(kw)
    wdt = _weight_dtype(kw)
    if not kernels_enabled():
        if wdt is None:
            return ref.streamed_moe_ref(xe, w_g, w_u, w_d, activation)
        return ref.streamed_moe_quant_ref(xe, w_g, w_u, w_d, activation, wdt)
    opts = tuple(sorted(kw.items()))
    return _streamed_moe_diff(activation, wdt, opts, xe, w_g, w_u, w_d)


# ---------------------------------------------------------------------------
# ragged_moe — the same, over rows grouped by expert (moe_capacity's layout)
# ---------------------------------------------------------------------------

def _ragged_moe_raw(activation, weight_dtype, opts, xs, sizes, order, w_g,
                    w_u, w_d):
    ws, scales = _streamed_weights(weight_dtype, w_g, w_u, w_d)
    return ragged_moe_kernel(xs, sizes, order, *ws, activation=activation,
                             **scales, **dict(opts))


_ragged_moe_diff = jax.custom_vjp(_ragged_moe_raw, nondiff_argnums=(0, 1, 2))


def _ragged_moe_fwd(activation, weight_dtype, opts, xs, sizes, order, w_g,
                    w_u, w_d):
    out = _ragged_moe_raw(activation, weight_dtype, opts, xs, sizes, order,
                          w_g, w_u, w_d)
    return out, (xs, sizes, order, w_g, w_u, w_d)


def _ragged_moe_bwd(activation, weight_dtype, opts, res, g):
    # straight-through, as streamed_moe: the full-precision oracle's VJP
    xs, sizes, order, w_g, w_u, w_d = res
    _, vjp = jax.vjp(
        lambda xs, wg, wu, wd: ref.ragged_moe_ref(xs, sizes, order, wg, wu,
                                                  wd, activation),
        xs, w_g, w_u, w_d)
    d_xs, d_wg, d_wu, d_wd = vjp(g)
    return d_xs, None, None, d_wg, d_wu, d_wd


_ragged_moe_diff.defvjp(_ragged_moe_fwd, _ragged_moe_bwd)


def ragged_moe(xs, sizes, order, w_g, w_u, w_d, activation: str, **kw):
    """Grouped expert FFN over rows grouped by expert
    (``streamed_moe.ragged_moe_kernel``: xs (N,Tc,d), ``sizes`` rows per
    group in layout order, ``order`` the expert of each group or None).
    ``weight_dtype`` and the oracle fallback under ``use_kernels(False)``
    as :func:`streamed_moe`; the oracle is ``ref.ragged_moe_ref``."""
    kw = dict(kw)
    wdt = _weight_dtype(kw)
    if not kernels_enabled():
        if wdt is None:
            return ref.ragged_moe_ref(xs, sizes, order, w_g, w_u, w_d,
                                      activation)
        return ref.ragged_moe_quant_ref(xs, sizes, order, w_g, w_u, w_d,
                                        activation, wdt)
    return _ragged_moe_diff(activation, wdt, tuple(sorted(kw.items())), xs,
                            sizes, order, w_g, w_u, w_d)


def streamed_moe_autotuned(xe, w_g, w_u, w_d, activation: str):
    """``streamed_moe`` with tile kwargs chosen by the ``core.autotune``
    planner for this call's (E, C, d, m) shape, honoring the ambient
    autotune level — ``off`` (kernel defaults, the pre-autotuner
    lowering), ``analytic`` (cost-model tiles), or ``measured``
    (wall-clock-timed tiles memoized under ``artifacts/autotune/``).

    This is the one scheduler every padded-layout expert-FFN path
    dispatches through: the FSE-DP ring step, the hybrid tiers and the
    EP/TP baselines.  The ambient weight dtype feeds the planner its
    streamed bytes-per-param, so quantized runs plan (and cost) larger
    hidden tiles per VMEM block."""
    opts = {}
    if kernels_enabled():
        from repro.core import autotune
        E, C, d = xe.shape
        m = w_u.shape[-1]
        stored = jax.numpy.dtype(w_u.dtype).itemsize
        opts = autotune.kernel_opts_for(
            E, C, d, m, activation, dtype_bytes=stored,
            weight_bytes=quant.weight_bytes(default=stored))
    return streamed_moe(xe, w_g, w_u, w_d, activation, **opts)


def flash_attention(q, k, v, **kw):
    if kernels_enabled():
        return flash_attention_kernel(q, k, v, **kw)
    return ref.flash_attention_ref(q, k, v)


def ssd_intra_chunk(xc, Bc, Cc, Ac, A_cumsum, **kw):
    if kernels_enabled():
        return ssd_intra_chunk_kernel(xc, Bc, Cc, Ac, A_cumsum, **kw)
    return ref.ssd_intra_chunk_ref(xc, Bc, Cc, Ac, A_cumsum)
