"""Compiled-artifact analysis: collective-byte parsing + roofline terms.

collective_bytes is not in ``cost_analysis()`` — we parse the
(post-SPMD, per-device) HLO text and sum the output-shape bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op ("-start" variants counted once, "-done"
skipped).
"""
from __future__ import annotations

import re
from typing import Dict

# Published peaks of one TPU chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (200 GB/s, 50 GB/s on each of the 2x2 torus's 4 links).
TPU_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}


def tpu_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; a kind the
    table does not hold is an error, never a default."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for TPU device kind "
                         f"{device_kind!r}; add them to "
                         f"launch.analysis.TPU_PEAKS with their source"
                         ) from None


# the dry-run's roofline models a pod of v5e chips
_V5E = TPU_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E["bf16_flops"]   # FLOP/s
HBM_BW = _V5E["hbm_bw"]           # bytes/s
ICI_BW = _V5E["ici_link_bw"]      # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([a-z0-9\-]+)(?:-start)?\(", re.M)


def cost_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict (empty where the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def lowering_cost(fn, *args) -> Dict[str, float]:
    """Lower+compile ``fn`` on ``args`` and return its normalized XLA cost
    dict — the predicted-side record of the measured-autotune cache (the
    autotuner stores these next to wall-clock times per tile candidate)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return cost_dict(compiled)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-category byte totals from HLO text (per device)."""
    out = {c: 0.0 for c in _COLLECTIVES}
    out["count"] = 0
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if base.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            out[base] += _shape_bytes(shape_str)
            out["count"] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   chips: int) -> Dict[str, float]:
    """Three roofline terms in seconds.

    ``flops``/``hbm_bytes``/``coll_bytes`` are GLOBAL totals (summed over
    devices); the dry-run records per-device numbers × chips.
    """
    compute = flops / (chips * PEAK_FLOPS)
    memory = hbm_bytes / (chips * HBM_BW)
    collective = coll_bytes / (chips * ICI_BW)
    dom = max(("compute", compute), ("memory", memory),
              ("collective", collective), key=lambda kv: kv[1])
    return {"compute_s": compute, "memory_s": memory, "collective_s": collective,
            "dominant": dom[0], "bound_s": dom[1]}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D train / 2·N·D prefill / 2·N·B decode (active params)."""
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch            # one token per sequence
