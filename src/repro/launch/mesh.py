"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.

Every mesh the repo builds goes through :func:`make_mesh`, which gives
each axis the ``Auto`` type: ``jax.make_mesh`` defaults to ``Explicit``
axes, under which ``with_sharding_constraint`` (the sharding rules in
``repro.parallel.sharding``) refuses to name an axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over ``axes`` with ``Auto`` axis types (tests,
    examples and benchmarks use small host-device meshes; ``devices``
    pins the device list, e.g. a described topology's)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: every axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")
