"""Production mesh builders.

Functions (not module constants) so importing never touches jax device
state. Single pod = 16x16 = 256 v5e chips (data, model); multi-pod adds a
leading pod axis (2 x 16 x 16 = 512 chips) used as extra data parallelism.

Every axis is ``AxisType.Auto``: the model code places arrays through
NamedShardings and leaves propagation to GSPMD. ``jax.make_mesh`` defaults
to ``Explicit`` axes, under which the embedding gather refuses to infer its
output sharding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh: one chip, a four-chip host, or CPU tests (which need the
    XLA_FLAGS host-device override when data*model > 1)."""
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes_for(mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
