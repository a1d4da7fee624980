"""Production meshes. FUNCTIONS (not module-level constants) so importing
this module never touches jax device state.

Every mesh here has ``Auto`` axes: the model code steers layouts with
``with_sharding_constraint`` hints (``sharding.hints``, ``launch.steps``)
and lets the partitioner propagate the rest. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which those hints become assertions."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (uses however many devices exist)."""
    return make_mesh((data, model), ("data", "model"))
