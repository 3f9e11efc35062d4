"""Mesh builders.

Every mesh here has Auto axes: the sharding rules (PartitionSpecs plus
sharding constraints) assume Auto, and ``jax.make_mesh`` defaults to
Explicit axes.

`make_production_mesh` is a FUNCTION (not a module constant) so importing this
module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType

from repro.core.sharding import AXIS_DATA, AXIS_MODEL, AXIS_POD


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def abstract_mesh(axis_shapes, axis_names):
    """Device-free AbstractMesh with Auto axes (shape-only builds, dry runs)."""
    return AbstractMesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (AXIS_POD, AXIS_DATA, AXIS_MODEL) if multi_pod else (AXIS_DATA, AXIS_MODEL)
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4, pod: int | None = None):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), (AXIS_POD, AXIS_DATA, AXIS_MODEL))
    return make_mesh((data, model), (AXIS_DATA, AXIS_MODEL))


def batch_axes_for(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in (AXIS_POD, AXIS_DATA))
