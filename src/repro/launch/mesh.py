"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.models.layers import set_mesh_axis_sizes


def compat_make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``
    (GSPMD-propagated sharding, what the model code's specs assume)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh = compat_make_mesh(shape, axes)
    set_mesh_axis_sizes(dict(zip(axes, shape)))
    return mesh


def make_local_mesh(model: int = 1, data: Optional[int] = None) -> Mesh:
    """Whatever this process has: (data, model) covering jax.device_count().
    Used by examples and tests; on the CPU container this is (1, 1)."""
    n = jax.device_count()
    data = data or (n // model)
    assert data * model == n, (data, model, n)
    mesh = compat_make_mesh((data, model), ("data", "model"))
    set_mesh_axis_sizes({"data": data, "model": model})
    return mesh


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
