"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The single-pod mesh
is 16x16 = 256 chips (TPU v5e pod); multi-pod adds a leading ``pod`` axis
(2 pods = 512 chips over DCN).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.models.layers import MeshInfo


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    # Auto axes: the model code places activations with
    # ``with_sharding_constraint``, which refuses Explicit axes (the
    # default of ``jax.make_mesh``)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU multi-device tests (host platform device count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def mesh_info(mesh, global_batch: Optional[int] = None) -> MeshInfo:
    """Build MeshInfo; batch axes are dropped when the global batch does not
    divide them (e.g. long_500k batch=1 -> replicate, see DESIGN.md)."""
    axes = tuple(mesh.axis_names)
    batch_axes: Tuple[str, ...] = tuple(a for a in axes if a != "model")
    if global_batch is not None:
        n = 1
        for a in batch_axes:
            n *= mesh.shape[a]
        if global_batch % n != 0:
            batch_axes = ()
    model_axis = "model" if "model" in axes else None
    return MeshInfo(mesh=mesh, batch_axes=batch_axes, model_axis=model_axis)
