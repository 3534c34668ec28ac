"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The single-pod mesh is 16×16 = 256 chips (v5e pod),
axes (data, model); the multi-pod mesh adds a leading pod axis:
2×16×16 = 512 chips, axes (pod, data, model). ``pod`` is an outer
data-parallel axis — gradients reduce hierarchically (fast ICI inside a pod
first, the slower inter-pod hop once).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires XLA host-device override)."""
    return _make_mesh(shape, axes)


def replica_submeshes(mesh):
    """Carve a ``(..., model)`` mesh into one TP submesh per data index.

    Every non-``model`` axis is flattened into replica groups: a
    ``(2, 16, 16)`` (pod, data, model) mesh yields 32 submeshes of shape
    ``(1, 16)`` with axes ``("data", "model")``. This is the data-parallel
    serving decomposition — each replica group runs its own
    tensor-parallel engine (``repro.serve.router.ReplicaRouter``), so no
    collective ever crosses replica boundaries.
    """
    import numpy as np
    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
    if mesh.axis_names[-1] != "model":
        raise ValueError("the 'model' axis must be trailing (fastest-"
                         f"varying), got {mesh.axis_names}")
    msize = mesh.shape["model"]
    groups = np.asarray(mesh.devices).reshape(-1, msize)
    return [jax.sharding.Mesh(row.reshape(1, msize), ("data", "model"))
            for row in groups]
