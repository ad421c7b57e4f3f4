"""Production mesh construction.

Importing this module never touches jax device state; meshes are built only
inside the function, so tests see 1 CPU device while the dry-run (which sets
XLA_FLAGS before any import) sees 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips; the pod
    axis extends data parallelism across the inter-pod (DCN) boundary.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)}; the "
            "dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def mesh_axes(mesh) -> tuple:
    """(dp_axes, tp_axis) for a mesh built by make_production_mesh."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return ("data",), "model"


def smoke_mesh():
    """1-device mesh for CPU tests of the sharding machinery."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
