"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — jax locks the device count at first backend init, and the
dry-run needs to set XLA_FLAGS before that happens.
"""
from __future__ import annotations

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(n_data: int = 2, n_model: int = 4):
    """Small host-device mesh for tests (requires XLA host-device flag)."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
