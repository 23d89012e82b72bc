"""Production mesh builders (deliverable (e)).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before any jax
import (launch/dryrun.py lines 1-2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh with the production axis names (CPU smoke)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]
