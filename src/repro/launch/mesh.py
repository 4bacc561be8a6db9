"""Production mesh construction.

Functions, not module-level constants, so importing this module never touches
jax device state (device count is locked at first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import MeshInfo


def _auto_mesh(shape, axes):
    # Auto axes: the steps place arrays with with_sharding_constraint, which
    # refers only to Auto axes (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_info(*, multi_pod: bool = False) -> MeshInfo:
    return MeshInfo(make_production_mesh(multi_pod=multi_pod))


def make_host_mesh(data: int = 1, model: int = 1) -> MeshInfo:
    """Small mesh over the devices of one host."""
    return MeshInfo(_auto_mesh((data, model), ("data", "model")))
