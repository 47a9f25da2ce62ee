"""Production mesh construction.

Defined as functions (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with all-Auto axis types."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this process actually has (tests / local runs)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def mesh_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
