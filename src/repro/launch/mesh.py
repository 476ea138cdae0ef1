"""Production mesh builders (launch-side; dist/mesh.py holds the generic
machinery).  FUNCTIONS, not module-level constants — importing this module
must never touch jax device state, because the dry-run sets XLA_FLAGS
before the first jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
