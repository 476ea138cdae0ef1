"""Device-mesh specifications and construction.

A MeshSpec is a *declarative* mesh description (shape + axis names) that
can be reasoned about without touching jax device state — the dry-run
and the sharding tests resolve rules against specs (or duck-typed fake
meshes) long before any devices exist.  ``make_mesh`` turns a spec into
a real ``jax.sharding.Mesh`` over whatever devices the process has
(production chips, or fake CPU devices forced via
``--xla_force_host_platform_device_count``).

Axis conventions (shared with dist.sharding):

  pod    — outermost data-parallel axis (inter-pod DCN-class links)
  data   — intra-pod data-parallel / FSDP axis
  model  — tensor-parallel axis (heads / mlp / vocab / experts)
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax

# Axes over which the global batch is folded (outermost first).
DP_AXES = ("pod", "data")


class MeshSpec(NamedTuple):
    """Shape + axis names; construction-free mesh description."""
    shape: tuple
    axes: tuple

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @property
    def dp_axes(self) -> tuple:
        """The data-parallel axes this mesh actually has."""
        return tuple(a for a in self.axes if a in DP_AXES)

    @property
    def axis_sizes(self) -> dict:
        return dict(zip(self.axes, self.shape))


SINGLE_POD = MeshSpec((16, 16), ("data", "model"))
MULTI_POD = MeshSpec((2, 16, 16), ("pod", "data", "model"))


def _pow2_factor(n: int, cap: int) -> int:
    """Largest power-of-two divisor of n, capped at `cap`."""
    f = 1
    while n % (f * 2) == 0 and f * 2 <= cap:
        f *= 2
    return f


def spec_for(n: int, *, multi_pod: bool = False) -> MeshSpec:
    """A MeshSpec for exactly `n` devices.

    The model (TP) axis takes the largest power-of-two factor of n (up to
    16, the production TP width); the data axis absorbs the rest, so
    non-power-of-two device counts still produce a valid mesh (the odd
    factor lands on 'data' where divisibility only gates batch folding).
    `multi_pod` peels a pod axis of 2 off first when n is even.
    """
    if n <= 0:
        raise ValueError(f"device count must be positive, got {n}")
    if multi_pod:
        pod = 2 if n % 2 == 0 else 1
        rest = n // pod
        model = _pow2_factor(rest, 16)
        return MeshSpec((pod, rest // model, model),
                        ("pod", "data", "model"))
    model = _pow2_factor(n, 16)
    return MeshSpec((n // model, model), ("data", "model"))


def make_mesh(spec: MeshSpec, devices=None) -> jax.sharding.Mesh:
    """Materialize a spec over real devices (default: all local devices).

    Requires ``spec.num_devices`` devices; the multi-device tests run in
    a subprocess with ``--xla_force_host_platform_device_count`` set
    before jax initializes.
    """
    if devices is None:
        # Auto axes: the rules engine places arrays with
        # with_sharding_constraint, which Explicit axes (make_mesh's
        # default) refuse
        return jax.make_mesh(
            spec.shape, spec.axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(spec.axes))
    import numpy as np
    arr = np.asarray(devices).reshape(spec.shape)
    return jax.sharding.Mesh(arr, spec.axes)


def axis_sizes(mesh) -> dict:
    """{axis name: size} for a real Mesh, a MeshSpec, or any duck-typed
    object with .axis_names + .devices (the tests' FakeMesh)."""
    if isinstance(mesh, MeshSpec):
        return mesh.axis_sizes
    return dict(zip(mesh.axis_names, mesh.devices.shape))
