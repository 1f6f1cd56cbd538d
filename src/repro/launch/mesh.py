"""Production meshes.

Single pod: 16 x 16 (256 chips) -> axes (data, model).
Multi-pod:  2 x 16 x 16 (512 chips) -> axes (pod, data, model); the pod
axis is the outer data-parallel axis (crosses DCI) and realizes the
paper's "add another rack of remote servers" scale-out dimension.

These are FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init, so a caller that
wants virtual devices sets XLA_FLAGS before importing anything).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    # Auto axes: the sharding rules place arrays with
    # with_sharding_constraint, which Explicit axes (make_mesh's default)
    # refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally-available devices (tests/examples)."""
    n = len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return int(mesh.size)
