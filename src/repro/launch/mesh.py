"""Production mesh factory.

A FUNCTION (not module-level state) so importing this module never touches
jax device initialization — required because the dry-run pins the host
platform device count before first jax use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding layer places
    activations with ``with_sharding_constraint``, which only accepts Auto
    axes (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 (256 chips) per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_tp(mesh) -> int:
    return mesh.shape.get("model", 1)


def mesh_dp(mesh) -> int:
    return mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
