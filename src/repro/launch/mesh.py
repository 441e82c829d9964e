"""Production mesh factory.

A FUNCTION, not a module constant — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
sees 512 placeholder host devices).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "make_mesh_compat"]


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis in automatic sharding mode."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh():
    """Whatever devices this host actually has — used by runnable examples."""
    n = len(jax.devices())
    return make_mesh_compat((n, 1), ("data", "model"))
