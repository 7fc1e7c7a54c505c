"""Production meshes.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes.  JAX defaults to Explicit axes,
    on which the model's ``with_sharding_constraint`` pins
    (``parallel.sharding.constrain``) are refused."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips — the pod axis is the
    DCN dimension; gradients reduce hierarchically (ICI inside each pod,
    then one DCN all-reduce across pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (CPU tests: 1 device)."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))
