"""Production mesh construction.

Target hardware: TPU v5e, 256 chips per pod (16 x 16), optionally 2 pods.
Defined as functions (NOT module-level constants) so importing this module
never touches jax device state — the 512-device override is applied only by
dryrun.py before its own first jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code places values with with_sharding_constraint,
    # which Explicit axes (jax.make_mesh's default) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small-footprint mesh for CI (8 virtual host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
