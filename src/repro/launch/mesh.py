"""Production mesh definitions (functions, never module-level constants —
importing this module must not touch jax device state).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the default
placement runs DP over ``pod`` (one cross-pod gradient all-reduce per
step); the GPipe pipeline over ``pod`` is available as a feature
(repro.parallel.pipeline).
"""

from __future__ import annotations

import jax



def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=None):
    """Small CPU mesh for tests/examples (uses however many local devices
    exist)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
