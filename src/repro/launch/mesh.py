"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — see dryrun.py,
which must set XLA_FLAGS before any jax import)."""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = one v5e pod (256 chips); multi_pod adds a 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over the first ``prod(shape)`` devices: the chips of one
    host, or CPU devices under xla_force_host_platform_device_count.

    A shape that wants more devices than the host has is an error, never
    a smaller mesh: a run that asked for N devices and got fewer would
    report results it never produced."""
    need, have = math.prod(shape), jax.device_count()
    if need > have:
        raise ValueError(f"mesh shape {tuple(shape)} needs {need} devices; "
                         f"this host has {have}")
    return _make_mesh(shape, axes)
