"""Production mesh definition.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets the host-device-count XLA flag
before its first jax import; anything at module scope here would lock the
device count prematurely)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over forced host devices (tests)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
