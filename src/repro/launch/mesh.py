"""Production mesh builders.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
device query).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is the
DCN tier — pure data parallelism with (optionally compressed) gradient
reduction, no parameter or activation sharding crosses it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int = 0, *, model: int = 1):
    """Small mesh over the locally-visible devices (tests, examples)."""
    n = n or len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
