"""Production mesh: 16×16 single pod (256 chips) / 2×16×16 multi-pod.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import).
"""
from __future__ import annotations

import jax


def auto_axis_kwargs(n_axes: int) -> dict:
    """``jax.make_mesh`` kwargs that make every axis ``AxisType.Auto``."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axis_kwargs(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(min(model, n // data), 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         **auto_axis_kwargs(2))
