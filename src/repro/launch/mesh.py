"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 placeholder host devices).
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import AxisType, set_mesh

__all__ = ["make_host_mesh", "make_production_mesh", "set_mesh", "shard_map"]


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods of
    256 = 512 chips (pod, data, model); the ``pod`` axis is an extra
    data-parallel dimension whose collectives cross the inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"),
        **_axis_kwargs(2),
    )
