"""Helpers shared by the Pallas kernels: the device capability check and
a prefix sum that lowers in Mosaic (which has no ``cumsum``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def has_tpu() -> bool:
    """True when JAX's default backend is a TPU — the capability check
    deciding whether kernels run compiled (``interpret=False``) or must
    interpret. A backend that fails to initialise raises here instead of
    reading as "no TPU"."""
    return jax.default_backend() == "tpu"


def lane_prefix_sum(v: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis of a 2-D in-kernel value:
    log2(width) Hillis-Steele steps, each a lane rotation with the wrapped
    lanes masked to zero."""
    width = v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    shift = 1
    while shift < width:
        v = v + jnp.where(lane >= shift, pltpu.roll(v, shift, v.ndim - 1), 0)
        shift *= 2
    return v
