"""Elastic scaling: restore a checkpoint onto a different topology.

The checkpoint format is topology-independent (host-side arrays keyed by
tree path); elasticity is therefore a *placement* problem: rebuild the
mesh from the currently-available device count, re-derive every leaf's
sharding with the same logical-axis rules, and device_put accordingly.
``remesh`` is the entry point the launcher calls after a failure shrinks
(or an allocation grows) the slice.

Divisibility: the sharding rule engine already falls back per-tensor when
a dimension stops dividing the new axis size, so shrinking 16→8→4 devices
needs no per-arch handling. Global batch is rebalanced by the data
pipeline (batch axis = whatever the new mesh provides).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType

from ..distributed.sharding import DEFAULT_RULES, tree_shardings


def available_mesh(model_parallel: int = 1, devices=None):
    """Largest (data, model) mesh over the devices that are still alive."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mp = model_parallel
    while n % mp:
        mp -= 1
    return jax.make_mesh(
        (n // mp, mp), ("data", "model"), devices=devices,
        axis_types=(AxisType.Auto, AxisType.Auto),
    )


def remesh(
    tree: Any,
    axes_tree: Any,
    new_mesh,
    rules=DEFAULT_RULES,
) -> Any:
    """Re-place every leaf of ``tree`` for ``new_mesh`` (host round-trip —
    on a real pod this is the post-restart restore path, so arrays are on
    host already)."""
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    sh = tree_shardings(shapes, axes_tree, new_mesh, rules)
    return jax.tree.map(lambda x, s: jax.device_put(np.asarray(jax.device_get(x)), s), tree, sh)
