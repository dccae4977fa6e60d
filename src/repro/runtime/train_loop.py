"""Distributed train-step factory: microbatched gradient accumulation,
sharded AdamW update, donated buffers.

Gradient accumulation serves three purposes at pod scale:
* activation memory (micro-rows sized per arch),
* MoE dispatch-buffer memory (capacity buffers scale with micro tokens),
* compute/comm overlap: per-microbatch grads are accumulated locally and
  the cross-replica reduction happens ONCE per step, overlapped by XLA
  with the last microbatch's backward (the sharded-update reduce-scatter
  pattern falls out of pjit output shardings).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..optim.adamw import AdamW, AdamWState


@dataclass(frozen=True)
class TrainStepConfig:
    n_microbatches: int = 1
    loss_scale: float = 1.0  # static loss scaling for bf16 grads


def split_microbatches(batch: dict, n: int) -> dict:
    """(B, ...) -> (n, B/n, ...) on every leaf."""
    def sp(x):
        b = x.shape[0]
        assert b % n == 0, f"batch {b} not divisible by microbatches {n}"
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(sp, batch)


def make_input_pipeline(
    dataset,
    *,
    epochs: int | None = None,
    prefetch: int = 2,
    sharding: Any = None,
    stats: dict | None = None,
    overlap: bool = False,
    donate: bool = True,
    profiler: Any = None,
):
    """Wire a streaming :class:`~repro.core.dataset.Dataset` into the
    learner: batches stream out of the dataset's shard executor — reader
    threads, local worker processes, or the distributed data plane when
    the chain carries ``.workers(n, remote=...)`` — through an
    :class:`~repro.core.async_loader.AsyncLoader` that device-puts ahead
    of compute.

    This is the actor/learner split at pipeline level: preprocessing
    actors (possibly on other hosts) feed the device step loop, and a
    dead actor costs throughput, never correctness — its leased shards
    are reassigned and the batch stream is unchanged. Returns the loader;
    call ``.close()`` (or let a ``finally`` do it) when training stops
    mid-epoch so remote workers shut down instead of preprocessing into a
    queue nobody drains. ``stats`` (a dict) receives executor and cache
    counters after each epoch, and the epochs' start times.

    ``overlap=True`` (or passing a ``profiler``) upgrades the tail to a
    :class:`~repro.core.device_pipeline.DeviceFeed`: batches snap onto the
    plan's fixed bucket grid (the jit'd step compiles once per grid cell),
    transfers double-buffer one batch ahead, the consuming step donates
    its input buffers (``donate``), and the feed's
    :class:`~repro.core.device_pipeline.OverlapProfiler` counts the
    step loop's wait for host batches (``feed.wait``) and the transfers
    (``feed.transfer``). The step's own wait for the device is counted
    where the loop reads the step's results: a loop driven by
    :class:`~repro.runtime.fault_tolerance.TrainController` finds it in
    ``TrainController.stats`` (the ``train.sync`` spans).
    """
    from ..core.async_loader import AsyncLoader

    batches = dataset.iter_batches(epochs=epochs, stats=stats)
    if overlap or profiler is not None:
        from ..core.device_pipeline import DeviceFeed

        return DeviceFeed(
            batches,
            grid=dataset.bucket_grid_spec(),
            prefetch=prefetch,
            sharding=sharding,
            donate=donate,
            profiler=profiler,
            stats=stats,
        )
    return AsyncLoader(batches, prefetch=prefetch, sharding=sharding)


def make_train_step(
    loss_fn: Callable[[Any, dict], jax.Array],
    optimizer: AdamW,
    cfg: TrainStepConfig = TrainStepConfig(),
):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def grads_of(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads

    def train_step(params, opt_state: AdamWState, batch: dict):
        n = cfg.n_microbatches
        if n <= 1:
            loss, grads = grads_of(params, batch)
        else:
            micro = split_microbatches(batch, n)

            def body(carry, mb):
                loss_acc, grad_acc = carry
                loss, grads = grads_of(params, mb)
                grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
                )
                return (loss_acc + loss, grad_acc), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_sum, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero), micro)
            loss = loss_sum / n
            grads = jax.tree.map(lambda g: g / n, grads)
        new_params, new_opt, gnorm = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm}
        return new_params, new_opt, metrics

    return train_step
