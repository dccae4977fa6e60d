"""Fault-tolerant training orchestration.

At thousand-node scale the failure model is: a worker dies (hardware,
preemption), the SPMD step cannot proceed, the job restarts on the
surviving/replacement topology and must resume from the last committed
checkpoint with zero manual intervention. This module provides that
control plane at single-process scale with the same interfaces:

* ``TrainController`` — wraps the step loop: periodic atomic checkpoints,
  resume-from-latest on construction, crash-equivalent kill points in
  tests (the integration test SIGKILLs a child mid-run and verifies the
  restarted run continues from the committed step, not from scratch).
* ``Heartbeat`` — liveness file the launcher can monitor (a real cluster
  would use the coordination service; the artifact is the same: detect a
  dead worker, trigger restart).
* Elastic restarts go through ``repro.runtime.elastic``: the checkpoint
  is topology-independent (host arrays + current-mesh shardings).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Callable, Iterator

from ..spans import span

# NOTE: Checkpointer (and through it jax) is imported lazily inside
# TrainController.__init__. The distributed preprocessing workers import
# this module for Heartbeat, and the worker tier must stay jax-free at
# module level (contract R001, enforced by `python -m repro.analysis`).


class Heartbeat:
    """Liveness beacon file: ``<step> <unix-time>``.

    Writes go to a temp file in the same directory and are atomically
    renamed into place, so a monitor (``is_alive``) can never observe a
    torn, partially-written beat — a reader sees either the previous beat
    or the new one. The remote preprocessing coordinator
    (:mod:`repro.distributed.coordinator`) monitors these files to decide
    worker liveness alongside TCP connection state.
    """

    def __init__(self, path: str | Path, interval_s: float = 5.0):
        self.path = Path(path)
        self.interval_s = interval_s
        self._last = 0.0

    def beat(self, step: int, *, force: bool = False) -> None:
        now = time.time()
        if not force and now - self._last < self.interval_s:
            return
        tmp = self.path.with_name(self.path.name + f".tmp{os.getpid()}")
        tmp.write_text(f"{step} {now}")
        os.replace(tmp, self.path)
        self._last = now

    @staticmethod
    def last_beat(path: str | Path) -> float | None:
        """Unix time of the last committed beat, or None when the file is
        missing or unreadable (never raises: a vanished/garbage file just
        means "no beat")."""
        try:
            _, ts = Path(path).read_text().split()
            return float(ts)
        except (OSError, ValueError):
            # OSError: file missing / unreadable. ValueError: garbage
            # content (wrong field count or a non-float timestamp) — with
            # atomic beats that means corruption, not a torn write.
            return None

    @staticmethod
    def is_alive(path: str | Path, timeout_s: float) -> bool:
        ts = Heartbeat.last_beat(path)
        return ts is not None and (time.time() - ts) < timeout_s


class TrainController:
    """Checkpointed step loop: resumes from the latest committed step.

    ``stats`` counts the steps :meth:`run` took and ``sync_s``, the time
    spent turning each step's metrics into floats (the ``train.sync``
    spans): the loop's wait for the device to finish the step.
    """

    def __init__(
        self,
        ckpt_dir: str | Path,
        train_step: Callable,  # (params, opt_state, batch) -> (params, opt, metrics)
        init_state: Callable[[], tuple[Any, Any]],  # () -> (params, opt_state)
        *,
        save_every: int = 50,
        keep: int = 3,
        shardings: Any | None = None,
        heartbeat: Heartbeat | None = None,
    ):
        from ..checkpoint.checkpointer import Checkpointer

        self.ckpt = Checkpointer(ckpt_dir, keep=keep)
        self.train_step = train_step
        self.save_every = save_every
        self.heartbeat = heartbeat
        self.shardings = shardings
        self.stats = {"steps": 0, "sync_s": 0.0}

        latest = self.ckpt.latest()
        if latest is None:
            self.params, self.opt_state = init_state()
            self.step = 0
            self.resumed = False
        else:
            params, opt_state = init_state()  # structure donor
            (self.params, self.opt_state), extra = self.ckpt.restore(
                (params, opt_state), latest, shardings=self.shardings
            )
            self.step = int(extra.get("step", latest))
            self.resumed = True

    def run(self, batches: Iterator, n_steps: int) -> list[dict]:
        history = []
        for batch in batches:
            if self.step >= n_steps:
                break
            self.params, self.opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch
            )
            self.step += 1
            if self.heartbeat is not None:
                self.heartbeat.beat(self.step)
            with span("train.sync", self.stats, "sync_s"):
                scalars = {k: float(v) for k, v in metrics.items()}
            self.stats["steps"] += 1
            history.append({"step": self.step, **scalars})
            if self.step % self.save_every == 0:
                self.save()
        self.save()
        return history

    def save(self) -> None:
        self.ckpt.save(self.step, (self.params, self.opt_state), extra={"step": self.step})
