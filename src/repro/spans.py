"""Program spans: one timer that lands on the profiler's trace and in a ledger.

``span(name, ledger, field)`` times a block. On the way in it opens a
``jax.profiler.TraceAnnotation(name)``, so while a profiler trace runs the
block appears on the host plane, on the same clock as the device's
operations. On the way out it adds the block's seconds to
``ledger.<field>`` (a dataclass or any object) or ``ledger[field]`` (a
dict). Without a ledger it only annotates.

The ledgers are always on: a span costs two clock reads and one
annotation, which records nothing unless a profiler trace is running.

This module imports no jax. A span annotates only in a process that has
already imported jax (a process without it has no profiler to record
into), so the jax-free worker tier and the serve hot path may use it.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Callable


class span:
    """Time a block into ``ledger.<field>`` (or ``ledger[field]``) and mark
    it as ``name`` on the profiler trace. After the block, ``seconds`` holds
    its duration. ``clock`` is injectable for tests that run on a fake
    clock."""

    __slots__ = ("name", "ledger", "field", "clock", "seconds", "_t0", "_mark")

    def __init__(
        self,
        name: str,
        ledger: Any = None,
        field: str | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.name = name
        self.ledger = ledger
        self.field = field
        self.clock = clock
        self.seconds = 0.0
        self._mark = None

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            self._mark = profiler.TraceAnnotation(self.name)
            self._mark.__enter__()
        self._t0 = self.clock()
        return self

    def __exit__(self, *exc) -> None:
        dt = self.seconds = self.clock() - self._t0
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        if self.ledger is None:
            return
        if isinstance(self.ledger, dict):
            self.ledger[self.field] = self.ledger.get(self.field, 0.0) + dt
        else:
            setattr(self.ledger, self.field, getattr(self.ledger, self.field) + dt)
