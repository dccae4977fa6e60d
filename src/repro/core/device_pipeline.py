"""Host→device overlap engine: the batch-assembly tail of the lazy plan.

The paper's framing: the accelerator idles while the host preprocesses
text. PRs 1–6 made the host side fast, cached, and distributed; this
module closes the loop at the device boundary. :class:`DeviceFeed` takes
the length-bucketed token batches streaming out of the plan, snaps every
batch onto the **fixed bucket grid** (row-pads partial batches, width-pads
each bucketed column up to its grid rung — so the jit'd step sees a small
closed shape set and compiles once per cell), and transfers via
double-buffered, sharding-aware ``jax.device_put``: batch k+1's transfer
is issued before batch k is yielded, so host work and H2D copies hide
behind device compute. Donation is handled at the *step* boundary: the
consuming jit'd step donates the batch buffers back to XLA, and the feed
marks the yielded :class:`DeviceBatch` consumed — a reuse-after-donate is
a hard error, not silent corruption.

The :class:`OverlapProfiler` is the measurement half of the paper's
claim: per step it accounts host-wait (the device would have idled) vs
device-compute time and reports a **device-idle fraction** — ~0 on a warm
cache means preprocessing is fully hidden (``bench_cumulative --overlap``
gates this in CI).

The seed-era on-accelerator cleaning path (:class:`DeviceCleaner`,
char-level cleaning as a Pallas kernel) remains, rebuilt on ``col()``
expressions instead of the deprecated ``Stage`` shims.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Mapping, Sequence

import numpy as np

from ..data.tokenizer import PAD
from ..spans import span
from .async_loader import AsyncLoader, LoaderStats

# ---------------------------------------------------------------------------
# Fixed bucket grid: the closed shape set the jit'd step compiles against
# ---------------------------------------------------------------------------


class BucketGrid:
    """The static shape contract between batch assembly and the device step.

    ``widths`` maps each bucketed array column to its ladder of bucket
    widths (ascending). :meth:`snap` pads a host batch onto the grid: rows
    up to ``batch_size`` (PAD rows), each laddered column up to the
    smallest rung that fits. Every snapped batch then has one of
    ``n_cells`` shapes, so an epoch compiles the device step at most once
    per cell — never once per batch.
    """

    def __init__(self, batch_size: int, widths: Mapping[str, Sequence[int]]):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.batch_size = int(batch_size)
        self.widths = {
            c: tuple(sorted(int(w) for w in ws)) for c, ws in widths.items()
        }
        for c, ws in self.widths.items():
            if not ws:
                raise ValueError(f"empty bucket ladder for column {c!r}")

    @property
    def n_cells(self) -> int:
        n = 1
        for ws in self.widths.values():
            n *= len(ws)
        return n

    def _rung(self, column: str, width: int) -> int:
        ladder = self.widths[column]
        for w in ladder:
            if width <= w:
                return w
        raise ValueError(
            f"column {column!r} is {width} wide, beyond the top bucket "
            f"{ladder[-1]} — the batch was not assembled on this grid"
        )

    def snap(self, batch: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Pad ``batch`` onto the grid (prefix-preserving, PAD fill)."""
        out: dict[str, np.ndarray] = {}
        for k, v in batch.items():
            v = np.asarray(v)
            rows = v.shape[0]
            width = v.shape[1] if v.ndim > 1 else None
            target_w = (
                self._rung(k, width)
                if width is not None and k in self.widths
                else width
            )
            if rows == self.batch_size and (width is None or target_w == width):
                out[k] = v
                continue
            shape = (self.batch_size,) + (
                (target_w,) + v.shape[2:] if width is not None else v.shape[1:]
            )
            padded = np.full(shape, PAD, dtype=v.dtype)
            if width is None:
                padded[:rows] = v
            else:
                padded[:rows, :width] = v
            out[k] = padded
        return out

    def cell_key(self, batch: Mapping[str, Any]) -> tuple:
        """Hashable static-shape key of a (snapped) batch."""
        return tuple(sorted((k, tuple(np.shape(v))) for k, v in batch.items()))


# ---------------------------------------------------------------------------
# Device batches with donation safety
# ---------------------------------------------------------------------------


class DeviceBatch(Mapping):
    """One grid-snapped batch on device.

    Behaves as a read-only mapping of device arrays. Once the consuming
    step donated the buffers (:meth:`mark_donated`, done by
    ``DeviceFeed.step(...)`` on exit), any further access raises — XLA has
    already reused the memory, so a late read would be garbage.
    """

    def __init__(self, arrays: dict[str, Any], cell: tuple):
        self._arrays = arrays
        self.cell = cell
        self.donated = False

    def mark_donated(self) -> None:
        self.donated = True

    def _check(self) -> None:
        if self.donated:
            raise RuntimeError(
                "reuse after donate: this DeviceBatch was consumed by a "
                "donating device step; its buffers belong to XLA now"
            )

    @property
    def arrays(self) -> dict[str, Any]:
        self._check()
        return self._arrays

    def __getitem__(self, key: str):
        self._check()
        return self._arrays[key]

    def __iter__(self):
        return iter(self._arrays)

    def __len__(self) -> int:
        return len(self._arrays)


# ---------------------------------------------------------------------------
# Overlap accounting
# ---------------------------------------------------------------------------


@dataclass
class OverlapReport:
    """Per-epoch overlap accounting (all times from the profiler clock).

    ``device_idle_fraction`` is steady-state: the first-batch pipeline
    fill (``startup_s``) is startup latency, not overlap failure, so it is
    reported separately and excluded from the fraction.
    """

    steps: int = 0
    host_wait_s: float = 0.0  # post-startup consumer stalls (device idle)
    startup_s: float = 0.0  # first-batch pipeline fill
    device_s: float = 0.0  # time inside profiled device steps
    transfer_s: float = 0.0  # host→device copies issued by the feed
    starved_steps: int = 0  # steps that waited > eps on the host

    @property
    def device_idle_fraction(self) -> float:
        busy = self.host_wait_s + self.device_s
        return self.host_wait_s / busy if busy > 0 else 0.0

    def as_dict(self) -> dict:
        d = dict(self.__dict__)
        d["device_idle_fraction"] = self.device_idle_fraction
        return d


class OverlapProfiler:
    """Accumulates host-wait vs device-compute time for one feed epoch.

    The clock is injectable, so the idle-fraction math is exactly testable
    against a fake clock; ``starvation_eps`` separates true stalls from
    the microseconds a warm queue handoff costs on a real clock.
    """

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        starvation_eps: float = 1e-3,
    ):
        self.clock = clock
        self.starvation_eps = starvation_eps
        self._r = OverlapReport()

    def record_wait(self, dt: float, startup: bool = False) -> None:
        if startup:
            self._r.startup_s += dt
            return
        self._r.host_wait_s += dt
        if dt > self.starvation_eps:
            self._r.starved_steps += 1

    @contextmanager
    def step(self):
        """Time one device-compute segment (caller blocks on the result
        inside the ``with`` for honest accounting)."""
        t0 = self.clock()
        yield
        self._r.device_s += self.clock() - t0
        self._r.steps += 1

    def report(self) -> OverlapReport:
        return self._r


# ---------------------------------------------------------------------------
# The feed
# ---------------------------------------------------------------------------


class DeviceFeed:
    """Donated, double-buffered host→device handoff with idle accounting.

    ``batches`` is an iterator of host dict-batches (token arrays out of
    ``Dataset.iter_batches``). With ``prefetch >= 1`` an
    :class:`~repro.core.async_loader.AsyncLoader` in host mode runs the
    upstream pipeline in a fill thread (its :class:`LoaderStats` expose
    queue depth/starvation); ``prefetch=0`` pulls synchronously — no
    threads, exact fake-clock semantics for tests.

    Iteration yields :class:`DeviceBatch` objects one transfer ahead:
    batch k+1 is already in flight when batch k is handed to the step.
    Wrap each device step in :meth:`step` — it times the compute segment
    and, when ``donate=True`` (default), marks the batch consumed so the
    donating jit'd step (``donate_argnums``) can never observe a stale
    read.

    The wait for each host batch is the ``feed.wait`` span and each
    batch's ``device_put`` calls the ``feed.transfer`` span, counted in
    the report's ``host_wait_s`` and ``transfer_s``. ``stats`` (the plan's
    stats dict, when given) also receives the running ``transfer_s``.
    """

    def __init__(
        self,
        batches: Iterator,
        *,
        grid: BucketGrid | None = None,
        prefetch: int = 2,
        sharding: Any = None,
        donate: bool = True,
        device_put: Callable[[np.ndarray], Any] | None = None,
        clock: Callable[[], float] = time.perf_counter,
        profiler: OverlapProfiler | None = None,
        stats: dict | None = None,
    ):
        self.grid = grid
        self._stats = stats
        self.donate = donate
        self._sharding = sharding
        self._device_put = device_put
        self._clock = clock
        self.profiler = profiler or OverlapProfiler(clock=clock)
        self._loader: AsyncLoader | None = None
        if prefetch >= 1:
            self._loader = AsyncLoader(
                batches,
                prefetch=prefetch,
                device_put=lambda b: b,  # host prefetch only; we transfer
                clock=clock,
            )
            self._source: Iterator = iter(self._loader)
        else:
            self._source = iter(batches)

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        if self._loader is not None:
            self._loader.close()
        else:
            finalize = getattr(self._source, "close", None)
            if finalize is not None:
                finalize()

    @property
    def loader_stats(self) -> LoaderStats | None:
        """Queue gauges of the host prefetch stage (None when prefetch=0)."""
        return self._loader.stats if self._loader is not None else None

    # -- transfer ----------------------------------------------------------
    def _put_leaf(self, x: np.ndarray):
        if self._device_put is not None:
            return self._device_put(x)
        import jax

        if self._sharding is not None:
            return jax.device_put(x, self._sharding)
        return jax.device_put(x)

    def _transfer(self, host_batch: Mapping[str, np.ndarray]) -> DeviceBatch:
        snapped = self.grid.snap(host_batch) if self.grid is not None else host_batch
        cell = (
            self.grid.cell_key(snapped)
            if self.grid is not None
            else tuple(sorted((k, np.shape(v)) for k, v in snapped.items()))
        )
        report = self.profiler.report()
        with span("feed.transfer", report, "transfer_s", clock=self._clock):
            arrays = {k: self._put_leaf(np.asarray(v)) for k, v in snapped.items()}
        if self._stats is not None:
            self._stats["transfer_s"] = report.transfer_s
        return DeviceBatch(arrays, cell)

    # -- consumption -------------------------------------------------------
    def __iter__(self) -> Iterator[DeviceBatch]:
        pending: DeviceBatch | None = None
        first = True
        while True:
            try:
                with span("feed.wait", clock=self._clock) as waited:
                    host = next(self._source)
            except StopIteration:
                break
            self.profiler.record_wait(waited.seconds, startup=first)
            first = False
            nxt = self._transfer(host)
            if pending is not None:
                yield pending
            pending = nxt
        if pending is not None:
            yield pending

    @contextmanager
    def step(self, batch: DeviceBatch | None = None):
        """Time one device step; with ``donate=True`` the batch is marked
        consumed on exit (the step's ``donate_argnums`` owns it now)."""
        with self.profiler.step():
            yield
        if batch is not None and self.donate:
            batch.mark_donated()

    def report(self) -> OverlapReport:
        return self.profiler.report()


# ---------------------------------------------------------------------------
# On-accelerator cleaning (expression-native rebuild of the seed path)
# ---------------------------------------------------------------------------


class DeviceCleaner:
    """Drop-in cleaning engine: char-level stages on device, word-level on
    host. Equivalent to ``lower + strip_html + keep_letters`` character
    classes (no contraction mapping — recorded divergence: contractions
    lose their apostrophes instead of expanding; see DESIGN.md). The host
    half is a ``col()`` expression chain (word-level verbs only), compiled
    once and applied to the flat byte buffers the device pass returns.
    ``interpret=None`` runs the kernel compiled on a TPU and interpreted
    elsewhere (the capability check of ``clean_rows``).
    """

    def __init__(
        self, word_expr: Callable | None = None, interpret: bool | None = None
    ):
        from . import expr as E

        self.interpret = interpret
        if word_expr is None:
            self._ops: tuple = ()
        else:
            compiled = E.compile_expr(word_expr(E.col("__device_cleaned")))
            kind, source, ops = compiled
            if kind != "chain" or source != "__device_cleaned":
                raise ValueError(
                    "word_expr must be a pure per-column chain "
                    "(Expr -> Expr over its input column)"
                )
            self._ops = tuple(ops)

    def transform(self, frame, cols: list[str]):
        from ..kernels.text_clean.ops import clean_rows
        from . import bytesops as B

        out = frame
        for c in cols:
            rows = ["" if v is None else str(v) for v in out[c]]
            cleaned = clean_rows(rows, interpret=self.interpret)
            buf = B.flatten(cleaned)
            if self._ops:
                buf = B.apply_ops(buf, list(self._ops))
            out = out.with_flat(c, buf)
        return out


def device_case_study_cleaner(interpret: bool | None = None) -> DeviceCleaner:
    """The case-study word tail (stopwords + short words) over the device
    char-level pass — expression form of the old Stage pair."""
    return DeviceCleaner(
        word_expr=lambda e: e.remove_stopwords().min_word_len(2),
        interpret=interpret,
    )
