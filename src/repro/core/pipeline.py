"""Spark-ML-style Pipeline: chained transformer stages over a ColumnarFrame.

Fidelity to the paper (Algorithm 1, steps 11-14):

* stages are declared up front (step 11),
* ``Pipeline.fit`` produces a ``PipelineModel`` (step 13; all our stages are
  pure transformers so fitting is structural, exactly like a Spark pipeline
  that contains only transformers),
* ``PipelineModel.transform`` runs all stages (step 14).

Both classes are thin adapters over the expression layer: a
``PipelineModel`` compiles its stages into per-column op plans
(``column_plans``; each stage's ops derive from its expression, see
:meth:`repro.core.stages.Stage.to_expr`) and hands them to
:func:`run_column_plans`. The ``Dataset`` planner (:mod:`repro.core.plan`)
runs the same expressions through its ``Project`` nodes, so both paths are
byte-identical by construction.

Execution model — the P3SAPP speedup: per *column* we flatten once into a
byte buffer, run that column's stage chain as vectorized passes, and
unflatten once. Two executor modes:

* ``optimize=False`` — paper-faithful: each stage's ops run in sequence.
* ``optimize=True``  — beyond-paper: the per-column op list is fused
  Catalyst-style across stage boundaries (LUT∘LUT, OR-ed word predicates,
  deduped collapses) before execution. Exact, see bytesops docstring.

Optionally the per-column work fans out over a process pool (Spark
``local[k]`` analogue) by splitting the buffer on row boundaries into ``k``
chunks — embarrassingly parallel because every stage is row-local.
"""

from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from typing import Sequence

import numpy as np

from . import bytesops as B
from .engine_config import EngineConfig
from .frame import ColumnarFrame
from .stages import Stage

# One compiled per-column execution unit: read input_col, run ops, write
# output_col. The plan optimizer and the streaming executor share this form.
ColumnPlan = tuple[str, str, list[B.Op]]


class Pipeline:
    def __init__(self, stages: Sequence[Stage]):
        self.stages = list(stages)

    def fit(self, frame: ColumnarFrame) -> "PipelineModel":
        return PipelineModel([s.fit(frame) for s in self.stages])


def _split_on_rows(buf: np.ndarray, k: int) -> list[np.ndarray]:
    """Split a flat buffer into <=k chunks at row-separator boundaries."""
    if k <= 1 or buf.size == 0:
        return [buf]
    sep_idx = np.flatnonzero(buf == B.ROW_SEP)
    if sep_idx.size < k:
        return [buf]
    cut_rows = np.linspace(0, sep_idx.size, k + 1).astype(np.int64)[1:-1]
    cuts = sep_idx[cut_rows - 1] + 1
    return np.split(buf, cuts)


def _run_ops(args) -> np.ndarray:
    """Pool task: ``(ops, buf)`` or ``(ops, buf, backend)``. The driver
    resolves the backend through :class:`EngineConfig` before fan-out, so
    every chunk of a run uses the same backend regardless of worker env —
    its :func:`~repro.core.bytesops.worker_backend` form, since the device
    belongs to the parent."""
    ops, buf = args[0], args[1]
    backend = args[2] if len(args) > 2 else None
    return B.execute_ops(buf, ops, backend)


def compile_column_plans(
    stages: Sequence[Stage], optimize: bool
) -> list[ColumnPlan]:
    """Ordered (input_col, output_col, ops) execution plans for a stage chain.

    Consecutive stages reading/writing the same column merge into one plan;
    a stage with ``output_col != input_col`` forks a new plan fed by the
    current state of its input column.
    """
    plans: list[ColumnPlan] = []
    current: dict[str, int] = {}  # column -> index of its live plan
    for s in stages:
        ops = s.flat_ops()
        if s.input_col not in current:
            plans.append((s.input_col, s.input_col, []))
            current[s.input_col] = len(plans) - 1
        if s.output_col == s.input_col:
            plans[current[s.input_col]][2].extend(ops)
        else:
            src_plan = current[s.input_col]
            plans.append((plans[src_plan][1], s.output_col, list(ops)))
            current[s.output_col] = len(plans) - 1
            # Seal the source plan: later stages on input_col must not
            # retroactively change what this fork read (Spark order
            # semantics) — they start a fresh plan instead.
            current.pop(s.input_col, None)
    if optimize:
        plans = [(i, o, B.fuse_ops(ops)) for i, o, ops in plans]
    return plans


def run_column_plans(
    frame: ColumnarFrame,
    plans: Sequence[ColumnPlan],
    workers: int = 1,
    backend: str | None = None,
) -> ColumnarFrame:
    """Physical executor: flatten each input column once, run its fused op
    chain (optionally fanned out over a process pool), unflatten once."""
    backend = EngineConfig(backend=backend).resolve_backend()
    bufs: dict[str, np.ndarray] = {}
    out = frame
    pool = ProcessPoolExecutor(max_workers=workers) if workers > 1 else None
    try:
        for in_col, out_col, ops in plans:
            src = bufs.get(in_col)
            if src is None:
                src = frame.flat(in_col)
            if pool is None:
                res = _run_ops((ops, src, backend))
            else:
                chunks = _split_on_rows(src, workers)
                parts = list(pool.map(
                    _run_ops, [(ops, c, B.worker_backend(backend)) for c in chunks]
                ))
                res = np.concatenate(parts) if parts else src
            bufs[out_col] = res
            out = out.ensure_column(out_col).with_flat(out_col, res)
    finally:
        if pool is not None:
            pool.shutdown()
    return out


class PipelineModel:
    def __init__(self, stages: Sequence[Stage]):
        self.stages = list(stages)

    def column_plans(self, optimize: bool) -> list[ColumnPlan]:
        return compile_column_plans(self.stages, optimize)

    def transform(
        self,
        frame: ColumnarFrame,
        workers: int = 1,
        optimize: bool = True,
        backend: str | None = None,
    ) -> ColumnarFrame:
        return run_column_plans(
            frame, self.column_plans(optimize), workers, backend=backend
        )


def default_workers() -> int:
    return max(1, os.cpu_count() or 1)
