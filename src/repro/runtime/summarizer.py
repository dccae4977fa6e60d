"""The paper's case study as one callable: the canonical P3SAPP chain
streamed through the process shard executor into the Seq2Seq title
generator (paper §4.2.3), checkpointed by :class:`TrainController`.

``examples/train_summarizer.py`` and ``chip_smoke.py`` both run
:func:`train_summarizer`. The serving side lowers the abstract half of the
same chain to a row program (:func:`serving_chain`).
"""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Any, Sequence

import jax

from ..core.dataset import Dataset
from ..core.expr import abstract_expr, col, title_expr
from ..data.batching import seq2seq_specs
from ..data.tokenizer import WordTokenizer
from ..models.seq2seq import Seq2Seq, Seq2SeqConfig
from ..optim.adamw import AdamW, warmup_cosine
from .fault_tolerance import TrainController

BUCKET_BY = ("encoder_tokens", "decoder_tokens")


def case_study_chain(corpus_dirs: Sequence[str | Path]) -> Dataset:
    """``from_json_dirs → where → drop_duplicates → transform → where``
    over the title and abstract of every shard under ``corpus_dirs``."""
    keep = col("title").not_empty() & col("abstract").not_empty()
    return (
        Dataset.from_json_dirs(list(corpus_dirs))
        .where(keep)
        .drop_duplicates()
        .transform(abstract=abstract_expr(), title=title_expr())
        .where(keep)
    )


def serving_chain(corpus_dirs: Sequence[str | Path]) -> Dataset:
    """The abstract half of :func:`case_study_chain`, which is what a
    request carries: no title, and no ``drop_duplicates`` (a cross-row
    step no per-request row program can run)."""
    keep = col("abstract").not_empty()
    return (
        Dataset.from_json_dirs(list(corpus_dirs), fields=("abstract",))
        .where(keep)
        .transform(abstract=abstract_expr())
        .where(keep)
    )


@dataclass
class TrainRun:
    """What one :func:`train_summarizer` call did."""

    model: Seq2Seq
    params: Any
    tokenizer: WordTokenizer
    history: list[dict]  # one {"step", "loss", "grad_norm"} per step run
    first_step: int  # > 0 when the run resumed from a checkpoint
    traces: dict[tuple, int]  # bucket-grid cell -> traces of the train step
    feed_stats: dict  # the stream's executor and cache counters
    wall_s: float


def train_summarizer(
    corpus_dirs: Sequence[str | Path],
    cfg: Seq2SeqConfig,
    *,
    steps: int,
    ckpt_dir: str | Path,
    batch_size: int = 32,
    workers: int = 2,
    lr: float = 3e-3,
    seed: int = 0,
    save_every: int = 100,
) -> TrainRun:
    """Fit the vocabulary on the corpus, then train until ``steps``.

    The plan is ``case_study_chain → fit_vocab → tokenize(seq2seq_specs)
    → batched(bucket_by=(encoder, decoder)) → workers(process) → prefetch
    → device_batches``: shards stream through ``workers`` worker processes
    (the single dedup takes the two-pass protocol there) and every batch
    snaps onto the plan's fixed bucket grid, so the jitted step traces at
    most once per grid cell."""
    t0 = time.perf_counter()
    clean = case_study_chain(corpus_dirs).workers(workers, executor="process")
    tok = clean.fit_vocab(vocab_size=cfg.vocab_size)
    stream = (
        clean.tokenize(tok, seq2seq_specs(cfg.max_abstract_len, cfg.max_title_len))
        .batched(batch_size, shuffle=True, seed=seed, bucket_by=BUCKET_BY)
        .prefetch(2)
    )
    model = Seq2Seq(cfg)
    opt = AdamW(learning_rate=warmup_cosine(lr, 20, steps), weight_decay=1e-4)
    n_traces = [0]

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        n_traces[0] += 1  # runs only while tracing
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, gnorm = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    traces: Counter = Counter()

    def counted_step(params, opt_state, batch):
        before = n_traces[0]
        out = step(params, opt_state, batch.arrays)
        traces[batch.cell] += n_traces[0] - before
        return out

    def init_state():
        params = model.init(jax.random.PRNGKey(seed))
        return params, opt.init(params)

    controller = TrainController(
        ckpt_dir, counted_step, init_state, save_every=save_every
    )
    first_step = controller.step
    feed_stats: dict = {}
    feed = stream.device_batches(epochs=None, overlap=True, stats=feed_stats)
    try:
        history = controller.run(iter(feed), n_steps=steps)
    finally:
        feed.close()  # endless stream: stop the prefetch thread and workers
    return TrainRun(
        model=model,
        params=controller.params,
        tokenizer=tok,
        history=history,
        first_step=first_step,
        traces=dict(traces),
        feed_stats=feed_stats,
        wall_s=time.perf_counter() - t0,
    )
