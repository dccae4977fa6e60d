"""Training driver: the plan-fed summarizer step, driven for a time window.

Set-up builds what ``repro.runtime.summarizer.train_summarizer`` builds
(the case-study plan on ``workers`` process executors, ``fit_vocab``,
``tokenize``, bucketed shuffled batches, ``prefetch``, the overlapped
``device_batches`` feed, the jitted AdamW step with donated state, and a
``TrainController``), with the weights made by the benchmark from the
seed. It compiles the step for every cell of the plan's bucket grid,
then takes the first ``checked_steps`` steps through the controller and
the feed that the window goes on with. The window is one more
``TrainController.run`` over a feed iterator that stops at the deadline.
No checkpoint falls inside it.

``check`` compares, after the window: the token rows of the checked
batches and of a seeded sample of window batches with the plain
reference of the plan; and the first steps' losses, first gradient and
parameter change with the plain reference of the step.
"""

from __future__ import annotations

import itertools
import sys
import time
from collections import Counter
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus, flops
from bench.reference import seq2seq as ref_model
from bench.reference import text as ref_text

NEVER = 10**12  # a save cadence that no run reaches


def prng_key(seed: int):
    """A JAX key for any whole-number seed (seeds may exceed 32 bits)."""
    if 0 <= seed < 2**31:
        return jax.random.PRNGKey(seed)
    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1))


def model_config(cfg: dict):
    from repro.models.seq2seq import Seq2SeqConfig

    keys = ("vocab_size", "d_embed", "d_hidden", "n_encoder_layers", "max_abstract_len",
            "max_title_len", "init_scale")
    return Seq2SeqConfig(**{k: cfg[k] for k in keys})


class TrainSession:
    """One cell's training state, from set-up through the check."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float, workdir: Path, *, annotate):
        self.cfg, self.traffic, self.seed, self.seconds = cfg, traffic, seed, seconds
        self.workdir = Path(workdir)
        self.annotate = annotate
        self.corpus_dir = self.workdir / "corpus"

    # -- set-up ------------------------------------------------------------
    def setup(self, init_params=None) -> None:
        from repro.data.batching import seq2seq_specs
        from repro.models.seq2seq import Seq2Seq
        from repro.optim.adamw import AdamW, warmup_cosine
        from repro.runtime.fault_tolerance import TrainController
        from repro.runtime.summarizer import BUCKET_BY, case_study_chain

        cfg, tr = self.cfg, self.traffic
        t0 = time.perf_counter()
        corpus.write_corpus(self.corpus_dir, int(tr["corpus_mb"] * 1e6), tr["shards"], self.seed)
        chain = case_study_chain([self.corpus_dir]).workers(cfg["workers"], executor=cfg["executor"])
        if tr["cache"]:
            chain = chain.cache(self.workdir / "shard_cache")
        tok = chain.fit_vocab(vocab_size=cfg["vocab_size"])
        stream = (
            chain.tokenize(tok, seq2seq_specs(cfg["max_abstract_len"], cfg["max_title_len"]))
            .batched(cfg["batch_size"], shuffle=True, seed=self.seed, bucket_by=BUCKET_BY)
            .prefetch(2)
        )
        if tr["cache"]:
            for _ in stream.iter_batches(epochs=1):  # fills the token cache
                pass
        self.grid = stream.bucket_grid_spec()
        t1 = time.perf_counter()

        model = Seq2Seq(model_config(cfg))
        o = cfg["optimizer"]
        opt = AdamW(
            learning_rate=warmup_cosine(o["lr"], o["warmup_steps"], o["schedule_steps"]),
            b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
            clip_norm=o["clip_norm"],
        )

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            params, opt_state, gnorm = opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss, "grad_norm": gnorm}

        if init_params is None:
            key = prng_key(self.seed)
            init = jax.jit(lambda k: ref_model.init_params(k, cfg))  # one program for every seed
            init_params = lambda: init(key)  # noqa: E731

        def init_state():
            params = init_params()
            return params, opt.init(params)

        self.first_m = None

        def counted_step(params, opt_state, batch):
            with self.annotate("train.step"):
                out = step(params, opt_state, batch.arrays)
            if self.first_m is None:  # the optimizer's state after step 1
                self.first_m = jax.device_get(out[1].m)
            return out

        # Compile the step for every cell of the bucket grid, on copies.
        params, opt_state = init_state()
        self.initial_params = jax.device_get(params)
        for cell in self._grid_cells():
            batch = {k: jnp.zeros((cfg["batch_size"], w), jnp.int32) for k, w in cell}
            copy = jax.tree.map(jnp.copy, (params, opt_state))
            jax.block_until_ready(step(*copy, batch))
        del params, opt_state
        t2 = time.perf_counter()

        self.controller = TrainController(
            self.workdir / "ckpt", counted_step, init_state, save_every=NEVER
        )
        self.feed_stats: dict = {}
        self.feed = stream.device_batches(epochs=None, overlap=True, stats=self.feed_stats)
        self.batches = iter(self.feed)
        self.checked: list = []

        def first(n):
            for _ in range(n):
                batch = next(self.batches)
                self.checked.append(jax.device_get(batch.arrays))
                yield batch

        n = tr["checked_steps"]
        self.first_history = self.controller.run(first(n), n_steps=n)
        self.params_after = jax.device_get(self.controller.params)
        print(f"setup: corpus, vocabulary{' and cache fill' if tr['cache'] else ''} {t1 - t0:.1f} s, "
              f"step compiles {t2 - t1:.1f} s, first steps {time.perf_counter() - t2:.1f} s", file=sys.stderr)

    def _grid_cells(self):
        cols = list(self.grid.widths)
        return [tuple(zip(cols, ws)) for ws in itertools.product(*(self.grid.widths[c] for c in cols))]

    # -- window ------------------------------------------------------------
    def window(self, on_trace=None) -> dict:
        """Train until the window's seconds have passed. ``on_trace()`` is called
        once the window has ``trace_seconds`` left; it returns the trace
        slice, which is told when the window ends and stopped after."""
        cfg, tr = self.cfg, self.traffic
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 7]))
        kept: list = []  # (device batch) of every step, counted after the window
        waits: list = []
        t0 = time.perf_counter()
        end = t0 + self.seconds
        trace_at = end - tr["trace_seconds"] if on_trace else None
        state = {"trace": None, "window_end": None}

        def until_deadline():
            while True:
                now = time.perf_counter()
                if trace_at is not None and state["trace"] is None and now >= trace_at:
                    state["trace"] = on_trace()
                    now = time.perf_counter()
                if now >= end:
                    state["window_end"] = now
                    if state["trace"]:
                        state["trace"].end_window()
                    return
                with self.annotate("feed.next"):
                    batch = next(self.batches)
                waits.append(time.perf_counter() - now)
                kept.append(batch)
                yield batch

        history = self.controller.run(until_deadline(), n_steps=NEVER)
        trace = state["trace"].stop() if state["trace"] else None
        window_s = state["window_end"] - t0
        arrays = [jax.device_get(b.arrays) for b in kept[: len(history)]]
        tokens = sum(int(np.count_nonzero(a[k])) for a in arrays for k in a)
        step_flops = [
            flops.seq2seq_step_flops(cfg, cfg["batch_size"], a["encoder_tokens"].shape[1],
                                     a["decoder_tokens"].shape[1])
            for a in arrays
        ]
        sample = [a for a in arrays if rng.random() < 1.0 / tr["sample_every"]]
        self.sampled = sample
        return {
            "window_s": window_s,
            "steps": len(history),
            "tokens": tokens,
            "feed_wait_s": float(sum(waits)),
            "flops": float(sum(step_flops)),
            "cells": dict(Counter(
                f"{a['encoder_tokens'].shape[1]}x{a['decoder_tokens'].shape[1]}" for a in arrays
            )),
            "feed_stats": dict(self.feed_stats),
            "trace": trace,
        }

    def close(self) -> None:
        self.feed.close()

    # -- check -------------------------------------------------------------
    def check(self) -> dict:
        """The numbers compared with the plain reference (see module doc)."""
        cfg = self.cfg
        rows, _ = ref_text.token_rows(
            self.corpus_dir, cfg["vocab_size"], cfg["max_abstract_len"], cfg["max_title_len"]
        )
        rungs = {k: set(v) for k, v in self.grid.widths.items()}
        wrong = 0
        seen: set = set()
        repeated = 0
        for i, batch in enumerate(self.checked + self.sampled):
            for k, a in batch.items():
                if a.shape[1] not in rungs.get(k, {a.shape[1]}):
                    wrong += 1
            enc, dec = batch["encoder_tokens"], batch["decoder_tokens"]
            for e_row, d_row in zip(enc, dec):
                if not e_row.any() and not d_row.any():
                    continue  # a pad row of a short batch
                row = (tuple(int(t) for t in e_row[e_row != 0]), tuple(int(t) for t in d_row[d_row != 0]))
                wrong += row not in rows
                if i < len(self.checked):
                    repeated += row in seen
                    seen.add(row)

        o = dict(self.cfg["optimizer"])
        batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in self.checked]
        start = jax.tree.map(jnp.asarray, self.initial_params)
        ref_losses, ref_grad, ref_params = ref_model.train_steps(start, batches, o)
        prog_losses = [h["loss"] for h in self.first_history]
        out = {"token_rows_wrong": float(wrong), "checked_rows_repeated": float(repeated)}
        out.update(compare_steps(
            prog_losses, jax.tree.map(lambda m: np.asarray(m) / (1 - o["b1"]), self.first_m),
            self.params_after, ref_losses, ref_grad, ref_params, self.initial_params,
        ))
        return out


def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64))) for x in jax.tree.leaves(tree)])


def compare_steps(prog_losses, prog_grad, prog_params, ref_losses, ref_grad, ref_params, start) -> dict:
    """The three numbers of the training comparison.

    ``loss_gap``: the largest relative gap of a step's loss.
    ``grad_gap``: over leaves, the gap between the program's and the
    reference's norm of the first (clipped) gradient, over the larger of
    the reference leaf's norm and the median leaf's norm.
    ``update_gap``: the same for the norm of each leaf's change over the
    checked steps, leaving out the leaves whose reference gradient is
    under a thousandth of the median leaf's (they move by weight decay
    and round-off alone)."""
    losses = np.abs(np.asarray(prog_losses) - np.asarray(ref_losses)) / np.abs(ref_losses)
    g_prog, g_ref = _leaf_norms(prog_grad), _leaf_norms(ref_grad)
    g_floor = np.maximum(g_ref, np.median(g_ref))
    d_prog = _leaf_norms(jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, prog_params, start))
    d_ref = _leaf_norms(jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, ref_params, start))
    moved = g_ref >= 1e-3 * np.median(g_ref)
    d_floor = np.maximum(d_ref, np.median(d_ref[moved]))
    return {
        "loss_gap": float(losses.max()),
        "grad_gap": float((np.abs(g_prog - g_ref) / g_floor).max()),
        "update_gap": float((np.abs(d_prog - d_ref) / d_floor)[moved].max()),
    }
