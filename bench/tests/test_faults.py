"""A run with its timed path broken underneath comes out not correct.

Each test drives a cell's driver at a small size on the CPU (no look for
a chip), plants one fault in the system under test, and judges the
check's numbers against the cell's own limits (``bench/limits/``):

* training: a step that returns its state unchanged; half of each batch
  left out, the mean taken over the rest; a token altered where the
  vocabulary produces it;
* serving: a decoded token altered where the step produces it; answers
  lost; a prompt token altered where the row program produces it.

The controls (the reference at the next lower precision in the
program's place) fail the same limits at this size too.
"""

import contextlib
import json

import jax.numpy as jnp
import pytest

from bench import control, run
from bench.drivers import serve, train
from bench.tests import smoke

LIMITS = run.BENCH / "limits"


def nothing(_name):
    return contextlib.nullcontext()


def limits(cell):
    return json.loads((LIMITS / f"{cell}.json").read_text())


def train_session(tmp_path, traffic="train_stream"):
    s = train.TrainSession(smoke.seq2seq_cfg(), smoke.train_traffic(traffic), 21, 0.5, tmp_path,
                           annotate=nothing)
    s.setup()
    s.window()
    s.close()
    return s


def serve_session(tmp_path, monkeypatch, **size):
    monkeypatch.setattr(serve.ServeSession, "program_config", lambda self: smoke.lm_arch(**size))
    s = serve.ServeSession(smoke.lm_cfg(**size), smoke.serve_traffic(), 31, 2.0, tmp_path,
                           annotate=nothing)
    s.setup()
    s.window()
    return s


def unchanged_state(monkeypatch):
    from repro.optim.adamw import AdamW

    monkeypatch.setattr(AdamW, "update", lambda self, g, state, params: (params, state, jnp.float32(0)))


def half_batch(monkeypatch):
    from repro.models.seq2seq import Seq2Seq

    loss = Seq2Seq.loss
    monkeypatch.setattr(
        Seq2Seq, "loss",
        lambda self, params, batch: loss(self, params, {k: v[: v.shape[0] // 2] for k, v in batch.items()}),
    )


def vocabulary_token(monkeypatch):
    from repro.data.tokenizer import WordTokenizer

    from_counts = WordTokenizer.from_counts.__func__

    def swapped(cls, counts, vocab_size=8000):
        tok = from_counts(cls, counts, vocab_size)
        tok.itos[4], tok.itos[5] = tok.itos[5], tok.itos[4]
        tok.stoi = {w: i for i, w in enumerate(tok.itos)}
        return tok

    monkeypatch.setattr(WordTokenizer, "from_counts", classmethod(swapped))


@pytest.mark.parametrize("cell", ["s2s.train.stream", "s2s.train.cached"])
@pytest.mark.parametrize("fault", [unchanged_state, half_batch, vocabulary_token])
def test_train_fault_is_not_correct(tmp_path, monkeypatch, cell, fault):
    fault(monkeypatch)
    s = train_session(tmp_path, "train_cached" if cell.endswith("cached") else "train_stream")
    correct, compared, _ = run.judge(s.check(), limits(cell))
    assert not correct, compared


@pytest.mark.parametrize("cell", ["s2s.train.stream", "s2s.train.cached"])
def test_train_control_is_not_correct(tmp_path, cell):
    s = train_session(tmp_path)
    readings = control.train_readings(s)
    assert run.judge(readings["program"], limits(cell))[0]
    bound = limits(cell)
    low = {k: v for k, v in readings["control"].items() if k in bound}
    assert any(v > bound[k] for k, v in low.items()), low


def decoded_token(monkeypatch):
    from repro.runtime import serve_loop

    make = serve_loop.make_serve_step

    def altered(model):
        step = make(model)

        def serve_step(params, tokens, state, pos):
            nxt, logits, state = step(params, tokens, state, pos)
            return (nxt + 1) % logits.shape[-1], logits, state

        return serve_step

    monkeypatch.setattr(serve_loop, "make_serve_step", altered)


def lost_answers(monkeypatch):
    from repro.runtime import serve_loop

    serve_text = serve_loop.serve_text

    def losing(*args, **kwargs):
        out = serve_text(*args, **kwargs)
        return {k: v for i, (k, v) in enumerate(sorted(out.items())) if i % 2}

    monkeypatch.setattr(serve_loop, "serve_text", losing)


def prompt_token(monkeypatch):
    from repro.runtime.row_program import RowProgram

    call = RowProgram.__call__

    def altered(self, row):
        out = call(self, row)
        if out is not None:
            out = {k: v.copy() for k, v in out.items()}
            for v in out.values():
                v[0, 0] = v[0, 0] % 7 + 4
        return out

    monkeypatch.setattr(RowProgram, "__call__", altered)


@pytest.mark.parametrize("fault", [decoded_token, lost_answers, prompt_token])
def test_serve_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    s = serve_session(tmp_path, monkeypatch)
    correct, compared, _ = run.judge(s.check(), limits("stablelm3b.serve.titles"))
    assert not correct, compared


def test_serve_control_is_not_correct(tmp_path, monkeypatch):
    # wide and deep enough that float8 rounding moves the served tokens
    s = serve_session(tmp_path, monkeypatch, hidden=512, layers=8, ff=1280, heads=8)
    r = s.check(control=True)
    bound = limits("stablelm3b.serve.titles")["logit_gap_mean"]
    assert r["logit_gap_mean"] <= bound < r["control_logit_gap_mean"]
