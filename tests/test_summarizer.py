"""The case study's training and serving entry points
(:mod:`repro.runtime.summarizer`), which ``examples/train_summarizer.py``
and ``chip_smoke.py`` share."""

import math

import pytest

from repro.configs.p3sapp_summarizer import SMOKE
from repro.data.batching import seq2seq_specs
from repro.data.synthetic import write_corpus
from repro.runtime.summarizer import serving_chain, train_summarizer


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("summarizer_corpus")
    write_corpus(d, total_bytes=300_000, n_files=4, seed=3)
    return d


def test_train_streams_through_processes_and_resumes(corpus, tmp_path):
    run = train_summarizer(
        [corpus], SMOKE, steps=6, ckpt_dir=tmp_path, batch_size=8, workers=2,
        save_every=3,
    )
    assert [h["step"] for h in run.history] == list(range(1, 7))
    assert all(math.isfinite(h["loss"]) for h in run.history)
    assert run.first_step == 0
    assert run.feed_stats["executor"] == "process"
    assert run.traces and set(run.traces.values()) == {1}  # once per grid cell
    assert abs(run.history[0]["loss"] - math.log(SMOKE.vocab_size)) < 0.5

    resumed = train_summarizer(
        [corpus], SMOKE, steps=8, ckpt_dir=tmp_path, batch_size=8, workers=2
    )
    assert resumed.first_step == 6
    assert [h["step"] for h in resumed.history] == [7, 8]
    assert resumed.tokenizer.itos == run.tokenizer.itos


def test_serving_chain_encodes_like_training(corpus, tmp_path):
    run = train_summarizer(
        [corpus], SMOKE, steps=1, ckpt_dir=tmp_path, batch_size=8, workers=2
    )
    spec = seq2seq_specs(SMOKE.max_abstract_len, SMOKE.max_title_len)[0]
    program = serving_chain([corpus]).tokenize(run.tokenizer, [spec]).row_program()
    assert program("") is None  # an empty abstract is filtered
    w1, w2, w3 = run.tokenizer.itos[4:7]  # fitted words, past the specials
    row = program(f"{w1.upper()} <b>{w2}</b> ({w3}) {w3}")
    tokens = row["encoder_tokens"][0]
    assert tokens.shape == (SMOKE.max_abstract_len,)
    assert run.tokenizer.decode(tokens).split() == [w1, w2, w3]
