"""The training driver on the CPU at a small size."""

import contextlib

import jax
import numpy as np

from bench.drivers import train
from bench.reference import seq2seq as ref_model
from bench.tests import smoke


def nothing(_name):
    return contextlib.nullcontext()


def test_reference_init_matches_the_programs():
    from repro.configs.p3sapp_summarizer import CONFIG
    from repro.models.seq2seq import Seq2Seq

    cfg = smoke.seq2seq_cfg(**{k: getattr(CONFIG, k) for k in (
        "vocab_size", "d_embed", "d_hidden", "n_encoder_layers", "init_scale")})
    key = jax.random.PRNGKey(11)
    ours = ref_model.init_params(key, cfg)
    theirs = Seq2Seq(train.model_config(cfg)).init(key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_first_steps_match_train_summarizer(tmp_path):
    from repro.models.seq2seq import Seq2Seq
    from repro.runtime.summarizer import train_summarizer

    steps, seed = 5, 3
    cfg = smoke.seq2seq_cfg()
    cfg["optimizer"] = dict(cfg["optimizer"], schedule_steps=steps)
    s = train.TrainSession(cfg, smoke.train_traffic(checked_steps=steps), seed, 1.0,
                           tmp_path / "bench", annotate=nothing)
    mcfg = train.model_config(cfg)
    s.setup(init_params=lambda: Seq2Seq(mcfg).init(jax.random.PRNGKey(seed)))
    s.close()
    run = train_summarizer([s.corpus_dir], mcfg, steps=steps, ckpt_dir=tmp_path / "ckpt",
                           batch_size=cfg["batch_size"], workers=2, seed=seed)
    assert [h["loss"] for h in s.first_history] == [h["loss"] for h in run.history]


def test_window_and_check(tmp_path):
    s = train.TrainSession(smoke.seq2seq_cfg(), smoke.train_traffic("train_cached"), 2**33 + 5,
                           1.0, tmp_path, annotate=nothing)
    s.setup()
    rec = s.window()
    s.close()
    assert rec["steps"] > 0 and rec["tokens"] > 0 and rec["window_s"] >= 1.0
    assert rec["feed_stats"]["executor"] == "process"
    checks = s.check()
    assert checks["token_rows_wrong"] == 0 and checks["checked_rows_repeated"] == 0
    assert checks["loss_gap"] < 1e-5 and checks["update_gap"] < 1e-3
