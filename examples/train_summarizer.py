"""End-to-end driver (paper case study): title generation from abstracts.

One declarative ``Dataset`` chain takes the synthetic CORE corpus all the
way to device-resident batches — ingestion, pre-cleaning, the Spark-ML-style
stage chain, tokenization, bucketed batching and async prefetch are a
single lazy plan whose shards stream through worker processes while the
device trains. The model side is an LSTM seq2seq with Bahdanau attention,
checkpointed training (resume-capable), and greedy inference samples on a
held-out corpus. :func:`repro.runtime.summarizer.train_summarizer` is the
whole training path; ``chip_smoke.py`` runs the same function.

Runs a few hundred steps on CPU by default:

    PYTHONPATH=src python examples/train_summarizer.py --steps 300
"""

import argparse
import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro.configs.p3sapp_summarizer import CONFIG, SMOKE
from repro.data.batching import seq2seq_specs
from repro.data.synthetic import write_corpus
from repro.launch.env import enable_compile_cache
from repro.runtime.summarizer import case_study_chain, train_summarizer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--corpus-mb", type=float, default=4.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--smoke", action="store_true", help="tiny model config")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = SMOKE if args.smoke else CONFIG
    t0 = time.perf_counter()
    corpus = tempfile.mkdtemp(prefix="p3sapp_corpus_")
    write_corpus(corpus, total_bytes=int(args.corpus_mb * 1e6), n_files=8, seed=1)
    held_out = tempfile.mkdtemp(prefix="p3sapp_heldout_")
    write_corpus(held_out, total_bytes=200_000, n_files=1, seed=2)

    run = train_summarizer(
        [corpus],
        cfg,
        steps=args.steps,
        ckpt_dir=args.ckpt_dir or tempfile.mkdtemp(prefix="p3sapp_ckpt_"),
        batch_size=args.batch_size,
        workers=args.workers,
    )
    if run.first_step:
        print(f"resumed from step {run.first_step}")
    print(f"shards streamed by the {run.feed_stats.get('executor')} executor; "
          f"train-step traces per bucket cell: {sorted(run.traces.values())}")
    if run.history:
        print(f"step {run.history[0]['step']}: loss={run.history[0]['loss']:.3f}")
        print(f"step {run.history[-1]['step']}: loss={run.history[-1]['loss']:.3f}")

    # validation loss + greedy samples (paper Algorithm 3) on a held-out
    # corpus, encoded with the fitted vocabulary
    specs = seq2seq_specs(cfg.max_abstract_len, cfg.max_title_len)
    val = case_study_chain([held_out]).tokenize(run.tokenizer, specs).arrays()
    model, params, tok = run.model, run.params, run.tokenizer
    val_loss = float(model.loss(params, {k: jnp.asarray(v[:64]) for k, v in val.items()}))
    print(f"val loss: {val_loss:.3f}")
    gen = model.generate(params, val["encoder_tokens"][:3])
    for i in range(3):
        print(f"  gold: {tok.decode(val['decoder_tokens'][i])}")
        print(f"  pred: {tok.decode(np.asarray(gen[i]))}\n")
    print(f"total wall time: {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
