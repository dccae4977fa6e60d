"""Text-in/title-out serving example: the zero-skew request path.

Builds a tiny corpus, fits the preprocessing plan + vocabulary, lowers the
*same compiled plan* the training executors run into a per-request
``RowProgram`` (``dataset.row_program()``), and serves raw abstract text
through continuous batching (``serve_text``): bounded admission queue,
fixed decode slots with prefill refill, and a ring cache that answers a
repeated abstract without touching the model. The decoded titles come
back through the same tokenizer the plan was fitted with.

    PYTHONPATH=src python examples/serve_summarizer.py
"""

import argparse
import dataclasses
import json
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core.dataset import Dataset
from repro.core.expr import abstract_expr, col
from repro.data.batching import TokenSpec
from repro.launch.env import enable_compile_cache
from repro.models.lm import LM
from repro.runtime.serve_loop import RingCache, ServeStats, TextRequest, serve_text

CORPUS = [
    {"abstract": "Deep learning methods now drive scholarly data applications."},
    {"abstract": "A Spark ML pipeline cleans abstracts before model training."},
    {"abstract": "Continuous batching keeps decode slots busy between requests."},
    {"abstract": "Columnar byte kernels make text preprocessing vectorized."},
    {"abstract": "The ring cache answers repeated prompts without decoding."},
    {"abstract": "Shard executors stream token batches to the training loop."},
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    # 1. Fit the preprocessing plan + vocabulary on a tiny corpus, exactly
    # like training would, then lower it to a per-request row program.
    corpus_dir = Path(tempfile.mkdtemp(prefix="serve_corpus_")) / "shards"
    corpus_dir.mkdir()
    with open(corpus_dir / "shard-0.jsonl", "w", encoding="utf-8") as f:
        for rec in CORPUS:
            f.write(json.dumps(rec) + "\n")
    ds = (
        Dataset.from_json_dirs([corpus_dir], fields=("abstract",))
        .where(col("abstract").not_empty())
        .transform(abstract=abstract_expr())
    )
    tok = ds.fit_vocab(vocab_size=200)
    row_program = (
        ds.tokenize(tok, [TokenSpec("abstract", 32)])
        .batched(4)
        .prefetch(2)
        .row_program()
    )
    print(f"row program: fields={row_program.fields} backend={row_program.backend}")

    # 2. A tiny decoder LM (smoke config, vocab swapped for the fitted
    # tokenizer's) stands in for a trained summarizer — this example
    # exercises the serving runtime, not model quality.
    cfg = dataclasses.replace(get_smoke("stablelm_3b"), vocab_size=len(tok.itos))
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    # 3. Serve raw text. The last request repeats the first abstract, so
    # it completes from the ring cache (watch cache_hits); the empty
    # request is filtered by the plan and answered with [].
    texts = [rec["abstract"] for rec in CORPUS] + ["", CORPUS[0]["abstract"]]
    reqs = [TextRequest(uid, t, max_new=args.max_new) for uid, t in enumerate(texts)]
    cache = RingCache(slots=32)
    stats = ServeStats()
    # Two waves so the repeat arrives after the original's answer is cached.
    results = dict(
        serve_text(model, params, row_program, reqs[:-1], slots=args.slots,
                   max_seq=64, cache=cache, stats=stats)
    )
    results.update(
        serve_text(model, params, row_program, reqs[-1:], slots=args.slots,
                   max_seq=64, cache=cache, stats=stats)
    )

    for uid in sorted(results):
        toks = results[uid]
        title = tok.decode(toks) if toks else "(filtered)"
        print(f"request {uid}: {texts[uid][:48]!r:50} -> {title!r}")
    print(
        f"served {stats.served}/{len(reqs)} through {args.slots} slots: "
        f"{stats.filtered} filtered, {stats.cache_hits} cache hit(s), "
        f"preprocess {stats.preprocess_s * 1e3:.1f} ms / "
        f"prefill {stats.prefill_s * 1e3:.1f} ms / "
        f"decode {stats.decode_s * 1e3:.1f} ms / "
        f"compile {stats.compile_s * 1e3:.1f} ms ({stats.compiles} programs)"
    )
    assert len(results) == len(reqs)
    assert stats.cache_hits >= 1 and results[len(texts) - 1] == results[0]


if __name__ == "__main__":
    main()
