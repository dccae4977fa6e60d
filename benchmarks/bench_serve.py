"""Serving latency: the per-request row-program path end to end.

Drives :func:`repro.runtime.serve_loop.serve_text` — raw abstract text in,
generated title tokens out — against a smoke-config LM, with requests
arriving in waves through the bounded admission queue and a shared
:class:`RingCache` (a fraction of prompts repeat across waves, so the
cache-hit path is exercised). Reports p50/p99 end-to-end latency and the
preprocess-vs-decode wall-time split; ``check_regression.py --mode serve``
gates the committed ``results/serve_latency.json`` in CI.
"""

from __future__ import annotations

import dataclasses
import json
import random
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core.dataset import Dataset
from repro.core.expr import abstract_expr, col
from repro.data.batching import TokenSpec
from repro.models.lm import LM
from repro.runtime.serve_loop import RingCache, ServeStats, TextRequest, serve_text

from .common import RESULTS_DIR, dataset_dirs

SERVE_JSON = RESULTS_DIR / "serve_latency.json"
REPEAT_FRAC = 0.25  # fraction of requests repeating an earlier prompt
MAX_SEQ = 96
MAX_NEW = 10


def build_row_program(directory: Path):
    # The serve-side plan encodes the request text only: at request time
    # there is no title (the model generates it), so the program reads the
    # abstract column alone — bare-string requests lower to it directly.
    base = (
        Dataset.from_json_dirs([directory], fields=("abstract",))
        .where(col("abstract").not_empty())
        .transform(abstract=abstract_expr())
    )
    tok = base.fit_vocab(vocab_size=2000)
    chain = base.tokenize(tok, [TokenSpec("abstract", 64)]).batched(8).prefetch(2)
    return chain.row_program(), tok


def sample_requests(directory: Path, n: int, seed: int = 7) -> list[TextRequest]:
    """``n`` raw-text requests: unique abstracts with ~REPEAT_FRAC repeats
    of earlier prompts mixed in (deterministic), so later waves hit the
    ring cache the way production repeat traffic would."""
    records = Dataset.from_json_dirs([directory]).dropna().collect().to_records()
    texts = [r["abstract"] for r in records if r.get("abstract")]
    rng = random.Random(seed)
    out: list[str] = []
    for i in range(n):
        if out and rng.random() < REPEAT_FRAC:
            out.append(out[rng.randrange(len(out))])
        else:
            out.append(texts[i % len(texts)])
    return [TextRequest(uid, text, max_new=MAX_NEW) for uid, text in enumerate(out)]


def run(quick: bool = False, requests: int | None = None, slots: int = 4) -> dict:
    n_requests = requests or (24 if quick else 64)
    _, directory, _ = dataset_dirs(quick=True)[0]
    row_program, tok = build_row_program(directory)

    cfg = dataclasses.replace(get_smoke("recurrentgemma_9b"), vocab_size=len(tok.itos))
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    reqs = sample_requests(directory, n_requests)
    # Warmup: compile the prefill/step kernels outside the measured window.
    serve_text(model, params, row_program, reqs[:2], slots=slots, max_seq=MAX_SEQ)

    cache = RingCache(slots=128)
    stats = ServeStats()
    wave = max(slots * 4, 8)
    tokens_generated = 0
    t0 = time.perf_counter()
    for lo in range(0, len(reqs), wave):
        results = serve_text(
            model,
            params,
            row_program,
            reqs[lo : lo + wave],
            slots=slots,
            max_seq=MAX_SEQ,
            queue_size=wave,
            cache=cache,
            stats=stats,
        )
        tokens_generated += sum(len(v) for v in results.values())
    wall_s = time.perf_counter() - t0

    lat_ms = sorted(v * 1e3 for v in stats.latency_s.values())
    host_s = stats.preprocess_s + stats.prefill_s + stats.decode_s
    return {
        "name": "serve_latency",
        "quick": quick,
        "requests": len(reqs),
        "slots": slots,
        "served": stats.served,
        "rejected": stats.rejected,
        "filtered": stats.filtered,
        "cache_hits": stats.cache_hits,
        "cache_misses": stats.cache_misses,
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "preprocess_s": round(stats.preprocess_s, 4),
        "prefill_s": round(stats.prefill_s, 4),
        "decode_s": round(stats.decode_s, 4),
        "compile_s": round(stats.compile_s, 4),
        "preprocess_frac": round(stats.preprocess_s / host_s, 5) if host_s else 0.0,
        "tokens_generated": tokens_generated,
        "requests_per_s": round(len(reqs) / wall_s, 2) if wall_s else 0.0,
    }


def main(
    quick: bool = False,
    requests: int | None = None,
    slots: int = 4,
    out: Path = SERVE_JSON,
) -> None:
    row = run(quick=quick, requests=requests, slots=slots)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(row, indent=2) + "\n")
    print(f"serve_latency,{row['p50_ms'] * 1e3},{json.dumps(row)}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default: 24 quick / 64 full)")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching decode slots")
    ap.add_argument("--out", type=Path, default=SERVE_JSON,
                    help="output JSON path")
    args = ap.parse_args()
    main(args.quick, args.requests, args.slots, args.out)
