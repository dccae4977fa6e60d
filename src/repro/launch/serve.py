"""Serving launcher: batched continuous-batching decode.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --smoke \
        --requests 8 --slots 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get, get_smoke
from ..models.lm import LM
from ..runtime.serve_loop import Request, serve_requests
from .env import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if not cfg.causal:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(4, cfg.vocab_size, size=int(rng.integers(4, 16))).astype(np.int32),
            max_new=args.max_new,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    results = serve_requests(
        model, params, reqs, slots=args.slots, max_seq=args.max_seq
    )
    dt = time.perf_counter() - t0
    n_tokens = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests / {n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens/dt:.1f} tok/s through {args.slots} slots)")
    for uid in sorted(results)[:4]:
        print(f"  req {uid}: {results[uid]}")


if __name__ == "__main__":
    main()
