"""Find a serving cell's knee: its window at several fixed rates (run on a TPU).

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates <r> [<r> ...]

In this one process: the cell's set-up once; then for each rate the
traffic drawn at that rate and warmed, one window, and a JSON line with the latency
percentiles, the generated tokens per second, the shed requests, and the
backlog: the mean latency of the last quarter of the requests (by due
time) over that of the first quarter. A backlog that grows reads well
above 1. The knee is the highest rate whose backlog does not grow and
whose p95 stays under the limit that ``PERF.md`` sets from the service
time measured here; the traffic file keeps it and the cell's rate as
numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as harness

    cell = harness.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.COMPILE_CACHE)
    from repro.launch.env import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    from bench.drivers import serve

    tmp = tempfile.TemporaryDirectory(prefix="bench_sweep_")
    s = serve.ServeSession(dict(cell["config"]), dict(cell["traffic"], rate_per_s=args.rates[0]),
                           args.seed, args.seconds, Path(tmp.name), annotate=jax.profiler.TraceAnnotation)
    s.setup()
    for rate in args.rates:
        s.traffic = dict(cell["traffic"], rate_per_s=rate)
        s.warm(serve.schedule(s.traffic, args.seconds, args.seed))
        rec = s.window()
        lat = rec["latency_s"]
        due = np.array([s.requests[k][0] for k in sorted(s.results) if s.results[k]])
        q = max(len(lat) // 4, 1)
        order = np.argsort(due)
        backlog = float(lat[order[-q:]].mean() / lat[order[:q]].mean())
        print(json.dumps({
            "workload": args.workload, "rate_per_s": rate, "attempted": rec["attempted"],
            "served": rec["served"], "shed": rec["shed"], "window_s": rec["window_s"],
            "p50_ms": float(np.percentile(lat, 50) * 1e3), "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "tokens_per_s": rec["tokens"] / rec["window_s"], "backlog": backlog,
            "generator_late_s": rec["generator_late_s"],
        }), flush=True)
    tmp.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
