"""Readings that the limits of ``bench/limits/`` are set from (run on a TPU).

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] [--seconds 15]

For each seed, in this one process: the cell's set-up, for a serving
cell a short window at the cell's own load, and then the numbers that
decide ``correct``, read three ways:

* ``program``: the system under test against the plain reference, as a
  benchmark run reads them (the lower readings);
* ``control``: the reference in the program's place at the next lower
  precision against the reference (bfloat16 for the float32 training
  step; float8 e4m3 weights and activations for the bfloat16 served
  model);
* training only, the planted faults: ``half_batch`` (the reference's step
  over half of each batch's rows, the mean taken over them) and
  ``unchanged`` (a step that returns its state as it was).

One JSON line per seed and reading goes to standard output, with whether
the numbers it holds lie within the cell's limits (`within_limits`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def train_readings(session) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers.train import compare_steps
    from bench.reference import seq2seq as ref_model

    o = dict(session.cfg["optimizer"])
    start = jax.tree.map(jnp.asarray, session.initial_params)
    batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in session.checked]
    ref = ref_model.train_steps(start, batches, o)
    out = {"program": session.check()}
    low = ref_model.train_steps(start, batches, o, dtype=jnp.bfloat16)
    out["control"] = compare_steps(*low, *ref, session.initial_params)
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]
    out["half_batch"] = compare_steps(*ref_model.train_steps(start, half, o), *ref, session.initial_params)
    zero_grad = jax.tree.map(np.zeros_like, ref[1])
    out["unchanged"] = compare_steps(ref[0], zero_grad, session.initial_params, *ref, session.initial_params)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as harness

    cell = harness.load_cell(args.workload)
    from repro.launch.env import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.COMPILE_CACHE)
    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    from bench.drivers import serve, train

    cfg, traffic = cell["config"], cell["traffic"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="bench_control_") as tmp:
            if traffic["driver"] == "train":
                s = train.TrainSession(cfg, traffic, seed, args.seconds, Path(tmp),
                                       annotate=jax.profiler.TraceAnnotation)
                s.setup()
                s.sampled = []
                s.close()
                readings = train_readings(s)
            else:
                s = serve.ServeSession(cfg, traffic, seed, args.seconds, Path(tmp),
                                       annotate=jax.profiler.TraceAnnotation)
                s.setup(warm=False)  # the readings need no warm window
                s.window()
                r = s.check(control=True)
                readings = {
                    "program": {k: v for k, v in r.items() if not k.startswith("control_")},
                    "control": {k[len("control_"):]: v for k, v in r.items() if k.startswith("control_")},
                }
        for kind, values in readings.items():
            within = all(v <= cell["limits"][k] for k, v in values.items() if k in cell["limits"])
            print(json.dumps({"workload": args.workload, "seed": seed, "reading": kind, **values,
                              "within_limits": within}), flush=True)
        print(f"control: seed {seed} took {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
