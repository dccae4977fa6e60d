"""How many held experts a decode token reaches, in a benchmark cell's
traffic and seeded weights: the average that ``serve.step_hbm_share``'s
least read (``bench/reference/mla_moe.py:step_bytes``) assumes,
top_k * held / router_experts.

Needs the chip at the cell's real size:

    python tests/reached_experts.py --seed 3000001701 [--workload dsv2lite.serve.titles] [--requests 49]

Sets up the cell as ``bench/run.py`` does (vocabulary, row program, the
reference's seeded weights, the window's requests), then serves each
request greedily through the program's own decode step, one token at a
time: the prompt as it would be prefilled, then ``max_new`` tokens or up
to ``<end>``. A host callback wrapped around the router counts, for each
MoE layer a token passes, the assignments that fall among the held
experts (at one token, the distinct held experts the step reads). Only
the steps whose input is a generated token count, as in serving's decode.
Prints one JSON line. It adds a host callback to every layer of every
step, so it is no timing.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _decode(session, requests, counts: list[int]) -> tuple[list[int], int]:
    """Serves ``requests`` greedily one token a step; returns, for each
    decode step, the held experts its MoE layers read in all (from the
    router's ``counts``), and the tokens generated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.serve_loop import PAD_ID, make_serve_step

    model, params, sc = session.model, session.params, session.serve_cfg
    max_seq, eos = sc["max_seq"], 2
    cache_dtype = getattr(jnp, sc["cache_dtype"])
    step = jax.jit(make_serve_step(model))
    program = session.row_program.program
    per_step: list[int] = []
    tokens = 0
    for _, text, max_new in requests:
        row = program(text)
        prompt = None if row is None else row[program.output_names[0]][0]
        if prompt is None or not (prompt := prompt[prompt != PAD_ID][: max_seq - 1]).size:
            continue
        state = model.init_decode_state(1, max_seq, cache_dtype)
        for pos, t in enumerate(prompt):
            nxt, _, state = step(params, jnp.full((1, 1), int(t), jnp.int32), state, jnp.int32(pos))
        out, pos = [int(nxt[0, 0])], int(prompt.size)  # the int waits for every callback so far
        start = len(counts)
        while not (out[-1] == eos or len(out) >= max_new or pos + 1 >= max_seq):
            nxt, _, state = step(params, jnp.full((1, 1), out[-1], jnp.int32), state, jnp.int32(pos))
            out.append(int(nxt[0, 0]))
            pos += 1
        per_step.extend(np.asarray(counts[start:]).reshape(-1, model.n_units).sum(1).tolist())
        tokens += len(out)
    return per_step, tokens


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="dsv2lite.serve.titles")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--requests", type=int, default=0, help="the first N of the window (0: all)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np

    from bench.drivers.serve import ServeSession
    from bench.run import load_cell
    from repro.models import moe as MOE

    cell = load_cell(args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    counts: list[int] = []
    route = MOE._route

    def counted_route(xf, router, m):
        ids, probs, aux = route(xf, router, m)
        if xf.shape[0] == 1:
            local = ids - m.first_held
            held = ((local >= 0) & (local < m.n_held)).sum()
            jax.debug.callback(lambda c: counts.append(int(c)), held)
        return ids, probs, aux

    MOE._route = counted_route
    with tempfile.TemporaryDirectory(prefix="reached_") as tmp:
        session = ServeSession(cfg, traffic, args.seed, args.seconds, Path(tmp),
                               annotate=jax.profiler.TraceAnnotation)
        session.setup(warm=False)
        requests = session.requests[: args.requests or None]
        per_step, tokens = _decode(session, requests, counts)
    m = session.model.cfg.moe
    layers = session.model.n_units
    held = np.asarray(counts)  # prompt steps are counted too; decode steps are per_step
    decode = np.asarray(per_step) / layers
    shares = np.bincount(held, minlength=m.top_k + 1) / max(held.size, 1)
    print(json.dumps({
        "workload": args.workload,
        "seed": args.seed,
        "requests": len(requests),
        "generated_tokens": tokens,
        "decode_steps": len(per_step),
        "moe_layers": layers,
        "held_reached_per_layer": float(decode.mean()) if decode.size else None,
        "held_reached_per_layer_p10_p90": (np.percentile(decode, [10, 90]).tolist()
                                           if decode.size else None),
        "assumed": m.top_k * m.n_held / m.n_experts,
        "all_steps_share_by_count": shares.round(4).tolist(),
        "all_steps_mean": float(held.mean()) if held.size else None,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
