"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_3b --smoke \
        --steps 30 --batch 8 --seq-len 64 --ckpt /tmp/run1

Wires the full stack: P3SAPP preprocessing -> packed LM batches -> mesh ->
logical-axis shardings -> microbatched train step -> fault-tolerant
checkpointed loop (resume-from-latest on restart). On CPU containers use
--smoke (reduced config); on a real pod the same flags drive the full
config with `make_production_mesh`.
"""

from __future__ import annotations

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get, get_smoke
from ..core.dataset import Dataset
from ..core.expr import abstract_expr, col, title_expr
from ..data.synthetic import write_corpus
from ..distributed.sharding import DEFAULT_RULES, data_axis_names, tree_shardings
from ..models.lm import LM, MeshContext
from ..optim.adamw import AdamW, warmup_cosine
from ..runtime.fault_tolerance import TrainController
from ..runtime.train_loop import TrainStepConfig, make_train_step
from .mesh import make_host_mesh, make_production_mesh, set_mesh


def build_dataset(cfg, seq_len: int, corpus_mb: float, seed: int) -> np.ndarray:
    corpus = tempfile.mkdtemp(prefix="p3sapp_train_")
    write_corpus(corpus, total_bytes=int(corpus_mb * 1e6), n_files=6, seed=seed)
    # The canonical chain in expression form (see repro.core.expr):
    # where() predicates filter on raw byte buffers before any cleaning,
    # transform() fuses the per-column expression chains.
    keep = col("title").not_empty() & col("abstract").not_empty()
    ds = (
        Dataset.from_json_dirs([corpus])
        .where(keep)
        .drop_duplicates()
        .transform(abstract=abstract_expr(), title=title_expr())
        .where(keep)
    )
    records, timings = ds.execute(optimize=True)
    print(f"P3SAPP: {len(records)} records in {timings.cumulative:.2f}s")
    # vocabulary fitting as a plan verb (shard-merged counts when the
    # frame is not yet materialized; here it reuses the memoized frame)
    tok = ds.fit_vocab(["abstract"], vocab_size=cfg.vocab_size)
    stream: list[int] = []
    for r in records:
        stream.extend(tok.stoi.get(w, 3) for w in r["abstract"].split())
    n = (len(stream) // seq_len) * seq_len
    return np.asarray(stream[:n], np.int32).reshape(-1, seq_len) % cfg.vocab_size


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm_3b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--corpus-mb", type=float, default=2.0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    # Tuned env for everything forked from here (shard-executor workers,
    # remote worker spawns). LD_PRELOAD/XLA pinning for *this* process must
    # come from the wrapper: python -m repro.launch.env -- python -m ...
    from .env import apply as apply_tuned_env, enable_compile_cache

    apply_tuned_env()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    mesh = (
        make_production_mesh() if args.production_mesh
        else make_host_mesh(model_parallel=args.model_parallel)
    )
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} params~{cfg.param_count()/1e6:.1f}M")

    seqs = build_dataset(cfg, args.seq_len, args.corpus_mb, seed=0)
    mctx = MeshContext(mesh, data_axis_names(mesh), "model")
    model = LM(cfg, mctx, remat=True, dtype=jnp.float32)
    opt = AdamW(learning_rate=warmup_cosine(args.lr, 10, args.steps))
    step = make_train_step(model.loss, opt, TrainStepConfig(args.microbatches))

    with set_mesh(mesh):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shardings = tree_shardings(shapes, model.param_axes(), mesh, DEFAULT_RULES)

        def init_state():
            params = jax.tree.map(
                jax.device_put, model.init(jax.random.PRNGKey(0)), shardings
            )
            return params, opt.init(params)

        jstep = jax.jit(step, donate_argnums=(0, 1))
        ckpt = args.ckpt or tempfile.mkdtemp(prefix="p3sapp_ckpt_")
        controller = TrainController(
            ckpt, jstep, init_state, save_every=args.save_every
        )
        if controller.resumed:
            print(f"resumed from step {controller.step}")

        bsh = NamedSharding(mesh, P(data_axis_names(mesh) if len(data_axis_names(mesh)) > 1 else "data", None))
        rng = np.random.default_rng(controller.step)

        def stream():
            while True:
                idx = rng.integers(0, len(seqs), size=args.batch)
                yield {"tokens": jax.device_put(jnp.asarray(seqs[idx]), bsh)}

        history = controller.run(stream(), n_steps=args.steps)
    for h in history[:: max(len(history) // 6, 1)]:
        print(f"step {h['step']:5d} loss={h['loss']:.4f} gnorm={h['grad_norm']:.3f}")
    print(f"final checkpoint at step {controller.step} in {ckpt}")


if __name__ == "__main__":
    main()
