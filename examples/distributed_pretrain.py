"""Distributed LM pretraining on scholarly text (8 placeholder devices).

Demonstrates the production path end-to-end at example scale: P3SAPP
pipeline → packed LM batches → (data, model) mesh → sharded params via
the logical-axis rule engine → microbatched train step → checkpointed
loop. MUST be launched directly (device count is locked at jax init):

    PYTHONPATH=src python examples/distributed_pretrain.py --steps 20
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke
from repro.core.p3sapp import p3sapp_dataset
from repro.data.synthetic import write_corpus
from repro.distributed.sharding import tree_shardings
from repro.launch.env import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.lm import LM, MeshContext
from repro.optim.adamw import AdamW
from repro.runtime.train_loop import TrainStepConfig, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    corpus = tempfile.mkdtemp(prefix="p3sapp_corpus_")
    write_corpus(corpus, total_bytes=2_000_000, n_files=4, seed=7)
    ds = p3sapp_dataset([corpus])
    records, _ = ds.execute(optimize=True)
    tok = ds.fit_vocab(["abstract"], vocab_size=2000)

    cfg = get_smoke(args.arch)
    # pack abstracts into contiguous LM sequences
    stream = []
    for r in records:
        stream.extend(tok.stoi.get(w, 3) for w in r["abstract"].split())
    stream = np.asarray(stream[: (len(stream) // args.seq_len) * args.seq_len], np.int32)
    seqs = stream.reshape(-1, args.seq_len) % cfg.vocab_size

    mesh = make_host_mesh(model_parallel=2)
    print(f"mesh: {dict(mesh.shape)}")
    mctx = MeshContext(mesh, ("data",), "model")
    model = LM(cfg, mctx, remat=True, dtype=jnp.float32)
    opt = AdamW(learning_rate=3e-3)
    step = make_train_step(model.loss, opt, TrainStepConfig(n_microbatches=2))

    with jax.sharding.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        params = jax.tree.map(jax.device_put, params, tree_shardings(shapes, model.param_axes(), mesh))
        opt_state = opt.init(params)
        jstep = jax.jit(step, donate_argnums=(0, 1))
        bsh = NamedSharding(mesh, P("data", None))
        rng = np.random.default_rng(0)
        for i in range(args.steps):
            idx = rng.integers(0, len(seqs), size=args.batch)
            batch = {"tokens": jax.device_put(jnp.asarray(seqs[idx]), bsh)}
            params, opt_state, m = jstep(params, opt_state, batch)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(m['loss']):.4f} gnorm={float(m['grad_norm']):.3f}")
    print("distributed pretrain example complete")


if __name__ == "__main__":
    main()
