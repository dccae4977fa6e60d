"""Chip smoke test: drive the system's main paths once on one TPU.

    python chip_smoke.py [--seed 0] [--corpus-mb 24] [--steps 40]

Runs four phases in this one process (the process that holds the chip)
and exits non-zero if any of them fails:

1. device: ``jax.devices()[0]`` must be a TPU. Nothing continues on a CPU,
   in interpret mode or on the host scan.
2. train: the paper's case study at full width. A corpus of JSON shards
   made from ``--seed`` streams through the canonical plan
   (``where → drop_duplicates → transform → where → fit_vocab → tokenize
   → batched(bucket_by) → workers(2) → prefetch → device_batches``) and
   the process shard executor into ``Seq2Seq(CONFIG)``, which takes
   ``--steps`` steps through ``TrainController``
   (:func:`repro.runtime.summarizer.train_summarizer`). Every loss must be
   finite, the first near ``ln(vocab)``, the loss must fall, and the
   model's loss on the chip must agree with a float32 reference on the
   host CPU.
3. serve: a row program lowered from the same fitted plan feeds
   ``serve_text`` on stablelm-3b at its published widths in bfloat16,
   weights drawn from ``--seed``: 8 requests through 4 slots, one of them
   a repeat (answered from the ring cache) and one empty (filtered).
4. pallas: the canonical cleaning chain runs through the ``pallas`` byte
   backend on every shard column; the bytes must equal the ``loops``
   backend's and the Mosaic kernel must have run with no decline.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"


def fail(phase: str, why: str) -> int:
    print(f"FAIL {phase}: {why}", file=sys.stderr)
    return 1


class CacheEvents:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def phase_train(args, corpus: Path, work: Path):
    import jax
    import numpy as np

    from repro.configs.p3sapp_summarizer import CONFIG
    from repro.models.seq2seq import Seq2Seq
    from repro.runtime.summarizer import train_summarizer

    # The model's math on the chip against a float32 host reference, on a
    # small seeded batch at the configuration's full width.
    model = Seq2Seq(CONFIG)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    batch = {
        "encoder_tokens": rng.integers(4, CONFIG.vocab_size, (8, 32), dtype=np.int32),
        "decoder_tokens": rng.integers(4, CONFIG.vocab_size, (8, 12), dtype=np.int32),
    }
    chip_loss = float(jax.jit(model.loss)(params, batch))
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        ref_loss = float(
            jax.jit(model.loss)(
                jax.device_put(jax.device_get(params), cpu),
                jax.device_put(batch, cpu),
            )
        )
    print(f"train: reference loss chip={chip_loss!r} cpu_f32={ref_loss!r}")
    if not abs(chip_loss - ref_loss) <= 1e-2 * abs(ref_loss):
        return None, f"chip loss {chip_loss} disagrees with the f32 reference {ref_loss}"

    run = train_summarizer(
        [corpus], CONFIG, steps=args.steps, ckpt_dir=work / "ckpt",
        batch_size=32, workers=2, seed=args.seed,
    )
    losses = [h["loss"] for h in run.history]
    print(f"train: steps={len(run.history)} executor={run.feed_stats.get('executor')} "
          f"wall_s={run.wall_s!r}")
    for cell, n in sorted(run.traces.items()):
        shapes = {k: v for k, v in cell}
        print(f"train: bucket cell {shapes} traces={n}")
    print(f"train: loss first={losses[0] if losses else None!r} "
          f"last={losses[-1] if losses else None!r}")
    if len(run.history) != args.steps:
        return None, f"took {len(run.history)} of {args.steps} steps"
    if not all(math.isfinite(v) for v in losses):
        return None, f"non-finite loss in {losses}"
    if run.feed_stats.get("executor") != "process":
        return None, f"shards ran on the {run.feed_stats.get('executor')} executor"
    if any(n != 1 for n in run.traces.values()):
        return None, f"train step traced more than once per cell: {run.traces}"
    if abs(losses[0] - math.log(CONFIG.vocab_size)) > 0.1 * math.log(CONFIG.vocab_size):
        return None, f"first loss {losses[0]} is far from ln(vocab)"
    if not np.mean(losses[-5:]) < losses[0]:
        return None, f"loss did not fall: {losses}"
    return run, None


def phase_serve(args, corpus: Path, held_out: Path, run):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get
    from repro.configs.p3sapp_summarizer import CONFIG
    from repro.core.dataset import Dataset
    from repro.data.batching import seq2seq_specs
    from repro.models.lm import LM
    from repro.runtime.serve_loop import RingCache, ServeStats, TextRequest, serve_text
    from repro.runtime.summarizer import serving_chain

    encoder_spec = seq2seq_specs(CONFIG.max_abstract_len, CONFIG.max_title_len)[0]
    row_program = (
        serving_chain([corpus]).tokenize(run.tokenizer, [encoder_spec]).row_program()
    )
    cfg = get("stablelm_3b")
    model = LM(cfg, remat=False, dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"serve: {cfg.name} {n_params} params in bfloat16, init {time.perf_counter() - t0!r} s")

    texts = [
        r["abstract"]
        for r in Dataset.from_json_dirs([held_out], fields=("abstract",)).to_records()
        if r["abstract"]
    ][:6]
    texts += ["", texts[0]]  # filtered, then a repeat
    reqs = [TextRequest(uid, t, max_new=16) for uid, t in enumerate(texts)]
    cache, stats = RingCache(slots=32), ServeStats()
    kw = dict(slots=4, max_seq=256, cache=cache, stats=stats, cache_dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    # Two waves: the repeat arrives after the original's answer is cached.
    results = dict(serve_text(model, params, row_program, reqs[:-1], **kw))
    results.update(serve_text(model, params, row_program, reqs[-1:], **kw))
    wall = time.perf_counter() - t0
    print(f"serve: {len(results)}/{len(reqs)} answered in {wall!r} s; served={stats.served} "
          f"filtered={stats.filtered} cache_hits={stats.cache_hits} "
          f"preprocess_s={stats.preprocess_s!r} prefill_s={stats.prefill_s!r} "
          f"decode_s={stats.decode_s!r} compiles={stats.compiles} compile_s={stats.compile_s!r}")
    if len(results) != len(reqs):
        return f"answered {len(results)} of {len(reqs)} requests"
    empty, repeat = len(texts) - 2, len(texts) - 1
    if results[empty] != [] or stats.filtered != 1:
        return f"the empty request was not filtered: {results[empty]}"
    if stats.cache_hits != 1 or results[repeat] != results[0]:
        return "the repeated request missed the ring cache"
    for uid in range(empty):
        toks = results[uid]
        if not toks or not all(0 <= t < cfg.vocab_size for t in toks):
            return f"request {uid} came back as {toks}"
    # One prefill checked for finite logits of the expected shape.
    prompt = row_program(texts[0])["encoder_tokens"][0]
    prompt = prompt[prompt != 0][None]
    logits, _ = jax.jit(model.decode_step)(
        params, jnp.asarray(prompt), model.init_decode_state(1, 256, jnp.bfloat16),
        jnp.int32(0),
    )
    logits = np.asarray(logits, np.float32)
    if logits.shape != (1, 1, cfg.vocab_size) or not np.isfinite(logits).all():
        return f"prefill logits {logits.shape} are not finite"
    return None


def phase_pallas(corpus: Path):
    import numpy as np

    from repro.core import bytesops as B
    from repro.core import expr as E
    from repro.core import ingest as ing
    from repro.kernels.text_clean.ops import text_scan_op

    stats: dict = {}
    n_bytes = 0
    t0 = time.perf_counter()
    for shard in ing.list_shards([corpus]):
        data, _ = ing.read_shard_bytes(shard)
        frame = ing.parse_shard_bytes(data, ("title", "abstract"))
        for column, expr in (("abstract", E.abstract_expr()), ("title", E.title_expr())):
            ops = list(E.compile_expr(expr)[2])
            buf = frame.flat(column)
            want = B.execute_ops(buf, ops, "loops")
            got = B.execute_ops(buf, ops, "pallas", stats=stats)
            if not np.array_equal(got, want):
                return f"{shard.name}:{column} differs from the loops backend"
            n_bytes += buf.size
    print(f"pallas: {n_bytes} bytes in {time.perf_counter() - t0!r} s; "
          f"kernel calls={stats.get('pallas_calls', 0)} "
          f"declines={stats.get('pallas_declines', 0)} "
          f"compiled shapes={text_scan_op._cache_size()}")
    if not stats.get("pallas_calls"):
        return "the Mosaic kernel never ran"
    if stats.get("pallas_declines"):
        return f"{stats['pallas_declines']} scan passes declined to the host scan"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--corpus-mb", type=float, default=24.0)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    try:
        from repro.launch.env import enable_compile_cache
    except ImportError as e:
        return fail("setup", f"the repro package is not importable from {SRC}: {e}")
    cache_dir = enable_compile_cache()

    import jax

    cache_events = CacheEvents()
    jax.monitoring.register_event_listener(cache_events)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"device: {device}")
    if dev.platform != "tpu":
        return fail("device", f"first device is {dev.platform!r}, not a TPU")

    from repro.data.synthetic import write_corpus

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        corpus, held_out = work / "corpus", work / "held_out"
        t0 = time.perf_counter()
        write_corpus(corpus, total_bytes=int(args.corpus_mb * 1e6), n_files=8,
                     seed=args.seed)
        write_corpus(held_out, total_bytes=100_000, n_files=1, seed=args.seed + 1)
        print(f"corpus: {args.corpus_mb} MB in 8 shards, {time.perf_counter() - t0!r} s")

        run, err = phase_train(args, corpus, work)
        if err:
            return fail("train", err)
        err = phase_serve(args, corpus, held_out, run)
        if err:
            return fail("serve", err)
        err = phase_pallas(corpus)
        if err:
            return fail("pallas", err)

    print(f"compile cache: dir={cache_dir} hits={cache_events.hits} "
          f"misses={cache_events.misses}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
