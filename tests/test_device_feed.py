"""DeviceFeed / overlap-profiler suite: exact idle accounting under a fake
clock, fixed-grid compile-once behavior, donated-buffer safety, and the
end-to-end ``make_input_pipeline(overlap=True)`` wiring.

The integration test streams a real Dataset chain, so it runs through
whichever shard executor the CI leg selects (REPRO_EXECUTOR: thread,
process, or remote) — the feed is executor-agnostic by construction."""

import threading
import time

import jax
import numpy as np
import pytest

from repro.core.device_pipeline import BucketGrid, DeviceFeed
from repro.data.tokenizer import PAD


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


def _batch(i, rows=4, width=8):
    return {"x": np.full((rows, width), i + 1, dtype=np.int32)}


# ---------------------------------------------------------------------------
# Overlap accounting
# ---------------------------------------------------------------------------


def test_idle_fraction_math_exact_under_fake_clock():
    """Synchronous feed (prefetch=0) + fake clock: a producer that takes
    2s/batch against a 6s device step gives exactly known accounting.
    The first batch's wait is startup (pipeline fill), not idle."""
    clock = FakeClock()

    def slow_src(n=4):
        for i in range(n):
            clock.advance(2.0)  # host preprocessing time per batch
            yield _batch(i)

    feed = DeviceFeed(
        slow_src(), prefetch=0, device_put=lambda x: x, clock=clock
    )
    for batch in feed:
        with feed.step(batch):
            clock.advance(6.0)  # device compute time per step
    r = feed.report()
    assert r.steps == 4
    assert r.startup_s == pytest.approx(2.0)
    assert r.host_wait_s == pytest.approx(6.0)  # 3 post-startup waits
    assert r.device_s == pytest.approx(24.0)
    assert r.starved_steps == 3
    assert r.device_idle_fraction == pytest.approx(6.0 / 30.0)


def test_fast_producer_zero_idle():
    """When the host is instant on the fake clock, idle fraction is 0."""
    clock = FakeClock()
    feed = DeviceFeed(
        iter([_batch(i) for i in range(5)]),
        prefetch=0,
        device_put=lambda x: x,
        clock=clock,
    )
    for batch in feed:
        with feed.step(batch):
            clock.advance(3.0)
    r = feed.report()
    assert r.steps == 5
    assert r.host_wait_s == 0.0
    assert r.starved_steps == 0
    assert r.device_idle_fraction == 0.0


def test_slow_producer_increments_starvation_threaded():
    """Threaded mode: a producer gated on an event starves the feed; the
    starved step lands in the report and in the loader's queue stats."""
    gate = threading.Event()

    def src():
        # three ungated batches: the feed's first yield needs them (the
        # loader and the feed each hold one double-buffer pending)
        yield _batch(0)
        yield _batch(1)
        yield _batch(2)
        gate.wait(timeout=5.0)
        time.sleep(0.02)  # real stall, well over starvation_eps
        yield _batch(3)

    feed = DeviceFeed(src(), prefetch=2, device_put=lambda x: x)
    it = iter(feed)
    first = next(it)  # batch 0, no gated pull needed
    assert int(np.asarray(first["x"])[0, 0]) == 1
    done = []
    t = threading.Thread(target=lambda: done.extend(it), daemon=True)
    t.start()
    # the feed is now blocked pulling the gated batch 3
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not feed.loader_stats.starvation:
        time.sleep(0.002)
    gate.set()
    t.join(timeout=5.0)
    assert len(done) == 3
    r = feed.report()
    assert r.starved_steps >= 1
    assert r.host_wait_s > 0.0
    assert feed.loader_stats.starvation >= 1


# ---------------------------------------------------------------------------
# Fixed bucket grid: snap + compile-once
# ---------------------------------------------------------------------------


def test_grid_snap_pads_rows_and_widths():
    grid = BucketGrid(4, {"x": (8, 16)})
    snapped = grid.snap({"x": np.ones((2, 5), np.int32), "y": np.arange(2)})
    assert snapped["x"].shape == (4, 8)
    assert snapped["y"].shape == (4,)
    # payload prefix preserved, PAD fill elsewhere
    assert (snapped["x"][:2, :5] == 1).all()
    assert (snapped["x"][2:] == PAD).all()
    assert (snapped["x"][:2, 5:] == PAD).all()
    assert grid.n_cells == 2


def test_grid_rejects_off_grid_width():
    grid = BucketGrid(4, {"x": (8, 16)})
    with pytest.raises(ValueError, match="beyond the top bucket"):
        grid.snap({"x": np.ones((4, 32), np.int32)})


def test_fixed_grid_jit_compiles_once_per_cell():
    """An epoch of ragged batches snapped onto a 2-rung grid triggers at
    most 2 traces of the jit'd step; without the grid every distinct width
    would compile separately."""
    traces = [0]

    @jax.jit
    def step(x):
        traces[0] += 1
        return x.sum()

    widths = [3, 5, 8, 9, 12, 16, 6, 14, 8, 11]
    rows = [4, 4, 4, 3, 4, 2, 4, 4, 1, 4]
    batches = [_batch(i, rows=r, width=w) for i, (r, w) in enumerate(zip(rows, widths))]
    assert len({(r, w) for r, w in zip(rows, widths)}) > 2  # ragged input

    feed = DeviceFeed(
        iter(batches), grid=BucketGrid(4, {"x": (8, 16)}), prefetch=2
    )
    n = 0
    for batch in feed:
        with feed.step(batch):
            jax.block_until_ready(step(batch["x"]))
        n += 1
    assert n == len(batches)
    assert traces[0] == 2, "one compilation per grid cell, not per batch"
    assert feed.report().steps == n


def test_snapped_batches_preserve_payload():
    grid = BucketGrid(3, {"x": (4,)})
    feed = DeviceFeed(
        iter([{"x": np.array([[7, 8]], np.int32)}]),
        grid=grid,
        prefetch=0,
        device_put=lambda x: x,
    )
    [batch] = list(feed)
    np.testing.assert_array_equal(
        batch["x"],
        np.array([[7, 8, PAD, PAD], [PAD] * 4, [PAD] * 4], np.int32),
    )


# ---------------------------------------------------------------------------
# Donation safety
# ---------------------------------------------------------------------------


def test_reuse_after_donate_raises():
    feed = DeviceFeed(
        iter([_batch(0), _batch(1)]), prefetch=0, device_put=lambda x: x
    )
    seen = []
    for batch in feed:
        _ = batch["x"]  # reads inside the step window are fine
        with feed.step(batch):
            seen.append(batch["x"].sum())
        with pytest.raises(RuntimeError, match="reuse after donate"):
            batch["x"]
        with pytest.raises(RuntimeError, match="reuse after donate"):
            batch.arrays
    assert len(seen) == 2


def test_donate_false_allows_rereads():
    feed = DeviceFeed(
        iter([_batch(0)]), prefetch=0, device_put=lambda x: x, donate=False
    )
    [batch] = list(feed)
    with feed.step(batch):
        pass
    assert batch["x"].shape == (4, 8)  # no donation: re-read is legal


# ---------------------------------------------------------------------------
# Double buffering at the device boundary
# ---------------------------------------------------------------------------


def test_transfer_of_next_batch_precedes_yield():
    events = []

    def fake_put(x):
        events.append(("put", int(x[0, 0]) - 1))
        return x

    feed = DeviceFeed(
        iter([_batch(i) for i in range(4)]), prefetch=2, device_put=fake_put
    )
    for b in feed:
        events.append(("yield", int(np.asarray(b["x"])[0, 0]) - 1))
    for k in range(3):
        assert events.index(("put", k + 1)) < events.index(("yield", k))


def test_close_joins_pipeline():
    def endless():
        i = 0
        while True:
            yield _batch(i)
            i += 1

    feed = DeviceFeed(endless(), prefetch=2, device_put=lambda x: x)
    it = iter(feed)
    next(it)
    feed.close()
    assert not feed._loader.running


# ---------------------------------------------------------------------------
# End-to-end: plan → bucketed batches → DeviceFeed (executor-agnostic)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from repro.data.synthetic import write_corpus

    d = tmp_path_factory.mktemp("overlap_corpus")
    write_corpus(d, total_bytes=300_000, n_files=4, seed=21)
    return d


def test_make_input_pipeline_overlap_end_to_end(corpus):
    from repro.core.dataset import Dataset
    from repro.core.expr import abstract_expr, col, title_expr
    from repro.data.batching import seq2seq_specs
    from repro.runtime.train_loop import make_input_pipeline

    keep = col("title").not_empty() & col("abstract").not_empty()
    base = (
        Dataset.from_json_dirs([corpus])
        .where(keep)
        .drop_duplicates()
        .transform(abstract=abstract_expr(), title=title_expr())
        .where(keep)
    )
    tok = base.fit_vocab(vocab_size=500)
    pipe = (
        base.tokenize(tok, seq2seq_specs(max_abstract_len=32, max_title_len=8))
        .batched(
            8,
            shuffle=False,
            bucket_by="encoder_tokens",
            drop_remainder=False,
            pad_to=8,
        )
        .prefetch(2)
    )
    grid = pipe.bucket_grid_spec()
    assert grid is not None and grid.batch_size == 8

    feed = make_input_pipeline(pipe, epochs=1, prefetch=2, overlap=True)
    try:
        steps = 0
        cells = set()
        for batch in feed:
            assert isinstance(batch["encoder_tokens"], jax.Array)
            assert batch["encoder_tokens"].shape[0] == 8
            assert batch["encoder_tokens"].shape[1] in grid.widths["encoder_tokens"]
            cells.add(batch.cell)
            with feed.step(batch):
                jax.block_until_ready(batch["encoder_tokens"].sum())
            steps += 1
    finally:
        feed.close()
    assert steps > 0
    assert len(cells) <= grid.n_cells
    r = feed.report()
    assert r.steps == steps
    assert r.device_s > 0.0


def test_dataset_device_batches_overlap_terminal(corpus):
    from repro.core.dataset import Dataset
    from repro.core.device_pipeline import DeviceFeed as DF
    from repro.core.expr import abstract_expr, col, title_expr

    keep = col("title").not_empty() & col("abstract").not_empty()
    base = (
        Dataset.from_json_dirs([corpus])
        .where(keep)
        .transform(abstract=abstract_expr(), title=title_expr())
        .where(keep)
    )
    tok = base.fit_vocab(vocab_size=300)
    feed = base.tokenize(tok, col="abstract", max_len=16).batch(
        4, shuffle=False, drop_remainder=False, pad_to=4
    ).prefetch(2).device_batches(overlap=True)
    assert isinstance(feed, DF)
    try:
        n = sum(1 for _ in feed)
    finally:
        feed.close()
    assert n > 0


def test_feed_spans_count_waits_and_transfers_under_fake_clock():
    """``feed.wait`` and ``feed.transfer`` count on the feed's clock, and a
    stats dict given to the feed receives the running ``transfer_s``."""
    clock = FakeClock()

    def src(n=3):
        for i in range(n):
            clock.advance(2.0)
            yield _batch(i)

    def put(x):
        clock.advance(0.5)  # one leaf's transfer
        return x

    stats: dict = {}
    feed = DeviceFeed(src(), prefetch=0, device_put=put, clock=clock, stats=stats)
    assert sum(1 for _ in feed) == 3
    r = feed.report()
    assert (r.startup_s, r.host_wait_s) == (pytest.approx(2.0), pytest.approx(4.0))
    assert r.transfer_s == pytest.approx(1.5)
    assert stats == {"transfer_s": pytest.approx(1.5)}


def test_stream_batches_counts_epoch_starts(corpus):
    """Each epoch's executor start to its first batch is one
    ``plan.epoch_start`` span in the plan's stats dict."""
    from repro.core.dataset import Dataset
    from repro.core.expr import abstract_expr, col

    base = (
        Dataset.from_json_dirs([corpus])
        .where(col("abstract").not_empty())
        .transform(abstract=abstract_expr())
    )
    tok = base.fit_vocab(vocab_size=300)
    chain = base.tokenize(tok, col="abstract", max_len=16).batch(4, shuffle=False).prefetch(2)
    stats: dict = {}
    once = sum(1 for _ in chain.iter_batches(epochs=1))
    assert sum(1 for _ in chain.iter_batches(epochs=2, stats=stats)) == 2 * once
    assert stats["epochs"] == 2
    assert stats["epoch_start_s"] > 0.0
