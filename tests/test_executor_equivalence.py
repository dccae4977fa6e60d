"""Differential harness for the three physical executors.

Whole-frame (:func:`repro.core.plan.execute_frame_plan`), streaming-thread
(:class:`repro.core.executor.ThreadShardExecutor`) and multi-process
(:class:`repro.core.executor.ProcessShardExecutor`) execution of the same
plan must produce byte-identical record multisets (arrival order is
nondeterministic under work stealing) and attribute wall time to the same
set of paper stages. The same harness drives token space: executor-emitted
int32 token arrays must be byte-identical to the eager
``encode_frame_columns`` oracle, and shard-merged vocabulary fits must
equal the whole-frame fit exactly. Corpora are hypothesis-generated and
include the nasty cases: unicode, empty rows, NUL bytes, giant rows.
"""

import json
import random

import pytest

try:  # hypothesis drives the property search when installed (CI); the
    # deterministic + seeded-fuzz corpora below run everywhere regardless.
    from hypothesis import HealthCheck, example, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # pragma: no cover - bare container
    HAVE_HYPOTHESIS = False

from repro.core import executor as EX
from repro.core import ingest as ing
from repro.core import plan as P
from repro.core.dataset import Dataset
from repro.core.frame import ColumnarFrame
from repro.core.p3sapp import case_study_stages
from repro.data.batching import encode_frame_columns, seq2seq_specs
from repro.data.tokenizer import WordTokenizer

FIELDS = ("title", "abstract")

_FUZZ_CHARS = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
    " <>()'.,!?-\t\x00ΩμέλΛñé漢字🙂"
)


def fuzz_records(seed: int, n: int) -> list[dict]:
    """Seeded pseudo-random corpus over the same nasty alphabet the
    hypothesis strategy draws from."""
    rng = random.Random(seed)

    def text():
        roll = rng.random()
        if roll < 0.1:
            return None
        if roll < 0.2:
            return ""
        return "".join(rng.choice(_FUZZ_CHARS) for _ in range(rng.randrange(1, 60)))

    return [{"title": text(), "abstract": text()} for _ in range(n)]


EDGE_RECORDS = [
    {"title": "", "abstract": ""},  # empty row
    {"title": None, "abstract": "only abstract survives dropna? no"},  # null
    {"title": "NUL\x00inside", "abstract": "tab\there and CR"},  # NUL bytes
    {"title": "Ωμέγα ένα <b>δύο</b>", "abstract": "naïve café — résumé 漢字"},
    {"title": "plain Title 42", "abstract": "The QUICK brown fox isn't slow."},
]
GIANT_RECORDS = [
    {
        "title": "Giant <b>Row</b> " + "Lorem IPSUM (drop me) " * 2000,
        "abstract": "word " * 20_000 + "end",
    },
    {"title": "small", "abstract": "row"},
]


def write_shards(root, records, n_files=3):
    d = root / "corpus"
    d.mkdir(parents=True, exist_ok=True)
    for i in range(n_files):
        with open(d / f"s{i}.jsonl", "w", encoding="utf-8") as fh:
            for r in records[i::n_files]:
                fh.write(json.dumps(r, ensure_ascii=False) + "\n")
    return d


def chain(d):
    """The canonical Algorithm 1 chain (sans dedup, so every executor —
    including the process pool — can run it)."""
    return (
        Dataset.from_json_dirs([d], FIELDS)
        .dropna(FIELDS)
        .apply(*case_study_stages())
        .dropna(FIELDS)
    )


def optimized_program(ds):
    frame_nodes, _ = P.split_plan(ds.plan)
    opt = P.optimize_plan(frame_nodes, ds.schema)
    return EX.compile_shard_program(opt, optimize=True)


def record_multiset(records):
    return sorted(tuple(sorted(r.items(), key=lambda kv: kv[0])) for r in records)


def executor_records(executor):
    frames = [res.frame for res in executor]
    executor.stop()
    if not frames:
        return []
    return ColumnarFrame.concat(frames).to_records()


def nonzero_stages(timings):
    return {
        name
        for name in ("ingestion", "pre_cleaning", "cleaning", "post_cleaning")
        if getattr(timings, name) > 0.0
    }


# ---------------------------------------------------------------------------
# the differential property
# ---------------------------------------------------------------------------


def token_row_multiset(token_dicts):
    """Row-wise byte multiset over a list of per-shard token dicts."""
    rows = []
    for tokens in token_dicts:
        keys = sorted(tokens)
        n = len(tokens[keys[0]]) if keys else 0
        for i in range(n):
            rows.append(tuple(tokens[k][i].tobytes() for k in keys))
    return sorted(rows)


def executor_tokens(executor):
    out = [res.tokens for res in executor]
    executor.stop()
    return out


SPECS = seq2seq_specs(max_abstract_len=16, max_title_len=8)


def token_program(ds, tok, specs=SPECS):
    frame_nodes, _ = P.split_plan(ds.plan)
    spec_cols = tuple(dict.fromkeys(s.column for s in specs))
    return EX.compile_shard_program(
        P.optimize_plan(frame_nodes, spec_cols),
        optimize=True,
        output_columns=spec_cols,
        tokens=EX.TokenPlan(tuple(specs), dict(tok.stoi), tok.fingerprint),
    )


def check_token_executors(d, ds, frame):
    """Executor-emitted token arrays must be byte-identical to the eager
    encode_frame_columns oracle, and per-shard-counted vocabularies must
    equal the whole-frame fit."""
    tok = WordTokenizer.fit(
        [(v or "") for col in FIELDS for v in frame[col]], vocab_size=256
    )
    oracle = encode_frame_columns(
        {c: frame[c] for c in FIELDS}, tok, SPECS
    )
    want = token_row_multiset([oracle])
    shards = ing.list_shards([d])
    program = token_program(ds, tok)

    got_thread = token_row_multiset(
        executor_tokens(EX.ThreadShardExecutor(shards, program, workers=2))
    )
    assert got_thread == want
    got_proc = token_row_multiset(
        executor_tokens(EX.ProcessShardExecutor(shards, program, workers=2))
    )
    assert got_proc == want

    # vocabulary fitting: shard-merged Counters (thread and process) must
    # reproduce the whole-frame fit word for word
    whole_ds = chain(d)
    whole_ds.collect()  # materialize → fit_vocab counts the memoized frame
    vocab_whole = whole_ds.fit_vocab(vocab_size=64)
    vocab_thread = chain(d).fit_vocab(vocab_size=64, workers=2, executor="thread")
    vocab_proc = chain(d).fit_vocab(vocab_size=64, workers=2, executor="process")
    assert vocab_thread.itos == vocab_whole.itos
    assert vocab_proc.itos == vocab_whole.itos


def check_three_executors(root, records):
    d = write_shards(root, records)
    ds = chain(d)
    frame_nodes, _ = P.split_plan(ds.plan)
    frame, whole_t = P.execute_frame_plan(frame_nodes, final_schema=ds.schema)
    want = record_multiset(frame.to_records())

    program = optimized_program(ds)
    shards = ing.list_shards([d])

    thread_ex = EX.ThreadShardExecutor(shards, program, workers=2)
    got_thread = record_multiset(executor_records(thread_ex))
    assert got_thread == want

    proc_ex = EX.ProcessShardExecutor(shards, program, workers=2)
    got_proc = record_multiset(executor_records(proc_ex))
    assert got_proc == want

    # Identical timing attribution: all three executors charge the same
    # paper stages (values differ, the *stage set* must not).
    assert nonzero_stages(thread_ex.timings) == nonzero_stages(whole_t)
    assert nonzero_stages(proc_ex.timings) == nonzero_stages(whole_t)

    # Token space over the same corpus: arrays and vocabularies.
    check_token_executors(d, ds, frame)


@pytest.mark.parametrize(
    "records",
    [
        pytest.param([], id="empty-corpus"),
        pytest.param(EDGE_RECORDS, id="edge-cases"),
        pytest.param(GIANT_RECORDS, id="giant-rows"),
        pytest.param(fuzz_records(1, 40), id="fuzz-1"),
        pytest.param(fuzz_records(2, 40), id="fuzz-2"),
    ],
)
def test_three_executors_byte_identical(tmp_path, records):
    check_three_executors(tmp_path, records)


if HAVE_HYPOTHESIS:
    TEXT = st.text(
        alphabet=st.one_of(
            st.characters(min_codepoint=32, max_codepoint=126),
            st.sampled_from("ΩμέλΛñé漢字🙂\t\x00"),
        ),
        max_size=40,
    )
    RECORDS = st.lists(
        st.fixed_dictionaries(
            {
                "title": st.none() | st.just("") | TEXT,
                "abstract": st.none() | st.just("") | TEXT,
            }
        ),
        max_size=24,
    )

    @settings(
        max_examples=6,
        deadline=None,
        suppress_health_check=[HealthCheck.function_scoped_fixture],
    )
    @given(records=RECORDS)
    @example(records=EDGE_RECORDS)
    def test_three_executors_byte_identical_property(tmp_path, records):
        check_three_executors(tmp_path, records)


# ---------------------------------------------------------------------------
# expression pipelines vs the legacy Stage oracle
# ---------------------------------------------------------------------------


def _stage_oracle(d):
    """The eager Stage path (Pipeline over a ColumnarFrame + row filters),
    kept as the oracle the expression pipelines must reproduce byte for
    byte."""
    from repro.core.pipeline import Pipeline

    frame = ing.ingest([d], FIELDS)
    frame = frame.dropna(list(FIELDS))
    frame = Pipeline(case_study_stages()).fit(frame).transform(frame)
    frame = frame.dropna(list(FIELDS))
    return frame


def expr_chain(d):
    """The canonical chain rebuilt from composable expressions — no Stage
    verbs anywhere."""
    from repro.core.expr import abstract_expr, col, title_expr

    return (
        Dataset.from_json_dirs([d], FIELDS)
        .where(col("title").not_empty() & col("abstract").not_empty())
        .transform(abstract=abstract_expr(), title=title_expr())
        .where(col("title").not_empty() & col("abstract").not_empty())
    )


@pytest.mark.parametrize(
    "records",
    [
        pytest.param(EDGE_RECORDS, id="edge-cases"),
        pytest.param(fuzz_records(5, 40), id="fuzz-5"),
    ],
)
def test_expression_pipeline_matches_stage_oracle(tmp_path, records):
    d = write_shards(tmp_path, records)
    want = record_multiset(_stage_oracle(d).to_records())

    ds = expr_chain(d)
    frame_nodes, _ = P.split_plan(ds.plan)
    frame, _ = P.execute_frame_plan(frame_nodes, final_schema=ds.schema)
    assert record_multiset(frame.to_records()) == want

    program = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, ds.schema), optimize=True
    )
    shards = ing.list_shards([d])
    got_thread = record_multiset(
        executor_records(EX.ThreadShardExecutor(shards, program, workers=2))
    )
    assert got_thread == want
    got_proc = record_multiset(
        executor_records(EX.ProcessShardExecutor(shards, program, workers=2))
    )
    assert got_proc == want

    # token space: executor-encoded arrays off the expression pipeline must
    # equal the eager oracle encoding of the oracle frame
    frame_o = _stage_oracle(d)
    tok = WordTokenizer.fit(
        [(v or "") for col_ in FIELDS for v in frame_o[col_]], vocab_size=256
    )
    oracle_tokens = encode_frame_columns(
        {c: frame_o[c] for c in FIELDS}, tok, SPECS
    )
    program_t = token_program(ds, tok)
    got = token_row_multiset(
        executor_tokens(EX.ProcessShardExecutor(shards, program_t, workers=2))
    )
    assert got == token_row_multiset([oracle_tokens])


def test_expression_predicates_match_python_semantics(tmp_path):
    """where() predicates (word_count / contains / boolean algebra) on
    byte buffers must agree with the same predicate evaluated row-wise in
    Python — across whole-frame and both shard executors."""
    from repro.core.expr import col

    records = fuzz_records(9, 60)
    d = write_shards(tmp_path, records)
    ds = Dataset.from_json_dirs([d], FIELDS).where(
        (col("abstract").word_count() >= 2)
        & ~col("title").contains("x")
        & col("title").not_empty()
    )

    def keep(r):
        t, a = r.get("title") or "", r.get("abstract") or ""
        return len(a.split(" ")) - a.split(" ").count("") >= 2 and "x" not in t and t != ""

    # NB: word_count counts space-separated words on the byte buffer; rows
    # are compared through the same normalization ingestion applies.
    frame = ing.ingest([d], FIELDS)
    want = record_multiset(
        r for r in frame.to_records()
        if keep({k: (v if v is None else str(v).replace("\x00", " ")) for k, v in r.items()})
    )

    frame_nodes, _ = P.split_plan(ds.plan)
    got_frame, _ = P.execute_frame_plan(frame_nodes, final_schema=ds.schema)
    assert record_multiset(got_frame.to_records()) == want

    program = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, ds.schema), optimize=True
    )
    shards = ing.list_shards([d])
    for ex in (
        EX.ThreadShardExecutor(shards, program, workers=2),
        EX.ProcessShardExecutor(shards, program, workers=2),
    ):
        assert record_multiset(executor_records(ex)) == want


def _check_ds_three_executors(d, ds):
    """Whole-frame, thread, and process execution of an arbitrary
    frame-level dataset plan must produce byte-identical record
    multisets."""
    frame_nodes, _ = P.split_plan(ds.plan)
    frame, _ = P.execute_frame_plan(frame_nodes, final_schema=ds.schema)
    want = record_multiset(frame.to_records())
    program = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, ds.schema), optimize=True
    )
    shards = ing.list_shards([d])
    for make in (
        lambda: EX.ThreadShardExecutor(shards, program, workers=2),
        lambda: EX.ProcessShardExecutor(shards, program, workers=2),
    ):
        assert record_multiset(executor_records(make())) == want
    return want


@pytest.mark.parametrize(
    "records",
    [
        pytest.param(EDGE_RECORDS, id="edge-cases"),
        pytest.param(fuzz_records(11, 50), id="fuzz-11"),
    ],
)
def test_cse_plan_byte_identical_across_executors(tmp_path, records):
    """A chain shared between a ``where`` predicate and a projected
    derived column (hoisted by cross-node CSE into a ``__cse_*``
    intermediate) must stay byte-identical to whole-frame on every
    executor, and the synthetic column must not leak into the results."""
    from repro.core.expr import clean_text, col

    d = write_shards(tmp_path, records)
    shared = clean_text(col("abstract"))
    ds = (
        Dataset.from_json_dirs([d], FIELDS)
        .where(shared.word_count() >= 2)
        .with_column("abstract", shared)
        .with_column("short", clean_text(col("abstract")))
    )
    opt = ds.optimized_plan()
    assert any(
        out.startswith("__cse_")
        for n in opt
        if isinstance(n, P.Project)
        for out, _ in n.exprs
    ), "expected a hoisted CSE intermediate in the optimized plan"
    want = _check_ds_three_executors(d, ds)
    for rec in want:
        assert not any(k.startswith("__cse_") for k, _ in rec)


@pytest.mark.parametrize(
    "records",
    [
        pytest.param(EDGE_RECORDS, id="edge-cases"),
        pytest.param(fuzz_records(12, 50), id="fuzz-12"),
    ],
)
def test_conjunct_split_byte_identical_across_executors(tmp_path, records):
    """A mixed raw/derived ``&`` predicate (split by the optimizer so the
    raw conjunct filters below the Project) must keep the exact row set of
    the unsplit plan on every executor."""
    from repro.core.expr import abstract_expr, col

    d = write_shards(tmp_path, records)
    ds = (
        Dataset.from_json_dirs([d], FIELDS)
        .with_column("abstract", abstract_expr())
        .where(
            (col("abstract").word_count() >= 1)
            & col("title").not_empty()
            & ~col("title").contains("x")
        )
    )
    opt = ds.optimized_plan()
    filters = [n for n in opt if isinstance(n, P.Filter)]
    assert len(filters) == 2, "expected the conjunction to split at the Project"
    _check_ds_three_executors(d, ds)


@pytest.mark.parametrize(
    "records",
    [
        pytest.param(EDGE_RECORDS * 3, id="edge-dups"),
        pytest.param(fuzz_records(13, 60) * 2, id="fuzz-dups"),
    ],
)
def test_two_pass_fit_vocab_matches_whole_frame(tmp_path, records):
    """fit_vocab on a partial-subset dedup plan must run the streaming
    two-pass canonical-survivor protocol (no whole-frame fallback) and
    produce the byte-identical vocabulary on thread and process
    executors."""

    def pipe():
        return (
            Dataset.from_json_dirs([d], FIELDS)
            .dropna(FIELDS)
            .drop_duplicates(["title"])  # partial subset
            .apply(*case_study_stages())
        )

    d = write_shards(tmp_path, records, n_files=4)
    whole_ds = pipe()
    whole_ds.collect()  # materialize → fit_vocab counts the memoized frame
    vocab_whole = whole_ds.fit_vocab(vocab_size=64)

    for executor in ("thread", "process"):
        stats: dict = {}
        vocab = pipe().fit_vocab(
            vocab_size=64, workers=2, executor=executor, stats=stats
        )
        assert stats["executor"] == executor, stats
        assert stats["two_pass"] is True
        assert vocab.itos == vocab_whole.itos


def test_full_dedup_streams_through_processes_when_asked(tmp_path):
    """A full-subset drop_duplicates needs cross-shard state in one pass,
    which only the thread executor holds. A run that explicitly asks for
    worker processes takes the two-pass protocol instead and really runs
    on them, with the same vocabulary and batch stream as threads."""
    d = write_shards(tmp_path, EDGE_RECORDS * 3, n_files=4)

    def base():
        return (
            Dataset.from_json_dirs([d], FIELDS)
            .dropna(FIELDS)
            .drop_duplicates()
            .apply(*case_study_stages())
        )

    runs = {}
    for executor in ("thread", "process"):
        fit_stats: dict = {}
        tok = base().fit_vocab(
            vocab_size=64, workers=2, executor=executor, stats=fit_stats
        )
        assert fit_stats["executor"] == executor
        assert fit_stats["two_pass"] is (executor == "process")
        stream_stats: dict = {}
        rows = batch_rows(
            base()
            .tokenize(tok, seq2seq_specs(max_abstract_len=16, max_title_len=8))
            .batch(4, shuffle=False, drop_remainder=False)
            .prefetch(2)
            .iter_batches(workers=2, executor=executor, stats=stream_stats)
        )
        assert stream_stats["executor"] == executor
        runs[executor] = (tok.itos, rows)
    assert runs["process"] == runs["thread"]


def test_dedup_plan_thread_matches_whole_frame(tmp_path):
    records = EDGE_RECORDS + EDGE_RECORDS  # every row duplicated across shards
    d = write_shards(tmp_path, records)
    ds = (
        Dataset.from_json_dirs([d], FIELDS)
        .dropna(FIELDS)
        .drop_duplicates(FIELDS)
        .apply(*case_study_stages())
    )
    frame_nodes, _ = P.split_plan(ds.plan)
    frame, _ = P.execute_frame_plan(frame_nodes, final_schema=ds.schema)
    want = record_multiset(frame.to_records())

    program = optimized_program(ds)
    assert program.has_dedup
    got = record_multiset(
        executor_records(
            EX.ThreadShardExecutor(ing.list_shards([d]), program, workers=3)
        )
    )
    assert got == want


# ---------------------------------------------------------------------------
# full streaming pipeline (tokenize + batch) across executors
# ---------------------------------------------------------------------------


def batch_rows(batches):
    rows = []
    for b in batches:
        keys = sorted(b)
        for i in range(len(b[keys[0]])):
            rows.append(tuple(bytes(b[k][i].tobytes()) for k in keys))
    return sorted(rows)


def test_streaming_batches_match_across_executors(tmp_path):
    d = write_shards(tmp_path, EDGE_RECORDS * 8, n_files=4)
    base = chain(d)
    tok = WordTokenizer.fit(
        [r["abstract"] or "" for r in base.collect().to_records()]
    )

    def pipe():
        return (
            chain(d)
            .tokenize(tok, seq2seq_specs(max_abstract_len=16, max_title_len=8))
            .batch(4, shuffle=False, drop_remainder=False)
            .prefetch(2)
        )

    whole = batch_rows(pipe().iter_batches(workers=1, executor="thread"))
    stats_t: dict = {}
    threaded = batch_rows(
        pipe().iter_batches(workers=2, executor="thread", stats=stats_t)
    )
    stats_p: dict = {}
    processed = batch_rows(
        pipe().iter_batches(workers=2, executor="process", stats=stats_p)
    )
    assert threaded == whole
    assert processed == whole
    assert stats_t["executor"] == "thread"
    assert stats_p["executor"] == "process"


# ---------------------------------------------------------------------------
# byte-kernel backends: fused / pallas(interpret) vs the loops oracle
# ---------------------------------------------------------------------------

# Adversarial span nesting: interleaved html/paren spans, stray closers,
# unclosed openers — the cases where a fused single-pass scan could diverge
# from the iterated row-wise semantics.
SPAN_RECORDS = [
    {"title": "<a(b>c)d mixed", "abstract": "(a(b<c)d>e stray ) closer"},
    {"title": "unclosed <span swallows to row end", "abstract": "(so does paren"},
    {"title": ">> leading closers ((", "abstract": "nested ((deep (er))) out"},
    {"title": "<<< (((", "abstract": ")))) >>>>"},
]

BACKEND_CORPUS = EDGE_RECORDS + SPAN_RECORDS + fuzz_records(21, 40)


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_backend_three_executors_byte_identical(tmp_path, monkeypatch, backend):
    """The fused and pallas backends must reproduce the loops whole-frame
    records byte for byte — and the row-wise Stage oracle independently —
    on the whole-frame, thread, process, and remote executors, over
    non-ASCII, NUL-byte, and adversarial span-nesting rows."""
    if backend == "pallas":
        pytest.importorskip("jax")
        # No TPU in CI: force the kernel through the Pallas interpreter so
        # the kernel path itself is exercised, not the host fallback.
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    d = write_shards(tmp_path, BACKEND_CORPUS, n_files=4)
    ds = chain(d)
    frame_nodes, _ = P.split_plan(ds.plan)
    oracle, _ = P.execute_frame_plan(
        frame_nodes, final_schema=ds.schema, backend="loops"
    )
    want = record_multiset(oracle.to_records())
    # independent row-wise oracle (eager Stage path, no fused lowering)
    assert record_multiset(_stage_oracle(d).to_records()) == want

    got_frame, _ = P.execute_frame_plan(
        frame_nodes, final_schema=ds.schema, backend=backend
    )
    assert record_multiset(got_frame.to_records()) == want

    program = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, ds.schema), optimize=True, backend=backend
    )
    assert program.backend == backend
    shards = ing.list_shards([d])
    for make in (
        lambda: EX.ThreadShardExecutor(shards, program, workers=2),
        lambda: EX.ProcessShardExecutor(shards, program, workers=2),
    ):
        assert record_multiset(executor_records(make())) == want

    from repro.distributed.coordinator import RemoteShardExecutor

    remote = RemoteShardExecutor(
        shards, program, workers=2,
        remote={"lease_s": 5.0, "heartbeat_timeout": 3.0,
                "heartbeat_interval_s": 0.1},
    )
    assert record_multiset(executor_records(remote)) == want


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_backend_streaming_batches_match_loops(tmp_path, monkeypatch, backend):
    """End-to-end streamed token batches under a non-default backend must
    equal the loops stream on both in-host executors."""
    if backend == "pallas":
        pytest.importorskip("jax")
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    d = write_shards(tmp_path, BACKEND_CORPUS, n_files=4)
    tok = WordTokenizer.fit(
        [r["abstract"] or "" for r in chain(d).collect().to_records()]
    )

    def pipe(b=None):
        ds = chain(d)
        if b is not None:
            ds = ds.backend(b)
        return (
            ds.tokenize(tok, seq2seq_specs(max_abstract_len=16, max_title_len=8))
            .batch(4, shuffle=False, drop_remainder=False)
            .prefetch(2)
        )

    want = batch_rows(pipe().iter_batches(workers=1, executor="thread"))
    for executor in ("thread", "process"):
        got = batch_rows(
            pipe(backend).iter_batches(workers=2, executor=executor)
        )
        assert got == want, f"{backend}/{executor} diverged from loops"


def test_backend_resolution_and_validation(tmp_path, monkeypatch):
    """Explicit backend > REPRO_BYTES_BACKEND env > loops; unknown names
    are rejected at every entry point; the resolved backend is baked into
    the compiled program (it must travel to pickled workers, not re-read
    the worker's env)."""
    from repro.core import bytesops as B

    d = write_shards(tmp_path, EDGE_RECORDS)
    monkeypatch.delenv("REPRO_BYTES_BACKEND", raising=False)
    assert optimized_program(chain(d)).backend == "loops"
    monkeypatch.setenv("REPRO_BYTES_BACKEND", "fused")
    assert optimized_program(chain(d)).backend == "fused"
    frame_nodes, _ = P.split_plan(chain(d).plan)
    explicit = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, chain(d).schema), backend="pallas"
    )
    assert explicit.backend == "pallas"  # explicit beats env

    assert B.resolve_backend(None) == "fused"  # env
    monkeypatch.delenv("REPRO_BYTES_BACKEND")
    assert B.resolve_backend(None) == "loops"
    with pytest.raises(ValueError, match="bogus"):
        B.resolve_backend("bogus")
    with pytest.raises(ValueError, match="bogus"):
        chain(d).backend("bogus")
    # the verb is a lazy option: it renders in explain() and does not
    # perturb the logical plan nodes
    ds = chain(d).backend("fused")
    assert ds.plan == chain(d).plan
    assert "bytes backend: fused" in ds.explain()


@pytest.mark.parametrize("backend", ["loops", "fused", "pallas"])
def test_worker_processes_get_a_host_backend(tmp_path, monkeypatch, backend):
    """The parent decides when it compiles a program what its worker
    processes run: the pallas backend's device offload stays with the
    parent, and the process and remote executors ship the byte-identical
    host form, so no worker ever imports jax or reaches for the chip."""
    from repro.distributed.coordinator import RemoteShardExecutor

    d = write_shards(tmp_path, EDGE_RECORDS)
    ds = chain(d)
    frame_nodes, _ = P.split_plan(ds.plan)
    program = EX.compile_shard_program(
        P.optimize_plan(frame_nodes, ds.schema), optimize=True, backend=backend
    )
    host = "fused" if backend == "pallas" else backend
    assert program.backend == backend
    assert program.worker_backend == host
    assert program.for_workers().backend == host
    shards = ing.list_shards([d])
    shipped = []
    for_workers = EX.ShardProgram.for_workers
    monkeypatch.setattr(
        EX.ShardProgram, "for_workers",
        lambda self: shipped.append(for_workers(self)) or shipped[-1],
    )
    proc = EX.ProcessShardExecutor(shards, program, workers=2)
    proc.stop()
    assert [p.backend for p in shipped] == [host]
    remote = RemoteShardExecutor(shards, program, workers=1, remote={"spawn": False})
    try:
        assert remote._coord.program.backend == host
    finally:
        remote.stop()


# ---------------------------------------------------------------------------
# executor selection and fallback
# ---------------------------------------------------------------------------


def test_make_executor_selection_and_fallback(tmp_path, monkeypatch):
    d = write_shards(tmp_path, EDGE_RECORDS)
    shards = ing.list_shards([d])
    plain = optimized_program(chain(d))
    dedup_ds = Dataset.from_json_dirs([d], FIELDS).drop_duplicates(FIELDS)
    dedup = optimized_program(dedup_ds)

    monkeypatch.delenv("REPRO_EXECUTOR", raising=False)
    # The default selection depends on the *effective* core count (one
    # effective worker → threads); pin it so the assertions below test the
    # selection rules, not the machine the suite happens to run on.
    monkeypatch.setattr(EX.os, "cpu_count", lambda: 4)
    picks = {
        "default-1": EX.make_executor(shards, plain, workers=1),
        "default-4": EX.make_executor(shards, plain, workers=4),
        "forced-thread": EX.make_executor(shards, plain, workers=4, executor="thread"),
        "dedup-falls-back": EX.make_executor(shards, dedup, workers=4),
    }
    try:
        assert picks["default-1"].name == "thread"
        assert picks["default-4"].name == "process"
        assert picks["forced-thread"].name == "thread"
        assert picks["dedup-falls-back"].name == "thread"
    finally:
        for ex in picks.values():
            ex.stop()

    monkeypatch.setenv("REPRO_EXECUTOR", "thread")
    ex = EX.make_executor(shards, plain, workers=4)
    try:
        assert ex.name == "thread"
    finally:
        ex.stop()

    monkeypatch.setenv("REPRO_EXECUTOR", "bogus")
    with pytest.raises(ValueError):
        EX.make_executor(shards, plain, workers=2)

    with pytest.raises(EX.UnsupportedPlanError):
        EX.ProcessShardExecutor(shards, dedup, workers=2)
