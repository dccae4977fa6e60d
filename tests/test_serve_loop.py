"""Serving path: admission backpressure, ring-cache accounting, end-to-end
text-in/tokens-out over a row program, and the R005 hot-path contract.

The decode-level tests run against a deterministic echo model (argmax of a
one-hot is the input token) so slot/refill/admission mechanics are checked
without paying for a real LM; one smoke test drives the full stack with a
real smoke-config LM.
"""

import dataclasses
import gc
import json
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.contracts import ALL_RULES, lint_contracts
from repro.configs import get_smoke
from repro.core.dataset import Dataset
from repro.core.expr import abstract_expr, col
from repro.data.batching import TokenSpec
from repro.models.lm import LM
from repro.runtime.serve_loop import (
    AdmissionQueue,
    RingCache,
    ServeStats,
    TextRequest,
    serve_text,
)

# -- fixtures ---------------------------------------------------------------

CORPUS = [
    {"abstract": "deep learning methods for scholarly metadata extraction"},
    {"abstract": "spark pipelines accelerate large corpus preprocessing work"},
    {"abstract": "attention models summarize scientific abstracts neatly"},
    {"abstract": "tokenization vocabulary coverage affects downstream quality"},
    {"abstract": "distributed executors shard the cleaning workload evenly"},
    {"abstract": "ring buffers bound the decode cache memory footprint"},
]


@pytest.fixture(scope="module")
def row_program(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve_corpus")
    with open(d / "shard-0.jsonl", "w", encoding="utf-8") as f:
        for r in CORPUS:
            f.write(json.dumps(r) + "\n")
    ds = (
        Dataset.from_json_dirs([d], fields=("abstract",))
        .where(col("abstract").not_empty())
        .transform(abstract=abstract_expr())
    )
    tok = ds.fit_vocab(vocab_size=200)
    rp = (
        ds.tokenize(tok, [TokenSpec("abstract", 16)])
        .batched(2)
        .prefetch(2)
        .row_program()
    )
    return rp, tok


class _EchoModel:
    """argmax(one_hot(t)) == t: prefill emits the prompt's last token and
    decode repeats it, making every serve run deterministic and instant."""

    def init_decode_state(self, b, max_seq, cache_dtype=jnp.float32):
        return jnp.zeros((b,), jnp.int32)

    def decode_step(self, params, tokens, state, pos):
        return jax.nn.one_hot(tokens, 512, dtype=jnp.float32), state


# -- unit: admission queue --------------------------------------------------


def test_admission_queue_sheds_on_arrival():
    q = AdmissionQueue(maxsize=2)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")  # full: shed, not queued
    assert (q.admitted, q.rejected, len(q)) == (2, 1, 2)
    assert q.pop() == "a"  # FIFO
    assert q.offer("d")  # slot freed
    assert q.pop() == "b" and q.pop() == "d" and q.pop() is None
    with pytest.raises(ValueError):
        AdmissionQueue(maxsize=0)


# -- unit: ring cache -------------------------------------------------------


def test_ring_cache_fifo_eviction_and_accounting():
    c = RingCache(slots=2)
    assert c.get("k1") is None  # miss
    c.put("k1", [1, 2])
    c.put("k2", [3])
    assert c.get("k1") == [1, 2]  # hit
    c.put("k3", [4])  # evicts k1 (oldest inserted)
    assert len(c) == 2
    assert c.get("k1") is None
    assert c.get("k3") == [4]
    assert (c.hits, c.misses, c.evictions) == (2, 2, 1)
    # updating an existing key neither grows nor evicts
    c.put("k2", [5, 6])
    assert (len(c), c.evictions) == (2, 1)
    assert c.get("k2") == [5, 6]
    # returned lists are copies: mutating one can't poison the cache
    c.get("k2").append(99)
    assert c.get("k2") == [5, 6]
    with pytest.raises(ValueError):
        RingCache(slots=0)


# -- serve_text over the echo model ----------------------------------------


def test_serve_text_backpressure_rejects_overflow(row_program):
    rp, _ = row_program
    reqs = [TextRequest(i, CORPUS[i]["abstract"], max_new=3) for i in range(6)]
    stats = ServeStats()
    results = serve_text(
        _EchoModel(), None, rp, reqs, slots=2, max_seq=32, queue_size=2, stats=stats
    )
    assert stats.admitted == 2
    assert stats.rejected == 4
    assert stats.served == 2
    assert sorted(results) == [0, 1]  # shed requests get no entry at all
    assert all(len(v) == 3 for v in results.values())
    assert sorted(stats.latency_s) == [0, 1]
    assert stats.preprocess_s > 0.0


def test_serve_text_slots_refill_until_drained(row_program):
    rp, _ = row_program
    reqs = [TextRequest(i, CORPUS[i % len(CORPUS)]["abstract"]) for i in range(6)]
    results = serve_text(_EchoModel(), None, rp, reqs, slots=2, max_seq=32)
    assert sorted(results) == list(range(6))  # 2 slots still serve all 6


def test_serve_text_filtered_request_answers_empty(row_program):
    rp, _ = row_program
    reqs = [
        TextRequest(0, CORPUS[0]["abstract"], max_new=2),
        TextRequest(1, ""),  # dropped by where(not_empty)
        TextRequest(2, "a i x !"),  # cleans to an empty prompt
    ]
    stats = ServeStats()
    results = serve_text(_EchoModel(), None, rp, reqs, slots=2, max_seq=32, stats=stats)
    assert results[1] == [] and results[2] == []
    assert stats.filtered == 2
    assert stats.served == 1 and len(results[0]) == 2


def test_serve_text_ring_cache_round_trip(row_program):
    rp, _ = row_program
    cache = RingCache(slots=8)
    stats = ServeStats()
    first = serve_text(
        _EchoModel(),
        None,
        rp,
        [TextRequest(0, CORPUS[0]["abstract"]), TextRequest(1, CORPUS[1]["abstract"])],
        slots=2,
        max_seq=32,
        cache=cache,
        stats=stats,
    )
    assert (stats.cache_hits, stats.cache_misses) == (0, 2)
    # repeat one prompt: completes from the cache, byte-identical answer
    again = serve_text(
        _EchoModel(),
        None,
        rp,
        [TextRequest(7, CORPUS[0]["abstract"])],
        slots=2,
        max_seq=32,
        cache=cache,
        stats=stats,
    )
    assert again[7] == first[0]
    assert (stats.cache_hits, stats.cache_misses) == (1, 2)
    assert cache.hits == 1 and cache.misses == 2
    # cache keys bind the program fingerprint: a different program misses
    rp2 = dataclasses.replace(rp, fingerprint="other")
    miss = serve_text(
        _EchoModel(),
        None,
        rp2,
        [TextRequest(9, CORPUS[0]["abstract"])],
        slots=2,
        max_seq=32,
        cache=cache,
        stats=stats,
    )
    assert stats.cache_misses == 3 and miss[9] == first[0]


def test_serve_text_records_first_tokens_gaps_and_spans(row_program):
    """The ledger's per-token hook: every served uid gets a first-token
    time below its latency and ``len(out) - 1`` token gaps; prefill and
    decode are measured spans, not a residual; a fresh model instance per
    call is what makes each call compile."""
    rp, _ = row_program
    stats = ServeStats()
    for uid in range(3):  # one request per call: each call's gaps are its own
        before = len(stats.token_gaps_s)
        results = serve_text(
            _EchoModel(), None, rp, [TextRequest(uid, CORPUS[uid]["abstract"], max_new=2 + uid)],
            slots=2, max_seq=32, stats=stats,
        )
        assert len(results[uid]) == 2 + uid
        assert len(stats.token_gaps_s) - before == len(results[uid]) - 1
    assert sorted(stats.first_token_s) == [0, 1, 2]
    assert all(0 < stats.first_token_s[u] <= stats.latency_s[u] for u in range(3))
    assert all(g > 0 for g in stats.token_gaps_s)
    assert stats.prefill_s > 0.0 and stats.decode_s > 0.0
    assert stats.compiles >= 3 and stats.compile_s > 0.0
    assert stats.prefill_s + stats.decode_s < sum(stats.latency_s.values())


def test_serve_requests_decodes_as_before_without_a_ledger():
    from repro.runtime.serve_loop import Request, serve_requests

    reqs = [Request(i, jnp.asarray([5, 6, 7 + i], jnp.int32), max_new=3) for i in range(3)]
    out = serve_requests(_EchoModel(), None, reqs, slots=2, max_seq=16)
    assert out == {0: [7, 7, 7], 1: [8, 8, 8], 2: [9, 9, 9]}


# -- end-to-end with a real smoke LM ---------------------------------------


def test_serve_text_end_to_end_smoke(row_program):
    rp, tok = row_program
    cfg = dataclasses.replace(get_smoke("recurrentgemma_9b"), vocab_size=len(tok.itos))
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    reqs = [TextRequest(i, CORPUS[i]["abstract"], max_new=4) for i in range(3)]
    stats = ServeStats()
    results = serve_text(
        model, params, rp, reqs, slots=2, max_seq=32, stats=stats
    )
    assert sorted(results) == [0, 1, 2]
    for out in results.values():
        assert 1 <= len(out) <= 4
        assert all(0 <= t < cfg.vocab_size for t in out)
    assert stats.served == 3
    assert stats.decode_s > 0.0
    # greedy decode is deterministic: a re-serve reproduces every token
    rerun = serve_text(model, params, rp, reqs, slots=2, max_seq=32)
    assert rerun == results


def _smoke_lm(name, tok):
    cfg = dataclasses.replace(get_smoke(name), vocab_size=len(tok.itos))
    model = LM(cfg, remat=False, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def test_serve_text_reuses_the_models_programs(row_program):
    """A model's step and prefill are made on its first serve and found on
    every later one: a second call compiles nothing and answers the same.
    The programs belong to their instance: two models of different configs
    served in turn each answer as when served alone."""
    rp, tok = row_program
    reqs = [TextRequest(i, CORPUS[i]["abstract"], max_new=4) for i in range(3)]

    def serve(model, params, stats=None):
        return serve_text(model, params, rp, reqs, slots=2, max_seq=32, stats=stats)

    model, params = _smoke_lm("stablelm_3b", tok)
    first, second = ServeStats(), ServeStats()
    out = serve(model, params, first)
    assert (first.program_builds, first.program_reuses) == (1, 0)
    assert first.compiles > 0
    assert serve(model, params, second) == out
    assert (second.program_builds, second.program_reuses) == (0, 1)
    # nothing is compiled or loaded; only eager ops' cached primitives re-trace
    assert second.compiles == 0 and second.compile_s < first.compile_s / 10

    names = ("stablelm_3b", "recurrentgemma_9b")
    alone = {n: serve(*_smoke_lm(n, tok)) for n in names}
    models = {n: _smoke_lm(n, tok) for n in names}
    for _ in range(2):
        for n in names:
            assert serve(*models[n]) == alone[n], n


def test_served_model_is_not_kept_alive(row_program):
    """The programs kept for a model do not keep it alive once its caller
    drops it."""
    rp, tok = row_program
    model, params = _smoke_lm("stablelm_3b", tok)
    serve_text(model, params, rp, [TextRequest(0, CORPUS[0]["abstract"], max_new=3)],
               slots=2, max_seq=32)
    dead = weakref.ref(model)
    del model
    gc.collect()
    assert dead() is None


# -- R005: the serve hot path stays free of shard machinery -----------------

_PKG_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"


def test_serve_hot_path_contract_is_clean():
    assert "R005" in ALL_RULES
    diags = lint_contracts(_PKG_ROOT, rules=["R005"])
    assert diags == [], [d.message for d in diags]


def test_r005_flags_shard_machinery_imports(tmp_path):
    pkg = tmp_path / "repro"
    (pkg / "runtime").mkdir(parents=True)
    (pkg / "core").mkdir()
    (pkg / "runtime" / "serve_loop.py").write_text(
        "import multiprocessing\nfrom repro.core import executor\n"
    )
    (pkg / "runtime" / "row_program.py").write_text("x = 1\n")
    (pkg / "core" / "executor.py").write_text("POOL = None\n")
    diags = lint_contracts(pkg, rules=["R005"])
    codes = [d.code for d in diags]
    assert codes and set(codes) == {"R005"}
    msgs = " ".join(d.message for d in diags)
    assert "multiprocessing" in msgs
    assert "core.executor" in msgs
