"""The program-span view of a traced run: the second attribution of idle
gaps on a synthetic event set, the readers of the program's feed ledgers,
and ``bench/program_trace.py`` end to end on the CPU at a small size."""

import json

import pytest

from bench import program_trace as P
from bench import run
from bench import trace_reduce as T
from bench.drivers import serve, train
from bench.tests import smoke
from bench.tests.test_run_main import stub  # noqa: F401  (fixture)

MS = 1_000_000  # ns


def synthetic():
    """A 100 ms window: the device runs 0-10, 60-70 and 90-95 ms. The harness
    waits in ``feed.next`` over 10-60 ms, where the program's ``feed.wait``
    (main thread) and ``plan.epoch_start`` (fill thread) both cover the
    gap; over 70-90 ms the harness is in ``train.step`` and the program in
    none of its spans but a short ``train.sync`` at 88-92 ms."""
    events = {
        "host": [
            (T.WINDOW_SPAN, 0, 100 * MS),
            ("feed.next", 10 * MS, 60 * MS),
            ("train.step", 70 * MS, 90 * MS),
        ],
        "devices": {"/device:TPU:0": {
            "ops": [("a", 0, 10 * MS), ("b", 60 * MS, 70 * MS), ("c", 90 * MS, 95 * MS)],
            "modules": [("jit_step(1)", 0, 95 * MS)],
        }},
    }
    program = [
        ("plan.epoch_start", 5 * MS, 61 * MS),
        ("feed.wait", 9 * MS, 60 * MS),
        ("train.sync", 88 * MS, 92 * MS),
    ]
    return events, program


def test_gaps_are_named_for_the_innermost_program_span():
    events, program = synthetic()
    got = P.program_attribution(events, program)
    gaps = {round(g[1] * 1e3): g for g in got["program_idle_gaps"]}
    assert gaps[50] == ["feed.wait", pytest.approx(0.05), ["plan.epoch_start"]]
    assert gaps[20] == ["train.sync", pytest.approx(0.02), []]  # overlaps 2 of its 20 ms
    assert gaps[5] == ["host", pytest.approx(0.005), []]  # after every span
    assert sum(got["idle_by_program_span"].values()) == pytest.approx(0.075)
    assert got["idle_in_span"] == {"feed.wait": pytest.approx(0.05), "plan.epoch_start": pytest.approx(0.05),
                                   "train.sync": pytest.approx(0.002)}
    assert got["span_share"]["feed.wait"] == pytest.approx(0.51)


def test_the_harness_attribution_is_left_as_it_was():
    events, program = synthetic()
    before = T.reduce_events(events)
    P.program_attribution(events, program)
    assert T.reduce_events(events) == before
    assert [g[0] for g in before["idle_gaps"][:2]] == ["feed.next", "train.step"]


def test_a_gap_no_span_covers_keeps_the_harness_name():
    events, _ = synthetic()
    got = P.program_attribution(events, [])
    assert [g[0] for g in got["program_idle_gaps"][:2]] == ["feed.next", "train.step"]
    assert got["span_share"] == {}


def read(name, record, driver):
    return run.read_metric(name, {"record": record, "traffic": {"driver": driver}})


@pytest.mark.parametrize("name", ["train.epoch_start_share", "train.transfer_share"])
def test_feed_readers(name):
    serve_record = {"window_s": 10.0, "preprocess_s": 0.1}
    assert read(name, serve_record, "serve") is None
    older = {"window_s": 10.0, "feed_stats": {"executor": "process"}}  # a program without spans
    assert read(name, older, "train") is None
    stats = {"epochs": 4, "epoch_start_s": 2.0, "transfer_s": 0.3}
    value = read(name, {"window_s": 10.0, "feed_stats": stats}, "train")
    assert value == pytest.approx({"train.epoch_start_share": 15.0, "train.transfer_share": 3.0}[name])


def program_lines(capsys, monkeypatch, name, cfg, traffic):
    cell = run.load_cell(name)
    cell.update(config=cfg, traffic=traffic)
    monkeypatch.setattr(run, "load_cell", lambda _name: cell)
    trace_slice, train_session, serve_session = P._extended(run, serve, train)
    monkeypatch.setattr(run, "TraceSlice", trace_slice)
    monkeypatch.setattr(train, "TrainSession", train_session)
    monkeypatch.setattr(serve, "ServeSession", serve_session)
    assert run.main(["--workload", name, "--seed", "7", "--seconds", "1.5", "--trace", "1"]) == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    lines = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in out.err.splitlines() if ln.startswith(("program: ", "trace: "))}
    return result, lines


def test_train_cell_program_view(stub, capsys, monkeypatch):  # noqa: F811
    result, lines = program_lines(capsys, monkeypatch, "s2s.train.stream", smoke.seq2seq_cfg(),
                                  smoke.train_traffic("train_stream", trace_seconds=0.5))
    prog = lines["program"]
    assert prog["steps"] == result["attempted"] > 0
    assert 0 < prog["train.sync_share"] < 100 and prog["train.transfer_share"] > 0
    assert {"train.epoch_start_share", "train.transfer_share"} <= set(result["metrics"])
    assert set(lines["trace"]) == {"idle_by_span", "idle_by_program_span", "program_idle_gaps",
                                   "idle_in_span", "span_share"}
    assert lines["trace"]["span_share"]["train.sync"] > 0


def test_serve_cell_program_view(stub, capsys, monkeypatch):  # noqa: F811
    result, lines = program_lines(capsys, monkeypatch, "stablelm3b.serve.titles", smoke.lm_cfg(),
                                  smoke.serve_traffic(trace_seconds=1.0))
    prog = lines["program"]
    assert prog["served"] == result["attempted"] == 3
    assert 0 < prog["serve.ttft_p50_ms"] < prog["serve_p50_ms"]
    assert prog["serve.token_gap_p95_ms"] >= prog["serve.token_gap_p50_ms"] > 0
    assert prog["compiles"] == 0  # set-up's warm-up made every program the window runs
    assert lines["trace"]["span_share"]["serve.decode_step"] > 0
