"""``run.main`` end to end on the CPU at a small size, the chip look stubbed.

The device check is the one step skipped: ``jax.devices`` answers with a
stand-in that reports a TPU v5e, so the rest of a run (set-up, window,
trace, memory reading, check, metric readers, the result line) runs as on
the chip.
"""

import json

import jax
import pytest

from bench import run
from bench.drivers import serve
from bench.tests import smoke


class StandInChip:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 123}


@pytest.fixture
def stub(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "COMPILE_CACHE", tmp_path / "jax_cache")
    monkeypatch.setattr(jax, "devices", lambda *a: [StandInChip()])
    monkeypatch.setattr(serve.ServeSession, "program_config", lambda self: smoke.lm_arch())
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def result_of(capsys, monkeypatch, name, cfg, traffic, trace):
    cell = run.load_cell(name)
    cell.update(config=cfg, traffic=traffic)
    monkeypatch.setattr(run, "load_cell", lambda _name: cell)
    args = ["--workload", name, "--seed", "2147483659", "--seconds", "1.5", "--trace", str(trace)]
    assert run.main(args) == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return result


def test_train_cell_end_to_end(stub, capsys, monkeypatch):
    r = result_of(capsys, monkeypatch, "s2s.train.cached", smoke.seq2seq_cfg(),
                  smoke.train_traffic("train_cached"), 0)
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 123}
    assert set(r["checks"]) == {"token_rows_wrong", "checked_rows_repeated", "loss_gap", "grad_gap", "update_gap"}


def test_serve_cell_traced(stub, capsys, monkeypatch):
    r = result_of(capsys, monkeypatch, "stablelm3b.serve.titles", smoke.lm_cfg(),
                  smoke.serve_traffic(trace_seconds=1.0), 1)
    assert r["correct"] is True and r["attempted"] == 3
    # no TPU plane in a CPU trace: the device readers find nothing and stay out
    assert set(r["metrics"]) == {"serve.p80_ms", "serve.p95_ms", "serve.preprocess_share", "serve.step_mfu"}
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
