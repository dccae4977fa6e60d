"""A configuration brings its own reference, and the harness finds drivers by name.

The serve driver takes ``PROGRAM_KEYS``, ``init_params``,
``request_flops`` and ``served_gaps`` from the module that the
configuration's ``"reference"`` names; ``bench/run.py`` runs the session
class of ``bench/drivers/<driver>.py``. On the CPU at a small size.
"""

import contextlib
import dataclasses
import json
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, run
from bench.drivers import serve
from bench.reference import lm as ref_lm
from bench.tests import smoke
from bench.tests.test_run_main import stub  # noqa: F401  (fixture)


def nothing(_name):
    return contextlib.nullcontext()


def session(tmp_path, cfg, seed=4242):
    return serve.ServeSession(cfg, smoke.serve_traffic(), seed, 2.0, tmp_path, annotate=nothing)


def test_lm_through_the_seam_reads_as_direct_calls(tmp_path, monkeypatch):
    monkeypatch.setattr(serve.ServeSession, "program_config", lambda self: smoke.lm_arch())
    calls = []
    gaps = ref_lm.served_gaps
    monkeypatch.setattr(ref_lm, "served_gaps", lambda *a, **k: calls.append((a, k)) or gaps(*a, **k))
    cfg = smoke.lm_cfg()
    s = session(tmp_path, cfg)
    assert s.ref is ref_lm
    s.setup()
    assert s.model.dtype is jnp.bfloat16 and s.cache_dtype is jnp.bfloat16
    rec = s.window()
    checks = s.check()
    served = [k for k in s.results if s.results[k]]
    assert served and rec["flops"] == sum(
        flops.lm_request_flops(cfg, s._prompt_len(k), len(s.results[k])) for k in served
    )
    (params, prompts, answers, got_cfg), kwargs = calls[0]
    assert params is s.params and got_cfg is cfg and kwargs == {}
    direct = gaps(params, prompts, answers, {"rope_theta": cfg["rope_theta"]})
    assert checks["logit_gap"] == float(direct.max())
    assert checks["logit_gap_mean"] == float(direct.mean())
    # the theta is the configuration's: another base reads other gaps
    assert not np.array_equal(gaps(params, prompts, answers, {"rope_theta": 500.0}), direct)


def test_a_reference_module_alone_serves_a_new_configuration(tmp_path, monkeypatch):
    called = set()

    class Keys(dict):
        def items(self):
            called.add("PROGRAM_KEYS")
            return super().items()

    def init_params(key, cfg):
        called.add("init_params")
        return ref_lm.init_params(key, cfg)

    def request_flops(cfg, prompt_len, generated):
        called.add("request_flops")
        return 1.0

    def served_gaps(params, prompts, served, cfg, *, control=False):
        called.add("served_gaps")
        gaps = np.zeros(sum(len(s) for s in served))
        return (gaps, gaps + 1.0) if control else gaps

    stub = types.SimpleNamespace(PROGRAM_KEYS=Keys(ref_lm.PROGRAM_KEYS), init_params=init_params,
                                 request_flops=request_flops, served_gaps=served_gaps)
    monkeypatch.setitem(sys.modules, "bench.reference.stub_model", stub)
    monkeypatch.setattr("repro.configs.get", lambda name: smoke.lm_arch())
    s = session(tmp_path, dict(smoke.lm_cfg(), reference="stub_model"))
    s.setup()
    rec = s.window()
    checks = s.check(control=True)
    assert called == {"PROGRAM_KEYS", "init_params", "request_flops", "served_gaps"}
    assert rec["flops"] == rec["served"] > 0
    assert checks["logit_gap_mean"] == 0.0 and checks["control_logit_gap_mean"] == 1.0


@pytest.mark.parametrize("reference, missing", [
    (None, '"reference"'),
    ("no_such_model", "bench/reference/no_such_model.py"),
    ("../lm", "bench/reference/../lm.py"),
])
def test_a_configuration_must_name_a_reference_there_is(tmp_path, reference, missing):
    cfg = {k: v for k, v in smoke.lm_cfg().items() if k != "reference"}
    if reference is not None:
        cfg["reference"] = reference
    with pytest.raises(KeyError) as e:
        session(tmp_path, cfg)
    assert missing in str(e.value)
    if reference is not None:
        assert "'lm'" in str(e.value)  # the references there are


def test_a_configuration_without_a_reference_fails_at_set_up_naming_its_file(stub, monkeypatch, capsys):  # noqa: F811
    cell = run.load_cell("stablelm3b.serve.titles")
    cell["config"] = {k: v for k, v in cell["config"].items() if k != "reference"}
    monkeypatch.setattr(run, "load_cell", lambda _name: cell)
    assert run.main(["--workload", "stablelm3b.serve.titles", "--seed", "1", "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "bench/configs/stablelm-3b.json" in out.err and '"reference"' in out.err


def test_a_dotted_program_key_that_disagrees_is_refused(tmp_path, monkeypatch):
    from repro.configs import MoEConfig

    moe = MoEConfig(n_experts=8, top_k=2, d_expert=32)
    monkeypatch.setattr("repro.configs.get", lambda name: dataclasses.replace(smoke.lm_arch(), moe=moe))
    keys = dict(ref_lm.PROGRAM_KEYS, num_experts="moe.n_experts")
    monkeypatch.setattr(ref_lm, "PROGRAM_KEYS", keys)
    s = session(tmp_path, dict(smoke.lm_cfg(), num_experts=8))
    assert s.program_config().moe.n_experts == 8
    s = session(tmp_path, dict(smoke.lm_cfg(), num_experts=64))
    with pytest.raises(ValueError, match=r"moe\.n_experts = 8 but the benchmark runs num_experts = 64"):
        s.program_config()
    monkeypatch.setattr("repro.configs.get", lambda name: smoke.lm_arch())  # no moe group at all
    with pytest.raises(ValueError, match=r"moe\.n_experts = '<absent>'"):
        s.program_config()


def test_drivers_are_found_by_name():
    assert run.session_class("serve") is serve.ServeSession
    assert run.session_class("train").__name__ == "TrainSession"
    with pytest.raises(KeyError) as e:
        run.session_class("no_such_driver")
    assert "bench/drivers/no_such_driver.py" in str(e.value)
    assert "['serve', 'train']" in str(e.value)


def test_an_unknown_driver_fails_at_load(tmp_path, monkeypatch, capsys):
    cfg_file = tmp_path / "model.json"
    cfg_file.write_text("{}")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "m", "file": str(cfg_file)}],
        "workloads": [{"name": "m.cell", "config": "m", "traffic": "odd", "chips": 1}],
    }))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text(json.dumps({"driver": "no_such_driver"}))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", tmp_path)
    monkeypatch.setattr(run, "COMPILE_CACHE", tmp_path / "jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)  # main sets it; restored after
    assert run.main(["--workload", "m.cell", "--seed", "1", "--seconds", "1"]) == 1
    err = capsys.readouterr().err
    assert "bench/drivers/no_such_driver.py" in err and "['serve', 'train']" in err
