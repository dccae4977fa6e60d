"""The DeepSeek-V2-Lite reference (``bench/reference/mla_moe.py``) through the
serve driver's seam, its operation and byte counts, and the
``serve.step_hbm_share`` reader. On the CPU at a small size."""

import contextlib
import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.drivers import serve
from bench.reference import mla_moe
from bench.tests import mla_smoke, smoke

CONFIG = run.BENCH / "configs" / "deepseek-v2-lite.json"


def nothing(_name):
    return contextlib.nullcontext()


def session(tmp_path, cfg, seed=2**31 + 77):
    return serve.ServeSession(cfg, smoke.serve_traffic(), seed, 2.0, tmp_path, annotate=nothing)


def test_the_serve_driver_takes_the_four_names_from_mla_moe(tmp_path, monkeypatch):
    monkeypatch.setattr("repro.configs.get", lambda name: mla_smoke.arch(vocab_size=8192))
    cfg = mla_smoke.cfg(vocab_size=8192)
    s = session(tmp_path, cfg)
    assert s.ref is mla_moe
    s.setup()
    assert s.model.cfg.mla is not None and s.model.cfg.moe.n_held == 2
    assert s.model.dtype is jnp.bfloat16 and s.cache_dtype is jnp.bfloat16
    rec = s.window()
    served = [k for k in s.results if s.results[k]]
    assert served and rec["flops"] == sum(
        mla_moe.request_flops(cfg, s._prompt_len(k), len(s.results[k])) for k in served
    )
    checks = s.check(control=True)
    assert checks["answers_missing"] == checks["answers_wrong"] == checks["token_rows_wrong"] == 0
    assert checks["logit_gap_mean"] < checks["control_logit_gap_mean"]


@pytest.mark.parametrize("key, path, wrong", [
    ("n_routed_experts", "moe.n_held", 64),
    ("router_experts", "moe.n_experts", 16),
    ("kv_lora_rank", "mla.kv_lora_rank", 32),
    ("norm_topk_prob", "moe.norm_topk_prob", True),
])
def test_program_keys_refuse_a_mismatched_program_config(tmp_path, monkeypatch, key, path, wrong):
    monkeypatch.setattr("repro.configs.get", lambda name: mla_smoke.arch())
    assert session(tmp_path, mla_smoke.cfg()).program_config().mla.kv_lora_rank == 16
    s = session(tmp_path, mla_smoke.cfg(**{key: wrong}))
    with pytest.raises(ValueError, match=rf"{path} = .* but the benchmark runs {key} = {wrong!r}"):
        s.program_config()


def test_the_benchmark_configuration_is_the_programs(tmp_path):
    from repro.configs import get

    cfg = json.loads(CONFIG.read_text())
    arch = session(tmp_path, cfg).program_config()  # every PROGRAM_KEYS pair agrees
    assert arch is get("deepseek_v2_lite_ep8") and cfg["program_config"] == "deepseek_v2_lite_ep8"
    y, published = arch.rope_scaling, cfg["rope_scaling"]
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow, y.mscale, y.mscale_all_dim) == (
        published["factor"], published["original_max_position_embeddings"], published["beta_fast"],
        published["beta_slow"], published["mscale"], published["mscale_all_dim"],
    )
    assert arch.embed_scale is False and cfg["reduced"] == ["n_routed_experts"]


def test_request_flops_by_hand():
    cfg = mla_smoke.cfg()  # d 64, 4 heads, rank 16, nope 8, rope 8, v 12; 3 layers, 1 dense
    attention = 64 * 4 * 16 + 64 * (16 + 8) + 16 * 4 * (8 + 12) + 4 * 12 * 64  # 9984
    moe = 64 * 8 + 3 * 64 * 32 * 2 + (2 * 2 / 8) * 3 * 64 * 32  # router, shared, 0.5 held
    per_token = 3 * attention + 3 * 64 * 96 + 2 * moe
    tokens = 5 + 3 - 1
    attended = 2.0 * 3 * 4 * (16 + 12) * sum(range(1, tokens + 1))
    assert mla_moe.request_flops(cfg, 5, 3) == 2 * per_token * tokens + attended + 2 * 64 * 64 * 3


def test_step_bytes_by_hand():
    cfg = mla_smoke.cfg()
    attention = 9984
    weights = 3 * attention + 3 * 64 * 96 + 2 * (3 * 64 * 32 * 2 + 0.5 * 3 * 64 * 32) + 64 * 64
    assert mla_moe.step_bytes(cfg) == 2 * weights + 2 * 3 * 160 * (16 + 8)
    published = json.loads(CONFIG.read_text())
    # by the widths: about 1.27e9 parameters read in bfloat16, plus 27 x 256 x 576 cache values
    assert 2.5e9 < mla_moe.step_bytes(published) - 2 * 27 * 256 * 576 < 2.56e9


def _read(name: str, run_dict):
    return run.read_metric(name, run_dict)


def test_step_hbm_share_reads_a_synthetic_trace(monkeypatch):
    import jax

    class Tpu:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Tpu()])
    cfg = json.loads(CONFIG.read_text())
    per_call = 4e-3
    trace = {"modules": {"jit_serve_step": (10 * per_call, 10), "jit_decode_step": (1.0, 2)}}
    r = {"trace": trace, "traffic": {"driver": "serve"}, "cfg": cfg}
    want = 100.0 * mla_moe.step_bytes(cfg) / (per_call * 819e9)
    assert _read("serve.step_hbm_share", r) == pytest.approx(want, rel=1e-12)
    assert 50 < want < 100
    lm_cfg = json.loads((run.BENCH / "configs" / "stablelm-3b.json").read_text())
    assert _read("serve.step_hbm_share", dict(r, cfg=lm_cfg)) is None  # lm has no step_bytes
    assert _read("serve.step_hbm_share", dict(r, trace={"modules": {}})) is None
