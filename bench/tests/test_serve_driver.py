"""The serving driver on the CPU at a small size."""

import contextlib

import jax.numpy as jnp

from bench.drivers import serve
from bench.tests import smoke


def nothing(_name):
    return contextlib.nullcontext()


def test_schedule_fixes_the_work_and_the_seed_orders_it():
    tr = smoke.serve_traffic(rate_per_s=5.0)
    a, b = serve.schedule(tr, 20, 1), serve.schedule(tr, 20, 2**40 + 1)
    assert len(a) == len(b) == 100
    assert sorted(m for _, _, m in a) == sorted(m for _, _, m in b)
    assert [m for _, _, m in a] != [m for _, _, m in b]
    assert [d for d, _, _ in a] == [d for d, _, _ in b]  # the same arrivals for every seed
    assert abs(a[-1][0] - 20) < 2
    assert len({t for _, t, _ in a}) == 100


def test_schedule_arrivals_keep_the_rate():
    s = serve.schedule(smoke.serve_traffic(rate_per_s=5.0), 20, 3)
    due = [d for d, _, _ in s]
    assert due[0] == 0 and due == sorted(due) and len(set(due)) == 100
    assert abs(due[-1] / 99 - 1 / 5.0) < 0.05


def test_waves_answer_as_direct_serve_text(tmp_path, monkeypatch):
    from repro.runtime.serve_loop import RingCache, TextRequest, serve_text

    monkeypatch.setattr(serve.ServeSession, "program_config", lambda self: smoke.lm_arch())
    s = serve.ServeSession(smoke.lm_cfg(), smoke.serve_traffic(), 12345, 3.0, tmp_path,
                           annotate=nothing)
    s.setup()
    rec = s.window()
    assert rec["attempted"] == 6 and rec["served"] == 6 and rec["shed"] == 0
    sc = s.serve_cfg
    direct = serve_text(
        s.model, s.params, s.row_program.program,
        [TextRequest(k, text, m) for k, (_, text, m) in enumerate(s.requests)],
        slots=sc["slots"], max_seq=sc["max_seq"], cache=RingCache(), cache_dtype=jnp.bfloat16,
    )
    assert s.results == direct
    checks = s.check()
    assert checks["answers_missing"] == checks["answers_wrong"] == checks["token_rows_wrong"] == 0
    assert checks["logit_gap"] < 0.05
