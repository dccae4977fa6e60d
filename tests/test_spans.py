"""Program spans (``repro.spans``): one timer into a ledger and onto the
profiler's trace, importable without jax."""

import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

import pytest

from repro.analysis.contracts import lint_contracts
from repro.spans import span

_PKG_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@dataclass
class Ledger:
    busy_s: float = 0.0


def test_span_adds_to_a_dataclass_field_and_a_dict_key():
    clock = FakeClock()
    ledger, stats = Ledger(), {}
    for dt in (1.5, 2.0):
        with span("work", ledger, "busy_s", clock=clock) as s:
            clock.t += dt
        assert s.seconds == dt
        with span("work", stats, "busy_s", clock=clock):
            clock.t += dt
    assert ledger.busy_s == 3.5
    assert stats == {"busy_s": 3.5}


def test_span_without_a_ledger_only_times():
    clock = FakeClock()
    with span("mark", clock=clock) as s:
        clock.t += 0.25
    assert s.seconds == 0.25


def test_span_counts_a_block_that_raises():
    clock, ledger = FakeClock(), Ledger()
    with pytest.raises(KeyError):
        with span("fails", ledger, "busy_s", clock=clock):
            clock.t += 1.0
            raise KeyError("x")
    assert ledger.busy_s == 1.0


def test_span_lands_on_the_trace_while_a_profiler_runs(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("spans.test_block", {}, "s"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {
        e.name
        for plane in jax.profiler.ProfileData.from_file(str(pb)).planes
        for line in plane.lines
        for e in line.events
    }
    assert "spans.test_block" in names


def test_spans_and_the_ledger_modules_import_without_jax():
    code = (
        "import sys; import repro.spans, repro.runtime.fault_tolerance; "
        "assert 'jax' not in sys.modules, 'jax imported'"
    )
    src = str(_PKG_ROOT.parent)
    subprocess.run([sys.executable, "-c", code], check=True, env={"PYTHONPATH": src})


@pytest.mark.parametrize("rule", ["R001", "R005"])
def test_worker_tier_and_serve_hot_path_contracts_hold(rule):
    diags = lint_contracts(_PKG_ROOT, rules=[rule])
    assert diags == [], [d.message for d in diags]
