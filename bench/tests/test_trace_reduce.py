"""The trace reduction on a small trace recorded on a TPU v5e.

``bench/testdata/small_trace.xplane.pb`` was written by
``record_trace.py``: in a ``bench.trace_window`` span, three
``train.step`` spans each run a jitted 2048 x 2048 bf16 product, and each
is followed by a ``feed.next`` span that sleeps 5 ms.
"""

from pathlib import Path

import pytest

from bench import trace_reduce as T

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return T.read_events(TRACE)


def sweep_busy(intervals, lo, hi):
    """Busy time by a sweep over interval edges, for comparison."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals if e > lo and s < hi])
    busy, active, last = 0.0, 0, None
    for t, d in edges:
        if active > 0:
            busy += t - last
        active += d
        last = t
    return busy


def test_window_and_busy_union(events):
    r = T.reduce_events(events)
    (lo, hi), = [(s, e) for n, s, e in events["host"] if n == T.WINDOW_SPAN]
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    (dev,) = events["devices"].values()
    want = sweep_busy([(s, e) for _, s, e in dev["ops"]], lo, hi) / 1e9
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(want, rel=1e-9)
    assert 0 < r["busy_s"] < 0.01 * r["window_s"]  # the device idles through the sleeps


def test_top_operation_is_the_product(events):
    r = T.reduce_events(events)
    name, seconds = r["device_ops"][0]
    assert name == "jit__lambda/%fusion"
    assert seconds == pytest.approx(r["busy_s"], rel=0.01)
    assert r["modules"]["jit__lambda"][1] >= 2


def test_long_gaps_are_the_feed_waits(events):
    r = T.reduce_events(events)
    longest = r["idle_gaps"][:3]
    assert [name for name, _ in longest] == ["feed.next"] * 3
    assert all(seconds > 0.005 for _, seconds in longest)
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle, rel=1e-6)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.trace_window"):
        T.reduce_events({"host": [], "devices": {}})
