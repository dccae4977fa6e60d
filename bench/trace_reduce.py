"""Reduce a profiler trace (``*.xplane.pb``) to the benchmark's device numbers.

The traced window is the span ``bench.trace_window`` that the harness
opens right after the profiler starts and closes right before it stops.
Inside it, per device (a plane named ``/device:TPU:<n>``):

* busy: the union of the intervals of the events on the ``XLA Ops``
  line, clipped to the window; ``busy_s`` is its length averaged over
  the devices that ran anything;
* idle gaps: the rest of the window, each gap named for the harness
  span (``feed.next``, ``train.step``, ``serve.wave``, ``row_program``)
  that overlaps it most on any line of the host's plane, or ``host``
  where the harness was in none of them;
* device ops: time per operation, keyed ``<module>/<instruction>``, the
  module being the ``XLA Modules`` event that holds the op;
* modules: time and count of each ``XLA Modules`` program.

Host and device clocks in one trace agree to about a millisecond, so an
attribution is sure only for gaps much longer than that.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.trace_window"
HOST_SPANS = ("feed.next", "train.step", "serve.wave", "row_program")
_HASH = re.compile(r"\(\d+\)$")


def _short_module(name: str) -> str:
    return _HASH.sub("", name)


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def read_events(path: str | Path) -> dict:
    """The trace's raw material as plain tuples (ns):
    ``{"host": [(name, start, end)], "host_lines": {line: events},
    "devices": {plane: {"ops": [(name, start, end)], "modules": [(name,
    start, end)]}}}``; ``host`` keeps the harness's spans of every line."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host: list = []
    lines: dict = {}
    devices: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                lines[line.name] = sum(1 for _ in line.events)
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name in HOST_SPANS or e.name == WINDOW_SPAN
                )
    return {"host": host, "host_lines": lines, "devices": devices}


def reduce_events(events: dict, top: int = 10) -> dict:
    """Busy, idle and the breakdown inside the traced window."""
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(
            f"the trace has no {WINDOW_SPAN!r} span; host events per line: {events.get('host_lines')}"
        )
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in events["host"] if n in HOST_SPANS]
    busy_total, n_dev = 0.0, 0
    op_time: dict = defaultdict(float)
    modules: dict = defaultdict(lambda: [0.0, 0])
    gaps: list = []
    idle_by_span: dict = defaultdict(float)
    for dev in events["devices"].values():
        mods = sorted(m for m in (
            (s, e, _short_module(n)) for n, s, e in dev["modules"]
        ) if _clip(m[0], m[1], lo, hi))
        if not dev["ops"] and not mods:
            continue
        n_dev += 1
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            c = _clip(s, e, lo, hi)
            modules[name][0] += (c[1] - c[0]) / 1e9
            modules[name][1] += 1
        clipped = []
        for name, s, e in dev["ops"]:
            c = _clip(s, e, lo, hi)
            if not c:
                continue
            clipped.append(c)
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            op_time[f"{mod}/{_short_op(name)}"] += (c[1] - c[0]) / 1e9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, owner = 0.0, "host"
            for name, hs, he in spans:
                c = _clip(hs, he, s, e)
                if c and c[1] - c[0] > best:
                    best, owner = c[1] - c[0], name
            gaps.append((owner, (e - s) / 1e9))
            idle_by_span[owner] += (e - s) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_dev if n_dev else 0.0,
        "devices": n_dev,
        "op_events": sum(len(d["ops"]) for d in events["devices"].values()),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
        "idle_by_span": dict(idle_by_span),
        "modules": {k: (v[0], v[1]) for k, v in modules.items()},
    }


def reduce_trace(trace_dir: str | Path, top: int = 10) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return reduce_events(read_events(files[-1]), top=top)
