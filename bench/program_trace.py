"""Run one cell traced and print what the program's own spans and ledgers say.

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>

This is ``bench/run.py --trace 1`` (its ``main``, its result line) with the
cell's session and the trace slice extended. Before the result line it
prints to standard error:

* ``program:`` the program's ledgers over the whole window (differences
  from the window's start) and the shares read from them: for training
  ``train.sync_share``, ``train.transfer_share``, ``train.epoch_start_share``
  and the feed's own wait beside the harness's; for serving
  ``serve.ttft_p50_ms``, ``serve.token_gap_p50_ms``,
  ``serve.token_gap_p95_ms`` and ``serve.compile_share``;
* ``trace:`` the traced slice's idle gaps named by the program's spans
  (:func:`program_attribution`) beside the harness's names, and each
  program span's share of the slice.
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# The program's spans (``repro.spans``), as they appear on the host plane.
PROGRAM_SPANS = (
    "plan.epoch_start",
    "feed.wait",
    "feed.transfer",
    "train.sync",
    "serve.row_program",
    "serve.prefill",
    "serve.decode_step",
)


def read_program_spans(path) -> list:
    """``(name, start_ns, end_ns)`` of every program span on the host plane."""
    from jax.profiler import ProfileData

    return [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
        if e.name in PROGRAM_SPANS
    ]


def program_attribution(events: dict, program: list, top: int = 10) -> dict:
    """The traced window's idle gaps, each named for the program span that
    overlaps it most (the shortest, i.e. innermost, among equals), else for
    the harness span as ``bench/trace_reduce.py`` names it, else ``host``.

    ``events`` is :func:`bench.trace_reduce.read_events`' output and
    ``program`` :func:`read_program_spans`'. Returns
    ``idle_by_program_span`` (seconds per name), ``program_idle_gaps``
    (the longest gaps as ``[name, seconds, other program spans over the
    gap]``), ``idle_in_span`` (per program span name, the idle seconds
    during which one of its spans was open, on any thread: names overlap,
    so these do not add up to the idle time) and ``span_share`` (each
    program span's time in the window, on any thread, over the
    window)."""
    from bench import trace_reduce as T

    windows = [(s, e) for n, s, e in events["host"] if n == T.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {T.WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    harness = [(n, s, e) for n, s, e in events["host"] if n in T.HOST_SPANS]
    gaps, idle, covered = [], defaultdict(float), defaultdict(float)
    for dev in events["devices"].values():
        if not dev["ops"] and not any(T._clip(s, e, lo, hi) for _, s, e in dev["modules"]):
            continue  # as the harness's reduction: a device that ran nothing here
        busy = T._union([c for c in (T._clip(s, e, lo, hi) for _, s, e in dev["ops"]) if c])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            over = sorted(
                (-(c[1] - c[0]), pe - ps, n)
                for n, ps, pe in program
                if (c := T._clip(ps, pe, s, e))
            )
            if over:
                name = over[0][2]
            else:
                best = max(((c[1] - c[0], n) for n, hs, he in harness if (c := T._clip(hs, he, s, e))),
                           default=(0, "host"))
                name = best[1]
            also = sorted({n for _, _, n in over[1:]} - {name})
            for n in {n for _, _, n in over}:
                parts = T._union([c for m, ps, pe in program if m == n and (c := T._clip(ps, pe, s, e))])
                covered[n] += sum(b - a for a, b in parts) / 1e9
            gaps.append([name, (e - s) / 1e9, also])
            idle[name] += (e - s) / 1e9
    share = defaultdict(float)
    for n, s, e in program:
        if c := T._clip(s, e, lo, hi):
            share[n] += (c[1] - c[0]) / (hi - lo)
    return {
        "idle_by_program_span": dict(idle),
        "program_idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
        "idle_in_span": dict(covered),
        "span_share": dict(share),
    }


def _percentile_ms(values, q):
    import numpy as np

    return float(np.percentile(values, q) * 1e3) if len(values) else None


def _extended(run, serve, train):
    """The harness's trace slice and sessions, extended to report the
    program's view (see the module doc)."""

    class ProgramTraceSlice(run.TraceSlice):
        def stop(self) -> dict:
            from bench import trace_reduce as T

            out = super().stop()  # the raw trace stays on disk until the run's end
            path = sorted(Path(self.dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
            out["program"] = program_attribution(T.read_events(path), read_program_spans(path))
            return out

    def report_trace(record):
        trace = record.get("trace")
        if trace:
            print("trace: " + json.dumps({"idle_by_span": trace["idle_by_span"], **trace["program"]}),
                  file=sys.stderr)

    class TrainProgram(train.TrainSession):
        def _ledgers(self) -> dict:
            stats, report = self.feed_stats, self.feed.report()
            return {
                "steps": self.controller.stats["steps"],
                "sync_s": self.controller.stats["sync_s"],
                "transfer_s": report.transfer_s,
                "host_wait_s": report.host_wait_s,
                "epochs": stats.get("epochs", 0),
                "epoch_start_s": stats.get("epoch_start_s", 0.0),
            }

        def window(self, on_trace=None) -> dict:
            before = self._ledgers()
            record = super().window(on_trace)
            d = {k: v - before[k] for k, v in self._ledgers().items()}
            w = record["window_s"]
            d.update({
                "train.sync_share": 100.0 * d["sync_s"] / w,
                "train.transfer_share": 100.0 * d["transfer_s"] / w,
                "train.epoch_start_share": 100.0 * d["epoch_start_s"] / w,
                "program_feed_wait_share": 100.0 * d["host_wait_s"] / w,
                "harness_feed_wait_share": 100.0 * record["feed_wait_s"] / w,
            })
            print("program: " + json.dumps(d), file=sys.stderr)
            report_trace(record)
            return record

    class ServeProgram(serve.ServeSession):
        def _wave(self, reqs, cache, row_program, stats):
            if stats is not None:
                self.window_stats = stats
            return super()._wave(reqs, cache, row_program, stats)

        def window(self, on_trace=None) -> dict:
            record = super().window(on_trace)
            st, w = self.window_stats, record["window_s"]
            served = [k for k in self.results if self.results[k]]
            # the harness's latency less the program's is the wait for the wave
            ttft = [lat - st.latency_s[k] + st.first_token_s[k]
                    for k, lat in zip(served, record["latency_s"]) if k in st.first_token_s]
            d = {
                "served": len(served),
                "preprocess_s": st.preprocess_s,
                "prefill_s": st.prefill_s,
                "decode_s": st.decode_s,
                "compiles": st.compiles,
                "compile_s": st.compile_s,
                "token_gaps": len(st.token_gaps_s),
                "serve.ttft_p50_ms": _percentile_ms(ttft, 50),
                "serve_p50_ms": _percentile_ms(record["latency_s"], 50),
                "serve.token_gap_p50_ms": _percentile_ms(st.token_gaps_s, 50),
                "serve.token_gap_p95_ms": _percentile_ms(st.token_gaps_s, 95),
                "serve.compile_share": 100.0 * st.compile_s / w,
                "prefill_share": 100.0 * st.prefill_s / w,
                "decode_share": 100.0 * st.decode_s / w,
            }
            print("program: " + json.dumps(d), file=sys.stderr)
            report_trace(record)
            return record

    return ProgramTraceSlice, TrainProgram, ServeProgram


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" in argv:
        print("program_trace: the cell runs traced; give no --trace", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run

    # run.main points JAX at this cache before JAX loads; the drivers load it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.COMPILE_CACHE)
    from bench.drivers import serve, train

    run.TraceSlice, train.TrainSession, serve.ServeSession = _extended(run, serve, train)
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
