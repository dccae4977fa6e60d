"""Run one benchmark cell once, on the machine's accelerator.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (its file holds the sizes as run) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its driver: the run drives
the session class of ``bench/drivers/<driver>.py``, see
:func:`session_class`). Each metric of ``BENCHMARK.json`` is read by
``bench/metrics/<metric>.py``; each number that decides ``correct`` is
held to its limit in ``bench/limits/<workload>.json``.

A run: set-up (imports, device check, the traffic driver's set-up and warm-up;
``setup_s``), the window of ``--seconds``, the reading of device memory,
then the check against the plain references. With ``--trace 1`` the
profiler records the last ``trace_seconds`` of the window and the run
reports the per-layer metrics; with ``--trace 0`` the end-to-end ones.

The last line of standard output is the result as one JSON object. Each
number compared is printed beside its limit as the last lines of standard
error and, under ``checks``, as the result's last key. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_CACHE = ROOT / ".jax_cache"
DRIVERS = BENCH / "drivers"


def fail(why: str) -> int:
    print(f"bench: {why}", file=sys.stderr)
    return 1


def driver_path(driver: str) -> Path:
    """``bench/drivers/<driver>.py``; an unknown driver is an error that
    lists the drivers there are."""
    path = DRIVERS / f"{driver}.py"
    if not (str(driver).isidentifier() and path.is_file()):
        there = sorted(p.stem for p in DRIVERS.glob("*.py") if p.stem != "__init__")
        raise KeyError(f"the traffic names driver {driver!r}, but there is no bench/drivers/{driver}.py; "
                       f"there are {there}")
    return path


def session_class(driver: str):
    """The session class of ``bench/drivers/<driver>.py``: the driver's name
    in CamelCase and ``Session`` (``serve`` -> ``ServeSession``). It is
    looked up on the module when the run starts, so a subclass put in its
    place (``bench/program_trace.py`` does so) is what runs."""
    driver_path(driver)
    module = importlib.import_module(f"bench.drivers.{driver}")
    return getattr(module, driver.title().replace("_", "") + "Session")


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    driver_path(traffic["driver"])
    return {
        "cell": cell,
        "config_file": configs[cell["config"]]["file"],
        "config": json.loads((ROOT / configs[cell["config"]]["file"]).read_text()),
        "traffic": traffic,
        "limits": json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        "spec": spec,
    }


def judge(checks: dict, limits: dict) -> tuple[bool, dict, list]:
    """Each number compared beside its limit, and whether all are within."""
    compared = {k: {"value": v, "limit": limits[k]} for k, v in checks.items() if k in limits}
    missing = sorted(set(limits) - set(checks))
    correct = not missing and all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared, missing


def metrics_of(spec: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def read_metric(name: str, run: dict):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


class CompileEvents:
    """Compilations (persistent-cache misses), cache loads and lowerings."""

    def __init__(self):
        self.counts = {"compiles": 0, "cache_loads": 0, "lowerings": 0}

    def event(self, name: str, *_a, **_k) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["cache_loads"] += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
        elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.counts["lowerings"] += 1

    def snapshot(self) -> dict:
        c = dict(self.counts)
        c["compiles"] -= c["cache_loads"]  # the compile event also fires on a cache hit
        return c


class TraceSlice:
    """The profiler over the last seconds of the window."""

    def __init__(self, directory: Path):
        import jax

        self.jax, self.dir = jax, directory
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1  # the harness's spans and the runtime's events
        opts.python_tracer_level = 0  # every Python call would flood the host buffer
        jax.profiler.start_trace(str(directory), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.trace_window")
        self.span.__enter__()

    def end_window(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None

    def stop(self) -> dict:
        from bench.trace_reduce import reduce_trace

        self.end_window()
        self.jax.profiler.stop_trace()
        return reduce_trace(self.dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for key in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[key]  # the program's defaults, not the machine's knobs
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        cell = load_cell(args.workload)
        from repro.launch.env import enable_compile_cache
    except (OSError, KeyError, ImportError) as e:
        return fail(f"cannot load the cell or the system under test: {e!r}")

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    chips = cell["cell"]["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return fail(f"needs {chips} TPU chip(s), JAX finds {devices}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}

    from bench import flops

    events = CompileEvents()
    jax.monitoring.register_event_listener(events.event)
    jax.monitoring.register_event_duration_secs_listener(events.event)
    cfg, traffic = cell["config"], cell["traffic"]
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        work = Path(tmp)
        try:
            session = session_class(traffic["driver"])(
                cfg, traffic, args.seed, args.seconds, work, annotate=jax.profiler.TraceAnnotation
            )
        except KeyError as e:  # a key the driver needs is missing from the configuration
            return fail(f"cannot set up the cell from {cell['config_file']}: {e}")
        session.setup()
        setup_s = time.perf_counter() - T_START
        before = events.snapshot()
        on_trace = (lambda: TraceSlice(work / "trace")) if args.trace else None
        try:
            record = session.window(on_trace)
        finally:
            session.close()
        after = events.snapshot()
        in_window = {k: after[k] - before[k] for k in after}
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices[:chips]
        )
        print(f"window: {json.dumps(in_window)} (compiles should be 0)", file=sys.stderr)
        print(f"window: {json.dumps({k: v for k, v in record.items() if k not in ('latency_s', 'trace')}, default=str)}",
              file=sys.stderr)
        t_check = time.perf_counter()
        checks = session.check()
        print(f"check: {time.perf_counter() - t_check:.1f} s", file=sys.stderr)

    correct, compared, missing = judge(checks, cell["limits"])

    run = {
        "record": record,
        "setup_s": setup_s,
        "trace": record.get("trace"),
        "peak_flops_per_s": flops.peak(device["kind"]) * chips,
        "cfg": cfg,
        "traffic": traffic,
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(cell["spec"], args.workload, kind):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": record.get("attempted", record.get("steps")),
        "failed": record.get("shed", 0),
    }
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": [list(x) for x in run["trace"]["device_ops"]],
            "idle_gaps": [list(x) for x in run["trace"]["idle_gaps"]],
        }
    result["checks"] = compared
    for name in sorted(set(checks) - set(compared)):
        print(f"reading {name}: {checks[name]!r} (not compared)", file=sys.stderr)
    for name in missing:
        print(f"check {name}: no reading", file=sys.stderr)
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
