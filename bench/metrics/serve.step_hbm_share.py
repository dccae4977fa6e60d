"""Share of the chip's HBM bandwidth a decode step uses: the bytes a
batch-1 decode step must read (``step_bytes`` of the configuration's
reference module) over the ``jit_serve_step`` program's device time per
call in the traced slice (profiler trace) times the HBM bandwidth of
``bench/peaks.json``. Absent where the reference has no ``step_bytes`` or
no decode step ran."""

import importlib


def read(run):
    trace = run["trace"]
    if run["traffic"]["driver"] != "serve" or not trace or not run["cfg"].get("reference"):
        return None
    step_bytes = getattr(importlib.import_module(f"bench.reference.{run['cfg']['reference']}"), "step_bytes", None)
    seconds, count = trace["modules"].get("jit_serve_step", (0.0, 0))
    if step_bytes is None or not count:
        return None
    import jax

    from bench import flops

    bandwidth = flops.peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")
    return 100.0 * step_bytes(run["cfg"]) / (seconds / count * bandwidth)
