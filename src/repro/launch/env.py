"""Tuned launch environment: the process-level half of device overlap.

The input pipeline hides preprocessing behind device compute only if the
host side is not sabotaged by its own runtime: glibc malloc serializes the
multi-threaded byte-buffer churn (tcmalloc fixes it), TensorFlow's logging
taxes every worker fork, and on CPU containers jax presents one device
unless XLA is told to pin a host device count. This module derives the
production environment (the ``run.sh`` idiom of large-scale JAX trainers)
as data, so it is unit-testable and composes with an existing
environment instead of clobbering it:

    # print eval-able exports
    PYTHONPATH=src python -m repro.launch.env --devices 8

    # re-exec a training command under the tuned env (LD_PRELOAD needs to
    # be set before the process starts, so exec is the honest wiring)
    PYTHONPATH=src python -m repro.launch.env --devices 8 -- \
        python -m repro.launch.train --arch stablelm_3b --smoke
"""

from __future__ import annotations

import argparse
import os
import shlex
from pathlib import Path
from typing import Mapping, Sequence

# Preload candidates, most specific first: full tcmalloc, then the
# minimal build Debian/Ubuntu ship by default.
TCMALLOC_CANDIDATES: tuple[str, ...] = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)

# Keep numpy's large transient buffers (flat byte buffers, token arrays)
# below tcmalloc's large-alloc report chatter.
TCMALLOC_REPORT_THRESHOLD = "60000000000"


# JAX's persistent compilation cache. An entry is only found again from the
# same directory, so the fallback is one fixed path inside the checkout —
# never a temporary name, a pid or a timestamp.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory; otherwise the cache goes to
    ``<checkout>/.jax_cache``. Entry points call this from ``main()``,
    never at import."""
    configured = os.environ.get(COMPILE_CACHE_ENV)
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def find_tcmalloc(candidates: Sequence[str] | None = None) -> str | None:
    """First present tcmalloc shared object, or None (then no preload)."""
    if candidates is None:
        candidates = TCMALLOC_CANDIDATES  # read at call time: patchable
    for path in candidates:
        if os.path.exists(path):
            return path
    return None


def merge_xla_flags(existing: str, *flags: str) -> str:
    """Append ``flags`` to an ``XLA_FLAGS`` string, letting the new value
    win when the same ``--flag=`` is already present (re-launching with a
    different device count must not silently keep the old pin)."""
    merged: list[str] = []
    names = {f.split("=", 1)[0] for f in flags}
    for tok in existing.split():
        if tok.split("=", 1)[0] not in names:
            merged.append(tok)
    merged.extend(flags)
    return " ".join(merged)


def tuned_env(
    host_device_count: int | None = None,
    *,
    tcmalloc: bool = True,
    base: Mapping[str, str] | None = None,
) -> dict[str, str]:
    """The tuned launch variables as a plain dict.

    ``base`` (default ``os.environ``) supplies existing values to merge
    with — notably ``XLA_FLAGS``, which is extended, not replaced. Only
    variables this helper owns are returned; apply them with
    :func:`apply` or export them from a wrapper shell.
    """
    base = os.environ if base is None else base
    env: dict[str, str] = {
        # silence TF/absl banner spam in every worker process
        "TF_CPP_MIN_LOG_LEVEL": "4",
        # fp32 default without forcing x64 everywhere
        "JAX_DEFAULT_DTYPE_BITS": "32",
    }
    if tcmalloc:
        lib = find_tcmalloc()
        if lib is not None:
            env["LD_PRELOAD"] = lib
            env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = TCMALLOC_REPORT_THRESHOLD
    if host_device_count is not None:
        if host_device_count < 1:
            raise ValueError(f"host_device_count must be >= 1, got {host_device_count}")
        env["XLA_FLAGS"] = merge_xla_flags(
            base.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={host_device_count}",
        )
    return env


def apply(
    env: Mapping[str, str] | None = None,
    *,
    host_device_count: int | None = None,
    overwrite: bool = False,
) -> dict[str, str]:
    """Set the tuned variables on ``os.environ`` and return what was set.

    Values the user already exported win unless ``overwrite=True``
    (``XLA_FLAGS`` from :func:`tuned_env` already merged them). Note
    ``LD_PRELOAD`` only affects *future* processes (worker forks, an
    ``exec``'d trainer) — preloading the current process is the wrapper
    shell's job (see module docstring).
    """
    env = tuned_env(host_device_count) if env is None else dict(env)
    applied: dict[str, str] = {}
    for k, v in env.items():
        if overwrite or k not in os.environ:
            os.environ[k] = v
            applied[k] = v
    return applied


def render_exports(env: Mapping[str, str]) -> str:
    """Eval-able ``export K=V`` lines for a wrapper shell."""
    return "\n".join(f"export {k}={shlex.quote(v)}" for k, v in sorted(env.items()))


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument(
        "--devices", type=int, default=None,
        help="pin --xla_force_host_platform_device_count",
    )
    ap.add_argument(
        "--no-tcmalloc", action="store_true", help="skip the LD_PRELOAD probe"
    )
    ap.add_argument(
        "command", nargs="*",
        help="after '--': command to exec under the tuned environment",
    )
    args = ap.parse_args(argv)
    env = tuned_env(args.devices, tcmalloc=not args.no_tcmalloc)
    if args.command:
        os.environ.update(env)
        os.execvpe(args.command[0], list(args.command), os.environ)
    print(render_exports(env))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
