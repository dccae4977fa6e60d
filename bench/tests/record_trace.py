"""Record the small trace that ``test_trace_reduce.py`` reads (run on a TPU).

    python bench/tests/record_trace.py <out_dir>

Inside a ``bench.trace_window`` span: three ``train.step`` spans, each
running a jitted matrix product to completion, and after each a
``feed.next`` span that sleeps 5 ms with the device idle. The newest
``*.xplane.pb`` under ``<out_dir>`` is what ``bench/testdata/`` keeps.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("feed.next"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
