#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads.

    python3 bench/tests/record_trace.py <out_dir>

On the chip: a ``bench.window`` span holding a ``bench.solve`` span (a few
jitted products), a ``bench.arrival_wait`` span (the host sleeps 50 ms, so
the device idles) and a ``bench.step`` span (one more product). The test
keeps the resulting ``.xplane.pb`` as ``bench/tests/data/small.xplane.pb``,
with the source paths it records rewritten to ``./checkout/`` (same length,
so the file stays valid).
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.default_backend() != "tpu":
        sys.exit("record_trace: needs the chip")
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.float32)
    f(a).block_until_ready()                  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.solve"):
            for _ in range(4):
                f(a).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.arrival_wait"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.step"):
            f(a).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
