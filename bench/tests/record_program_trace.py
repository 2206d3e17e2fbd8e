#!/usr/bin/env python3
"""Record the profiler trace the program-span test reads.

    python3 bench/tests/record_program_trace.py <out_file>

On the chip: a ``bench.window`` span holding a ``bench.solve`` span (a few
jitted products) and a ``bench.step`` span inside which the program's own
tracer (``repro.obs.Tracer``) opens nested spans: ``tick`` holding
``batch`` (one product) and ``resolve``, in which the host sleeps 50 ms, so
the device idles there. The trace is written to ``<out_file>`` with the
checkout's path, wherever the trace records it, overwritten by a
placeholder of the same length (so the file stays valid). The test keeps
it as ``bench/tests/data/program.xplane.pb``.
"""
import glob
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.obs import Tracer  # noqa: E402


def main(out_file: str) -> None:
    if jax.default_backend() != "tpu":
        sys.exit("record_program_trace: needs the chip")
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.float32)
    f(a).block_until_ready()                  # compile outside the trace
    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.solve"):
                for _ in range(4):
                    f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.step"):
                with tr.span("tick"):
                    with tr.span("batch"):
                        f(a).block_until_ready()
                    with tr.span("resolve", ticket=0):
                        time.sleep(0.05)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        with open(path, "rb") as fh:
            data = fh.read()
    root = ROOT.encode() + b"/"
    data = data.replace(root, b"./" + b"_" * (len(root) - 3) + b"/")
    with open(out_file, "wb") as fh:
        fh.write(data)


if __name__ == "__main__":
    main(sys.argv[1])
