#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names the general
generator in ``bench/drivers/``), the limits its answers are held to
(``bench/limits/<cell>.json``) and, in a traced run, one reader per
per-layer metric (``bench/metrics/<metric>.py``).

The run loads its configuration's graph (one dataset, drawn from a fixed
seed by ``lib.generators.make_graph``), makes its queries and roots from
``--seed``, warms up every program the window drives (set-up), measures
for ``--seconds``, then checks what the window produced against the plain
reference of the algorithm its traffic names
(``bench/algorithms/<algorithm>.py``).
Earlier lines report the set-up split, the compilations inside the window
and every number compared beside its limit (also the last lines on standard
error). The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``checks``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the metrics are the cell's per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from lib import spec
    from lib.runtime import NoDevice, require_device

    try:
        cell = spec.load_cell(ROOT, args.workload)
    except (FileNotFoundError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # the compilation cache lives inside the checkout at a fixed path, so
    # only a cell's first run here compiles; the program takes it from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        require_device(cell.chips)
    except NoDevice as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the system under test is not in this checkout ({e})",
              file=sys.stderr)
        return 4
    enable_compile_cache(ROOT)

    from lib.harness import run_cell

    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t0=T_START,
                   scratch=os.path.join(ROOT, ".bench_traces"),
                   graph_cache=os.path.join(ROOT, ".jax_cache", "graphs"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
