#!/usr/bin/env python3
"""The control of a cell: its plain reference, one precision lower than the
configuration states, put in the program's place and judged by the run's
own comparison.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--seconds 40]

For each seed it runs the cell as ``bench/run.py`` does, with every answer
the program produces replaced by the control of the algorithm the cell's
traffic names (``control`` in ``bench/algorithms/<algorithm>.py``): each
solve's result, from that solve's root, or each served query's answer as
the server resolves it. It prints the run's numbers compared beside their
limits; ``correct`` has to come out false. The benchmark's own runs never
run it. Runs on the chip; without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from unittest import mock

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


@contextlib.contextmanager
def in_place(cell, seed: int, seconds: float, graph_cache):
    """Put the control in the program's place for one run of ``cell``: the
    control of the algorithm its traffic names (``bench/algorithms/``),
    in the program's float32."""
    import numpy as np

    import repro
    from lib import spec
    from lib.harness import load_graph
    from repro.serving.server import GraphServer

    n, src, dst, w = load_graph(cell.config["graph"], graph_cache)
    alg = spec.load_algorithm(cell.bench_dir, cell.traffic["algorithm"])
    params = cell.traffic["params"]
    driver = cell.traffic["driver"]

    def answers(roots):
        return [np.asarray(x, np.float32)
                for x in alg.control(n, src, dst, w, params, roots)]

    if driver == "closed_loop":
        real, control = repro.solve, {}

        def solve(algo, *a, **kw):
            res = real(algo, *a, **kw)
            root = None if alg.ROOT_ARG is None else algo.params[alg.ROOT_ARG]
            if root not in control:
                (control[root],) = answers([root])
            res.x = control[root].copy()
            return res

        with mock.patch.object(repro, "solve", solve):
            yield
    elif driver == "open_loop":
        ol = spec.load_module(os.path.join(BENCH, "drivers", "open_loop.py"), "ol_control")
        _, seeds = ol.queries(cell.traffic, seconds, seed, n)
        wanted = sorted(set(int(v) for v in seeds))
        control = {}
        for lo in range(0, len(wanted), 32):
            part = wanted[lo:lo + 32]
            control.update(zip(part, answers(part)))
        real_resolve = GraphServer._resolve

        def _resolve(self, fam, j, t, converged):
            real_resolve(self, fam, j, t, converged)
            v = int(t.params["seeds"][0])
            if v in control:
                t.result = control[v].copy()

        with mock.patch.object(GraphServer, "_resolve", _resolve):
            yield
    else:
        raise ValueError(f"no control for driver {driver!r}")


def run(cell, seed: int, seconds: float, scratch: str, graph_cache) -> dict:
    """One run of ``cell`` with the control in the program's place."""
    from lib.harness import run_cell

    with in_place(cell, seed, seconds, graph_cache):
        return run_cell(cell, seed=seed, seconds=seconds, trace=False,
                        t0=time.perf_counter(), scratch=scratch,
                        graph_cache=graph_cache)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float,
                   help="window length (default: BENCHMARK.json's run_seconds)")
    args = p.parse_args(argv)

    from lib import spec
    from lib.runtime import NoDevice, require_device

    cell = spec.load_cell(ROOT, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        require_device(cell.chips)
    except NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
            seconds = json.load(f)["run_seconds"]
    for seed in args.seeds:
        out = run(cell, seed, seconds, os.path.join(ROOT, ".bench_traces"),
                  os.path.join(ROOT, ".jax_cache", "graphs"))
        print(json.dumps({"control": cell.name, "seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
