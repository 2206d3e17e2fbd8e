#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell to find its knee: the highest
rate its server sustains.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds 25 --rates 16 20 24 28

One process builds the cell's graph and server and warms up as a run does,
then offers each rate in turn for ``--seconds`` (the cell's traffic with
only ``rate_qps`` changed) and prints one JSON line per rate: offered and
served queries per second, latency median and 95th percentile, and how long
the backlog took to drain after the window. A cell's traffic file states a
rate set once from such a sweep. Runs on the chip; without a TPU it exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)

    import numpy as np

    from lib import spec
    from lib.harness import Context
    from lib.runtime import NoDevice, require_device

    cell = spec.load_cell(ROOT, args.workload)
    if cell.traffic["driver"] != "open_loop":
        print(f"knee: {cell.name} serves no open-loop traffic", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        require_device(cell.chips)
    except NoDevice as e:
        print(f"knee: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import GraphServer
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    ol = spec.load_module(os.path.join(BENCH, "drivers", "open_loop.py"), "ol_knee")
    ctx = Context(cell, args.seed, args.seconds, False, time.perf_counter(),
                  os.path.join(ROOT, ".bench_traces"), os.path.join(ROOT, ".jax_cache", "graphs"))
    g, rank, (n, _, _, _) = ctx.build_graph()
    srv = GraphServer(g, rank=rank, **cell.config["server"])
    algo, params = cell.traffic["algorithm"], dict(cell.traffic["params"])
    slots = cell.config["server"]["slots"]
    for v in np.random.default_rng([args.seed, 1]).integers(0, n, 2 * slots):
        srv.submit(algo, dict(params, seeds=[int(v)]))
    srv.run()
    for k, rate in enumerate(args.rates):
        traffic = dict(cell.traffic, rate_qps=rate)
        due, seeds = ol.queries(traffic, args.seconds, args.seed + k, n)
        start = time.perf_counter()
        sv = ol.serve(srv, algo, params, start + due, seeds)
        lat = sv["resolved"] - sv["due"]
        end = start + args.seconds
        print(json.dumps({
            "offered_qps": rate,
            "served_qps": float(np.sum(sv["resolved"] <= end)) / args.seconds,
            "p50_s": float(np.percentile(lat, 50)), "p95_s": float(np.percentile(lat, 95)),
            "drain_s": sv["t_end"] - end,
            "occupancy": sv["round_slots"] / max(1, sv["rounds"] * slots),
            "batches": sv["batches"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
