"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON payloads under
experiments/paper/. ``python -m benchmarks.run [--only fig8] [--fast]``.
``--fast`` shrinks every graph ~10x (tiny graphs, few iters) — the CI smoke
mode that keeps the benchmark scripts from rotting.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

MODULES = [
    "table2_metric_rounds",   # Table II
    "fig5_6_overall",         # Fig. 5 + 6
    "fig7_convergence",       # Fig. 7
    "fig8_async",             # Fig. 8
    "fig9_locality",          # Fig. 9/10 (TPU locality proxies)
    "fig12_degrees",          # Fig. 12
    "fig13_partition",        # Fig. 13
    "block_sensitivity",      # TPU adaptation ablation (DESIGN.md §3)
    "priority_sched",         # beyond-paper: Priter-style block scheduling
    "kernel_bench",           # Pallas kernel structural bench
    "roofline_report",        # dry-run roofline aggregation
    "batched_queries",        # batched multi-query engine throughput
    "incremental",            # evolving graphs: warm vs cold serving
    "serving_bench",          # continuous vs static batching (GraphServer)
    "push_bench",             # vertex-granular push vs block sweeps on deltas
    "reorder_bench",          # online reordering on a sustained delta stream
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    p.add_argument("--out", default="experiments/paper")
    p.add_argument("--fast", action="store_true",
                   help="tiny graphs / few iters (CI smoke mode)")
    args = p.parse_args()

    if args.fast:
        # must be set before any benchmark module imports benchmarks.common
        os.environ["REPRO_BENCH_FAST"] = "1"

    mods = [m for m in MODULES if args.only is None or args.only in m]
    print("name,us_per_call,derived")
    t_start = time.time()
    failures = 0
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(args.out)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,BENCH-FAILED {type(e).__name__}: {e}")
            continue
        for rname, us, derived in rows:
            derived = str(derived).replace(",", ";")
            print(f"{rname},{us:.1f},{derived}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    print(f"# total {time.time() - t_start:.1f}s, failures={failures}",
          file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
