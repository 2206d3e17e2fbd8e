"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run holds, and
shortest-path cells that it does not hold yet, built here.

Only the graph size and the window shrink: the drivers, the reference,
the limits and the metric readers are the cells' own."""
from __future__ import annotations

import copy
import json
import os

from lib import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]

SMALL_GRAPHS = {
    "powerlaw_cluster": {"n": 2048},
    "lattice_2d": {"rows": 32, "cols": 32},
}


# Built on plc-web26-gograph's graph, as a shortest-path cell would be:
# unordered, through solve(engine="auto") (a single root routes to the push
# engine) on the pallas backend, 4 roots drawn from the seed. sssp runs on
# weights in [1, 256), bfs on the unweighted graph.
SHORTEST_PATH = {"plc-sssp-small": ("sssp", [1, 256]), "plc-bfs-small": ("bfs", None)}
SHORTEST_PATH_LIMITS = {"dist_rel_gap": 1e-4, "reach_mismatch": 0}


def _shortest_path_cell(name: str) -> spec.Cell:
    algorithm, weights = SHORTEST_PATH[name]
    cell = spec.load_cell(ROOT, "plc-pagerank")
    cell.name = name
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"]["weights"] = weights
    cell.config["order"] = "none"
    cell.config["engine"] = {"engine": "auto", "backend": "pallas", "max_iters": 2000}
    cell.traffic = {"driver": "closed_loop", "algorithm": algorithm,
                    "params": {"eps": 0.5}, "roots": 4}
    cell.limits = dict(SHORTEST_PATH_LIMITS)
    return cell


def small_cell(name: str, **traffic) -> spec.Cell:
    cell = _shortest_path_cell(name) if name in SHORTEST_PATH else spec.load_cell(ROOT, name)
    cell.config = copy.deepcopy(cell.config)
    args = cell.config["graph"]["args"]
    args.update(SMALL_GRAPHS[cell.config["graph"]["generator"]])
    cell.traffic = dict(copy.deepcopy(cell.traffic), **traffic)
    return cell


def run_small(name: str, seed: int = 5, seconds: float = 2.0, trace: bool = False,
              tmp: str = "/nonexistent", **traffic) -> dict:
    import time

    from lib.harness import run_cell

    return run_cell(small_cell(name, **traffic), seed=seed, seconds=seconds,
                    trace=trace, t0=time.perf_counter(), scratch=tmp)
