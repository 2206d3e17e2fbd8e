"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run holds.

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


def small_cell(name: str, **traffic) -> spec.Cell:
    cell = spec.load_cell(ROOT, name)
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
