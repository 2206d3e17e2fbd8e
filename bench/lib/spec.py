"""A cell as ``BENCHMARK.json`` and the files it names describe it."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json: check name -> limit
    end_to_end: list      # the cell's end-to-end metric entries
    per_layer: list       # the cell's per-layer metric entries
    bench_dir: str


def _read(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, or without that key every cell that reports the end-to-end
    metric it ``moves`` (every cell, for an end-to-end metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(root: str, name: str) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = os.path.join(root, "bench")
    e2e = [m for m in bench["end_to_end"] if reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_read(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=_read(os.path.join(bench_dir, "limits", name + ".json"))["limits"],
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if reports(m, name, e2e_names)],
        bench_dir=bench_dir,
    )


def load_module(path: str, name: str):
    """Import a file by path (metric readers and drivers have dotted names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_algorithm(bench_dir: str, name: str):
    """The module ``bench/algorithms/<name>.py`` of the algorithm a traffic
    names: its float64 reference, its control one precision lower and the
    numbers compared (see ``bench/algorithms/pagerank.py``)."""
    path = os.path.join(bench_dir, "algorithms", name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no reference or control for algorithm {name!r}: "
                         f"{path} is missing")
    return load_module(path, "algorithm_" + name)
