"""``BENCHMARK.json`` and the files it names hang together, and the traffic
generator offers every seed the same work."""
import importlib.util
import json
import os
import re

import numpy as np
import pytest

from lib import spec
from small import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        assert len(e.get("why", "x")) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len({e["name"] for e in entries}) == len(entries)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = spec.load_cell(ROOT, cell)
    assert os.path.exists(os.path.join(c.bench_dir, "drivers", c.traffic["driver"] + ".py"))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
        path = os.path.join(c.bench_dir, "metrics", m["name"] + ".py")
        s = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        assert callable(mod.read)
    assert c.limits and all(v > 0 for v in c.limits.values())


def test_arrivals_same_gaps_for_every_seed():
    path = os.path.join(ROOT, "bench", "drivers", "open_loop.py")
    s = importlib.util.spec_from_file_location("open_loop", path)
    ol = importlib.util.module_from_spec(s)
    s.loader.exec_module(ol)
    a = ol.arrivals(18.0, 40.0, 2**40 + 3, "exponential")
    b = ol.arrivals(18.0, 40.0, 7, "exponential")
    assert len(a) == len(b) == 720
    assert a[-1] < 40.0 and b[-1] < 40.0
    assert np.allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def test_lattice_every_edge_both_ways():
    from lib.generators import lattice_2d

    n, src, dst, w = lattice_2d(3, 4, seed=1)
    arcs = set(zip(src.tolist(), dst.tolist()))
    assert n == 12 and w is None and len(arcs) == len(src) == 2 * (3 * 3 + 2 * 4)
    assert all((d, s) in arcs for s, d in arcs)
    assert all(abs(s - d) in (1, 4) for s, d in arcs)


def test_graph_is_drawn_from_its_sections_seed(tmp_path):
    from lib.generators import GRAPH_SEED, make_graph
    from lib.harness import load_graph
    from small import small_cell

    graph = small_cell("plc-pagerank").config["graph"]
    assert "seed" not in graph
    named = dict(graph, seed=GRAPH_SEED)
    other = dict(graph, seed=GRAPH_SEED + 1)
    a, b, c = make_graph(graph), load_graph(named, str(tmp_path)), load_graph(other, str(tmp_path))
    assert all(np.array_equal(x, y) for x, y in zip(a[1:3], b[1:3]))
    assert not (len(a[1]) == len(c[1]) and np.array_equal(a[1], c[1]))
    assert len(list(tmp_path.iterdir())) == 2


@pytest.mark.parametrize("cell", CELLS)
def test_graph_cache_gives_the_generated_graph(cell, tmp_path):
    from lib.generators import make_graph
    from lib.harness import load_graph
    from small import small_cell

    graph = small_cell(cell).config["graph"]
    made = make_graph(graph)
    first = load_graph(graph, str(tmp_path))
    again = load_graph(graph, str(tmp_path))
    assert len(list(tmp_path.iterdir())) == 1
    for a, b, c in zip(made, first, again):
        if a is None:
            assert b is None and c is None
        else:
            assert np.array_equal(a, b) and np.array_equal(a, c)
