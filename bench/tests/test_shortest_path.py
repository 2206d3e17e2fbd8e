"""The shortest-path reference against a plain Dijkstra, its comparison,
and the roots a rooted traffic solves from."""
import heapq
import os

import numpy as np
import pytest

from lib import spec
from lib.generators import roots
from small import ROOT

BENCH = os.path.join(ROOT, "bench")
SSSP = spec.load_algorithm(BENCH, "sssp")
BFS = spec.load_algorithm(BENCH, "bfs")


def heapq_dijkstra(n, src, dst, w, root):
    adj = [[] for _ in range(n)]
    for u, v, c in zip(src.tolist(), dst.tolist(), w.tolist()):
        adj[u].append((v, c))
    dist = [float("inf")] * n
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, c in adj[u]:
            if d + c < dist[v]:
                dist[v] = d + c
                heapq.heappush(heap, (d + c, v))
    return np.array(dist)


def small_graphs():
    rng = np.random.default_rng(7)
    # a path with a shortcut, a parallel arc and a vertex (4) nothing reaches
    yield 5, np.array([0, 1, 2, 0, 0, 4]), np.array([1, 2, 3, 3, 1, 0]), \
        np.array([1.0, 2.0, 3.0, 7.5, 0.5, 1.0])
    # two components: 0..29 and 30..59, arcs drawn inside each
    a = rng.integers(0, 30, (2, 120))
    src = np.concatenate([a[0], a[0] + 30])
    dst = np.concatenate([a[1], a[1] + 30])
    yield 60, src, dst, rng.uniform(1, 256, len(src))
    src, dst = rng.integers(0, 200, (2, 1500))
    yield 200, src, dst, rng.uniform(1, 256, len(src))


@pytest.mark.parametrize("case", range(3))
def test_reference_is_plain_dijkstra(case):
    n, src, dst, w = list(small_graphs())[case]
    rs = [0, n // 2, n - 1]
    got = SSSP.reference(n, src, dst, w.astype(np.float32), {}, rs)
    hops = BFS.reference(n, src, dst, w, {}, rs)
    for r, x, h in zip(rs, got, hops):
        np.testing.assert_array_equal(x, heapq_dijkstra(n, src, dst, w.astype(np.float32), r))
        np.testing.assert_array_equal(h, heapq_dijkstra(n, src, dst, np.ones(len(src)), r))
    if case < 2:
        assert np.isinf(got[0]).any(), "the case has unreachable vertices"


def test_compare_reads_gaps_and_reach():
    ref = np.array([0.0, 0.5, 10.0, np.inf])
    big = np.float32(3.0e38)
    x = np.array([0.0, 0.5, 10.0, big], np.float32)
    assert SSSP.compare(x, ref) == {"dist_rel_gap": 0.0, "reach_mismatch": 0.0}
    x[1] = 0.6                      # below 1 the gap is absolute
    x[2] = 10.01
    assert SSSP.compare(x, ref)["dist_rel_gap"] == pytest.approx(0.1, rel=1e-6)
    x[2], x[3] = big, 4.0           # one reached vertex lost, one gained
    assert SSSP.compare(x, ref)["reach_mismatch"] == 2.0


def test_control_stays_near_reference_and_off_it():
    n, src, dst, w = list(small_graphs())[2]
    w = w.astype(np.float32)
    ref, ctl = SSSP.reference(n, src, dst, w, {}, [3])[0], SSSP.control(n, src, dst, w, {}, [3])[0]
    read = SSSP.compare(ctl, ref)
    assert read["reach_mismatch"] == 0.0
    assert 1e-4 < read["dist_rel_gap"] < 2e-2


def test_roots_drawn_from_the_seed():
    src = np.array([0, 0, 3, 5, 5, 9], np.int32)
    a = roots(12, src, 3, 2**40 + 1)
    assert np.array_equal(a, roots(12, src, 3, 2**40 + 1))
    assert len(set(a.tolist())) == 3 and set(a.tolist()) <= {0, 3, 5, 9}
    draws = {tuple(roots(12, src, 3, s).tolist()) for s in range(20)}
    assert len(draws) > 1
    with pytest.raises(ValueError):
        roots(12, src, 5, 1)


def test_unknown_algorithm_is_named():
    with pytest.raises(ValueError, match="'katz'"):
        spec.load_algorithm(BENCH, "katz")


def test_bfs_has_no_control():
    n, src, dst, w = list(small_graphs())[0]
    with pytest.raises(NotImplementedError, match="bfs has no control"):
        BFS.control(n, src, dst, w, {}, [0])
