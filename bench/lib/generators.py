"""Graph data made from a seed: the benchmark's own copy of the generators.

Data is part of the yardstick, so the graphs the cells run on are made here
and not by the program's ``repro.graphs.generators``, which a later change
could alter. ``powerlaw_cluster`` follows that module's original step for
step, so the same arguments and seed give the same edges.

Each returns ``(n, src, dst, w)``: int32 edge endpoints and float32 weights,
or ``w = None`` for an unweighted graph.
"""
from __future__ import annotations

import numpy as np


def _dedup(n: int, src: np.ndarray, dst: np.ndarray):
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = src.astype(np.int64) * n + dst
    _, first = np.unique(key, return_index=True)
    first.sort()
    return src[first], dst[first]


def powerlaw_cluster(n: int, m: int, p: float, seed: int):
    """Holme–Kim growth: preferential attachment with triad closure, so the
    degrees follow a power law and neighbourhoods are clustered. Each edge
    is then flipped with probability 1/2 so both directions occur."""
    rng = np.random.default_rng(seed)
    repeated: list[int] = []
    src_l: list[int] = []
    dst_l: list[int] = []
    for v in range(1, min(m + 1, n)):
        src_l.append(v)
        dst_l.append(v - 1)
        repeated.extend((v, v - 1))
    for v in range(m + 1, n):
        last_target = None
        made = 0
        while made < m:
            if last_target is not None and rng.random() < p:
                t = repeated[rng.integers(len(repeated))]
            else:
                t = repeated[rng.integers(len(repeated))] if repeated \
                    else int(rng.integers(v))
            if t != v:
                src_l.append(v)
                dst_l.append(t)
                repeated.extend((v, t))
                last_target = t
                made += 1
    src = np.asarray(src_l, dtype=np.int32)
    dst = np.asarray(dst_l, dtype=np.int32)
    flip = rng.random(len(src)) < 0.5
    src2 = np.where(flip, dst, src).astype(np.int32)
    dst2 = np.where(flip, src, dst).astype(np.int32)
    src, dst = _dedup(n, src2, dst2)
    return n, src, dst, None


def lattice_2d(rows: int, cols: int, seed: int):
    """The ``rows`` x ``cols`` square grid graph, ids in row-major order:
    every pair of horizontal or vertical neighbours is joined by an arc in
    each direction. Planar, degree at most 4, diameter rows + cols - 2.
    Nothing is drawn from ``seed``."""
    del seed
    n = rows * cols
    vid = np.arange(n, dtype=np.int32).reshape(rows, cols)
    a = np.concatenate([vid[:, :-1].ravel(), vid[:-1, :].ravel()])
    b = np.concatenate([vid[:, 1:].ravel(), vid[1:, :].ravel()])
    return n, np.concatenate([a, b]), np.concatenate([b, a]), None


GENERATORS = {"powerlaw_cluster": powerlaw_cluster, "lattice_2d": lattice_2d}


def scramble(n: int, src: np.ndarray, dst: np.ndarray, seed: int):
    """Random relabelling: vertex v becomes ``perm[v]``."""
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    return perm[src], perm[dst]


def random_weights(m: int, lo: float, hi: float, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).uniform(lo, hi, size=m).astype(np.float32)


def make_graph(spec: dict, seed: int):
    """``(n, src, dst, w)`` for a configuration's ``graph`` section.

    ``spec`` names the generator and its arguments, and optionally
    ``scramble`` (relabel the ids at random) and ``weights`` (``[lo, hi)``
    uniform). Every random stream is drawn from ``seed``, each at its own
    offset, so one seed gives one graph.
    """
    gen = GENERATORS[spec["generator"]]
    n, src, dst, w = gen(**spec["args"], seed=seed)
    if spec.get("scramble"):
        src, dst = scramble(n, src, dst, seed + 1)
    if spec.get("weights"):
        lo, hi = spec["weights"]
        w = random_weights(len(src), lo, hi, seed + 2)
    return n, src, dst, w
