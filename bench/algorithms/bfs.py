"""Breadth-first search: hop counts from the root, that is shortest paths
with every arc of weight 1 (``sssp.py`` with unit weights, whatever weights
the graph carries).

There is no control yet: bfloat16 holds every hop count below 256 exactly,
so the precision control reads what the reference does and could never come
out not correct. A ``bfs`` cell waits for a control that breaks a guarantee
the configuration states."""
from __future__ import annotations

import os

from lib.spec import load_algorithm

_sssp = load_algorithm(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "sssp")
ROOT_ARG = _sssp.ROOT_ARG
compare = _sssp.compare


def reference(n, src, dst, w, params, roots):
    return _sssp.reference(n, src, dst, None, params, roots)


def control(n, src, dst, w, params, roots):
    raise NotImplementedError(
        "bfs has no control: bfloat16 holds hop counts below 256 exactly, so "
        "a control one precision lower reads the reference's answer")
