"""PageRank: the float64 fixpoint, its bfloat16 control and the gaps
compared (the arithmetic is in ``lib.reference``).

Every ``bench/algorithms/<algorithm>.py`` that a closed-loop traffic names
gives:

* ``ROOT_ARG``: None for an algorithm solved once over the whole graph;
  for a rooted one, the keyword of the program's constructor that takes
  the root (the traffic's ``roots`` says how many are drawn);
* ``reference(n, src, dst, w, params, roots)``: one float64 answer per root,
  written from the definition and importing nothing of the program; an
  unrooted algorithm is handed ``roots = [None]``;
* ``control(n, src, dst, w, params, roots)``: the same, one precision below
  the configuration's, to be put in the program's place;
* ``compare(x, ref)``: the numbers compared, ``{name: value}``, each held to
  the limit of that name in the cell's limits file.

``params`` is the traffic's ``params``; ``w`` is None on an unweighted graph.
"""
from __future__ import annotations

from lib.reference import gaps, pagerank_control, pagerank_f64

ROOT_ARG = None


def reference(n, src, dst, w, params, roots):
    return [pagerank_f64(n, src, dst, w, params["damping"]) for _ in roots]


def control(n, src, dst, w, params, roots):
    return [pagerank_control(n, src, dst, w, params["damping"]) for _ in roots]


compare = gaps
