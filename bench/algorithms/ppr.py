"""Personalized PageRank from single seeds, as the open-loop driver serves
it: every answer is checked by its float64 residual, which needs no
reference solve (``lib.reference.residual_max``), and the control is the
bfloat16 iteration from the same seeds, one column per seed.

* ``control(n, src, dst, w, params, roots)``: one answer per seed vertex;
* ``readings(n, src, dst, w, params, roots, answers)``: the numbers
  compared, ``answers[i]`` being the answer to the query from ``roots[i]``.
"""
from __future__ import annotations

from lib.reference import pagerank_control, residual_max


def control(n, src, dst, w, params, roots):
    return list(pagerank_control(n, src, dst, w, params["damping"], seeds=roots).T)


def readings(n, src, dst, w, params, roots, answers):
    return {"resid_max": residual_max(n, src, dst, w, params["damping"], roots, answers)}
