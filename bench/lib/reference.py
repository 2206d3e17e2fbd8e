"""PageRank-family arithmetic of the plain reference, and its control.

Each algorithm a traffic can name has its module in ``bench/algorithms/``
(reference, control, numbers compared; ``pagerank.py`` says what each
gives). The PageRank family's modules, ``pagerank.py`` and ``ppr.py``, call
the functions here; the shortest-path ones (``sssp.py``, ``bfs.py``) stand
alone. :func:`widest` serves every algorithm.

PageRank and personalized PageRank as the configuration states them, written
from their definitions and not from the program: the unnormalized PageRank

    x_v = (1 - a) * r_v + a * sum_{(u, v) in E} w_uv * x_u / outdeg(u)

with damping ``a``, ``outdeg(u)`` the number of out-edges of u (at least 1)
and ``w_uv`` the edge weight (1 on an unweighted graph). ``r`` is all ones
for PageRank and the seed's indicator for personalized PageRank, whose
columns are independent.

:func:`pagerank_f64` solves that system in float64 with scipy's sparse
product, to round-off. :func:`residual_max` applies it once, in float64, to
given answers: ``b + A x - x`` is how far one more sweep would move each
vertex, zero only at the fixpoint, and since every column of ``A`` sums to
at most ``damping`` (edge weights at most 1), ``|x - x*|_1 <= |b + A x - x|_1 / (1 - damping)``.
:func:`pagerank_control` is the same iteration put in the program's place
one precision lower than the configuration states (float32 state ->
bfloat16 state and products, float32 sums), run on the device with jax; it
must come out not correct. :func:`gaps` and :func:`residual_max` give the
numbers compared.
"""
from __future__ import annotations

import numpy as np


def _operator(n: int, src, dst, w, damping: float):
    """``A`` with ``A[v, u] = damping * w_uv / outdeg(u)``, as CSR float64."""
    import scipy.sparse as sp

    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    outdeg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    wt = np.ones(len(src)) if w is None else np.asarray(w, np.float64)
    vals = damping * wt / outdeg[src]
    return sp.csr_matrix((vals, (dst, src)), shape=(n, n))


def restart(n: int, damping: float, seeds=None) -> np.ndarray:
    """``(1 - a) * r``: ``(n,)`` for PageRank, ``(n, k)`` for ``k`` seeds."""
    if seeds is None:
        return np.full(n, 1.0 - damping)
    r = np.zeros((n, len(seeds)))
    r[np.asarray(seeds, np.int64), np.arange(len(seeds))] = 1.0 - damping
    return r


def pagerank_f64(n: int, src, dst, w, damping: float, seeds=None,
                 tol: float = 1e-13, max_iters: int = 5000,
                 check_every: int = 4) -> np.ndarray:
    """The fixpoint in float64, by Jacobi iteration until a step moves no
    entry by more than ``tol`` (the contraction is at most ``damping`` per
    step); the step is measured every ``check_every`` iterations."""
    a = _operator(n, src, dst, w, damping)
    b = restart(n, damping, seeds)
    x = b.copy()
    for it in range(1, max_iters + 1):
        x_new = a @ x
        x_new += b
        if it % check_every == 0 and np.max(np.abs(x_new - x)) <= tol:
            return x_new
        x = x_new
    raise RuntimeError(f"float64 reference did not converge in {max_iters} steps")


def residual_max(n: int, src, dst, w, damping: float, seeds, answers,
                 batch: int = 64) -> float:
    """The widest ``|b + A x - x|`` over every vertex of every answer, in
    float64. ``answers[i]`` is the ``(n,)`` answer to personalized PageRank
    from ``seeds[i]``; a missing or non-finite answer reads infinitely far."""
    a = _operator(n, src, dst, w, damping)
    worst = 0.0
    for lo in range(0, len(answers), batch):
        cols = answers[lo:lo + batch]
        if any(x is None or np.shape(x) != (n,) or not np.all(np.isfinite(x))
               for x in cols):
            return float("inf")
        x = np.stack([np.asarray(c, np.float64) for c in cols], axis=1)
        r = a @ x
        r += restart(n, damping, seeds[lo:lo + batch])
        r -= x
        worst = max(worst, float(np.abs(r).max()))
    return worst


def pagerank_control(n: int, src, dst, w, damping: float, seeds=None,
                     max_iters: int = 1000) -> np.ndarray:
    """The reference put in the program's place one precision lower
    (bfloat16 state and edge factors, each product exact and the sums in
    float32, as the TPU computes a bfloat16 product), on the default device,
    iterated until the state stops changing."""
    import jax
    import jax.numpy as jnp

    src = np.asarray(src, np.int32)
    outdeg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float32)
    wt = np.ones(len(src), np.float32) if w is None else np.asarray(w, np.float32)
    bf, f32 = jnp.bfloat16, jnp.float32
    fac = jnp.asarray((damping * wt / outdeg[src]).astype(np.float32)).astype(bf)
    b = jnp.asarray(restart(n, damping, seeds).astype(np.float32))
    s, d = jnp.asarray(src), jnp.asarray(np.asarray(dst, np.int32))

    @jax.jit
    def step(x):
        f = fac if x.ndim == 1 else fac[:, None]
        msg = x[s].astype(f32) * f.astype(f32)
        return (b + jax.ops.segment_sum(msg, d, num_segments=n)).astype(bf)

    x = b.astype(bf)
    for _ in range(max_iters):
        x_new = step(x)
        if bool(jnp.all(x_new == x)):
            break
        x = x_new
    return np.asarray(jax.device_get(x_new), np.float64)


def gaps(x, ref) -> dict:
    """The widest gaps of an answer from the reference, over every vertex
    (and column): ``abs_gap`` in the answer's units and ``rel_gap`` as a
    share of the reference value. Non-finite answers read as infinitely
    far."""
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    if x.shape != ref.shape or not np.all(np.isfinite(x)):
        return {"abs_gap": float("inf"), "rel_gap": float("inf")}
    diff = np.abs(x - ref)
    return {"abs_gap": float(diff.max()),
            "rel_gap": float((diff / np.maximum(np.abs(ref), 1e-30)).max())}


def widest(readings) -> dict:
    """The widest of several answers' :func:`gaps`, kind by kind."""
    out: dict = {}
    for g in readings:
        for k, v in g.items():
            out[k] = max(out.get(k, 0.0), v)
    return out
