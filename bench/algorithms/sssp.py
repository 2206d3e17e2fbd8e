"""Single-source shortest paths: Dijkstra's distances in float64, a
bfloat16 Bellman-Ford as the control, and the two numbers compared.

The distance of v from the root r is the least sum of arc weights over the
paths r -> v (the weights are positive; of parallel arcs the lightest
counts), ``inf`` where no path exists. The program states float32
distances; an unreached vertex holds its ``BIG`` (3e38), and anything at or
above ``UNREACHED`` reads as unreached.

Numbers compared, for one answer ``x`` against its root's reference:

* ``dist_rel_gap``: over the vertices the reference reaches, the widest
  ``|x - ref| / max(ref, 1)``. Every relaxation adds in float32, so a
  sound answer departs by a few roundings along the path; a weight or a
  sum kept in bfloat16 departs by a share of 2^-9.
* ``reach_mismatch``: the vertices that one side reaches and the other does
  not. Reachability does not depend on the precision, so any count is a
  fault: a frontier that stopped early, a state never written.
"""
from __future__ import annotations

import numpy as np

ROOT_ARG = "source"
UNREACHED = 1e30


def _weights(m: int, w) -> np.ndarray:
    return np.ones(m) if w is None else np.asarray(w, np.float64)


def reference(n, src, dst, w, params, roots):
    """float64 distances from each root by ``scipy.sparse.csgraph.dijkstra``."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    wt = _weights(len(src), w)
    # a sparse matrix would add parallel arcs up: keep the lightest of each
    order = np.lexsort((wt, src * n + dst))
    src, dst, wt = src[order], dst[order], wt[order]
    first = np.ones(len(src), bool)
    first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    a = csr_matrix((wt[first], (src[first], dst[first])), shape=(n, n))
    return list(dijkstra(a, directed=True, indices=np.asarray(roots, np.int64)))


def control(n, src, dst, w, params, roots):
    """Frontier Bellman-Ford on the default device with the weights in
    bfloat16 and every sum rounded to bfloat16: each round relaxes the
    out-arcs of the vertices the last round lowered, until none is."""
    import jax
    import jax.numpy as jnp

    bf, f32 = jnp.bfloat16, jnp.float32
    s = jnp.asarray(np.asarray(src, np.int32))
    d = jnp.asarray(np.asarray(dst, np.int32))
    wt = jnp.asarray(_weights(len(src), w).astype(np.float32)).astype(bf).astype(f32)

    @jax.jit
    def one(root):
        def relax(state):
            x, lowered = state
            msg = jnp.where(lowered[s], (x[s].astype(f32) + wt).astype(bf), jnp.inf)
            new = jnp.minimum(x, jax.ops.segment_min(msg, d, num_segments=n))
            return new, new < x

        x0 = jnp.full(n, jnp.inf, bf).at[root].set(0)
        x, _ = jax.lax.while_loop(lambda st: jnp.any(st[1]), relax, (x0, x0 == 0))
        return x

    return [np.asarray(jax.device_get(one(int(r))), np.float64) for r in roots]


def compare(x, ref) -> dict:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    if x.shape != ref.shape:
        return {"dist_rel_gap": float("inf"), "reach_mismatch": float("inf")}
    reached = np.isfinite(ref)
    gap = np.abs(x[reached] - ref[reached]) / np.maximum(ref[reached], 1.0)
    return {"dist_rel_gap": float(gap.max()) if gap.size else 0.0,
            "reach_mismatch": float(np.sum(reached != (x < UNREACHED)))}
