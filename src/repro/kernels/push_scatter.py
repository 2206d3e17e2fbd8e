"""Bucketed vertex-granular residual-push scatter kernel.

The block engines (and the persistent megakernel's frontier) skip work at
``bs``-block granularity; when a serving delta or a personalized query
touches a handful of vertices, whole blocks still sweep. This kernel is the
ultra-sparse regime: the push engine (`engine.push`) maintains ``(p, r)``
state per column — ``p`` the settled estimate, ``r`` the pending residual —
and each launch processes one *round* of active vertices, binned by the
host into priority buckets.

Grid = ``(buckets, cap)``: TPU grids run sequentially with the bucket
dimension outermost, so bucket 0's vertices (best priority — smallest
tentative distance for min_plus, largest pending residual for the sum
semiring) settle before bucket 1 reads them. That ordering is exactly
delta-stepping for SSSP, and largest-residual-first push for PageRank — and
because every slot reads ``(p, r)`` through the *aliased outputs*, each
vertex sees every earlier scatter of the same launch (Gauss–Seidel
freshness at vertex granularity).

Per slot ``k = b * cap + j`` with vertex ``u = vid[k]`` (``-1`` pads):

    sum (plus_times):      push = r[u];  p[u] += push;  r[u] = 0
                           r[v] += w_uv * push              (out-edges u->v)
    lattice (min/max):     push = combine(p[u], r[u]);  p[u] = push
                           r[u] = ACC_IDENTITY
                           r[v] = reduce(r[v], edge_op(push, w_uv))

``u``'s rows are settled *before* the scatter, so a self-loop lands its
message on the emptied residual row (the sum invariant ``r = c + Wp - p``
survives self-loops).

The CSR out-neighbor segment ``nbrs[seg_start[k] : +seg_len[k]]`` is walked
in the ``ecap``-aligned chunks it overlaps: each chunk is one DMA of neighbor
ids and weights into SMEM scratch (scalar-indexable), then per-edge (1, d)
residual rows are gather/scatter-DMA'd through VMEM. A segment can start
anywhere, so the chunk start is rounded down to a multiple of ``ecap`` and
the edge loop skips the slots outside the segment: the TPU tiles a 1-D HBM
array by 1,024 elements and accepts only a DMA start it can prove aligned.
``nbrs``/``ew`` are padded to a multiple of ``ecap`` (`pad_edges`); a vertex
of degree ``deg`` costs at most ``ceil(deg/ecap) + 1`` chunk DMAs.

VMEM per step: four (1, d) rows + two (1, 1) counters; SMEM: the two
(ecap,) edge buffers — independent of n, m, and d beyond the rows
(budgeted in `kernels.budgets` as ``push_scatter_pallas``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.semirings import ACC_IDENTITY

# semirings the scatter body implements; mirror pack_algorithm's guard so
# direct callers fail loudly instead of pushing with a wrong identity
_SUPPORTED = ("plus_times", "min_plus", "max_min", "max_times")


def _check_semiring(semiring: str) -> None:
    if semiring not in _SUPPORTED:
        raise NotImplementedError(
            f"push_scatter: unsupported semiring {semiring!r}; "
            f"supported: {sorted(_SUPPORTED)}"
        )


def _make_kernel(semiring: str, buckets: int, cap: int, ecap: int):
    ident = ACC_IDENTITY[semiring]

    def kernel(vid_ref, seg_ref, len_ref, nbrs_hbm, ew_hbm, p_hbm, r_hbm,
               p_out, r_out, pushed_out, edges_out,
               urow, rrow, vrow, push, cnt, ecnt, ebuf, wbuf,
               sem_u, sem_r, sem_v, sem_e):
        b = pl.program_id(0)
        j = pl.program_id(1)
        k = b * cap + j

        # bucket start: zero this bucket's work counters
        @pl.when(j == 0)
        def _bucket_reset():
            cnt[...] = jnp.zeros_like(cnt)
            ecnt[...] = jnp.zeros_like(ecnt)

        u = vid_ref[k]

        @pl.when(u >= 0)
        def _push_vertex():
            # u's (p, r) rows, read through the aliased outputs so every
            # earlier slot's settle/scatter this launch is already visible
            cp_u = pltpu.make_async_copy(p_out.at[pl.ds(u, 1)], urow, sem_u)
            cp_r = pltpu.make_async_copy(r_out.at[pl.ds(u, 1)], rrow, sem_r)
            cp_u.start()
            cp_r.start()
            cp_u.wait()
            cp_r.wait()

            if semiring == "plus_times":
                push[...] = rrow[...]
                urow[...] = urow[...] + rrow[...]
            elif semiring == "min_plus":
                push[...] = jnp.minimum(urow[...], rrow[...])
                urow[...] = push[...]
            else:  # max_min / max_times
                push[...] = jnp.maximum(urow[...], rrow[...])
                urow[...] = push[...]
            rrow[...] = jnp.full_like(rrow, ident)

            # settle u BEFORE scattering: a self-loop u->u must land its
            # message on the emptied residual row, not the pre-push one
            wb_u = pltpu.make_async_copy(urow, p_out.at[pl.ds(u, 1)], sem_u)
            wb_u.start()
            wb_u.wait()
            wb_r = pltpu.make_async_copy(rrow, r_out.at[pl.ds(u, 1)], sem_r)
            wb_r.start()
            wb_r.wait()

            lo = seg_ref[k]
            deg = len_ref[k]
            hi = lo + deg
            first = lo // ecap

            def chunk(ci, _):
                # one aligned static-size DMA per chunk the segment overlaps;
                # the edge loop's bounds drop the slots outside [lo, hi)
                off = pl.multiple_of((first + ci) * ecap, ecap)
                cp_n = pltpu.make_async_copy(
                    nbrs_hbm.at[pl.ds(off, ecap)], ebuf, sem_e.at[0]
                )
                cp_w = pltpu.make_async_copy(
                    ew_hbm.at[pl.ds(off, ecap)], wbuf, sem_e.at[1]
                )
                cp_n.start()
                cp_w.start()
                cp_n.wait()
                cp_w.wait()

                def edge(t, _):
                    v = ebuf[t]
                    w = wbuf[t]
                    cp_v = pltpu.make_async_copy(
                        r_out.at[pl.ds(v, 1)], vrow, sem_v
                    )
                    cp_v.start()
                    cp_v.wait()
                    if semiring == "plus_times":
                        vrow[...] = vrow[...] + w * push[...]
                    elif semiring == "min_plus":
                        vrow[...] = jnp.minimum(vrow[...], push[...] + w)
                    elif semiring == "max_min":
                        vrow[...] = jnp.maximum(
                            vrow[...], jnp.minimum(push[...], w)
                        )
                    else:  # max_times
                        vrow[...] = jnp.maximum(vrow[...], push[...] * w)
                    wb_v = pltpu.make_async_copy(
                        vrow, r_out.at[pl.ds(v, 1)], sem_v
                    )
                    wb_v.start()
                    wb_v.wait()
                    return 0

                jax.lax.fori_loop(jnp.maximum(lo - off, 0),
                                  jnp.minimum(hi - off, ecap), edge, 0)
                return 0

            nchunks = jnp.where(deg > 0, (hi - 1) // ecap - first + 1, 0)
            jax.lax.fori_loop(0, nchunks, chunk, 0)

            cnt[...] += 1.0
            ecnt[...] += deg.astype(jnp.float32)

        pushed_out[...] = cnt[...]
        edges_out[...] = ecnt[...]

    return kernel


# edges per chunk DMA: the TPU tiles a 1-D 32-bit HBM array by 1,024
EDGE_CHUNK = 1024


def pad_edges(a: np.ndarray, ecap: int = EDGE_CHUNK) -> np.ndarray:
    """Zero-pad a CSR edge array to a whole number of ``ecap`` chunks (at
    least one), the length `push_scatter_pallas` requires of ``nbrs``/``ew``."""
    out = np.zeros(max(1, -(-len(a) // ecap)) * ecap, a.dtype)
    out[: len(a)] = a
    return out


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "buckets", "cap", "ecap", "interpret"),
)
def push_scatter_pallas(
    vid: jnp.ndarray,        # int32[buckets*cap]  vertex per slot, -1 = pad
    seg_start: jnp.ndarray,  # int32[buckets*cap]  CSR out-segment start
    seg_len: jnp.ndarray,    # int32[buckets*cap]  CSR out-segment length
    nbrs: jnp.ndarray,       # int32[m_pad]        CSR out-neighbors (pad_edges)
    ew: jnp.ndarray,         # f32[m_pad]          edge weights (pad_edges)
    p: jnp.ndarray,          # f32[n, d]           settled state (aliased)
    r: jnp.ndarray,          # f32[n, d]           pending residual (aliased)
    *,
    semiring: str = "plus_times",
    buckets: int,
    cap: int,
    ecap: int = EDGE_CHUNK,
    interpret: bool,
):
    """One bucketed push round. Returns ``(p, r, pushed, edges)``:

    * ``p``, ``r``    f32[n, d] — state after the round (inputs aliased)
    * ``pushed``      f32[buckets, 1] — vertices settled per bucket
    * ``edges``       f32[buckets, 1] — edge messages scattered per bucket

    Slots run in flat ``b * cap + j`` order; the host places the best
    priority bucket first. Padding slots (``vid < 0``) are predicated
    no-ops: zero DMAs, zero messages.

    ``interpret`` runs the Pallas interpreter instead of lowering for the
    TPU (`repro.kernels.ops.interpret_mode`). Lowered, ``d`` must be a
    multiple of 128: the (1, d) row DMAs move whole lanes.
    """
    _check_semiring(semiring)
    if buckets < 1 or cap < 1 or ecap < 1:
        raise ValueError(f"buckets/cap/ecap must be >= 1, got "
                         f"{(buckets, cap, ecap)}")
    n, d = p.shape
    assert r.shape == (n, d), (r.shape, p.shape)
    assert vid.shape == (buckets * cap,), (vid.shape, buckets, cap)
    assert seg_start.shape == vid.shape and seg_len.shape == vid.shape
    assert nbrs.shape == ew.shape and nbrs.ndim == 1
    assert nbrs.shape[0] % ecap == 0, (nbrs.shape, ecap)
    kernel = _make_kernel(semiring, buckets, cap, ecap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(buckets, cap),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # nbrs, chunk-DMA'd manually
            pl.BlockSpec(memory_space=pl.ANY),  # ew
            pl.BlockSpec(memory_space=pl.ANY),  # p (aliased)
            pl.BlockSpec(memory_space=pl.ANY),  # r (aliased)
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),  # p (aliased)
            pl.BlockSpec(memory_space=pl.ANY),  # r (aliased)
            # 3-D so each (1, 1) block spans the array's last two dims
            pl.BlockSpec((None, 1, 1), lambda b, j, *_: (b, 0, 0)),  # pushed
            pl.BlockSpec((None, 1, 1), lambda b, j, *_: (b, 0, 0)),  # edges
        ),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),   # urow: u's settled row
            pltpu.VMEM((1, d), jnp.float32),   # rrow: u's residual row
            pltpu.VMEM((1, d), jnp.float32),   # vrow: neighbor residual row
            pltpu.VMEM((1, d), jnp.float32),   # push: the scattered message
            pltpu.VMEM((1, 1), jnp.float32),   # cnt: pushes this bucket
            pltpu.VMEM((1, 1), jnp.float32),   # ecnt: edges this bucket
            pltpu.SMEM((ecap,), jnp.int32),    # ebuf: neighbor-id chunk
            pltpu.SMEM((ecap,), jnp.float32),  # wbuf: weight chunk
            pltpu.SemaphoreType.DMA,           # sem_u (p row)
            pltpu.SemaphoreType.DMA,           # sem_r (r row)
            pltpu.SemaphoreType.DMA,           # sem_v (neighbor row)
            pltpu.SemaphoreType.DMA((2,)),     # sem_e (edge chunk pair)
        ],
    )
    p_out, r_out, pushed, edges = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n, d), p.dtype),
            jax.ShapeDtypeStruct((n, d), r.dtype),
            jax.ShapeDtypeStruct((buckets, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((buckets, 1, 1), jnp.float32),
        ),
        # p, r (after the 3 prefetch args + nbrs + ew) -> outputs 0, 1
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(vid, seg_start, seg_len, nbrs, ew, p, r)
    return p_out, r_out, pushed[:, 0, :], edges[:, 0, :]
