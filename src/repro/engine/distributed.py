"""Distributed engine: vertex blocks sharded across devices (shard_map).

Execution model (DESIGN.md §3): *synchronous across shards, Gauss–Seidel
within a shard*. Each device owns a contiguous range of blocks of the
processing order. Per superstep every device sweeps its own blocks
sequentially against a device-local copy of the full state matrix (so its own
earlier blocks contribute this-round values), then shards are re-assembled —
one all-gather of the state matrix per superstep.

GoGraph's partition-locality objective minimizes cross-shard edges, which is
exactly what keeps this hybrid close to fully-asynchronous Gauss–Seidel in
rounds; the paper's single-machine claim transfers because intra-shard edges
dominate after community-aware reordering.

States are batched ``f32[N, d]`` like every other engine — column j is an
independent query riding the same supersteps with per-column convergence
freezing in the shared round driver. The per-superstep collective volume is
|V|·d·4 bytes (the gathered state matrix), vs. the edge set held
shard-local — the same design large-scale systems (Gemini, Gluon) use for
power-law graphs.

:class:`DistContext` packs one algorithm *structure* (edges + block layout +
mesh) into device operands plus a jitted superstep driver. `run_distributed`
builds a throwaway context per call; `engine.async_block.AsyncBlockSession`
(``backend="distributed"``) keeps one alive as the resident backing of a
serving family whose state spans devices.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.engine.algorithms import AlgoInstance
from repro.engine.convergence import RunResult
from repro.engine import harness
from repro.engine import jax_ops as J


def _pad_blocks(arr: np.ndarray, nb_target: int, fill) -> np.ndarray:
    nb = arr.shape[0]
    if nb == nb_target:
        return arr
    pad = np.full((nb_target - nb,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def make_superstep(
    mesh, axis: str, nb: int, bs: int,
    sem_reduce: str, sem_edge: str, comb: str,
    identity: float, inner: int = 1,
):
    """Build the jittable one-superstep function over ``(N, d)`` states."""
    ndev = int(np.prod([mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]))
    assert nb % ndev == 0
    nb_local = nb // ndev
    axis_name = axis

    def superstep(x_full, esrc, edst, ew, emask, c_blk, fixed_blk, x0_blk):
        # everything below sees the *local* shard of the blocked arrays and a
        # replicated copy of the state matrix
        def inner_fn(x_full, esrc, edst, ew, emask, c_blk, fixed_blk, x0_blk):
            dev = jax.lax.axis_index(axis_name)
            d = x_full.shape[1]

            def block_update(j, x_work):
                gi = dev * nb_local + j  # global block id
                msgs = J.edge_op(sem_edge, x_work[esrc[j]], ew[j])
                msgs = jnp.where(emask[j][:, None], msgs, identity)
                agg = J.segment_reduce(sem_reduce, msgs, edst[j], bs, identity)
                old = jax.lax.dynamic_slice(x_work, (gi * bs, 0), (bs, d))
                new = J.combine(comb, agg, c_blk[j], old, fixed_blk[j], x0_blk[j])
                return jax.lax.dynamic_update_slice(x_work, new, (gi * bs, 0))

            def block_body(j, x_work):
                def one(_, xx):
                    return block_update(j, xx)
                return jax.lax.fori_loop(0, inner, one, x_work)

            x_work = jax.lax.fori_loop(0, nb_local, block_body, x_full)
            # each device contributes its own refreshed slice
            dev0 = dev * nb_local * bs
            return jax.lax.dynamic_slice(x_work, (dev0, 0), (nb_local * bs, d))

        # check_vma=False: the replicated state becomes device-varying after
        # the first block update, which the carry does not declare
        return jax.shard_map(
            inner_fn,
            mesh=mesh,
            in_specs=(P(None), P(axis_name), P(axis_name), P(axis_name),
                      P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
            out_specs=P(axis_name),
            check_vma=False,
        )(x_full, esrc, edst, ew, emask, c_blk, fixed_blk, x0_blk)

    return superstep, nb_local


class DistContext:
    """Packed shard_map operands + jitted round driver for one structure.

    Owns what is constant across runs of one algorithm family: the mesh, the
    device-resident blocked edge arrays (padded to a whole number of blocks
    per device), the padded ``(npad2, d)`` host operand templates, and the
    compiled driver. :meth:`run` then converges any ``(npad2, d)`` state
    against any (same-shape) operand columns — which is exactly what lets a
    serving session mutate operand columns on device between batches and
    keep calling the same compiled superstep loop.
    """

    def __init__(self, algo: AlgoInstance, bs: int, mesh=None,
                 axis: str = "data", inner: int = 1):
        if mesh is None:
            mesh = jax.make_mesh(
                (len(jax.devices()),), (axis,),
                axis_types=(jax.sharding.AxisType.Auto,),
            )
        self.mesh, self.axis, self.bs = mesh, axis, bs
        ndev = mesh.shape[axis]
        be, x0, c, fixed, npad = harness.pack(algo, bs)
        self.nb = ((be.nb + ndev - 1) // ndev) * ndev
        self.npad2 = self.nb * bs
        # each device holds only its own blocks' in-edges, placed where the
        # superstep's shard_map reads them
        edge_sharding = NamedSharding(mesh, P(axis))
        self._edges = tuple(jax.device_put(a, edge_sharding) for a in (
            _pad_blocks(be.esrc, self.nb, 0),
            _pad_blocks(be.edst, self.nb, 0),
            _pad_blocks(be.ew, self.nb, 0.0),
            _pad_blocks(be.emask, self.nb, False),
        ))

        def padm(a, fill):
            out = np.full((self.npad2,) + a.shape[1:], fill, dtype=a.dtype)
            out[: a.shape[0]] = a
            return out

        # host templates; callers device-transfer (sessions keep them there)
        self.x0 = padm(x0, np.asarray(algo.semiring.identity, x0.dtype))
        self.c = padm(c, np.asarray(algo.c_pad_fill, c.dtype))
        self.fixed = padm(fixed, True)
        real_mask = np.zeros(self.npad2, bool)
        real_mask[: algo.n] = True
        self._real_mask = jnp.asarray(real_mask)

        superstep, _ = make_superstep(
            mesh, axis, self.nb, bs,
            algo.semiring.reduce, algo.semiring.edge_op, algo.combine,
            algo.semiring.identity, inner=inner,
        )
        nb, res_kind, eps = self.nb, algo.residual, algo.eps

        @partial(jax.jit, static_argnames=("max_iters", "extrapolate_every"))
        def _run(x_start, esrc, edst, ew, emask, x0v, cv, fxv, real_mask,
                 max_iters: int, extrapolate_every: int):
            d = x_start.shape[1]
            c_blk = cv.reshape(nb, bs, d)
            fixed_blk = fxv.reshape(nb, bs, d)
            x0_blk = x0v.reshape(nb, bs, d)  # pins stay x0 when warm-started

            def round_fn(x):
                return superstep(x, esrc, edst, ew, emask, c_blk,
                                 fixed_blk, x0_blk)

            return harness.loop(
                round_fn, x_start, res_kind=res_kind, eps=eps,
                max_iters=max_iters, real_mask=real_mask,
                extrapolate_every=extrapolate_every,
            )

        self._run = _run

    def run(self, x_start, x0, c, fixed, *, max_iters: int,
            extrapolate_every: int = 0):
        """Drive supersteps to convergence; the `harness.loop` tuple."""
        with jax.set_mesh(self.mesh):
            return self._run(
                jnp.asarray(x_start), *self._edges, jnp.asarray(x0),
                jnp.asarray(c), jnp.asarray(fixed), self._real_mask,
                max_iters=max_iters, extrapolate_every=extrapolate_every,
            )


def _solve(algo: AlgoInstance, o) -> RunResult:
    """Engine body behind ``solve(algo, engine="distributed", ...)``; options
    are already validated (`engine.api.validate_options`)."""
    ctx = DistContext(algo, o.bs, mesh=o.mesh, axis=o.axis, inner=o.inner)
    x_start = harness.init_state(ctx.x0, o.x_init, algo.n)
    out = ctx.run(
        x_start, ctx.x0, ctx.c, ctx.fixed,
        max_iters=o.max_iters, extrapolate_every=o.extrapolate_every,
    )
    return harness.finalize(algo, *out)


def run_distributed(
    algo: AlgoInstance,
    mesh=None,
    axis: str = "data",
    bs: int = 256,
    max_iters: int = 2000,
    inner: int = 1,
    x_init: np.ndarray | None = None,
    extrapolate_every: int = 0,
) -> RunResult:
    """Thin shim over ``solve(algo, engine="distributed")`` — the legacy
    keyword spelling, parity-tested against `engine.api.solve`.

    ``x_init`` warm-starts from a prior state (incremental serving);
    ``extrapolate_every`` enables Aitken acceleration for linear systems
    (see `harness.loop`)."""
    from repro.engine.api import EngineOptions, solve

    return solve(algo, engine="distributed", options=EngineOptions(
        x_init=x_init, extrapolate_every=extrapolate_every, bs=bs,
        inner=inner, max_iters=max_iters, mesh=mesh, axis=axis,
    ))
