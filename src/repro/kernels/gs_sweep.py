"""Persistent multi-sweep block Gauss–Seidel megakernel.

The paper's reordering cuts *rounds*; this kernel removes the fixed
per-round tax that reordering cannot touch. One ``pallas_call`` now executes
up to ``sweeps`` Gauss–Seidel sweeps over a 2-D grid ``(sweeps, nb)`` — TPU
grids run sequentially with the sweep dimension outermost, so the state stays
resident in HBM (aliased input->output) across the whole batch and the host
checks convergence once per *batch* instead of once per sweep. Three fused
mechanisms make per-round cost proportional to remaining work:

* **In-kernel convergence.** Every block update folds its per-column delta
  (``kernels.semirings.DELTA_METRIC``: max-|residual| for the plus semiring,
  changed-entry count for the lattice semirings — the same metrics the host
  drivers threshold) into a VMEM accumulator; the end of each sweep writes
  the accumulated ``(1, d)`` row into the ``deltas[sweeps, d]`` output and
  sets an SMEM ``done`` flag once all columns drop to ``eps``.

* **Early-out.** Once ``done`` is set, the remaining grid steps are
  predicated no-ops: no gather DMAs, no tile DMAs, no reduction — the
  leftover sweeps of the batch cost grid bookkeeping only, and their delta
  rows report 0.

* **Active-frontier block skipping.** A per-row-block dirty bitmap (SMEM,
  seeded from the ``dirty`` input, exported to the ``dirty_out`` output so
  the next batch resumes the frontier) gates each block update behind
  ``@pl.when``: a block whose in-neighbor blocks all held still since its
  last update is skipped with zero HBM traffic. When an update *changes* a
  block (bitwise — any entry, any column), its dependents — read from the
  block reverse-dependency CSR ``revptr``/``revrows``
  (`graphs.blocked.FlatBSRMatrix.reverse_deps`) — are re-marked dirty:
  blocks later in this sweep see the mark immediately (Gauss–Seidel
  freshness at frontier granularity), earlier blocks next sweep. Because a
  clean block's recompute is bitwise a no-op by construction, frontier
  execution is **bitwise-equivalent** to full sweeps, per sweep, per column.

The frontier contract: a clean (``dirty == 0``) block asserts that its
current state already satisfies its update equation. Cold starts must
therefore seed all-dirty (``graphs.blocked.frontier_blocks(None, ...)``);
warm starts may seed only the delta-touched blocks (see
``engine.incremental``) because monotone combines keep every untouched
block self-consistent.

Data layout is the ragged flat BSR of `graphs.blocked.FlatBSRMatrix`
(tiles[nnz_blocks, bs, bs] + scalar-prefetched rowptr/tilecols), walked with
the double-buffered gather+tile DMA pipeline: tile t+1's adjacency tile and
gathered source block stream into the opposite scratch slot while tile t
reduces, and the destination block's previous-round fetch overlaps the whole
reduction.

Update rule per destination block i (semiring & combine as in the engines):

    agg  = REDUCE_t  tiles[t] (x) x[tilecols[t]],  t in [rowptr[i], rowptr[i+1])
    newb = combine(c[i], agg, oldb);  newb = fixed ? x0 : newb
    x[i] <- newb

VMEM per step: 2 adjacency tiles (bs, bs) + 7 state blocks (bs, d) + the
(1, d) delta row and (1, 1) active counter — independent of both k_max and
``sweeps``. SMEM holds the nb dirty flags and the done bit.

Supported (semiring, combine) pairs and their accumulator identities:

    plus_times / replace   acc 0     (PageRank family: c + sum w*x)
    min_plus   / min_old   acc +BIG  (SSSP/BFS/CC: min(old, c, min x+w))
    max_min    / max_old   acc -BIG  (SSWP: max(old, c, max min(x, w)))
    max_times  / max_old   acc -BIG  (reachability: max(old, c, max w*x);
                                      requires nonnegative states — absent
                                      in-tile edges contribute w=0 products)

``gs_sweep_pallas`` (the legacy single-sweep entry point) is the same kernel
with ``sweeps=1``, an all-dirty frontier, and the delta/frontier outputs
discarded — one body, one set of semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.semirings import ACC_IDENTITY, DELTA_METRIC, delta_cols


def or_dirty_blocks(dirty, vertex_mask, n: int, bs: int) -> np.ndarray:
    """OR a vertex-level support mask into a per-row-block dirty bitmap.

    This is the frontier seeding for a *column subset*: when the serving
    layer swaps a new query into one column of a resident state matrix, only
    the blocks whose update equations the newcomer's injection invalidates —
    its support (seeds/sources/pinned vertices, `engine.harness.
    column_support`) plus the vertices the support's out-edges feed — stop
    being self-consistent; OR-ing them into the carried bitmap makes the
    next megakernel batch re-touch exactly what the newcomer needs, while
    blocks that are clean for every other in-flight column stay skipped.
    Sound because the clean contract is per-block over *all* columns and an
    unsupported vertex of the fresh column whose in-neighbors are all
    unsupported holds its inert fill, whose update is a bitwise no-op until
    an in-neighbor moves (and the kernel re-marks dependents when one does).

    ``dirty`` may be host numpy or a device jax array; a jax bitmap is OR-ed
    functionally and stays on device (the serving session carries it across
    batches without host sync — the vertex mask itself is tiny, host-built
    from the newcomer query's own host-side vectors).
    """
    from repro.graphs.blocked import frontier_blocks

    add = frontier_blocks(np.asarray(vertex_mask), n, bs)
    if hasattr(dirty, "at"):  # jax array: stays device-resident
        return jnp.maximum(dirty, jnp.asarray(add)).astype(jnp.int32)
    return np.maximum(np.asarray(dirty, np.int32), add).astype(np.int32)

# semiring/combine pairs the kernel body implements, with the accumulator
# identity (kernels.semirings.ACC_IDENTITY) each reduction starts from.
# Anything else must fail loudly — a wrong identity silently computes
# garbage shaped like an answer.
_SUPPORTED = {
    ("plus_times", "replace"),
    ("min_plus", "min_old"),
    ("max_min", "max_old"),
    ("max_times", "max_old"),
}


# f32 tiles must not take the MXU's one-pass bf16 default on a TPU: the
# PageRank family converges to eps ~1e-6, far below bf16's 8-bit mantissa
_DOT_PRECISION = jax.lax.Precision.HIGHEST


def _reduce_tile(semiring: str, acc_ref, tile, xs):
    """acc <- acc (reduce) tile (x) xs for one (bs, bs) tile and (bs, d)
    source block."""
    if semiring == "plus_times":
        acc_ref[...] += jnp.dot(tile, xs, preferred_element_type=acc_ref.dtype,
                                precision=_DOT_PRECISION)
    elif semiring == "min_plus":
        part = jnp.min(tile[:, :, None] + xs[None, :, :], axis=1)
        acc_ref[...] = jnp.minimum(acc_ref[...], part)
    elif semiring == "max_min":
        part = jnp.max(jnp.minimum(tile[:, :, None], xs[None, :, :]), axis=1)
        acc_ref[...] = jnp.maximum(acc_ref[...], part)
    elif semiring == "max_times":
        part = jnp.max(tile[:, :, None] * xs[None, :, :], axis=1)
        acc_ref[...] = jnp.maximum(acc_ref[...], part)
    else:
        raise ValueError(semiring)


def _make_kernel(semiring: str, combine: str, res_kind: str, bs: int,
                 nb: int, sweeps: int, eps: float):
    def kernel(rowptr_ref, tilecols_ref, revptr_ref, revrows_ref,
               dirty_init_ref, tiles_hbm, c_ref, x0_ref, fixed_ref, x_hbm,
               x_out, deltas_out, active_out, dirty_out,
               xblk, tblk, oldblk, acc, dacc, cnt, dirty_s, done_s,
               sem_x, sem_t, sem_o):
        s = pl.program_id(0)
        i = pl.program_id(1)

        # batch start: load the caller's frontier, clear the done bit
        @pl.when(jnp.logical_and(s == 0, i == 0))
        def _seed_frontier():
            done_s[0] = 0

            def cp(j, _):
                dirty_s[j] = dirty_init_ref[j]
                return 0

            jax.lax.fori_loop(0, nb, cp, 0)

        # sweep start: zero this sweep's delta row and active counter, so
        # early-outed sweeps report 0 movement / 0 blocks touched
        @pl.when(i == 0)
        def _sweep_reset():
            dacc[...] = jnp.zeros_like(dacc)
            cnt[...] = jnp.zeros_like(cnt)

        work = jnp.logical_and(done_s[0] == 0, dirty_s[i] != 0)

        @pl.when(work)
        def _update():
            dirty_s[i] = 0
            lo = rowptr_ref[i]
            hi = rowptr_ref[i + 1]

            acc[...] = jnp.full_like(acc, ACC_IDENTITY[semiring])

            def gather(t, slot):
                # source block for tile t, read from the *aliased output* so
                # earlier grid steps' writes (this sweep) are visible
                c = tilecols_ref[t]
                return pltpu.make_async_copy(
                    x_out.at[pl.ds(c * bs, bs)], xblk.at[slot], sem_x.at[slot]
                )

            def fetch_tile(t, slot):
                return pltpu.make_async_copy(
                    tiles_hbm.at[t], tblk.at[slot], sem_t.at[slot]
                )

            # the destination block's previous value: fetched once, its DMA
            # overlaps the whole tile reduction below
            old_cp = pltpu.make_async_copy(
                x_out.at[pl.ds(i * bs, bs)], oldblk, sem_o
            )
            old_cp.start()

            # double-buffer warm-up: tile lo's DMAs go into slot 0
            @pl.when(lo < hi)
            def _warmup():
                gather(lo, 0).start()
                fetch_tile(lo, 0).start()

            def body(t, _):
                slot = jax.lax.rem(t - lo, 2)
                nxt = 1 - slot

                # start tile t+1's fetches before blocking on tile t's
                @pl.when(t + 1 < hi)
                def _prefetch():
                    gather(t + 1, nxt).start()
                    fetch_tile(t + 1, nxt).start()

                gather(t, slot).wait()
                fetch_tile(t, slot).wait()
                _reduce_tile(semiring, acc, tblk[slot], xblk[slot])
                return 0

            jax.lax.fori_loop(lo, hi, body, 0)

            old_cp.wait()
            old = oldblk[...]
            if combine == "replace":
                new = c_ref[...] + acc[...]
            elif combine == "min_old":
                new = jnp.minimum(old, jnp.minimum(c_ref[...], acc[...]))
            elif combine == "max_old":
                new = jnp.maximum(old, jnp.maximum(c_ref[...], acc[...]))
            else:
                raise ValueError(combine)
            new = jnp.where(fixed_ref[...] != 0, x0_ref[...], new)

            # per-column delta in the engines' residual metric — the shared
            # definition, so in-kernel and host convergence always agree
            dblk = delta_cols(res_kind, new, old, xp=jnp,
                              keepdims=True).astype(dacc.dtype)
            if res_kind == "linf":
                dacc[...] = jnp.maximum(dacc[...], dblk)
            else:
                dacc[...] += dblk
            cnt[...] += 1.0
            changed = jnp.any(new != old)

            acc[...] = new.astype(acc.dtype)
            cp = pltpu.make_async_copy(acc, x_out.at[pl.ds(i * bs, bs)], sem_o)
            cp.start()
            cp.wait()

            # this block moved (bitwise): every dependent's cached "my inputs
            # held still" claim is void — re-mark them via the reverse CSR.
            # A diagonal tile re-marks i itself, which is exactly right: its
            # own state is one of its inputs then.
            @pl.when(changed)
            def _mark_dependents():
                def mk(t, _):
                    dirty_s[revrows_ref[t]] = 1
                    return 0

                jax.lax.fori_loop(revptr_ref[i], revptr_ref[i + 1], mk, 0)

        deltas_out[...] = dacc[...]
        active_out[...] = cnt[...]

        # sweep end: all columns at or below eps -> predicate the remaining
        # sweeps of this batch away (sticky; zeroed deltas keep it set)
        @pl.when(i == nb - 1)
        def _sweep_end():
            done_now = jnp.where(jnp.all(dacc[...] <= eps), 1, 0)
            done_s[0] = jnp.maximum(done_s[0], done_now.astype(done_s.dtype))

        # batch end: export the frontier so the next batch resumes it
        @pl.when(jnp.logical_and(s == sweeps - 1, i == nb - 1))
        def _export_frontier():
            def wr(j, _):
                dirty_out[j] = dirty_s[j]
                return 0

            jax.lax.fori_loop(0, nb, wr, 0)

    return kernel


def _check_pair(semiring: str, combine: str):
    # each pair needs its own accumulator identity and reduction; an unknown
    # pair would start from the wrong identity and silently compute garbage.
    # Mirror pack_algorithm's guard (kernels/ops.py) here so direct kernel
    # callers fail loudly too.
    if (semiring, combine) not in _SUPPORTED:
        raise NotImplementedError(
            f"gs_sweep: unsupported semiring/combine pair "
            f"({semiring!r}, {combine!r}); supported: {sorted(_SUPPORTED)}"
        )


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "combine", "res_kind", "bs", "sweeps",
                     "eps", "interpret"),
)
def gs_multisweep_pallas(
    rowptr: jnp.ndarray,    # int32[nb + 1]      scalar-prefetched
    tilecols: jnp.ndarray,  # int32[nnz_blocks]  scalar-prefetched
    revptr: jnp.ndarray,    # int32[nb + 1]      reverse-dep CSR, prefetched
    revrows: jnp.ndarray,   # int32[nnz_blocks]  dependents of each src block
    dirty: jnp.ndarray,     # int32[nb]          frontier bitmap (1 = dirty)
    tiles: jnp.ndarray,     # f32[nnz_blocks, bs, bs]  ragged flat tiles
    c: jnp.ndarray,         # f32[nb*bs, d]   per-vertex const
    x0: jnp.ndarray,        # f32[nb*bs, d]
    fixed: jnp.ndarray,     # f32[nb*bs, d]   1.0 where pinned
    x: jnp.ndarray,         # f32[nb*bs, d]   state (aliased to output)
    *,
    semiring: str = "plus_times",
    combine: str = "replace",
    res_kind: str | None = None,
    bs: int,
    sweeps: int = 1,
    eps: float = -1.0,
    interpret: bool,
):
    """Run up to ``sweeps`` Gauss–Seidel sweeps in one persistent kernel.

    Returns ``(x, deltas, active, dirty_out)``:

    * ``x``        f32[n, d]  — state after the batch (input aliased)
    * ``deltas``   f32[sweeps, d] — per-sweep per-column convergence metric
      (``res_kind``; defaults to ``DELTA_METRIC[semiring]``). Early-outed
      sweeps report 0, so the host reconstructs exact per-column round
      counts from this trace.
    * ``active``   f32[sweeps, 1] — blocks actually updated per sweep (the
      ``active_block_fraction`` numerator; early-outed/skipped sweeps: 0)
    * ``dirty_out`` int32[nb] — the frontier after the batch; feed it back
      as ``dirty`` to resume, or all-ones to force a full sweep.

    ``deltas`` and ``active`` are also the megakernel's telemetry feed:
    the engine turns them (after its existing once-per-batch readout) into
    ``RunResult.convergence_trace`` — per-round residual and
    ``active_block_fraction`` in ``swept_block_cells`` units
    (`repro.obs.telemetry.trace_from_block_activity`) — so enabling
    observability never adds a device->host transfer.

    ``eps`` is the in-kernel early-out threshold (static): once a sweep's
    deltas are all <= eps, the batch's remaining sweeps are predicated
    no-ops. ``eps=-1.0`` disables the early-out (metrics are >= 0).

    ``interpret`` runs the Pallas interpreter instead of lowering for the
    TPU (`repro.kernels.ops.interpret_mode` decides it for every caller).
    Lowered, ``d`` must be a multiple of 128 (the lane width of the row-block
    DMAs) and ``bs`` a multiple of 128 (the tile DMA's minor dimension).
    """
    _check_pair(semiring, combine)
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    if res_kind is None:
        res_kind = DELTA_METRIC[semiring]
    nb = rowptr.shape[0] - 1
    n, d = x.shape
    assert n == nb * bs
    assert tiles.ndim == 3 and tiles.shape[1:] == (bs, bs)
    assert tilecols.shape[0] == tiles.shape[0]
    assert revptr.shape == rowptr.shape and dirty.shape == (nb,)
    # the batched engine (run_async_block(backend="pallas")) feeds real
    # multi-query columns here; all per-vertex operands must carry them
    assert c.shape == x0.shape == fixed.shape == (n, d), (
        c.shape, x0.shape, fixed.shape, (n, d)
    )
    kernel = _make_kernel(semiring, combine, res_kind, bs, nb, sweeps,
                          float(eps))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(sweeps, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # ragged tiles, DMA'd manually
            pl.BlockSpec((bs, d), lambda s, i, *_: (i, 0)),
            pl.BlockSpec((bs, d), lambda s, i, *_: (i, 0)),
            pl.BlockSpec((bs, d), lambda s, i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # per-sweep rows are 3-D so each block spans the array's last two
        # dims, the only sub-array block Mosaic takes below (8, 128); the
        # frontier is written one flag at a time, which only SMEM allows
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),                       # x
            pl.BlockSpec((None, 1, d), lambda s, i, *_: (s, 0, 0)),  # deltas
            pl.BlockSpec((None, 1, 1), lambda s, i, *_: (s, 0, 0)),  # active
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # dirty
        ),
        scratch_shapes=[
            pltpu.VMEM((2, bs, d), x.dtype),   # xblk: double-buffered gathers
            pltpu.VMEM((2, bs, bs), x.dtype),  # tblk: double-buffered tiles
            pltpu.VMEM((bs, d), x.dtype),      # oldblk
            pltpu.VMEM((bs, d), x.dtype),      # acc
            pltpu.VMEM((1, d), jnp.float32),   # dacc: sweep delta per column
            pltpu.VMEM((1, 1), jnp.float32),   # cnt: active blocks this sweep
            pltpu.SMEM((nb,), jnp.int32),      # dirty flags (the frontier)
            pltpu.SMEM((1,), jnp.int32),       # done bit (early-out)
            pltpu.SemaphoreType.DMA((2,)),     # sem_x
            pltpu.SemaphoreType.DMA((2,)),     # sem_t
            pltpu.SemaphoreType.DMA,           # sem_o (old fetch + writeback)
        ],
    )
    x_out, deltas, active, dirty_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((sweeps, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((sweeps, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
        ),
        # x (after the 5 prefetch args) -> output 0
        input_output_aliases={9: 0},
        interpret=interpret,
    )(rowptr, tilecols, revptr, revrows, dirty, tiles, c, x0, fixed, x)
    return x_out, deltas[:, 0, :], active[:, 0, :], dirty_out


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "combine", "bs", "interpret"),
)
def gs_sweep_pallas(
    rowptr: jnp.ndarray,    # int32[nb + 1]      scalar-prefetched
    tilecols: jnp.ndarray,  # int32[nnz_blocks]  scalar-prefetched
    tiles: jnp.ndarray,     # f32[nnz_blocks, bs, bs]  ragged flat tiles
    c: jnp.ndarray,         # f32[nb*bs, d]   per-vertex const
    x0: jnp.ndarray,        # f32[nb*bs, d]
    fixed: jnp.ndarray,     # f32[nb*bs, d]   1.0 where pinned
    x: jnp.ndarray,         # f32[nb*bs, d]   state (donated; aliased to output)
    *,
    semiring: str = "plus_times",
    combine: str = "replace",
    bs: int,
    interpret: bool,
) -> jnp.ndarray:
    """One full sweep, state in / state out — the legacy per-sweep entry
    point, now the ``sweeps=1`` megakernel with an all-dirty frontier and the
    delta/frontier outputs discarded (an empty reverse-dep CSR makes the
    dirty bookkeeping a no-op). Bitwise-identical to the dedicated
    single-sweep kernel it replaces: every block updates, in the same order,
    with the same tile walk."""
    _check_pair(semiring, combine)
    nb = rowptr.shape[0] - 1
    x_new, _, _, _ = gs_multisweep_pallas(
        rowptr, tilecols,
        jnp.zeros((nb + 1,), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.ones((nb,), jnp.int32),
        tiles, c, x0, fixed, x,
        semiring=semiring, combine=combine, bs=bs, sweeps=1, eps=-1.0,
        interpret=interpret,
    )
    return x_new
