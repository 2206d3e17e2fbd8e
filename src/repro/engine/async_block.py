"""Block Gauss–Seidel engine — the TPU adaptation of the paper's async mode.

The paper's Eq. 2 updates vertices one at a time in processing order, each
consuming neighbors already updated *this* round. A per-vertex sequential
sweep is degenerate on TPU, so we process the order in contiguous *blocks*
(DESIGN.md §3): blocks run sequentially inside one sweep, each block update
gathers the *current* state matrix — blocks earlier in the order therefore
contribute this-round values (positive edges at block granularity), later
blocks contribute previous-round values, exactly Eq. 2 lifted to tiles.

States are batched ``f32[n, d]``: column j is an independent query
(personalized-PageRank seed, SSSP source, ...) riding the same sweep, with
per-column convergence freezing in the shared round driver
(`engine.harness.loop`) so each query keeps its scalar round count and final
state. ``d = 1`` reproduces the scalar engine exactly.

`inner > 1` re-runs each block update against the refreshed state, making
intra-block edges fresh too (local Gauss–Seidel refinement); `inner=1` is the
plain blocked sweep. The engine assumes the algorithm instance has already
been relabeled with the processing order (``AlgoInstance.relabel``), so block
b covers ordinals [b*bs, (b+1)*bs).

``backend="pallas"`` runs sweeps through the fused `kernels.gs_sweep` Pallas
kernel (ragged flat-BSR tiles; interpreted on the CPU, see
`kernels.ops.interpret_mode`) instead of the
pure-JAX gather/segment-reduce sweep. With ``sweeps_per_call=1`` (default)
each sweep is its own kernel launch and the per-sweep driver
(`harness.loop`) keeps the exact per-column freezing semantics; with
``sweeps_per_call=R > 1`` the persistent multi-sweep megakernel executes up
to R sweeps per launch with in-kernel convergence, early-out, and
active-frontier block skipping, and the host checks convergence once per
batch (`harness.sweep_batched_loop`). ``frontier`` optionally seeds the
dirty bitmap from a vertex mask (warm starts whose untouched blocks are
already self-consistent — see `engine.incremental`).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.algorithms import AlgoInstance
from repro.engine.convergence import RunResult
from repro.engine import harness
from repro.engine import jax_ops as J
from repro.obs.trace import tspan


@partial(
    jax.jit,
    static_argnames=(
        "bs", "nb", "sem_reduce", "sem_edge", "comb", "res_kind",
        "max_iters", "inner", "n_real", "extrapolate_every",
    ),
)
def _run(
    esrc, edst, ew, emask, x_start, x0, c, fixed,
    bs: int, nb: int, n_real: int,
    sem_reduce: str, sem_edge: str, comb: str, res_kind: str,
    eps: float, max_iters: int, identity: float, inner: int,
    extrapolate_every: int,
):
    d = x0.shape[1]
    c_blk = c.reshape(nb, bs, d)
    fixed_blk = fixed.reshape(nb, bs, d)
    x0_blk = x0.reshape(nb, bs, d)  # pin source stays x0 even when warm-started
    real_mask = (jnp.arange(nb * bs) < n_real)

    def block_update(i, x):
        srcs = esrc[i]
        msgs = J.edge_op(sem_edge, x[srcs], ew[i])
        msgs = jnp.where(emask[i][:, None], msgs, identity)
        agg = J.segment_reduce(sem_reduce, msgs, edst[i], bs, identity)
        old = jax.lax.dynamic_slice(x, (i * bs, 0), (bs, d))
        new = J.combine(comb, agg, c_blk[i], old, fixed_blk[i], x0_blk[i])
        return jax.lax.dynamic_update_slice(x, new, (i * bs, 0))

    def block_body(i, x):
        def one(_, xx):
            return block_update(i, xx)
        return jax.lax.fori_loop(0, inner, one, x)

    def sweep(x):
        return jax.lax.fori_loop(0, nb, block_body, x)

    return harness.loop(
        sweep, x_start, res_kind=res_kind, eps=eps, max_iters=max_iters,
        real_mask=real_mask, extrapolate_every=extrapolate_every,
    )


@partial(
    jax.jit,
    static_argnames=("semiring", "combine", "bs", "res_kind", "max_iters",
                     "n_real", "interpret", "extrapolate_every"),
)
def _run_pallas(
    rowptr, tilecols, tiles, c, x0, fixed, x_start,
    semiring: str, combine: str, bs: int, n_real: int,
    res_kind: str, eps: float, max_iters: int, interpret: bool,
    extrapolate_every: int,
):
    from repro.kernels.gs_sweep import gs_sweep_pallas

    real_mask = (jnp.arange(x0.shape[0]) < n_real)

    def sweep(x):
        return gs_sweep_pallas(
            rowptr, tilecols, tiles, c, x0, fixed, x,
            semiring=semiring, combine=combine, bs=bs, interpret=interpret,
        )

    return harness.loop(
        sweep, x_start, res_kind=res_kind, eps=eps, max_iters=max_iters,
        real_mask=real_mask, extrapolate_every=extrapolate_every,
    )


def _solve(algo: AlgoInstance, o) -> RunResult:
    """Engine body behind ``solve(algo, engine="async_block", ...)``; options
    are already validated (`engine.api.validate_options`)."""
    if o.backend == "pallas":
        return _run_async_block_pallas(
            algo, o.bs, o.max_iters, o.inner, o.x_init,
            extrapolate_every=o.extrapolate_every,
            sweeps_per_call=o.sweeps_per_call, frontier=o.frontier,
            tracer=o.trace,
        )
    with tspan(o.trace, "pack", algo=algo.name, n=algo.n, d=algo.d, bs=o.bs):
        be, x0, c, fixed, npad = harness.pack(algo, o.bs)
    x_start = harness.init_state(x0, o.x_init, algo.n)
    out = _run(
        jnp.asarray(be.esrc), jnp.asarray(be.edst), jnp.asarray(be.ew),
        jnp.asarray(be.emask), jnp.asarray(x_start), jnp.asarray(x0),
        jnp.asarray(c), jnp.asarray(fixed),
        bs=o.bs, nb=be.nb, n_real=algo.n,
        sem_reduce=algo.semiring.reduce,
        sem_edge=algo.semiring.edge_op,
        comb=algo.combine,
        res_kind=algo.residual,
        eps=algo.eps,
        max_iters=o.max_iters,
        identity=algo.semiring.identity,
        inner=o.inner,
        extrapolate_every=o.extrapolate_every,
    )
    return harness.finalize(algo, *out)


def run_async_block(
    algo: AlgoInstance, bs: int = 256, max_iters: int = 2000, inner: int = 1,
    x_init: np.ndarray | None = None, backend: str = "jax",
    extrapolate_every: int = 0, sweeps_per_call: int = 1,
    frontier: np.ndarray | None = None,
) -> RunResult:
    """Thin shim over ``solve(algo, engine="async_block")`` — the legacy
    keyword spelling, parity-tested bitwise against `engine.api.solve`.

    x_init: resume from a previous state (checkpointed macro-stepping or
    the incremental serving engine's warm starts).

    backend: "jax" (gather/segment-reduce sweep) or "pallas" (fused
    `gs_sweep` kernel per sweep over the ragged flat-BSR layout; interpreted
    on the CPU; sum/min/max semirings — see kernels/gs_sweep._SUPPORTED).

    extrapolate_every: Aitken acceleration period for linear (sum-semiring)
    systems; 0 = off (see `harness.loop`).

    sweeps_per_call (pallas backend): sweeps batched into one persistent
    megakernel launch; >1 trades per-sweep host convergence checks (and
    per-column state freezing — see `harness.sweep_batched_loop`) for one
    check per batch plus in-kernel early-out and frontier skipping.

    frontier (pallas backend, bool[n]): vertex-level dirty seed for the
    megakernel's active-frontier path. A vertex outside the frontier claims
    its block's state already satisfies its update equation; None = all
    dirty (the only safe cold-start value).
    """
    from repro.engine.api import EngineOptions, solve

    return solve(algo, engine="async_block", options=EngineOptions(
        x_init=x_init, extrapolate_every=extrapolate_every, backend=backend,
        bs=bs, inner=inner, sweeps_per_call=sweeps_per_call,
        frontier=frontier, max_iters=max_iters,
    ))


def _run_async_block_pallas(
    algo, bs, max_iters, inner, x_init, extrapolate_every=0,
    sweeps_per_call=1, frontier=None, tracer=None,
) -> RunResult:
    from repro.engine.api import EngineOptions, validate_options
    from repro.kernels.ops import interpret_mode, pack_algorithm

    # also reachable through the kernels.ops back-compat shim, which skips
    # solve(); route its options through the same single validation pass
    validate_options("async_block", EngineOptions(
        x_init=x_init, extrapolate_every=extrapolate_every, backend="pallas",
        bs=bs, inner=inner, sweeps_per_call=sweeps_per_call,
        frontier=frontier, max_iters=max_iters, trace=tracer,
    ), algo)
    with tspan(tracer, "pack", algo=algo.name, n=algo.n, d=algo.d, bs=bs):
        ops = pack_algorithm(algo, bs)
    x_start = harness.init_state(ops["x0_host"], x_init, algo.n, d=algo.d)
    interp = interpret_mode()
    if sweeps_per_call == 1 and frontier is None:
        out = _run_pallas(
            ops["rowptr"], ops["tilecols"], ops["tiles"], ops["c"], ops["x0"],
            ops["fixed"], jnp.asarray(x_start),
            semiring=ops["semiring"], combine=ops["combine"], bs=bs,
            n_real=algo.n, res_kind=algo.residual, eps=algo.eps,
            max_iters=max_iters, interpret=interp,
            extrapolate_every=extrapolate_every,
        )
        return harness.finalize(algo, *out)
    from repro.graphs.blocked import frontier_blocks
    from repro.kernels.gs_sweep import gs_multisweep_pallas

    nb = int(ops["rowptr"].shape[0]) - 1
    dirty0 = jnp.asarray(frontier_blocks(frontier, algo.n, bs))

    def batch_fn(x, dirty):
        return gs_multisweep_pallas(
            ops["rowptr"], ops["tilecols"], ops["revptr"], ops["revrows"],
            dirty, ops["tiles"], ops["c"], ops["x0"], ops["fixed"], x,
            semiring=ops["semiring"], combine=ops["combine"],
            res_kind=algo.residual, bs=bs, sweeps=sweeps_per_call,
            eps=float(algo.eps), interpret=interp,
        )

    real_mask = np.arange(x_start.shape[0]) < algo.n
    out = harness.sweep_batched_loop(
        batch_fn, jnp.asarray(x_start), dirty0,
        eps=algo.eps, max_iters=max_iters, sweeps=sweeps_per_call, nb=nb,
        real_mask=real_mask, tracer=tracer,
    )
    res = harness.finalize(algo, *out[:6])
    res.active_block_fraction = out[6]
    # replace finalize's column-granular trace with the megakernel's finer
    # block-granular work accounting (the frontier-skipping bill)
    from repro.obs.telemetry import trace_from_block_activity

    res.convergence_trace = trace_from_block_activity(
        res.residuals, out[6], rounds=res.rounds, nb=nb, bs=bs, d=algo.d,
    )
    return res


@dataclasses.dataclass
class BatchReport:
    """Outcome of one bounded-round session batch (host-side, per column)."""

    rounds: int                # rounds the batch actually executed
    col_done: np.ndarray       # bool[d]  — converged within THIS batch
    col_rounds: np.ndarray     # int32[d] — rounds each column was active


class AsyncBlockSession:
    """Pre-packed block-GS runner for repeated bounded-round batches over a
    resident ``f32[npad, d]`` state — the engine side of continuous batching.

    `run_async_block` packs, converges, unpacks — one query batch per call.
    A serving event loop (`repro.serving`) instead keeps *one* state matrix
    resident across many short batches, swapping finished query columns out
    and queued queries in between batches. This session packs the family's
    edge structure **once**; each :meth:`run_batch` drives up to
    ``max_iters`` rounds through the shared round driver (per-column
    convergence freezing included), and :meth:`swap_in` performs the
    mid-run column re-init (`harness.swap_in_column`): newcomer ``x0 / c /
    fixed`` written into the packed operand columns, resident state column
    reset to the newcomer's start.

    The session owns the *cumulative* per-column accounting
    (``col_done`` / ``col_rounds``, folded from every batch's report):
    :meth:`swap_in` inverts it for exactly the swapped column
    (`convergence.reinit_columns`), so ``col_rounds[j]`` always reads the
    rounds the slot's **current** query has consumed since its swap-in —
    the number the serving layer bills to its ticket.

    The session is **device-resident**: the packed state matrix, the operand
    matrices (``x0``/``c``/``fixed``), the dirty-block bitmap, and the
    cumulative per-column accounting all live as jax arrays for the
    session's whole life. Batches chain device-to-device (the next batch
    consumes the previous batch's output buffer), swaps are jitted
    functional column updates with a traced slot index
    (`harness.swap_in_column_device` — only the newcomer's three length-n
    vectors transfer H2D), and the only host transfers are the tiny
    ``(d,)`` per-batch report and whatever the serving layer reads at
    ticket resolution via :attr:`state`.

    Backends mirror `solve`: ``"jax"`` (gather/segment-reduce sweep),
    ``"pallas"`` (fused flat-BSR kernel), and ``"distributed"`` (the
    shard_map superstep of `engine.distributed.DistContext`, for families
    whose resident state spans devices; ``mesh``/``axis`` select the
    device mesh). With ``sweeps_per_call > 1`` the persistent megakernel
    runs and the dirty-block frontier bitmap is carried across batches
    *and* swaps: a swapped-in column ORs exactly its support blocks into
    the bitmap (`kernels.gs_sweep.or_dirty_blocks`), so the kernel only
    re-touches what the newcomer needs while blocks clean for every
    in-flight column stay skipped.

    A column's trajectory from swap-in to convergence is exactly what a
    solo `run_async_block` of that query produces: sweeps act columnwise
    independently and batch boundaries are invisible (`harness.loop` keeps
    an active column's post-sweep state, a converging column's pre-sweep
    state). Min/max-semiring columns match a solo run bitwise; sum columns
    to eps under ``sweeps_per_call > 1`` (no mid-batch freezing — see
    `harness.sweep_batched_loop`).
    """

    def __init__(
        self, algo: AlgoInstance, bs: int = 256, inner: int = 1,
        backend: str = "jax", sweeps_per_call: int = 1,
        mesh=None, axis: str = "data",
        trace=None, trace_attrs: dict | None = None,
    ):
        from repro.engine.api import EngineOptions, validate_options

        engine = "distributed" if backend == "distributed" else "async_block"
        validate_options(engine, EngineOptions(
            backend="jax" if backend == "distributed" else backend,
            bs=bs, inner=inner, sweeps_per_call=sweeps_per_call,
            mesh=mesh, axis=axis, trace=trace,
        ), algo)
        self.algo = algo
        self.bs = bs
        self.inner = inner
        self.backend = backend
        self.sweeps_per_call = sweeps_per_call
        self.n = algo.n
        self.d = algo.d
        # span tracer + constant attributes (tenant / family / graph_version)
        # the serving layer stamps on every span this session emits
        self.trace = trace
        self.trace_attrs = dict(trace_attrs or {})
        pack_span = tspan(trace, "pack", algo=algo.name, n=algo.n, d=algo.d,
                          bs=bs, backend=backend, **self.trace_attrs)
        if backend == "jax":
            with pack_span:
                be, x0, c, fixed, _ = harness.pack(algo, bs)
            self.nb = be.nb
            self._edges = tuple(
                jnp.asarray(a) for a in (be.esrc, be.edst, be.ew, be.emask)
            )
            self.x0 = jnp.asarray(x0)
            self.c = jnp.asarray(c)
            self.fixed = jnp.asarray(fixed)
        elif backend == "distributed":
            from repro.engine.distributed import DistContext

            with pack_span:
                self._dist = DistContext(algo, bs, mesh=mesh, axis=axis,
                                         inner=inner)
            self.nb = self._dist.nb
            self.x0 = jnp.asarray(self._dist.x0)
            self.c = jnp.asarray(self._dist.c)
            self.fixed = jnp.asarray(self._dist.fixed)
        else:
            from repro.kernels.ops import interpret_mode, pack_algorithm

            with pack_span:
                ops = pack_algorithm(algo, bs)
            self._interpret = interpret_mode()
            self.nb = int(ops["rowptr"].shape[0]) - 1
            # the session owns the per-column operands; _ops keeps only the
            # graph, so a swap's replaced operands are freed, not kept alive
            self.x0 = ops.pop("x0")
            self.c = ops.pop("c")
            self.fixed = ops.pop("fixed")
            # the packed start state is already a buffer of its own
            self.x = ops.pop("x")
            self._ops = ops
            # cold start: every block dirty (the only safe default; swaps
            # and batches keep the bitmap faithful from here on)
            self.dirty = jnp.ones(self.nb, jnp.int32)
        if backend != "pallas":
            # the resident state: a device buffer distinct from x0 (the
            # engines must never write through to x0, which swaps reuse)
            self.x = jnp.array(self.x0, copy=True)
        # cumulative per-column accounting across batches; swap_in inverts
        # it for exactly the swapped column (convergence.reinit_columns)
        self.col_done = jnp.zeros(self.d, bool)
        self.col_rounds = jnp.zeros(self.d, jnp.int32)

    @property
    def state(self):
        """The resident (n, d) state, padding rows and lanes stripped.

        A device jax array — the serving layer transfers it to host only at
        ticket resolution (`GraphServer._resolve`), never between batches.
        """
        return self.x[: self.n, : self.d]

    def load_state_column(self, j: int, col) -> None:
        """Overwrite state column ``j`` rows ``< n`` (delta-rebuild carry).

        The serving layer rebuilds a family on a mutated graph and carries
        each in-flight query's warm state into the fresh session; padding
        rows keep their pinned fills. Functional device update — rare path
        (once per family per delta), so no jit wrapper.
        """
        col = jnp.asarray(col, jnp.float32).reshape(-1)
        self.x = self.x.at[: self.n, j].set(col)

    def set_col_rounds(self, j: int, rounds: int) -> None:
        """Seed column ``j``'s cumulative round count (delta-rebuild carry)."""
        self.col_rounds = self.col_rounds.at[j].set(int(rounds))

    def swap_in(self, j: int, q_x0, q_c, q_fixed) -> None:
        """Install a new query into column ``j`` (between batches)."""
        from repro.engine.convergence import reinit_columns

        self.col_done, self.col_rounds = reinit_columns(
            self.col_done, self.col_rounds, [j]
        )
        q_x0, q_c = np.asarray(q_x0), np.asarray(q_c)
        q_fixed = np.asarray(q_fixed).astype(bool)
        self.x, self.x0, self.c, self.fixed = harness.swap_in_column_device(
            self.x, self.x0, self.c, self.fixed, j, self.n, q_x0, q_c,
            q_fixed,  # cast to the operands' dtype (f32 pinned=1.0 on pallas)
            x0_fill=self.algo.semiring.identity,
            c_fill=self.algo.c_pad_fill,
        )
        if self.backend == "pallas" and self.sweeps_per_call > 1:
            from repro.kernels.gs_sweep import or_dirty_blocks

            support = harness.column_support(
                q_x0, q_c, q_fixed,
                reduce=self.algo.semiring.reduce,
                c_fill=self.algo.c_pad_fill,
            )
            # seed the support vertices AND everything their out-edges feed:
            # an injected seed (e.g. the SSSP source) can already satisfy its
            # own update equation, in which case its block never *changes*
            # and would never re-mark dependents — the newcomer's frontier
            # must start at the first vertices whose equations the injection
            # invalidates, exactly the depth-1 out-closure of the support.
            from repro.graphs.delta import out_closure

            touched = out_closure(
                self.algo.src, self.algo.dst, support, self.n, depth=1
            )
            self.dirty = or_dirty_blocks(self.dirty, touched, self.n, self.bs)

    def run_batch(self, max_iters: int) -> BatchReport:
        """Advance every column up to ``max_iters`` rounds; converged
        columns freeze (jax / single-sweep pallas) and the batch stops early
        once all columns are done. Updates the resident state in place.

        With a tracer attached (``trace=`` at construction) the batch is
        wrapped in a ``batch`` span carrying the session's constant
        attributes plus this batch's round count and per-round residuals —
        the residual buffer rides the *same* per-batch ``device_get`` as the
        convergence report, so tracing never adds a sync point.
        """
        with tspan(self.trace, "batch", backend=self.backend,
                   max_iters=max_iters, **self.trace_attrs) as sp:
            return self._run_batch_inner(max_iters, sp)

    def _run_batch_inner(self, max_iters: int, sp) -> BatchReport:
        a = self.algo
        if max_iters % self.sweeps_per_call:
            # the megakernel always executes sweeps_per_call sweeps per
            # launch; a non-multiple budget would advance the state by
            # uncounted sweeps and desynchronize per-column round accounting
            raise ValueError(
                f"max_iters={max_iters} must be a multiple of "
                f"sweeps_per_call={self.sweeps_per_call}"
            )
        if self.backend == "jax":
            out = _run(
                *self._edges, self.x, self.x0, self.c, self.fixed,
                bs=self.bs, nb=self.nb, n_real=self.n,
                sem_reduce=a.semiring.reduce, sem_edge=a.semiring.edge_op,
                comb=a.combine, res_kind=a.residual, eps=a.eps,
                max_iters=max_iters, identity=a.semiring.identity,
                inner=self.inner, extrapolate_every=0,
            )
        elif self.backend == "distributed":
            out = self._dist.run(
                self.x, self.x0, self.c, self.fixed, max_iters=max_iters,
            )
        elif self.sweeps_per_call == 1:
            ops = self._ops
            out = _run_pallas(
                ops["rowptr"], ops["tilecols"], ops["tiles"],
                self.c, self.x0, self.fixed, self.x,
                semiring=ops["semiring"], combine=ops["combine"], bs=self.bs,
                n_real=self.n, res_kind=a.residual, eps=a.eps,
                max_iters=max_iters, interpret=self._interpret,
                extrapolate_every=0,
            )
        else:
            from repro.kernels.gs_sweep import gs_multisweep_pallas

            ops = self._ops
            c_dev, x0_dev, fx_dev = self.c, self.x0, self.fixed

            def batch_fn(x, dirty):
                return gs_multisweep_pallas(
                    ops["rowptr"], ops["tilecols"], ops["revptr"],
                    ops["revrows"], dirty, ops["tiles"], c_dev, x0_dev,
                    fx_dev, x,
                    semiring=ops["semiring"], combine=ops["combine"],
                    res_kind=a.residual, bs=self.bs,
                    sweeps=self.sweeps_per_call, eps=float(a.eps),
                    interpret=self._interpret,
                )

            real_mask = np.arange(self.x.shape[0]) < self.n
            out = harness.sweep_batched_loop(
                batch_fn, self.x, self.dirty,
                eps=a.eps, max_iters=max_iters, sweeps=self.sweeps_per_call,
                nb=self.nb, real_mask=real_mask, tracer=self.trace,
            )
            self.dirty = out[7]  # device bitmap carried into the next batch
        # the state never leaves the device: the next batch (and any swap)
        # consumes this output buffer directly
        self.x = out[0]
        if self.trace is not None and self.trace.enabled:
            # traced: the per-round residual buffer joins the SAME transfer
            # (out[4] is already host numpy on the megakernel path and
            # passes through device_get untouched)
            rounds, col_done, col_rounds, res_buf = jax.device_get(
                (out[1], out[2], out[3], out[4])
            )  # repro: allow-host-sync(per-batch convergence report for the caller)
            rounds = int(rounds)
            sp.set(rounds=rounds,
                   res=[float(v) for v in np.asarray(res_buf)[:rounds]])
        else:
            rounds, col_done, col_rounds = jax.device_get(
                (out[1], out[2], out[3])
            )  # repro: allow-host-sync(per-batch convergence report for the caller)
        # the pallas state's lane-padding columns are not part of the report
        rep = BatchReport(
            rounds=int(rounds),
            col_done=np.asarray(col_done)[: self.d],
            col_rounds=np.asarray(col_rounds, np.int32)[: self.d],
        )
        # fold into the cumulative device-side accounting: columns already
        # done before this batch only re-verified (their 1-round report is
        # not progress)
        still_active = ~self.col_done
        self.col_rounds = self.col_rounds + jnp.where(
            still_active, jnp.asarray(rep.col_rounds), 0
        )
        self.col_done = self.col_done | jnp.asarray(rep.col_done)
        return rep
