"""Shared packed-run harness for the iterative engines.

Every engine in this package is the same machine with a different sweep:
pack the algorithm's vertex arrays into whole blocks, then drive rounds of
``x -> sweep(x)`` until the residual drops below eps. This module holds the
two shared halves so the engines only contribute their sweep:

* :func:`pack` — the one block-padding path (previously duplicated between
  ``async_block`` and ``distributed`` with *inconsistent* padding fills for
  ``c``: min/max-semiring pads must be the reduce identity, not 0.0).

* :func:`loop` — the one round driver (previously three near-identical
  ``lax.while_loop`` bodies in sync / async_block / distributed). States are
  batched ``(n, d)`` matrices; convergence is tracked *per column*: a column
  whose residual first drops to eps is frozen (later sweeps cannot move it)
  and recorded at its own round count, so query j of a batched run finishes
  with exactly the state and round count of a scalar run of query j.

``loop`` is a plain traced function, not a jit boundary — each engine calls
it inside its own module-level ``jax.jit`` wrapper so compilation caching
keys on the engine's static config exactly as before.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.algorithms import AlgoInstance
from repro.engine.convergence import (
    RunResult,
    converge_step,
    freeze_columns,
)
from repro.engine import jax_ops as J
from repro.graphs.blocked import pack_in_edges, pad_state, padded_n
from repro.graphs.graph import Graph
from repro.obs.telemetry import trace_from_col_rounds
from repro.obs.trace import tspan


def check_extrapolation(algo: AlgoInstance, extrapolate_every: int) -> None:
    """Aitken extrapolation assumes a *linear* update (sum-semiring
    "replace" combine); on min/max lattice sweeps the geometric-tail jump is
    meaningless (it NaNs on BIG sentinels and can't move a min fixpoint
    anyway), so reject it loudly instead of returning garbage."""
    if extrapolate_every and algo.semiring.reduce != "sum":
        raise NotImplementedError(
            f"extrapolate_every is only valid for linear sum-semiring "
            f"systems; {algo.name!r} uses reduce={algo.semiring.reduce!r}"
        )
    if extrapolate_every and not extrapolate_every >= 2:
        # a period of 1 jumps every round off a rho estimated from the
        # previous jump's own step — the 19x amplifications compound with no
        # contraction rounds between and the iteration diverges to NaN
        raise ValueError(
            f"extrapolate_every must be 0 (off) or >= 2, got {extrapolate_every}"
        )


def pack(algo: AlgoInstance, bs: int):
    """Pad the algorithm's (n, d) vertex arrays up to whole blocks of ``bs``.

    Returns ``(be, x0, c, fixed, npad)`` with f32[npad, d] state arrays.
    Padding rows are pinned (``fixed = True``) at the reduce identity so they
    can never influence a real vertex; ``c`` pads use the reduce identity
    except under ``replace`` combine, whose additive pad must be 0.0.
    """
    g = Graph(algo.n, algo.src, algo.dst, algo.w)
    be = pack_in_edges(g, bs)
    npad = padded_n(algo.n, bs)
    ident = algo.semiring.identity
    x0 = pad_state(algo.x0, bs, fill=ident)
    c = pad_state(algo.c, bs, fill=algo.c_pad_fill)
    fixed = pad_state(algo.fixed, bs, fill=True)
    return be, x0, c, fixed, npad


def init_state(
    x0_packed: np.ndarray, x_init, n: int, d: Optional[int] = None
) -> np.ndarray:
    """Overlay a resume state onto the packed x0 (checkpointed macro-steps).

    ``x_init`` may be (n,), (n, 1) or (n, d) — 1-D resumes of a d = 1 run and
    full-matrix resumes of a batched run both work. ``d`` is the run's real
    column count when the packed matrix carries lane padding past it
    (`kernels.ops.pad_lanes`); the padding columns keep their x0.
    """
    if x_init is None:
        return x0_packed
    if d is None:
        d = x0_packed.shape[1]
    x = np.asarray(x_init, dtype=x0_packed.dtype)
    if x.size % n:
        raise ValueError(
            f"x_init has {x.shape} elements, expected (n, d) rows for n={n}"
        )
    x = x.reshape(n, -1)
    if x.shape[1] != d:
        raise ValueError(f"x_init has {x.shape[1]} columns, run has {d}")
    out = x0_packed.copy()
    out[:n, :d] = x
    return out


def swap_in_column(
    x: np.ndarray, x0: np.ndarray, c: np.ndarray, fixed: np.ndarray,
    j: int, n: int,
    q_x0: np.ndarray, q_c: np.ndarray, q_fixed: np.ndarray,
) -> None:
    """Mid-run per-column re-init — the inverse of :func:`loop`'s freeze.

    The serving layer's continuous batching resolves a converged column and
    packs a *queued* query into its slot between engine batches: overwrite
    column ``j`` of the packed ``(npad, d)`` operand matrices with the
    newcomer's vertex arrays and reset the resident state column to the
    newcomer's ``x0``. Rows ``>= n`` are padding and keep their fills (the
    fills are per-family constants, identical for every column, so a swap
    never has to re-pad). Mutates the arrays in place; the companion
    bookkeeping reset is :func:`repro.engine.convergence.reinit_columns`.
    """
    x0[:n, j] = np.asarray(q_x0, x0.dtype).reshape(-1)
    c[:n, j] = np.asarray(q_c, c.dtype).reshape(-1)
    fixed[:n, j] = np.asarray(q_fixed, fixed.dtype).reshape(-1)
    x[:, j] = x0[:, j]


@jax.jit
def _set_query_columns(x, x0, c, fixed, j, q_x0, q_c, q_fixed):
    # j is traced (an int32 operand, not a static arg): one compiled scatter
    # serves every slot, so serving swaps never recompile per column
    return (
        x.at[:, j].set(q_x0),
        x0.at[:, j].set(q_x0),
        c.at[:, j].set(q_c),
        fixed.at[:, j].set(q_fixed),
    )


def swap_in_column_device(
    x, x0, c, fixed, j: int, n: int,
    q_x0: np.ndarray, q_c: np.ndarray, q_fixed: np.ndarray,
    *, x0_fill: float, c_fill: float,
):
    """:func:`swap_in_column` for device-resident ``(npad, d)`` operands.

    Pads the newcomer's length-``n`` vectors with the family's per-column
    constant fills (the same fills :func:`pack` used, so padding rows stay
    pinned at the reduce identity) and writes all four columns in one jitted
    functional update. Returns new ``(x, x0, c, fixed)`` jax arrays — the
    matrices never round-trip to host; only the newcomer's three length-n
    vectors transfer H2D.
    """
    npad = x.shape[0]
    xq = np.full(npad, x0_fill, np.float32)
    xq[:n] = np.asarray(q_x0, np.float32).reshape(-1)
    cq = np.full(npad, c_fill, np.float32)
    cq[:n] = np.asarray(q_c, np.float32).reshape(-1)
    fq = np.ones(npad, fixed.dtype)  # pads pinned (bool on jax, f32 on pallas)
    fq[:n] = np.asarray(q_fixed).reshape(-1).astype(fq.dtype)
    return _set_query_columns(x, x0, c, fixed, jnp.int32(j), xq, cq, fq)


def permute_state(x: np.ndarray, rank: np.ndarray) -> np.ndarray:
    """Carry a served state across a relabel: vertex v's row moves to
    ``rank[v]`` — the same transform `AlgoInstance.relabel` applies to x0."""
    rank = np.asarray(rank)
    inv = np.empty_like(rank)
    inv[rank] = np.arange(len(rank))
    x = np.asarray(x)
    return x[inv]


@jax.jit
def _gather_rows(x, idx):
    return x[idx]


def gather_rows(x, idx):
    """Device-resident row gather ``x[idx]`` (jitted, returns a jax array).

    The order-swap primitive: permuting a family's packed state matrix (or
    one column) between two processing orders is two of these gathers —
    old-rank -> id space via ``rank_old``, id space -> new-rank via
    ``order_new`` — and a gather is a bit-copy, so min/max warm states move
    across orders bitwise without leaving the device (PR 6 residency
    contract)."""
    return _gather_rows(x, jnp.asarray(idx))


# The value an *untouched* vertex holds at the start of every workload the
# constructors build: 0 for the additive semiring, the +BIG sentinel for
# min-reduce (unreached SSSP/BFS/CC), 0 for max-reduce (SSWP width /
# reachability indicator of an unreached vertex). Vertices whose x0 differs
# from this — sources, seeds, pinned targets — are a query's *inputs*.
X0_FILL = {"sum": 0.0, "min": 3.0e38, "max": 0.0}


def column_support(
    q_x0: np.ndarray, q_c: np.ndarray, q_fixed: np.ndarray,
    *, reduce: str, c_fill: float, x: Optional[np.ndarray] = None,
) -> np.ndarray:
    """bool[n] — the vertices a query's column actually involves.

    A vertex is in a query's support when the query *injects* something at
    it (``x0`` off the workload's untouched-vertex fill, ``c`` off the pack
    fill, or pinned) or — when a finished state ``x`` is supplied — when the
    run *moved* it off ``x0``. Everything outside the support holds the
    inert fill through the whole run, which is what lets (a) a swapped-in
    column seed only its support blocks into the megakernel's dirty
    frontier, and (b) the result cache keep an entry alive across a graph
    delta that touches no supported block (`repro.serving.cache`).
    """
    q_x0 = np.asarray(q_x0).reshape(-1)
    q_c = np.asarray(q_c).reshape(-1)
    q_fixed = np.asarray(q_fixed).reshape(-1)
    support = (q_x0 != np.float32(X0_FILL[reduce])) | (q_c != np.float32(c_fill))
    support |= q_fixed.astype(bool)
    if x is not None:
        support |= np.asarray(x).reshape(-1) != q_x0
    return support


# Aitken extrapolation clamps the contraction-rate estimate here: a rho this
# close to 1 amplifies the current step by rho/(1-rho) = 19x, which a
# contracting base iteration recovers from in a few sweeps even when the
# estimate was noise.
_RHO_MAX = 0.95


def loop(
    round_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    *,
    res_kind: str,
    eps: float,
    max_iters: int,
    real_mask: Optional[jnp.ndarray] = None,
    extrapolate_every: int = 0,
):
    """Drive ``x -> round_fn(x)`` with per-column convergence freezing.

    x0: f32[N, d]. ``real_mask`` (bool[N]) masks padding rows out of the
    residual and the state-sum trace. Returns
    ``(x, k, col_done, col_rounds, res_buf, sum_buf, change_norm)`` where
    ``res_buf[t]`` is the max residual over the columns still active at round
    t (for d = 1 this is the legacy scalar residual trace).

    A column converging at round k keeps its *pre-sweep* state: the sweep that
    measures residual <= eps is a verification sweep whose candidate is
    discarded. Both the kept state and the candidate satisfy the stopping
    criterion (they differ by <= eps); keeping the pre-sweep one makes the
    driver idempotent — re-running with ``x_init`` set to a converged state
    performs exactly one verification sweep and returns the state bitwise
    unchanged, which is what lets warm-started serving re-runs be no-ops.

    ``extrapolate_every`` (static; 0 = off) enables per-column Aitken
    extrapolation every that-many rounds: the column's contraction rate rho is
    estimated from successive L1 step norms and the remaining geometric tail
    ``step * rho/(1-rho)`` is added in one jump. Only valid for *linear*
    updates (sum-semiring "replace" combine, e.g. the incremental engine's
    delta systems); min/max semiring sweeps are nonlinear and must keep 0.
    """
    d = x0.shape[1]
    res_buf = jnp.zeros((max_iters,), jnp.float32)
    sum_buf = jnp.zeros((max_iters,), jnp.float32)

    def mask_rows(x):
        if real_mask is None:
            return x
        return jnp.where(real_mask[:, None], x, 0.0)

    def cond(state):
        _, k, col_done, _, _, _, _ = state
        return jnp.logical_and(k < max_iters, ~jnp.all(col_done))

    def body(state):
        x, k, col_done, col_rounds, res_buf, sum_buf, prev_norm = state
        x_cand = round_fn(x)
        xm_cand = mask_rows(x_cand)
        xm_old = mask_rows(x)
        res_col = J.residual_cols(res_kind, xm_cand, xm_old)
        newly_done, active, col_done, col_rounds = converge_step(
            res_col, eps, col_done, col_rounds
        )
        x_keep = x_cand
        norm_col = prev_norm  # untouched dummy when extrapolation is off
        if extrapolate_every:  # static — off pays no per-round norm work
            norm_col = jnp.sum(jnp.abs(xm_cand - xm_old), axis=0)
            do_ex = jnp.logical_and(k > 0, (k + 1) % extrapolate_every == 0)
            rho = jnp.clip(
                norm_col / jnp.maximum(prev_norm, 1e-30), 0.0, _RHO_MAX
            )
            factor = jnp.where(
                jnp.logical_and(do_ex, prev_norm > 0), rho / (1.0 - rho), 0.0
            )
            x_keep = x_cand + (xm_cand - xm_old) * factor[None, :]
        # columns converging this round keep their pre-sweep state (see
        # docstring); already-frozen columns stay put; active ones advance
        x_new = freeze_columns(x_keep, x, active, newly_done)
        res_buf = res_buf.at[k].set(jnp.max(jnp.where(active, res_col, 0.0)))
        xm = mask_rows(x_new)
        sum_buf = sum_buf.at[k].set(
            jnp.sum(jnp.where(jnp.abs(xm) < 1e30, xm, 0.0))
        )
        return x_new, k + 1, col_done, col_rounds, res_buf, sum_buf, norm_col

    init = (
        x0, jnp.int32(0), jnp.zeros((d,), bool), jnp.zeros((d,), jnp.int32),
        res_buf, sum_buf, jnp.zeros((d,), jnp.float32),
    )
    return jax.lax.while_loop(cond, body, init)


def sweep_batched_loop(
    batch_fn: Callable,
    x0: jnp.ndarray,
    dirty0: jnp.ndarray,
    *,
    eps: float,
    max_iters: int,
    sweeps: int,
    nb: int,
    real_mask: Optional[np.ndarray] = None,
    tracer=None,
):
    """Host-side round driver for the persistent multi-sweep megakernel.

    ``batch_fn(x, dirty) -> (x, deltas[sweeps, d], active[sweeps, 1],
    dirty)`` runs up to ``sweeps`` Gauss–Seidel sweeps in one kernel launch
    (`kernels.gs_sweep.gs_multisweep_pallas`); this loop synchronizes with
    the host once per *batch*, then replays the kernel's per-sweep delta
    trace to reconstruct exactly the per-column round counts the per-sweep
    driver (:func:`loop`) would have produced: column j converges at the
    first sweep whose delta drops to eps, and skipped blocks contribute a
    bitwise-zero delta, so the trace is identical to full-sweep execution.

    Two documented deviations from :func:`loop`'s semantics, both invisible
    for the lattice (min/max) semirings where converged states are bitwise
    fixpoints of the sweep: (1) columns are not frozen at their pre-sweep
    state — a converged column keeps sweeping until the whole batch stops,
    drifting by at most eps per sweep for contractive sum systems; (2) the
    kernel's in-batch early-out uses the instantaneous all-columns test, so
    a batch may execute up to ``sweeps - 1`` extra sweeps past ``max_iters``
    or past the sticky per-column stop (their results are kept).

    Returns ``(x, k, col_done, col_rounds, res_trace, sum_trace,
    active_trace, dirty)`` — the :func:`loop` tuple shape plus the per-sweep
    active-block-fraction trace (``state_sums`` has batch granularity: the
    post-batch sum is attributed to each of the batch's sweeps) and the
    final dirty-block bitmap, which a serving session carries into its next
    batch so the frontier survives column swaps.

    ``tracer`` (`repro.obs.trace.Tracer`, optional) wraps each kernel launch
    in a ``sweep_call`` span covering dispatch *and* the batch-granular
    readout — the launch itself is asynchronous, so dispatch+readout is the
    only honest per-batch wall time. The span's residual/active attributes
    are stamped from the same once-per-batch ``device_get`` every untraced
    run performs — tracing adds no transfers.
    """
    x = x0
    dirty = dirty0
    d = int(x.shape[1])
    rm = None if real_mask is None else jnp.asarray(real_mask)
    col_done = np.zeros(d, bool)
    col_rounds = np.zeros(d, np.int32)
    res_trace: list[float] = []
    sum_trace: list[float] = []
    act_trace: list[float] = []
    k = 0
    while k < max_iters and not col_done.all():
        with tspan(tracer, "sweep_call", sweeps=sweeps, nb=nb, k=k) as sp:
            x, deltas, active, dirty = batch_fn(x, dirty)
            # state-sum trace on device: the batch only ships the (sweeps, d)
            # delta/active rows and this scalar to the host, never the state
            xm = x if rm is None else jnp.where(rm[:, None], x, 0.0)
            deltas_np, active_np, batch_sum = jax.device_get((
                deltas, active,
                jnp.sum(jnp.where(jnp.abs(xm) < 1e30, xm, 0.0)),
            ))  # repro: allow-host-sync(once-per-batch convergence trace readout)
            batch_sum = float(batch_sum)
            sp.set(
                max_delta=float(np.max(deltas_np)),
                active_blocks=[float(a) for a in active_np[:, 0]],
            )
        for s in range(sweeps):
            if k >= max_iters or col_done.all():
                break
            res_col = deltas_np[s]
            _, active_cols, col_done, col_rounds = converge_step(
                res_col, eps, col_done, col_rounds
            )
            res_trace.append(float(np.max(np.where(active_cols, res_col, 0.0))))
            sum_trace.append(batch_sum)
            act_trace.append(float(active_np[s, 0]) / max(1, nb))
            k += 1
    return (
        x, k, col_done, col_rounds,
        np.asarray(res_trace, np.float32), np.asarray(sum_trace, np.float32),
        np.asarray(act_trace, np.float32), dirty,
    )


def finalize(
    algo: AlgoInstance, x, k, col_done, col_rounds, res_buf, sum_buf, *_extra
) -> RunResult:
    """Convert raw loop outputs into a RunResult (d = 1 keeps 1-D x).

    Padding rows and any lane-padding columns past ``algo.d`` are dropped
    from the state and from the per-column accounting.

    Also attaches the uniform :class:`~repro.obs.telemetry.ConvergenceTrace`
    — derived purely from the residual buffer and ``col_rounds`` fetched by
    this function's single end-of-run readback, so telemetry never adds a
    transfer (the megakernel path overwrites it with its finer
    block-granular accounting).
    """
    # the one end-of-run device->host readback; device_get passes the sweep
    # drivers' host-side numpy outputs through untouched
    x, k, col_done, col_rounds, res_buf, sum_buf = jax.device_get(
        (x, k, col_done, col_rounds, res_buf, sum_buf)
    )  # repro: allow-host-sync(end-of-run RunResult readout)
    k = int(k)
    xr = np.asarray(x)[: algo.n, : algo.d]
    if algo.d == 1:
        xr = xr[:, 0]
    col_conv = np.asarray(col_done)[: algo.d]
    col_rounds = np.asarray(col_rounds)[: algo.d]
    residuals = np.asarray(res_buf)[:k]
    return RunResult(
        x=xr,
        rounds=k,
        converged=bool(col_conv.all()),
        residuals=residuals,
        state_sums=np.asarray(sum_buf)[:k],
        col_rounds=col_rounds,
        col_converged=col_conv,
        convergence_trace=trace_from_col_rounds(
            residuals, col_rounds, rounds=k, n=algo.n, d=algo.d
        ),
    )
