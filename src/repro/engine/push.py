"""Vertex-granular residual push engine — the ultra-sparse regime.

The block engines sweep at ``bs``-block granularity, so a serving delta or a
personalized query touching 0.01% of vertices still pays whole blocks per
round. This engine (ROADMAP item 2; InstantGNN-style residual push with
Maiter's accumulative-delta guarantee) does work proportional to the touched
neighborhood instead: it maintains a ``(p, r)`` pair per query column —
``p`` the settled estimate, ``r`` the pending residual — and each round
*pushes* only the vertices whose residual exceeds their per-vertex
threshold, scattering one semiring message per out-edge onto the neighbors'
residual rows.

**Sum semirings** (``plus_times`` / ``replace``) keep the invariant
``r = c + W p - p``: pushing u moves ``r_u`` into ``p_u`` and adds
``w_uv * r_u`` to each out-neighbor's residual, so ``p + r``'s fixpoint
distance only ever shrinks and ``p`` converges to the same fixpoint the
sweep engines reach (within eps — the stopping rule ``|r| <= eps`` is
exactly the sweeps' linf residual test). The per-vertex threshold is the
InstantGNN ``eps_vec = eps * outdeg**(1 - beta)`` idiom, lifted per column:
``beta = 1`` (default) reproduces the engines' uniform eps bitwise;
``beta < 1`` lets low-degree vertices stop earlier (degree-normalized
approximate PPR).

**Lattice semirings** (min/max) hold in ``r`` the best *pending candidate*
(initialized to the reduce identity): a vertex is pending while
``combine(p, r)`` beats ``p``; pushing installs the candidate and scatters
``edge_op(p_u, w)`` messages. Every scatter is one of the same f32
relaxations a sweep executes, and quiescence (no relaxation can improve
anything) pins the unique monotone closure — so the resolved state is
**bitwise identical** to ``run_async_block``'s.

Initialization is one uniform rule. Sum: ``p0 = x_init or x0``,
``r0 = dense_residual(algo, p0)`` — for `run_incremental`'s delta system
(``x0 = 0, c = r``) that is exactly the delta-touched rows, so a 10-edge
delta starts with a 10-destination frontier. Lattice: cold starts use
``p0 = identity`` with ``r0 = combine(x0, c)`` (the constant candidates —
support vertices seed themselves); warm starts (``x_init``) add one
vectorized full aggregate ``r0 = reduce(r0, W-agg(p0))`` so exactly the
rows whose equation the delta violated become pending. Pinned vertices
carry ``x0`` as their only candidate and are re-clamped every round.

Two backends behind ``EngineOptions.backend``:

* ``"jax"`` — one jitted Jacobi-style push round: all active vertices push
  simultaneously via masked edge messages + segment reduce. Frozen columns
  are masked out of the push, so converged queries stay put bitwise.
* ``"pallas"`` — the bucketed scatter kernel
  (`kernels.push_scatter.push_scatter_pallas`): the host bins the round's
  active vertices into ``EngineOptions.buckets`` priority buckets (best
  first — smallest tentative distance for min_plus, i.e. delta-stepping
  SSSP; largest residual for sum), and the sequential TPU grid gives
  Gauss–Seidel freshness *within* the round. Bucket caps round up to a
  power of two so recompiles stay O(log n) per solve.

The router (`estimate_frontier_fraction` + ``solve(engine="auto")``)
estimates the initial pending fraction from the same initialization rule
and routes to push below ``EngineOptions.push_threshold``, else to the
megakernel sweep.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.algorithms import AlgoInstance
from repro.engine.api import EngineOptions, validate_options
from repro.engine.convergence import RunResult, converge_step
from repro.graphs.graph import Graph
from repro.kernels.semirings import ACC_IDENTITY, pending_cols

# (reduce, edge_op) -> fused kernel semiring; mirrors kernels.ops. Anything
# else must fail loudly before any state is built.
_KERNEL_SEMIRING: dict[tuple[str, str], str] = {
    ("sum", "mul"): "plus_times",
    ("min", "add"): "min_plus",
    ("max", "min"): "max_min",
    ("max", "mul"): "max_times",
}

_COMBINES = {"plus_times": "replace", "min_plus": "min_old",
             "max_min": "max_old", "max_times": "max_old"}


def _kernel_semiring(algo: AlgoInstance) -> str:
    key = (algo.semiring.reduce, algo.semiring.edge_op)
    ks = _KERNEL_SEMIRING.get(key)
    if ks is None or algo.combine != _COMBINES[ks]:
        raise NotImplementedError(
            f"push engine: unsupported semiring/combine "
            f"({key}, {algo.combine!r}); supported: "
            f"{sorted((k, _COMBINES[v]) for k, v in _KERNEL_SEMIRING.items())}"
        )
    return ks


def _overlay_x_init(algo: AlgoInstance, x_init: Optional[np.ndarray]) -> np.ndarray:
    """(n, d) f32 start state: algo.x0 with x_init overlaid (harness.init_state
    semantics), pinned rows clamped to their pin."""
    x = np.asarray(algo.x0, np.float32).reshape(algo.n, algo.d).copy()
    if x_init is not None:
        xi = np.asarray(x_init, np.float32)
        if xi.ndim == 1:
            xi = xi[:, None]
        if xi.shape != (algo.n, algo.d):
            raise ValueError(
                f"x_init shape {xi.shape} != (n, d) = {(algo.n, algo.d)}"
            )
        x = xi.copy()
    return np.where(algo.fixed, algo.x0, x).astype(np.float32)


def _lattice_residual(
    algo: AlgoInstance, ks: str, p0: np.ndarray, aggregate: bool
) -> np.ndarray:
    """Initial pending-candidate matrix r0 for a lattice start at ``p0``.

    The constant candidates combine(x0, c) always participate; ``aggregate``
    adds one vectorized full pass of edge candidates ``edge_op(p0[src], w)``
    — needed for warm starts, a no-op for cold ones (every message from an
    identity row is the identity). All arithmetic stays f32 so candidates
    are the kernels' exact values. Pinned rows carry x0 as their only
    candidate (cold pins establish + push themselves; warm pins are already
    settled and stay quiet)."""
    n, d = algo.n, algo.d
    x0 = np.asarray(algo.x0, np.float32).reshape(n, d)
    c = np.asarray(algo.c, np.float32).reshape(n, d)
    lat_min = ks == "min_plus"
    pair = np.minimum if lat_min else np.maximum
    r0 = pair(x0, c).astype(np.float32)
    if aggregate and len(algo.src):
        w = np.asarray(algo.w, np.float32)[:, None]
        ps = p0[algo.src]
        with np.errstate(over="ignore"):
            if ks == "min_plus":
                msgs = ps + w
            elif ks == "max_min":
                msgs = np.minimum(ps, w)
            else:  # max_times
                msgs = ps * w
        agg = np.full((n, d), ACC_IDENTITY[ks], np.float32)
        if lat_min:
            np.minimum.at(agg, algo.dst, msgs.astype(np.float32))
        else:
            np.maximum.at(agg, algo.dst, msgs.astype(np.float32))
        r0 = pair(r0, agg).astype(np.float32)
    return np.where(algo.fixed, x0, r0).astype(np.float32)


def _init_state(
    algo: AlgoInstance, ks: str, x_init: Optional[np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """The uniform (p0, r0) initialization rule (module docstring)."""
    if ks == "plus_times":
        from repro.engine.incremental import dense_residual

        p0 = _overlay_x_init(algo, x_init)
        return p0, dense_residual(algo, p0)
    if x_init is None:
        p0 = np.full((algo.n, algo.d), ACC_IDENTITY[ks], np.float32)
        return p0, _lattice_residual(algo, ks, p0, aggregate=False)
    p0 = _overlay_x_init(algo, x_init)
    return p0, _lattice_residual(algo, ks, p0, aggregate=True)


def estimate_frontier_fraction(
    algo: AlgoInstance, x_init: Optional[np.ndarray] = None
) -> float:
    """Fraction of vertices the push engine would start active — the router
    signal behind ``solve(engine="auto")``.

    Derived from the engine's own initialization rule, so the estimate *is*
    the round-0 frontier: for sum semirings the rows with supra-eps initial
    residual (cold PageRank -> 1.0, a 1-seed PPR query or an incremental
    delta system -> O(touched)/n); for lattice semirings the rows holding a
    pending candidate (cold SSSP -> the sources; a warm tightened state ->
    the delta-touched destinations; cold max-semiring workloads -> 1.0,
    every vertex must establish its inert 0). One vectorized O(m) pass,
    no iteration.
    """
    ks = _kernel_semiring(algo)
    p0, r0 = _init_state(algo, ks, x_init)
    if ks == "plus_times":
        pend = np.any(np.abs(r0) > algo.eps, axis=1)
    elif ks == "min_plus":
        pend = np.any(np.minimum(p0, r0) != p0, axis=1)
    else:
        pend = np.any(np.maximum(p0, r0) != p0, axis=1)
    return float(pend.mean()) if algo.n else 0.0


def _eps_vec(algo: AlgoInstance, beta: float) -> np.ndarray:
    """Per-vertex push threshold ``eps * outdeg**(1 - beta)`` (sum only).

    beta = 1 -> uniform eps (the sweep engines' linf test, bitwise the same
    stopping rule); beta < 1 raises the bar for low-degree vertices — the
    InstantGNN degree-normalized approximate-push tradeoff."""
    if beta == 1.0:
        return np.full(algo.n, algo.eps, np.float32)
    deg = Graph(algo.n, algo.src, algo.dst, algo.w).out_degrees()
    return (algo.eps * np.maximum(deg, 1).astype(np.float64)
            ** (1.0 - beta)).astype(np.float32)


def _make_prep(ks: str, d: int) -> Callable[..., tuple[jnp.ndarray, ...]]:
    """Jitted per-round prep: pending mask, per-column metrics, the
    bucketing priority key, and the state-sum trace sample — one fused
    device pass, so the host reads back only what it must. Reads the first
    ``d`` columns only: the pallas backend's lane padding is not state."""
    lat_min = ks == "min_plus"

    @jax.jit
    def prep(p: jnp.ndarray, r: jnp.ndarray, eps_v: jnp.ndarray,
             col_live: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
        p, r = p[:, :d], r[:, :d]
        if ks == "plus_times":
            pend = jnp.abs(r) > eps_v[:, None]
            metric = jnp.max(jnp.abs(r), axis=0)
        else:
            newp = jnp.minimum(p, r) if lat_min else jnp.maximum(p, r)
            pend = newp != p
            metric = pending_cols(ks, p, r, xp=jnp)
        res_col = jnp.sum(pend.astype(jnp.float32), axis=0)
        live = pend & col_live[None, :]
        active_v = jnp.any(live, axis=1)
        if ks == "plus_times":
            key = -jnp.max(jnp.where(live, jnp.abs(r), 0.0), axis=1)
        else:
            cand = jnp.minimum(p, r) if lat_min else jnp.maximum(p, r)
            best = (jnp.min(cand, axis=1) if lat_min
                    else -jnp.max(cand, axis=1))
            key = jnp.where(active_v, best, jnp.float32(np.inf))
        ssum = jnp.sum(jnp.where(jnp.abs(p) < 1e30, p, 0.0))
        return active_v, res_col, metric, key, ssum

    return prep


def _make_round_jax(algo: AlgoInstance, ks: str) -> Any:
    """The vectorized (Jacobi-style) push round: every active vertex of
    every live column pushes at once; scatters land via segment reduce.
    Converged columns are masked out of the push, so they freeze bitwise."""
    src = jnp.asarray(algo.src)
    dst = jnp.asarray(algo.dst)
    w = jnp.asarray(algo.w, jnp.float32)[:, None]
    fixed = jnp.asarray(algo.fixed)
    x0 = jnp.asarray(algo.x0, jnp.float32).reshape(algo.n, algo.d)
    n = algo.n
    ident = ACC_IDENTITY[ks]
    lat_min = ks == "min_plus"

    @jax.jit
    def round_sum(p: jnp.ndarray, r: jnp.ndarray, active_v: jnp.ndarray,
                  col_live: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        mask = active_v[:, None] & col_live[None, :]
        push = jnp.where(mask, r, 0.0)
        p2 = p + push
        r2 = r - push
        r2 = r2.at[dst].add(w * push[src])
        r2 = jnp.where(fixed, 0.0, r2)
        return p2, r2

    @jax.jit
    def round_lattice(p: jnp.ndarray, r: jnp.ndarray, active_v: jnp.ndarray,
                      col_live: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        mask = active_v[:, None] & col_live[None, :]
        newp = jnp.minimum(p, r) if lat_min else jnp.maximum(p, r)
        p2 = jnp.where(mask, newp, p)
        r2 = jnp.where(mask, jnp.float32(ident), r)
        if ks == "min_plus":
            msgs = p2[src] + w
        elif ks == "max_min":
            msgs = jnp.minimum(p2[src], w)
        else:
            msgs = p2[src] * w
        msgs = jnp.where(mask[src], msgs, jnp.float32(ident))
        if lat_min:
            agg = jnp.full((n, p.shape[1]), ident, p.dtype).at[dst].min(msgs)
            r2 = jnp.minimum(r2, agg)
        else:
            agg = jnp.full((n, p.shape[1]), ident, p.dtype).at[dst].max(msgs)
            r2 = jnp.maximum(r2, agg)
        p2 = jnp.where(fixed, x0, p2)
        r2 = jnp.where(fixed, x0, r2)
        return p2, r2

    return round_sum if ks == "plus_times" else round_lattice


def _pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


class _PallasRound:
    """Host-side bucketing + kernel dispatch for one push round.

    The kernel moves whole 128-lane rows, so the ``(p, r)`` state it works
    on is lane-padded (:meth:`pad`). The padding columns are pinned: the
    post-round cleanup resets them, so they never pend and never reach a
    real column."""

    def __init__(self, algo: AlgoInstance, ks: str, buckets: int) -> None:
        from repro.kernels.ops import interpret_mode
        from repro.kernels.push_scatter import pad_edges

        indptr, nbrs, eid = Graph(algo.n, algo.src, algo.dst, algo.w).csr()
        self.indptr = indptr.astype(np.int64)
        self.ks = ks
        self.buckets = buckets
        self.interpret = interpret_mode()
        self.nbrs = jnp.asarray(pad_edges(nbrs.astype(np.int32)))
        self.ew = jnp.asarray(pad_edges(np.asarray(algo.w, np.float32)[eid]))
        ident = ACC_IDENTITY[ks]
        # the inert value of a padding column, in p and in r alike
        self._fill = 0.0 if ks == "plus_times" else ident
        self.fixed = jnp.asarray(self.pad(np.asarray(algo.fixed), True))
        self.x0 = jnp.asarray(self.pad(
            np.asarray(algo.x0, np.float32).reshape(algo.n, algo.d),
            self._fill))

        @jax.jit
        def cleanup(p: jnp.ndarray, r: jnp.ndarray, fixed: jnp.ndarray,
                    x0: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
            # pinned rows: clamp the state, drop incoming messages (their x0
            # candidate re-seeds only at init); sum discards pinned residual
            if ks == "plus_times":
                return jnp.where(fixed, x0, p), jnp.where(fixed, 0.0, r)
            return jnp.where(fixed, x0, p), \
                jnp.where(fixed, jnp.float32(ident), r)

        self._cleanup = cleanup

    def pad(self, a: np.ndarray, fill: Any = None) -> np.ndarray:
        """Lane-pad an (n, d) host matrix; ``fill`` defaults to the inert
        state value."""
        from repro.kernels.ops import pad_lanes

        return pad_lanes(a, self._fill if fill is None else fill)

    def __call__(
        self, p: jnp.ndarray, r: jnp.ndarray,
        ids: np.ndarray, key: np.ndarray,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        from repro.kernels.push_scatter import push_scatter_pallas

        order = np.argsort(key[ids], kind="stable")
        ids = ids[order].astype(np.int64)
        buckets = min(self.buckets, max(1, len(ids)))
        cap = _pow2(-(-len(ids) // buckets))  # pow2 caps bound recompiles
        vid = np.full(buckets * cap, -1, np.int32)
        vid[: len(ids)] = ids
        seg_s = np.zeros(buckets * cap, np.int32)
        seg_l = np.zeros(buckets * cap, np.int32)
        seg_s[: len(ids)] = self.indptr[ids]
        seg_l[: len(ids)] = self.indptr[ids + 1] - self.indptr[ids]
        p2, r2, _, _ = push_scatter_pallas(
            jnp.asarray(vid), jnp.asarray(seg_s), jnp.asarray(seg_l),
            self.nbrs, self.ew, p, r,
            semiring=self.ks, buckets=buckets, cap=cap,
            interpret=self.interpret,
        )
        return self._cleanup(p2, r2, self.fixed, self.x0)


def _solve(algo: AlgoInstance, o: EngineOptions) -> RunResult:
    """solve()'s dispatch target for ``engine="push"``.

    Besides the legacy working-round ``residuals`` buffer this driver keeps
    the uniform per-round :class:`~repro.obs.telemetry.ConvergenceTrace`:
    one entry per counted round — *including* the empty-frontier accounting
    rounds (residual 0, work 0) — whose residual is the **post**-round
    pending metric read at the next round's prep. The metric rides the same
    fused per-round readout the untraced driver already performs (this is a
    host-driven engine: its per-round syncs are its execution model, each
    audited below), so telemetry adds no transfers; only a budget-exhausted
    exit pays one extra prep to close the final entry.
    """
    from repro.obs.telemetry import trace_from_push_counts
    from repro.obs.trace import tspan

    ks = _kernel_semiring(algo)
    n, d = algo.n, algo.d
    with tspan(o.trace, "pack", algo=algo.name, n=n, d=d, engine="push",
               backend=o.backend):
        p0, r0 = _init_state(algo, ks, o.x_init)
        eps_v = (
            _eps_vec(algo, o.beta) if ks == "plus_times"
            else np.zeros(n, np.float32)
        )
        outdeg = np.bincount(algo.src, minlength=n).astype(np.int64)

        round_jax = _make_round_jax(algo, ks) if o.backend == "jax" else None
        round_pallas = (
            _PallasRound(algo, ks, o.buckets) if o.backend == "pallas" else None
        )
        if round_pallas is not None:
            p0, r0 = round_pallas.pad(p0), round_pallas.pad(r0)
        p = jnp.asarray(p0)
        r = jnp.asarray(r0)
        eps_dev = jnp.asarray(eps_v)
        prep = _make_prep(ks, d)

    col_done = np.zeros(d, bool)
    col_rounds = np.zeros(d, np.int32)
    res_buf: list[float] = []
    sum_buf: list[float] = []
    trace_res: list[float] = []     # post-round metric per counted round
    trace_pushed: list[float] = []  # vertices settled per counted round
    open_cols: Optional[np.ndarray] = None  # last round's active columns
    touched = np.zeros(n, bool)
    pushed_total = 0
    edges_total = 0
    k = 0
    while k < o.max_iters:
        col_live = jnp.asarray(~col_done)
        active_v, res_col, metric, key, ssum = prep(p, r, eps_dev, col_live)
        res_col_h, metric_h = (np.asarray(a) for a in jax.device_get(
            (res_col, metric)
        ))  # repro: allow-host-sync(per-round pending counts drive the host frontier loop)
        if open_cols is not None:
            # close the previous round's trace entry with its post-round
            # residual — the value this prep just measured
            trace_res.append(float(np.max(np.where(open_cols, metric_h, 0.0))))
            open_cols = None
        _, active_cols, col_done, col_rounds = converge_step(
            res_col_h, 0.0, col_done, col_rounds
        )
        if bool(col_done.all()):
            break
        mask_h = np.asarray(jax.device_get(
            active_v
        ))  # repro: allow-host-sync(frontier ids select this round's scatter set)
        ids = np.nonzero(mask_h)[0]
        if len(ids) == 0:
            # live columns with zero pending rows: they are done too (their
            # res_col was 0 and converge_step just flagged them) — loop once
            # more to fold the accounting, no work to dispatch
            trace_res.append(0.0)
            trace_pushed.append(0.0)
            k += 1
            continue
        res_buf.append(float(np.max(metric_h[active_cols])))
        sum_buf.append(float(jax.device_get(
            ssum
        )))  # repro: allow-host-sync(per-round state-sum trace sample)
        touched[ids] = True
        pushed_total += int(len(ids))
        edges_total += int(outdeg[ids].sum())
        trace_pushed.append(float(len(ids)))
        open_cols = active_cols.copy()
        if round_pallas is not None:
            key_h = np.asarray(jax.device_get(
                key
            ))  # repro: allow-host-sync(priority keys drive host-side bucketing)
            p, r = round_pallas(p, r, ids, key_h)
        else:
            assert round_jax is not None
            p, r = round_jax(p, r, active_v, col_live)
        k += 1

    if open_cols is not None:
        # budget exhausted mid-frontier: one extra fused prep supplies the
        # final round's post-push metric (unconverged exits only)
        _, _, metric, _, _ = prep(p, r, eps_dev, jnp.asarray(~col_done))
        metric_h = np.asarray(jax.device_get(
            metric
        ))  # repro: allow-host-sync(final trace entry on budget-exhausted exit)
        trace_res.append(float(np.max(np.where(open_cols, metric_h, 0.0))))

    converged = bool(col_done.all())
    x = np.asarray(jax.device_get(
        p[:, :d]
    ), np.float32)  # repro: allow-host-sync(end-of-run RunResult readout)
    if d == 1:
        x = x[:, 0]
    res = RunResult(
        x=x,
        rounds=k,
        converged=converged,
        residuals=np.asarray(res_buf, np.float32),
        state_sums=np.asarray(sum_buf, np.float32),
        col_rounds=col_rounds.copy(),
        col_converged=col_done.copy(),
        convergence_trace=trace_from_push_counts(trace_res, trace_pushed, n=n),
    )
    res.push_stats = {
        "pushed": pushed_total,
        "edges": edges_total,
        "touched": int(touched.sum()),
        "touched_fraction": float(touched.mean()) if n else 0.0,
        "rounds": k,
    }
    return res


def run_push(
    algo: AlgoInstance,
    *,
    x_init: Optional[np.ndarray] = None,
    backend: str = "jax",
    beta: float = 1.0,
    buckets: int = 4,
    max_iters: int = 2000,
) -> RunResult:
    """Thin shim: ``solve(algo, engine="push", ...)`` with the legacy
    keyword style of the other ``run_*`` entry points."""
    o = EngineOptions(x_init=x_init, backend=backend, beta=beta,
                      buckets=buckets, max_iters=max_iters)
    validate_options("push", o, algo)
    return _solve(algo, o)
