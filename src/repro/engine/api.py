"""The one validated entry path to the iterative engines: :func:`solve`.

The engines accreted four ``run_*`` entry points with copy-pasted,
partially-incompatible keyword surfaces; each validated its own corner of
the option space (``sweeps_per_call > 1`` on ``backend="jax"`` was rejected
in two places with two messages, ``extrapolate_every`` in three). This
module replaces that with a single frozen :class:`EngineOptions` record and
a single :func:`validate_options` pass, so every invalid combination is
rejected exactly once, with one exception family:

* :class:`EngineOptionsError` (a ``ValueError``) — the option combination
  is malformed or not meaningful (unknown engine/backend, non-positive
  budgets, pallas-only knobs on the pure-JAX backend).
* :class:`EngineUnsupportedError` (both an :class:`EngineOptionsError` and
  a ``NotImplementedError``) — the combination is meaningful but this build
  does not implement it (Aitken extrapolation on a nonlinear lattice
  semiring, extrapolation under sweep batching).

``except EngineOptionsError`` therefore catches *every* rejection the entry
path can raise, while pre-existing callers that caught ``ValueError`` or
``NotImplementedError`` keep working unchanged.

The legacy entry points — ``run_sync`` / ``run_async_block`` /
``run_distributed`` — survive as thin shims over :func:`solve` with their
old signatures, and ``run_incremental``'s engine routing goes through
:func:`solve` too, so there is exactly one dispatch table and one
validation pass in the package.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Optional

import numpy as np

if TYPE_CHECKING:  # avoid a module cycle: the engines import this module
    from repro.engine.algorithms import AlgoInstance
    from repro.engine.convergence import RunResult

ENGINES = ("sync", "async_block", "distributed", "push")
BACKENDS = ("jax", "pallas")


class EngineOptionsError(ValueError):
    """An :class:`EngineOptions` combination the engines reject.

    The single exception family for the entry path: every malformed or
    unsupported option combination raises this (or the
    :class:`EngineUnsupportedError` subclass), so callers can guard one
    ``except EngineOptionsError`` instead of enumerating ValueError /
    NotImplementedError / KeyError per entry point.
    """


class EngineUnsupportedError(EngineOptionsError, NotImplementedError):
    """A meaningful option combination this build does not implement.

    Subclasses both :class:`EngineOptionsError` (the family) and
    ``NotImplementedError`` (what the pre-`solve` entry points raised for
    these cases), so both old and new handling styles catch it.
    """


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Every knob the iterative engines accept, validated in one place.

    x_init : resume/warm-start state overlaid on the algorithm's ``x0``
        (``(n,)``, ``(n, 1)`` or ``(n, d)`` — see `harness.init_state`).
    extrapolate_every : Aitken acceleration period for linear sum-semiring
        systems; 0 = off, otherwise >= 2 (see `harness.loop`).
    backend : ``"jax"`` (gather/segment-reduce sweeps) or ``"pallas"``
        (fused flat-BSR kernel; ``engine="async_block"`` only).
    bs : block size of the processing order (block engines; ignored by
        ``engine="sync"``, which runs whole-graph Jacobi rounds). The pallas
        backend lowered for a TPU takes only multiples of 128.
    inner : per-block refinement sweeps (block engines, jax backend).
    sweeps_per_call : sweeps batched into one persistent megakernel launch
        (pallas backend only; > 1 enables in-kernel convergence and
        active-frontier block skipping).
    frontier : bool[n] dirty-vertex seed for the megakernel's frontier
        (pallas backend with ``sweeps_per_call > 1``; None = all dirty).
    max_iters : round budget.
    mesh / axis : device mesh for ``engine="distributed"`` (None = one
        mesh axis over every visible device).
    transfer_guard : device->host transfer sanitizer for the whole solve
        (None = jax default, or one of ``"allow"`` / ``"log"`` /
        ``"disallow"``); ``"disallow"`` turns any unaudited implicit
        device->host readback inside the engines into a hard fault.
    push_threshold : frontier-fraction cutoff for ``engine="auto"``: route
        to the vertex-granular push engine when
        `engine.push.estimate_frontier_fraction` estimates fewer than this
        fraction of vertices start pending, else to the block sweep. 0
        never routes to push, 1 always does (when the semiring supports it).
    beta : push engine only — per-vertex threshold exponent, ``eps_vec =
        eps * outdeg**(1 - beta)`` (sum semirings; 1.0 = the sweep engines'
        uniform eps, < 1 = InstantGNN-style degree-normalized early stop).
    buckets : push engine, pallas backend only — priority buckets per
        round (bucket 0 = best priority settles first: delta-stepping for
        min_plus, largest-residual-first for sums).
    rank : optional processing order (``rank[v]`` = ordinal position of v,
        e.g. a `core.gograph.gograph_order` / `extend_rank` result). The
        solve runs relabeled — ``x_init`` / ``frontier`` are permuted in and
        the returned state is permuted back — so callers stay in the
        instance's id space while the engine sweeps blocks in rank order.
    trace : optional `repro.obs.trace.Tracer` — span tracing for the solve
        (``solve`` / ``pack`` / ``sweep_call`` spans; see `repro.obs`).
        None or a disabled tracer costs nothing; an enabled one records at
        batch granularity or coarser, never per round, so a traced solve
        stays green under ``transfer_guard="disallow"`` and returns results
        bitwise identical to an untraced one.
    """

    x_init: Optional[np.ndarray] = None
    extrapolate_every: int = 0
    backend: str = "jax"
    bs: int = 256
    inner: int = 1
    sweeps_per_call: int = 1
    frontier: Optional[np.ndarray] = None
    max_iters: int = 2000
    mesh: Any = None
    axis: str = "data"
    transfer_guard: Optional[str] = None
    push_threshold: float = 0.05
    beta: float = 1.0
    buckets: int = 4
    rank: Optional[np.ndarray] = None
    trace: Any = None


def validate_options(
    engine: str, o: EngineOptions, algo: "AlgoInstance | None" = None
) -> None:
    """Reject every invalid (engine, options[, algorithm]) combination.

    The one validation pass behind :func:`solve`, the ``run_*`` shims, and
    `AsyncBlockSession`. ``algo`` enables the algorithm-dependent checks
    (extrapolation requires a linear sum semiring); pass None to validate
    options whose algorithm is not known yet.
    """
    if engine not in ENGINES:
        raise EngineOptionsError(
            f"unknown engine {engine!r}; one of {sorted(ENGINES)}"
        )
    if o.backend not in BACKENDS:
        raise EngineOptionsError(
            f"unknown backend {o.backend!r}; one of {sorted(BACKENDS)}"
        )
    if o.bs < 1:
        raise EngineOptionsError(f"bs must be >= 1, got {o.bs}")
    if o.inner < 1:
        raise EngineOptionsError(f"inner must be >= 1, got {o.inner}")
    if o.max_iters < 1:
        raise EngineOptionsError(f"max_iters must be >= 1, got {o.max_iters}")
    if o.sweeps_per_call < 1:
        raise EngineOptionsError(
            f"sweeps_per_call must be >= 1, got {o.sweeps_per_call}"
        )
    if o.x_init is not None and np.ndim(o.x_init) not in (1, 2):
        raise EngineOptionsError(
            f"x_init must be (n,), (n, 1) or (n, d), "
            f"got ndim={np.ndim(o.x_init)}"
        )
    if not isinstance(o.axis, str) or not o.axis:
        raise EngineOptionsError(
            f"axis must be a non-empty mesh-axis name, got {o.axis!r}"
        )
    if o.mesh is not None and engine != "distributed":
        raise EngineOptionsError(
            "mesh names the device mesh for engine='distributed'; "
            f"engine={engine!r} runs on one device"
        )
    if o.transfer_guard not in (None, "allow", "log", "disallow"):
        raise EngineOptionsError(
            f"transfer_guard must be None, 'allow', 'log' or 'disallow', "
            f"got {o.transfer_guard!r}"
        )
    if not 0.0 <= o.push_threshold <= 1.0:
        raise EngineOptionsError(
            f"push_threshold is a frontier fraction in [0, 1], "
            f"got {o.push_threshold}"
        )
    if not 0.0 <= o.beta <= 1.0:
        raise EngineOptionsError(
            f"beta (push threshold exponent) must be in [0, 1], got {o.beta}"
        )
    if o.buckets < 1:
        raise EngineOptionsError(f"buckets must be >= 1, got {o.buckets}")
    if o.rank is not None:
        if np.ndim(o.rank) != 1:
            raise EngineOptionsError(
                f"rank must be a 1-D permutation of 0..n-1 "
                f"(rank[v] = processing position), got ndim={np.ndim(o.rank)}"
            )
        if algo is not None and len(o.rank) != algo.n:
            raise EngineOptionsError(
                f"rank covers {len(o.rank)} vertices, instance has {algo.n}"
            )
    if o.trace is not None:
        from repro.obs.trace import Tracer

        if not isinstance(o.trace, Tracer):
            raise EngineOptionsError(
                f"trace must be None or a repro.obs.trace.Tracer, "
                f"got {type(o.trace).__name__}"
            )
    if o.backend == "pallas":
        if engine not in ("async_block", "push"):
            raise EngineUnsupportedError(
                f"backend='pallas' runs the fused block-GS sweep "
                f"(engine='async_block') or the bucketed residual-push "
                f"scatter (engine='push'); engine={engine!r} has no kernel"
            )
        if o.inner != 1:
            raise EngineOptionsError(
                "backend='pallas' runs the fused sweep; inner must be 1"
            )
        if engine == "async_block":
            from repro.kernels.ops import LANES, interpret_mode

            if o.bs % LANES and not interpret_mode():
                # the lowered megakernel DMAs (bs, bs) tiles whose minor
                # dimension must fill whole lanes; rounding bs up silently
                # would change the processing blocks the caller asked for
                raise EngineOptionsError(
                    f"backend='pallas' lowers for the TPU here, which takes "
                    f"a bs that is a multiple of {LANES}; got bs={o.bs}"
                )
    elif engine != "push" and (o.sweeps_per_call != 1 or o.frontier is not None):
        raise EngineOptionsError(
            "sweeps_per_call/frontier amortize kernel launches and DMAs — "
            "pallas-backend knobs; backend='jax' supports neither"
        )
    if engine == "push":
        if o.sweeps_per_call != 1 or o.frontier is not None:
            raise EngineOptionsError(
                "engine='push' schedules its own per-round frontier; "
                "sweeps_per_call/frontier are sweep-engine knobs"
            )
        if o.inner != 1:
            raise EngineOptionsError(
                "engine='push' settles one vertex at a time; inner is a "
                "block-engine knob"
            )
        if o.extrapolate_every:
            raise EngineUnsupportedError(
                "engine='push' is itself the sparse acceleration; Aitken "
                "extrapolation applies to the dense sweep engines only"
            )
    if engine == "sync" and o.inner != 1:
        raise EngineOptionsError(
            "engine='sync' runs whole-graph Jacobi rounds; inner is a "
            "block-engine knob"
        )
    if o.extrapolate_every:
        if algo is not None and algo.semiring.reduce != "sum":
            raise EngineUnsupportedError(
                f"extrapolate_every is only valid for linear sum-semiring "
                f"systems; {algo.name!r} uses reduce={algo.semiring.reduce!r}"
            )
        if not o.extrapolate_every >= 2:
            # a period of 1 jumps every round off a rho estimated from the
            # previous jump's own step — the amplifications compound with no
            # contraction rounds between and the iteration diverges to NaN
            raise EngineOptionsError(
                f"extrapolate_every must be 0 (off) or >= 2, "
                f"got {o.extrapolate_every}"
            )
        if o.sweeps_per_call > 1 or o.frontier is not None:
            # both knobs route through the megakernel's batched driver
            raise EngineUnsupportedError(
                "extrapolate_every needs per-sweep host control; "
                "use sweeps_per_call=1"
            )


def solve(
    algo: "AlgoInstance",
    engine: str = "async_block",
    options: Optional[EngineOptions] = None,
    **overrides,
) -> "RunResult":
    """Converge ``algo`` with the chosen engine — the single entry path.

    ``engine``: ``"sync"`` (Jacobi rounds, paper Eq. 1), ``"async_block"``
    (block Gauss–Seidel, the TPU adaptation of Eq. 2), ``"distributed"``
    (shard_map supersteps: synchronous across shards, Gauss–Seidel within),
    ``"push"`` (vertex-granular residual push — the ultra-sparse regime),
    or ``"auto"`` (the frontier-size router: estimate the initial pending
    fraction via `engine.push.estimate_frontier_fraction` and pick
    ``"push"`` below ``options.push_threshold``, ``"async_block"`` above —
    or whenever the semiring has no push formulation).

    ``options`` is an :class:`EngineOptions`; keyword ``overrides`` are
    applied on top (``solve(algo, "async_block", bs=64)`` is shorthand for
    ``solve(algo, "async_block", options=EngineOptions(bs=64))``). All
    validation happens here, in :func:`validate_options`, before any engine
    code runs; the legacy ``run_*`` entry points are shims over this
    function, parity-tested bitwise for the min/max semirings.
    """
    o = options if options is not None else EngineOptions()
    if overrides:
        try:
            o = dataclasses.replace(o, **overrides)
        except TypeError:
            bad = sorted(set(overrides) - {f.name for f in dataclasses.fields(o)})
            raise EngineOptionsError(
                f"unknown EngineOptions field(s) {bad}; valid fields: "
                f"{[f.name for f in dataclasses.fields(o)]}"
            ) from None
    if engine == "auto":
        # the frontier-size router — resolved before validation so the
        # chosen engine's constraints (and only those) apply. Sweep-only
        # knobs are dropped when push wins: the router's contract is "same
        # answer, work proportional to the touched neighborhood", and a
        # caller-seeded frontier/sweep batch has no push meaning.
        from repro.engine import push as _push

        try:
            frac = _push.estimate_frontier_fraction(algo, o.x_init)
            use_push = frac < o.push_threshold
        except NotImplementedError:
            use_push = False
        if use_push:
            engine = "push"
            o = dataclasses.replace(
                o, sweeps_per_call=1, frontier=None, extrapolate_every=0,
            )
        else:
            engine = "async_block"
    validate_options(engine, o, algo)
    rank: Optional[np.ndarray] = None
    if o.rank is not None:
        # run relabeled: the engines sweep blocks of consecutive ids, so the
        # order becomes real by renaming vertex v to id rank[v]; the caller's
        # id-space vectors permute in and the result permutes back out
        from repro.engine.harness import permute_state
        from repro.graphs.graph import check_permutation

        rank = np.asarray(o.rank)
        check_permutation(rank, algo.n)
        algo = algo.relabel(rank)
        o = dataclasses.replace(
            o,
            rank=None,
            x_init=None if o.x_init is None
            else permute_state(np.asarray(o.x_init), rank),
            frontier=None if o.frontier is None
            else permute_state(np.asarray(o.frontier), rank),
        )
    # lazy imports: the engine modules import this module for the error
    # family and the shims, so the dispatch edge must not exist at import time
    from repro.engine import async_block, distributed, push, sync

    impl = {
        "sync": sync._solve,
        "async_block": async_block._solve,
        "distributed": distributed._solve,
        "push": push._solve,
    }[engine]
    from repro.obs.trace import tspan

    with tspan(o.trace, "solve", algo=algo.name, engine=engine,
               backend=o.backend, n=algo.n, d=algo.d) as sp:
        if o.transfer_guard is not None:
            import jax

            # direction-scoped on purpose: host->device staging of inputs is
            # normal engine behavior; unaudited device->host readback is the
            # bug class this sanitizer exists to catch (audited readouts go
            # through jax.device_get, which the guard always permits)
            with jax.transfer_guard_device_to_host(o.transfer_guard):
                res = impl(algo, o)
        else:
            res = impl(algo, o)
        sp.set(rounds=res.rounds, converged=bool(res.converged))
    if rank is not None:
        x = np.asarray(res.x).reshape(algo.n, -1)[rank]
        if algo.d == 1:
            x = x[:, 0]
        res = dataclasses.replace(res, x=x)
    return res
