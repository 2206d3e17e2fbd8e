#!/usr/bin/env python3
"""Smoke run of the graph engine and server on one TPU chip.

Drives the main path once through the entry points a user calls —
``repro.solve``, ``repro.GraphServer`` and ``repro.run_incremental`` — at a
size a deployment would call real, on graphs generated from ``--seed``, and
checks every answer against an independent reference:

* ``analytics_jax``: PageRank and SSSP with ``engine="async_block"`` (jax
  backend) on a scrambled power-law graph of 2^20 vertices and ~8.4M edges,
  against the float64 / Dijkstra references of ``algo.exact()``.
* ``analytics_pallas``: the persistent megakernel (``backend="pallas"``,
  bs = 128, 16 sweeps per launch) on ``grid_2d(1024, 1024)`` in id order,
  whose ~41k dense tiles fit one chip (a scrambled power-law graph of this
  size would need terabytes of tiles). PageRank against ``exact()``; BFS
  bitwise against the jax backend and against ``exact()``.
* ``serving``: a ``GraphServer`` on the weighted grid answers 16
  personalized-PageRank queries (seeds anywhere) and 16 SSSP queries
  (sources in the top 64 rows) and absorbs one graph delta while they are
  in flight; every ticket matches a ``solve`` of its query, one column of
  a solve of all queries that resolved on the same graph (bitwise for
  SSSP, 1e-5 for PPR).
* ``push``: ``run_incremental(engine="push", backend="pallas")`` absorbs a
  10-edge SSSP delta; the result is bitwise the megakernel's.

Every kernel phase also proves the lowered kernel ran: it records the
Pallas calls the engines made and checks that they were not interpreted and
that their compiled program holds a ``tpu_custom_call``.

``--four-chips`` runs only the path across chips: ``solve(engine=
"distributed")`` over a 4-device mesh on the analytics graph, compared with
the single-device ``async_block`` answer (bitwise for SSSP, 2e-5 + 1e-4·|x|
for PageRank).

Per-phase lines come first; they are smoke output, not measurements. The
last line is one JSON object naming the device. Without a TPU the script
exits non-zero and prints no result; nothing falls back to the CPU.

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# the tolerance of the engine tests against the float64 references
ATOL, RTOL = 2e-5, 1e-4
# served PPR columns against a solo solve: both stop at eps = 1e-6 from
# different sides of the fixpoint (the serving tests' bound)
PPR_ATOL = 1e-5

ANALYTICS_N, ANALYTICS_M = 1 << 20, 8
GRID = (1024, 1024)
BS = 128
SWEEPS = 16
QUERIES = 16
# a block update is Jacobi inside its own block, so on the grid every
# in-block hop of a shortest path costs one sweep: rows of 1,024 vertices
# take ~1,000 sweeps to cross, and each upward hop one more
MAX_ROUNDS = 4096
# SSSP sources sit in the grid's top rows, so few paths climb (bounding
# the sweeps); PPR seeds are drawn from the whole grid
SOURCE_ROWS = 64

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Clock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    compilation-cache hits and misses, as its monitoring events report."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phase:
    """Times one phase: set-up (until :meth:`setup_done`), compile (from the
    clock) and run (the rest); prints one line of ``key=value`` fields."""

    def __init__(self, name: str, clock: Clock) -> None:
        self.name, self.clock = name, clock
        self.fields: dict = {}

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.c0 = self.clock.compile_s
        self.setup_s = None
        return self

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0

    def note(self, **kw) -> None:
        self.fields.update(kw)

    def __exit__(self, exc_type, exc, tb) -> None:
        import jax

        wall = time.perf_counter() - self.t0
        setup = self.setup_s or 0.0
        compile_s = self.clock.compile_s - self.c0
        stats = jax.devices()[0].memory_stats() or {}
        status = "ok" if exc_type is None else f"FAILED({exc_type.__name__})"
        fields = {
            "status": status, "setup_s": round(setup, 3),
            "compile_s": round(compile_s, 3),
            "run_s": round(max(wall - setup - compile_s, 0.0), 3),
            **self.fields,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
        print(f"[{self.name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
              flush=True)


class KernelSpy:
    """Records the distinct calls the engines make to one Pallas kernel
    while a phase runs, so the phase can show what actually executed."""

    def __init__(self, module: str, name: str) -> None:
        import importlib

        self.module, self.name = importlib.import_module(module), name
        self.fn = getattr(self.module, name)
        self.calls: dict = {}

    def __enter__(self) -> "KernelSpy":
        import jax

        def spy(*args, **kw):
            abstract = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
            key = repr((abstract, sorted(kw.items())))
            self.calls.setdefault(key, (abstract, kw))
            return self.fn(*args, **kw)

        setattr(self.module, self.name, spy)
        return self

    def __exit__(self, *exc) -> None:
        setattr(self.module, self.name, self.fn)

    def check_lowered(self) -> None:
        """Every recorded call ran lowered: not interpreted, and compiled to
        a program that holds the Mosaic kernel."""
        require(bool(self.calls), f"{self.name} never ran")
        for abstract, kw in self.calls.values():
            require(kw.get("interpret") is False,
                    f"{self.name} ran with interpret={kw.get('interpret')!r}")
            text = self.fn.lower(*abstract, **kw).compile().as_text()
            require("tpu_custom_call" in text,
                    f"{self.name} compiled without a tpu_custom_call")

    def tile_bytes(self) -> int:
        """``bsr_stats()["tile_bytes"]`` of the packed operands it ran on."""
        import numpy as np

        abstract = next(iter(self.calls.values()))[0]
        tiles = abstract[5]  # gs_multisweep_pallas(..., tiles, ...)
        return int(np.prod(tiles.shape)) * tiles.dtype.itemsize


def analytics_graphs(seed: int, n: int, m: int):
    from repro.graphs import generators as gen

    g = gen.scrambled(gen.powerlaw_cluster(n, m, seed=seed), seed=seed + 1)
    return g, gen.with_random_weights(g, lo=0.1, hi=1.0, seed=seed + 2)


def check_close(name: str, x, ref, atol: float = ATOL, rtol: float = RTOL) -> None:
    import numpy as np

    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    require(x.shape == ref.shape, f"{name}: shape {x.shape} != {ref.shape}")
    require(bool(np.all(np.isfinite(x))), f"{name}: non-finite values")
    err = np.abs(x - ref) - (atol + rtol * np.abs(ref))
    require(bool(np.all(err <= 0)),
            f"{name}: {int(np.sum(err > 0))} entries off, worst excess "
            f"{float(err.max()):.3g}")


def check_equal(name: str, x, ref) -> None:
    import numpy as np

    x, ref = np.asarray(x), np.asarray(ref)
    require(x.shape == ref.shape, f"{name}: shape {x.shape} != {ref.shape}")
    bad = int(np.sum(x != ref))
    require(bad == 0, f"{name}: {bad} entries differ bitwise")


def phase_analytics_jax(clock: Clock, seed: int, n: int, m: int) -> None:
    from repro import solve
    from repro.engine import get_algorithm

    with Phase("analytics_jax", clock) as ph:
        g, gw = analytics_graphs(seed, n, m)
        ph.setup_done()
        ph.note(n=g.n, m=g.m)
        for name, graph in (("pagerank", g), ("sssp", gw)):
            algo = get_algorithm(name, graph, **({"source": 0} if name == "sssp" else {}))
            t = time.perf_counter()
            res = solve(algo, engine="async_block")
            solve_s = time.perf_counter() - t
            require(bool(res.converged), f"{name} did not converge")
            t = time.perf_counter()
            check_close(f"{name} vs exact", res.x, algo.exact())
            ph.note(**{f"{name}_rounds": res.rounds,
                       f"{name}_solve_s": round(solve_s, 3),
                       f"{name}_reference_s": round(time.perf_counter() - t, 3)})


def phase_analytics_pallas(clock: Clock, grid: tuple) -> None:
    from repro import solve
    from repro.engine import get_algorithm
    from repro.graphs import generators as gen

    with Phase("analytics_pallas", clock) as ph, \
            KernelSpy("repro.kernels.gs_sweep", "gs_multisweep_pallas") as spy:
        g = gen.grid_2d(*grid)
        ph.setup_done()
        ph.note(n=g.n, m=g.m, bs=BS, sweeps_per_call=SWEEPS)
        pallas = dict(engine="async_block", backend="pallas", bs=BS,
                      sweeps_per_call=SWEEPS, max_iters=MAX_ROUNDS)
        secs = {}

        def timed(key, fn, *a, **kw):
            t = time.perf_counter()
            out = fn(*a, **kw)
            secs[f"{key}_s"] = round(time.perf_counter() - t, 3)
            return out

        pr = get_algorithm("pagerank", g)
        res = timed("pagerank_solve", solve, pr, **pallas)
        require(bool(res.converged), "pagerank (pallas) did not converge")
        check_close("pagerank (pallas) vs exact", res.x,
                    timed("pagerank_reference", pr.exact))
        bfs = get_algorithm("bfs", g, source=0)
        r_pal = timed("bfs_solve", solve, bfs, **pallas)
        # the jax backend takes any bs; whole grid rows keep its sweep short
        r_jax = timed("bfs_jax_solve", solve, bfs, engine="async_block",
                      bs=grid[1], max_iters=MAX_ROUNDS)
        require(bool(r_pal.converged and r_jax.converged), "bfs did not converge")
        check_equal("bfs pallas vs jax", r_pal.x, r_jax.x)
        check_equal("bfs pallas vs exact", r_pal.x, bfs.exact())
        spy.check_lowered()
        ph.note(pagerank_rounds=res.rounds, bfs_rounds=r_pal.rounds,
                bfs_jax_rounds=r_jax.rounds, tile_bytes=spy.tile_bytes(),
                **secs)


def _reference(algo_name: str, graph, cols: list):
    """Answers of the served queries from one ``solve`` of them all, one
    query per column (columns never interact). It runs on the megakernel:
    lane padding makes 16 columns cost what one does, where the jax
    backend's per-block gather and scatter grow with them (with jax-backend
    references this phase ran 1,120 s on a TPU v5 lite). The kernel itself
    is checked against the jax backend and ``exact()`` in
    ``analytics_pallas``."""
    from repro import solve
    from repro.engine import get_algorithm

    key = "seeds" if algo_name == "ppr" else "sources"
    batched = "ppr" if algo_name == "ppr" else "ms_sssp"
    res = solve(get_algorithm(batched, graph, **{key: cols}),
                engine="async_block", backend="pallas", bs=BS,
                sweeps_per_call=SWEEPS, max_iters=MAX_ROUNDS)
    require(bool(res.converged), f"{algo_name} reference did not converge")
    return res.x.reshape(graph.n, len(cols))


def phase_serving(clock: Clock, seed: int, grid: tuple):
    from repro import GraphServer
    from repro.graphs import generators as gen
    from repro.graphs.delta import random_delta

    import numpy as np

    with Phase("serving", clock) as ph, \
            KernelSpy("repro.kernels.gs_sweep", "gs_multisweep_pallas") as spy:
        rng = np.random.default_rng(seed)
        g0 = gen.with_random_weights(gen.grid_2d(*grid), lo=0.1, hi=1.0,
                                     seed=seed + 3)
        delta = random_delta(g0, frac_add=16 / g0.m, w_lo=0.1, w_hi=1.0,
                             seed=seed + 4)
        g1 = delta.apply(g0)
        seeds = [int(v) for v in rng.choice(g0.n, QUERIES, replace=False)]
        sources = [int(v) for v in rng.choice(
            min(SOURCE_ROWS * grid[1], g0.n), QUERIES, replace=False)]
        srv = GraphServer(
            g0, backend="pallas", bs=BS, sweeps_per_call=SWEEPS,
            rounds_per_batch=4 * SWEEPS, slots=QUERIES,
            max_rounds_per_query=MAX_ROUNDS, transfer_guard="disallow",
        )
        ph.setup_done()
        ph.note(n=g0.n, m=g0.m, delta_edges=g1.m - g0.m)
        tickets = [srv.submit("ppr", {"seeds": [s]}) for s in seeds]
        tickets += [srv.submit("sssp", {"source": s}) for s in sources]
        t0 = time.perf_counter()
        srv.step()                      # every query is in flight ...
        srv.apply_delta(delta)          # ... when the graph changes
        summary = srv.run()
        serve_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        require(all(t.done and t.converged for t in tickets),
                "a served query did not converge: "
                + str([(t.algo, t.params, t.status) for t in tickets
                       if not (t.done and t.converged)]))
        graphs = {0: g0, 1: g1}
        checked = 0
        for algo_name, key in (("ppr", "seeds"), ("sssp", "source")):
            for version, graph in graphs.items():
                mine = [t for t in tickets
                        if t.algo == algo_name and t.graph_version == version]
                if not mine:
                    continue
                cols = [int(np.ravel(t.params[key])[0]) for t in mine]
                ref = _reference(algo_name, graph, cols)
                for j, t in enumerate(mine):
                    name = f"{algo_name} ticket {t.id} (v{version})"
                    if algo_name == "ppr":
                        check_close(name, t.result, ref[:, j], atol=PPR_ATOL,
                                    rtol=0.0)
                    else:
                        check_equal(name, t.result, ref[:, j])
                    checked += 1
        require(checked == len(tickets), f"checked {checked} of {len(tickets)}")
        reference_s = time.perf_counter() - t0
        spy.check_lowered()
        ph.note(queries=len(tickets), batches=summary.get("batches"),
                tile_bytes=spy.tile_bytes(), serve_s=round(serve_s, 3),
                reference_s=round(reference_s, 3))
        survivors = [t for t in tickets
                     if t.algo == "sssp" and t.graph_version == 1]
        require(bool(survivors), "no SSSP query resolved after the delta")
        return g1, survivors[0]


def phase_push(clock: Clock, seed: int, g1, ticket) -> None:
    from repro import run_incremental
    from repro.engine import get_algorithm
    from repro.graphs.delta import random_delta

    import numpy as np

    with Phase("push", clock) as ph, \
            KernelSpy("repro.kernels.push_scatter", "push_scatter_pallas") as spy:
        delta = random_delta(g1, frac_add=10 / g1.m, w_lo=0.1, w_hi=1.0,
                             seed=seed + 5)
        g2 = delta.apply(g1)
        src = int(ticket.params["source"])
        old = get_algorithm("sssp", g1, source=src)
        new = get_algorithm("sssp", g2, source=src)
        ph.setup_done()
        ph.note(n=g2.n, delta_edges=g2.m - g1.m)
        # push rounds follow the changed distances hop by hop, up to the
        # grid's diameter of ~2,000 hops
        r_push = run_incremental(new, old, np.asarray(ticket.result),
                                 engine="push", backend="pallas",
                                 max_iters=2 * MAX_ROUNDS)
        r_sweep = run_incremental(new, old, np.asarray(ticket.result),
                                  engine="async_block", backend="pallas",
                                  bs=BS, sweeps_per_call=SWEEPS,
                                  max_iters=MAX_ROUNDS)
        require(bool(r_push.converged and r_sweep.converged),
                "incremental sssp did not converge")
        check_equal("push vs async_block", r_push.x, r_sweep.x)
        spy.check_lowered()
        ph.note(push_rounds=r_push.rounds,
                pushed=r_push.push_stats["pushed"],
                sweep_rounds=r_sweep.rounds)


def phase_four_chips(clock: Clock, seed: int, n: int, m: int) -> None:
    import jax

    from repro import solve
    from repro.engine import get_algorithm

    require(len(jax.devices()) == 4,
            f"--four-chips needs 4 devices, found {len(jax.devices())}")
    with Phase("distributed", clock) as ph:
        g, gw = analytics_graphs(seed, n, m)
        ph.setup_done()
        ph.note(n=g.n, m=g.m, devices=len(jax.devices()))
        for name, graph in (("pagerank", g), ("sssp", gw)):
            algo = get_algorithm(name, graph, **({"source": 0} if name == "sssp" else {}))
            r_dist = solve(algo, engine="distributed")
            r_one = solve(algo, engine="async_block")
            require(bool(r_dist.converged and r_one.converged),
                    f"{name} did not converge")
            if name == "sssp":
                check_equal("sssp distributed vs async_block", r_dist.x, r_one.x)
            else:
                check_close("pagerank distributed vs async_block",
                            r_dist.x, r_one.x)
            ph.note(**{f"{name}_rounds": r_dist.rounds,
                       f"{name}_one_device_rounds": r_one.rounds})


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the distributed path over 4 chips")
    args = parser.parse_args(argv)

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.runtime.compile_cache import cache_entries, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache(HERE)
    clock = Clock()
    entries0 = cache_entries(cache_dir)
    dev = jax.devices()[0]
    print(f"[start] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} cache_dir={cache_dir} "
          f"cache_entries={entries0}", flush=True)
    if args.four_chips:
        phase_four_chips(clock, args.seed, ANALYTICS_N, ANALYTICS_M)
    else:
        phase_analytics_jax(clock, args.seed, ANALYTICS_N, ANALYTICS_M)
        phase_analytics_pallas(clock, GRID)
        g1, ticket = phase_serving(clock, args.seed, GRID)
        phase_push(clock, args.seed, g1, ticket)
    print(f"[end] cache_entries={cache_entries(cache_dir)} "
          f"(was {entries0}) cache_hits={clock.hits} "
          f"cache_misses={clock.misses} compile_s={clock.compile_s:.3f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
