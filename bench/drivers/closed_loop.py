"""Closed loop of cold solves: one caller runs ``repro.solve`` on the cell's
graph again and again, each call from scratch, and waits for each result on
the host before it starts the next.

Traffic parameters (``bench/traffic/<name>.json``): ``algorithm`` and its
``params`` (for PageRank ``damping`` and ``eps``), passed to the program's
algorithm constructor. A rooted algorithm (one whose module in
``bench/algorithms/`` names a ``ROOT_ARG``) also takes ``roots``: that many
roots are drawn from the run's seed (``lib.generators.roots``), the warm-up
solves once from each, and the window's solves cycle through them, so that
no single root's reach decides a run. The configuration's ``engine``
section holds the ``solve`` options.

End to end: ``fixpoint_s``, the mean seconds of the solves that completed
inside the window. Checked: every solve the window started, against its
root's float64 reference, each reference computed once per run; the
algorithm's module gives the reference and the numbers compared, and the
cell's limits file names which of them are held to a limit.
"""
from __future__ import annotations

import time

import numpy as np

from lib import spec
from lib.generators import roots as draw_roots
from lib.harness import Outcome, annotate
from lib.reference import widest


def run(ctx) -> Outcome:
    from repro import solve
    from repro.engine import get_algorithm

    tr = ctx.traffic
    alg = spec.load_algorithm(ctx.cell.bench_dir, tr["algorithm"])
    opts = dict(ctx.config["engine"])
    g, rank, (n, src, dst, w) = ctx.build_graph()
    if alg.ROOT_ARG is None:
        roots = [None]
        algos = [get_algorithm(tr["algorithm"], g, **tr["params"])]
    else:
        roots = [int(r) for r in draw_roots(n, src, tr["roots"], ctx.seed)]
        algos = [get_algorithm(tr["algorithm"], g, **tr["params"], **{alg.ROOT_ARG: r})
                 for r in roots]
        ctx.log("roots", roots=roots)
    ctx.setup.mark("instance")
    # one solve from each root through the window's own call compiles every
    # program the window drives (the push engine's scatter is compiled per
    # power-of-two frontier cap, which differ from root to root); on a warm
    # cache it loads them
    for algo in algos:
        c0 = ctx.clock.snapshot()
        warm = solve(algo, rank=rank, **opts)
        c1 = ctx.clock.snapshot()
        ctx.log("warmup", rounds=warm.rounds, converged=bool(warm.converged),
                compiles=c1[1] - c0[1], cache_hits=c1[2] - c0[2])
    del warm

    solves = []
    with ctx.window() as win:
        while not win.over():
            i = len(solves) % len(algos)
            tracer = ctx.program_tracer()
            t = time.perf_counter()
            with annotate("bench.solve"):
                res = solve(algos[i], rank=rank, trace=tracer, **opts)
            t_end = time.perf_counter()
            pack = tracer.find("pack") if tracer is not None else []
            solves.append({
                "seconds": t_end - t, "in_window": t_end <= win.end, "root": i,
                "rounds": int(res.rounds), "converged": bool(res.converged),
                "pack_s": sum(s.duration_s for s in pack) if pack else None,
                "push": res.push_stats, "x": np.asarray(res.x),
            })
    ctx.read_device()
    del algos

    done = [s for s in solves if s["in_window"]]
    secs = [s["seconds"] for s in solves]
    ctx.log("solves", started=len(solves), completed_in_window=len(done),
            seconds_min=min(secs), seconds_median=float(np.median(secs)),
            seconds_max=max(secs), rounds=sorted({s["rounds"] for s in solves}))
    push = [s["push"] for s in solves if s["push"]]
    if push:
        ctx.log("push", solves=len(push),
                **{k: float(np.mean([p[k] for p in push])) for k in push[0]})
    end_to_end = {}
    if done:
        end_to_end["fixpoint_s"] = sum(s["seconds"] for s in done) / len(done)

    t = time.perf_counter()
    used = sorted({s["root"] for s in solves})
    refs = dict(zip(used, alg.reference(n, src, dst, w, tr["params"],
                                        [roots[i] for i in used])))
    read = widest(alg.compare(s["x"], refs[s["root"]]) for s in solves)
    ctx.log("reference", seconds=time.perf_counter() - t, roots=len(used),
            solves_checked=len(solves), **read)
    for s in solves:
        del s["x"]
    return Outcome(
        attempted=len(solves),
        failed=sum(not s["converged"] for s in solves),
        end_to_end=end_to_end,
        checks={k: read[k] for k in ctx.cell.limits},
        record={"solves": solves, "n": n, "m": len(src), "columns": 1},
    )
