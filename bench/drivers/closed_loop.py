"""Closed loop of cold solves: one caller runs ``repro.solve`` on the cell's
graph again and again, each call from scratch, and waits for each result on
the host before it starts the next.

Traffic parameters (``bench/traffic/<name>.json``): ``algorithm`` and its
``params`` (``damping``, ``eps``), passed to the program's algorithm
constructor and to the reference alike. The configuration's ``engine``
section holds the ``solve`` options.

End to end: ``fixpoint_s``, the mean seconds of the solves that completed
inside the window. Checked: every solve the window started, against the
float64 reference (the gaps of ``lib.reference.gaps`` that the cell's
limits file names).
"""
from __future__ import annotations

import time

import numpy as np

from lib.harness import Outcome, annotate
from lib.reference import gaps, pagerank_f64, widest


def run(ctx) -> Outcome:
    from repro import solve
    from repro.engine import get_algorithm

    tr = ctx.traffic
    opts = dict(ctx.config["engine"])
    g, rank, (n, src, dst, w) = ctx.build_graph()
    algo = get_algorithm(tr["algorithm"], g, **tr["params"])
    ctx.setup.mark("instance")
    # one solve through the window's own call compiles every program the
    # window drives; on a warm cache it loads them
    warm = solve(algo, rank=rank, **opts)
    ctx.log("warmup", rounds=warm.rounds, converged=bool(warm.converged))
    del warm

    solves = []
    with ctx.window() as win:
        while not win.over():
            tracer = ctx.program_tracer()
            t = time.perf_counter()
            with annotate("bench.solve"):
                res = solve(algo, rank=rank, trace=tracer, **opts)
            t_end = time.perf_counter()
            pack = tracer.find("pack") if tracer is not None else []
            solves.append({
                "seconds": t_end - t, "in_window": t_end <= win.end,
                "rounds": int(res.rounds), "converged": bool(res.converged),
                "pack_s": sum(s.duration_s for s in pack) if pack else None,
                "x": np.asarray(res.x),
            })
    ctx.read_device()
    del algo

    done = [s for s in solves if s["in_window"]]
    secs = [s["seconds"] for s in solves]
    ctx.log("solves", started=len(solves), completed_in_window=len(done),
            seconds_min=min(secs), seconds_median=float(np.median(secs)),
            seconds_max=max(secs), rounds=sorted({s["rounds"] for s in solves}))
    end_to_end = {}
    if done:
        end_to_end["fixpoint_s"] = sum(s["seconds"] for s in done) / len(done)

    t = time.perf_counter()
    ref = pagerank_f64(n, src, dst, w, tr["params"]["damping"])
    read = widest(gaps(s["x"], ref) for s in solves)
    ctx.log("reference", seconds=time.perf_counter() - t, solves_checked=len(solves),
            **read)
    for s in solves:
        del s["x"]
    return Outcome(
        attempted=len(solves),
        failed=sum(not s["converged"] for s in solves),
        end_to_end=end_to_end,
        checks={k: read[k] for k in ctx.cell.limits},
        record={"solves": solves, "n": n, "m": len(src), "columns": 1},
    )
