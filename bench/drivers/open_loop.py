"""Open loop of single-seed queries into a ``repro.GraphServer``.

Independent users send queries on a schedule, whether or not earlier ones
have resolved. Traffic parameters (``bench/traffic/<name>.json``):

* ``algorithm`` and ``params``: the query (``ppr`` with ``damping`` and
  ``eps``; each query adds its own ``seeds``),
* ``rate_qps``: the offered load; a window of ``s`` seconds carries
  ``round(rate_qps * s)`` queries,
* ``gaps``: the shape of the arrivals. ``"exponential"`` draws the gaps
  between arrivals once, from a fixed stream, scaled so the last query is
  due inside the window; each run's seed only reorders them, so every seed
  offers the same gaps (a Poisson process conditioned on its count) and
  only their order differs,
* ``seed_vertices``: ``"uniform"`` over all vertices.

The configuration's ``server`` section holds the ``GraphServer`` options.
The benchmark times every query itself, from when it was due to when the
``step`` that resolved it returned (or its ``submit``, on a cache hit);
queries still in flight when the window closes are followed to resolution.
End to end: ``query_p50_s``, ``query_p95_s`` over every query due in the
window, and ``served_qps``, the queries resolved inside the window over its
length. Checked: every answer to a query due in the window, by its float64
residual (``lib.reference.residual_max``, ``resid_max`` in the limits file).
"""
from __future__ import annotations

import time

import numpy as np

from lib.harness import Outcome, annotate
from lib.reference import residual_max

_GAP_STREAM = 20240719  # the fixed stream the arrival gaps are drawn from


def arrivals(rate_qps: float, seconds: float, seed: int, shape: str) -> np.ndarray:
    """Due times (seconds after the window opens) of the window's queries."""
    count = int(round(rate_qps * seconds))
    if shape != "exponential":
        raise ValueError(f"unknown arrival gaps {shape!r}")
    gaps_ = np.random.default_rng(_GAP_STREAM).exponential(1.0, count + 1)
    gaps_ *= seconds / gaps_.sum()          # the last query is due inside
    gaps_ = gaps_[:count]
    order = np.random.default_rng([seed, 2]).permutation(count)
    return np.cumsum(gaps_[order])


def queries(traffic: dict, seconds: float, seed: int, n: int):
    """The window's due times (seconds after it opens) and seed vertices."""
    if traffic.get("seed_vertices", "uniform") != "uniform":
        raise ValueError(f"unknown seed_vertices {traffic['seed_vertices']!r}")
    due = arrivals(traffic["rate_qps"], seconds, seed, traffic["gaps"])
    seeds = np.random.default_rng([seed, 3]).integers(0, n, len(due))
    return due, seeds


def serve(srv, algo_name: str, params: dict, due_abs, seeds) -> dict:
    """Offer the queries at their due times (``time.perf_counter`` seconds)
    and step the server until every one has resolved."""
    st = srv.stats
    n_q = len(due_abs)
    tickets: list = [None] * n_q
    sent = np.zeros(n_q)
    resolved = np.full(n_q, np.nan)
    c0 = (st.rounds_total, st.round_slots_total, st.batches)
    i, pending = 0, []
    while i < n_q or pending:
        now = time.perf_counter()
        while i < n_q and due_abs[i] <= now:
            with annotate("bench.submit"):
                t = srv.submit(algo_name, dict(params, seeds=[int(seeds[i])]))
            sent[i] = time.perf_counter()
            tickets[i] = t
            if t.done:               # answered from the result cache
                resolved[i] = sent[i]
            else:
                pending.append(i)
            i += 1
        if pending:
            with annotate("bench.step"):
                srv.step()
            now = time.perf_counter()
            still = []
            for j in pending:
                if tickets[j].done:
                    resolved[j] = now
                else:
                    still.append(j)
            pending = still
        elif i < n_q:
            with annotate("bench.arrival_wait"):
                time.sleep(max(0.0, due_abs[i] - time.perf_counter()))
    c1 = (st.rounds_total, st.round_slots_total, st.batches)
    return {"tickets": tickets, "due": np.asarray(due_abs), "sent": sent,
            "resolved": resolved, "t_end": time.perf_counter(),
            "rounds": c1[0] - c0[0], "round_slots": c1[1] - c0[1],
            "batches": c1[2] - c0[2]}


def run(ctx) -> Outcome:
    from repro import GraphServer

    tr = ctx.traffic
    algo_name, params = tr["algorithm"], dict(tr["params"])
    g, rank, (n, src, dst, w) = ctx.build_graph()
    srv = GraphServer(g, rank=rank, **ctx.config["server"])
    ctx.setup.mark("server")

    # warm-up: fill every slot twice over and drain, so the family is packed
    # and the batch, swap-in and readout programs are all compiled or loaded
    slots = ctx.config["server"]["slots"]
    warm = np.random.default_rng([ctx.seed, 1]).integers(0, n, 2 * slots)
    for v in warm:
        srv.submit(algo_name, dict(params, seeds=[int(v)]))
    srv.run()
    ctx.log("warmup", queries=len(warm), batches=srv.stats.batches)

    due, seeds = queries(tr, ctx.seconds, ctx.seed, n)
    with ctx.window() as win:
        sv = serve(srv, algo_name, params, win.start + due, seeds)
    ctx.read_device()

    tickets, due_abs, sent, resolved = sv["tickets"], sv["due"], sv["sent"], sv["resolved"]
    latency = resolved - due_abs
    late = sent - due_abs
    ctx.log("queries", due=len(due), resolved_in_window=int(np.sum(resolved <= win.end)),
            drain_s=sv["t_end"] - win.end, generator_late_p95_s=float(np.percentile(late, 95)),
            generator_late_max_s=float(late.max()),
            batches=sv["batches"], cache_hits=sum(t.from_cache for t in tickets))
    end_to_end = {
        "query_p50_s": float(np.percentile(latency, 50)),
        "query_p95_s": float(np.percentile(latency, 95)),
        "served_qps": float(np.sum(resolved <= win.end)) / ctx.seconds,
    }
    record = {
        "n": n, "m": len(src), "slots": slots,
        "tickets": [{"due": float(due_abs[j]), "started": tickets[j].started_at,
                     "resolved": float(resolved[j]), "rounds": int(tickets[j].rounds),
                     "from_cache": bool(tickets[j].from_cache)}
                    for j in range(len(due))],
        "rounds": sv["rounds"], "round_slots": sv["round_slots"],
        "batches": sv["batches"],
    }
    failed = sum(not (t.status in ("done", "cached") and t.converged) for t in tickets)
    answers = [t.result for t in tickets]
    del srv, tickets, sv
    t = time.perf_counter()
    read = {"resid_max": residual_max(n, src, dst, w, params["damping"],
                                      [int(v) for v in seeds], answers)}
    ctx.log("reference", seconds=time.perf_counter() - t, checked=len(answers), **read)
    return Outcome(attempted=len(due), failed=failed, end_to_end=end_to_end,
                   checks={k: read[k] for k in ctx.cell.limits}, record=record)
