"""One run of one cell: set-up, the measured window, the check, the line.

A traffic driver (``bench/drivers/<driver>.py``) does the cell's work
through a :class:`Context`. It builds the graph, warms up and marks each
set-up phase, runs the window inside :meth:`Context.window`, calls
:meth:`Context.read_device` before it frees the program's state, then
compares what the window produced with the reference. It returns a
:class:`Outcome`. This module turns that outcome into the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import shutil
import sys
import time

from lib import spec as spec_mod
from lib import trace as trace_mod
from lib.runtime import Clock, Setup, device_record


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.

    ``end_to_end`` holds every end-to-end metric the driver measures, by
    name; ``checks`` every number compared, ``{name: value}``, held to the
    cell's limit of that name; ``record`` whatever the per-layer readers
    read (solves, tickets, server counters, graph size)."""

    attempted: int
    failed: int
    end_to_end: dict
    checks: dict
    record: dict


class Window:
    """The measured window: ``seconds`` from when it opens."""

    def __init__(self, seconds: float) -> None:
        self.start = time.perf_counter()
        self.end = self.start + seconds

    def over(self) -> bool:
        return time.perf_counter() >= self.end


class Context:
    """What a driver sees of the run."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 t0: float, scratch: str, graph_cache: str | None) -> None:
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.scratch = scratch
        self.graph_cache = graph_cache
        self.clock = Clock()
        self.setup = Setup(t0)
        self.setup.mark("start")
        self.device = None
        self.trace_dir = None
        self.window_compiles = None
        self.window_cache = (None, None)
        self.setup_s = None

    def log(self, tag: str, **fields) -> None:
        print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
              flush=True)

    def build_graph(self):
        """The configuration's graph and, where it asks for one, the
        processing order the system computes for it. Returns
        ``(graph, rank, arrays)`` where ``arrays`` is the benchmark's own
        ``(n, src, dst, w)`` for the reference.

        The generated graph stands in for a dataset file, the same in every
        run (``lib.generators.make_graph``): with a ``graph_cache``
        directory it is kept there per graph section and loaded in later
        runs. The order is the system's own work and is computed in every
        run."""
        from repro.graphs.graph import Graph

        n, src, dst, w = load_graph(self.config["graph"], self.graph_cache)
        g = Graph(n, src.copy(), dst.copy(), None if w is None else w.copy())
        self.setup.mark("graph")
        rank = None
        order = self.config.get("order", "none")
        if order == "gograph":
            from repro.core.gograph import gograph_order

            rank = gograph_order(g)
            self.setup.mark("order")
        elif order != "none":
            raise ValueError(f"unknown order {order!r}")
        self.log("graph", n=n, m=len(src), order=order)
        return g, rank, (n, src, dst, w)

    def program_tracer(self):
        """The program's own span tracer in a traced run, else None."""
        if not self.trace:
            return None
        from repro.obs.trace import Tracer

        return Tracer()

    @contextlib.contextmanager
    def window(self):
        """Open the measured window; in a traced run the JAX profiler
        records it whole, inside a ``window`` host span."""
        import jax

        self.setup.mark("warmup")
        self.setup_s = self.setup.total()
        c0 = self.clock.snapshot()
        if self.trace:
            self.trace_dir = os.path.join(self.scratch, f"{self.cell.name}-{os.getpid()}")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # the benchmark's spans suffice
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with annotate("bench.window"):
                yield Window(self.seconds)
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            c1 = self.clock.snapshot()
            self.window_compiles = c1[1] - c0[1]
            self.window_cache = (c1[2] - c0[2], c1[3] - c0[3])

    def read_device(self) -> None:
        """Read the device record, peak memory included; call once the
        window has closed and before the reference runs."""
        self.device = device_record()


def load_graph(graph: dict, cache: str | None):
    """``make_graph(graph)``, kept in ``cache`` when one is given."""
    import hashlib
    import json

    import numpy as np

    from lib.generators import make_graph

    if cache is None:
        return make_graph(graph)
    key = hashlib.sha256(json.dumps(graph, sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(cache, f"{key}.npz")
    if os.path.exists(path):
        with np.load(path) as f:
            w = f["w"] if "w" in f else None
            return int(f["n"]), f["src"], f["dst"], w
    n, src, dst, w = make_graph(graph)
    os.makedirs(cache, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    arrays = {"n": np.int64(n), "src": src, "dst": dst}
    if w is not None:
        arrays["w"] = w
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return n, src, dst, w


def annotate(name: str):
    """A host span of the benchmark's own in the profiler's trace (one of
    ``lib.trace.HOST_SPANS``)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def _per_layer(cell, record: dict, tr, dev: dict) -> dict:
    from lib.work import peaks

    env = {"record": record, "trace": tr, "peaks": peaks(dev["kind"]),
           "config": cell.config}
    out = {}
    for m in cell.per_layer:
        path = os.path.join(cell.bench_dir, "metrics", m["name"] + ".py")
        reader = spec_mod.load_module(path, "metric_" + m["name"].replace(".", "_"))
        value = reader.read(env)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             scratch: str, graph_cache: str | None = None) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    ctx = Context(cell, seed, seconds, trace, t0, scratch, graph_cache)
    driver = spec_mod.load_module(
        os.path.join(cell.bench_dir, "drivers", cell.traffic["driver"] + ".py"),
        "driver_" + cell.traffic["driver"])
    outcome = driver.run(ctx)
    if ctx.device is None:
        raise RuntimeError("the driver never read the device record")
    ctx.log("setup", setup_s=ctx.setup_s, **ctx.setup.parts,
            compile_s=ctx.clock.compile_s, compiles=ctx.clock.compiles,
            cache_hits=ctx.clock.hits, cache_misses=ctx.clock.misses)
    ctx.log("window", compiles=ctx.window_compiles,
            cache_hits=ctx.window_cache[0], cache_misses=ctx.window_cache[1])

    dev = dict(ctx.device)
    breakdown = None
    if trace:
        xplane = trace_mod.find_xplane(ctx.trace_dir)
        tr = trace_mod.load(xplane)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        lo, hi = tr.window()
        dev["busy_s"] = trace_mod.busy_s(tr)
        dev["window_s"] = hi - lo
        ctx.log("trace", busy_s=dev["busy_s"], window_s=dev["window_s"],
                idle_by_span=trace_mod.idle_by_span(tr))
        metrics = _per_layer(cell, outcome.record, tr, dev)
        breakdown = trace_mod.breakdown(tr)
    else:
        e2e = dict(outcome.end_to_end, setup_s=ctx.setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"driver {cell.traffic['driver']!r} measured no "
                               f"{m['name']!r} for cell {cell.name!r}")
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    checks = {}
    correct = True
    for name, value in outcome.checks.items():
        if not math.isfinite(value):   # no answer, or a non-finite one
            value = sys.float_info.max
        limit = cell.limits[name]
        checks[name] = {"value": value, "limit": limit}
        correct = correct and value <= limit
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    ctx.log("checks", correct=correct,
            **{k: f"{c['value']!r}<={c['limit']!r}" for k, c in checks.items()})
    out = {"correct": correct, "attempted": outcome.attempted,
           "failed": outcome.failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks      # last: the numbers compared, beside their limits
    return out
