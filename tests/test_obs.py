"""The observability layer (PR tentpole): tracer, metrics registry,
convergence telemetry, and the serving wiring over them.

Four contracts:

1. **Zero-cost-when-disabled** — a ``None``/disabled tracer hands every
   call site the shared ``NULL_SPAN`` singleton and records nothing; a
   registry is inert until something observes into it.
2. **Telemetry is free and exact** — every engine's ``RunResult`` carries a
   ``convergence_trace`` built purely from already-transferred host data:
   length equals the round count, the final residual is the number that
   decided convergence (``<= eps`` iff converged within budget), and
   turning tracing ON changes nothing — bitwise for min/max semirings,
   identical round counts, on both jax and pallas backends, under
   ``transfer_guard="disallow"``.
3. **Exporters are honest** — ``summary()`` is a superset of the
   pre-registry `ServerStats` dict, and ``prometheus_text()`` emits
   parseable text exposition with cumulative histogram buckets.
4. **The cache-hit fix** — a cache hit contributes 0.0 to the *wait*
   population too (it used to skip it, overstating measured waits).
"""
import io
import json
import re

import numpy as np
import pytest

from repro import EngineOptions, EngineOptionsError, get_algorithm, solve
from repro.graphs import generators as gen
from repro.obs import (
    NULL_SPAN,
    ConvergenceTrace,
    MetricsRegistry,
    Tracer,
    active_columns_per_round,
    bounded_append,
    percentile,
    tspan,
)
from repro.serving.server import GraphServer
from repro.serving.stats import ServerStats

N = 300
BS = 64


@pytest.fixture(scope="module")
def gw():
    g = gen.scrambled(gen.powerlaw_cluster(N, 4, p=0.4, seed=1), seed=9)
    return gen.with_random_weights(g, lo=0.1, hi=1.0, seed=2)


# ------------------------------------------------------------- percentile


def test_percentile_edges():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(vals, 0) == 1.0      # rank clamps to 1 -> min
    assert percentile(vals, 100) == 5.0    # -> max
    assert percentile(vals, 50) == 3.0
    assert percentile([7.25], 0) == 7.25   # single sample for every q
    assert percentile([7.25], 99) == 7.25
    assert percentile([], 50) == 0.0       # empty -> 0.0, never raises


def test_percentile_is_an_observed_sample():
    vals = [0.1 * k for k in range(1, 101)]
    for q in (1, 37, 50, 90, 99, 100):
        assert percentile(vals, q) in vals


def test_bounded_append_window_halving():
    samples = []
    for v in range(10):
        bounded_append(samples, v, max_samples=6)
    # each overflow drops the oldest half; the tail is always the newest
    assert len(samples) <= 6
    assert samples[-1] == 9
    assert samples == sorted(samples)


def test_stats_module_reexports_percentile():
    # layering: serving's percentile IS the obs one (single implementation)
    from repro.serving import stats

    assert stats.percentile is percentile


# ----------------------------------------------------------------- tracer


def test_disabled_tracer_is_null_span():
    tr = Tracer(enabled=False)
    sp = tr.span("solve", algo="pagerank")
    assert sp is NULL_SPAN
    with sp as s:
        s.set(rounds=3)   # no-op, never raises
    assert len(tr.spans) == 0
    tr.event("resolve", rounds=1)
    assert len(tr.spans) == 0
    assert tspan(None, "batch") is NULL_SPAN
    assert tspan(tr, "batch") is NULL_SPAN


def test_ring_buffer_keeps_most_recent():
    tr = Tracer(ring=4)
    for k in range(7):
        tr.event("batch", k=k)
    assert len(tr.spans) == 4
    assert [s.attrs["k"] for s in tr.spans] == [3, 4, 5, 6]
    assert [s.attrs["k"] for s in tr.find("batch")] == [3, 4, 5, 6]
    assert tr.find("solve") == []


def test_jsonl_sink_flushes_per_span():
    sink = io.StringIO()
    tr = Tracer(jsonl=sink)
    with tr.span("solve", algo="sssp", engine="push") as sp:
        sp.set(rounds=5, converged=True)
    # flushed at exit: a live reader sees the line immediately
    lines = sink.getvalue().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["name"] == "solve"
    assert rec["algo"] == "sssp" and rec["engine"] == "push"
    assert rec["rounds"] == 5 and rec["converged"] is True
    assert rec["duration_s"] >= 0.0 and "t_start" in rec
    tr.event("resolve", tenant="default")
    assert len(sink.getvalue().splitlines()) == 2


def test_span_attrs_set_mid_span_land_in_record():
    tr = Tracer()
    with tr.span("batch", tenant="a") as sp:
        sp.set(rounds=8)
    (rec,) = tr.spans
    assert rec.attrs == {"tenant": "a", "rounds": 8}
    assert rec.duration_s >= 0.0


def test_spans_carry_ids_and_parents():
    sink = io.StringIO()
    tr = Tracer(jsonl=sink)
    with tr.span("tick") as tick:
        with tr.span("admit", ticket=7) as admit:
            pass
        tr.event("resolve", ticket=8, from_cache=True)
        with tr.span("swap_in", ticket=7):
            pass
    assert tick.parent is None and admit.parent == tick.id
    recs = [json.loads(line) for line in sink.getvalue().splitlines()]
    assert [r["name"] for r in recs] == ["admit", "resolve", "swap_in", "tick"]
    assert len({r["id"] for r in recs}) == 4
    assert all(r["parent"] == tick.id for r in recs[:3])
    assert recs[3]["parent"] is None and recs[1]["duration_s"] == 0.0
    assert recs[0]["ticket"] == recs[2]["ticket"] == 7
    # the stack unwinds through an exception too
    with pytest.raises(RuntimeError):
        with tr.span("solve"):
            with tr.span("pack"):
                raise RuntimeError("boom")
    with tr.span("batch") as after:
        pass
    assert after.parent is None


def test_disabled_path_builds_no_annotation(monkeypatch):
    """Off means off: a disabled tracer and ``tspan(None, ...)`` never
    reach the profiler; an enabled span opens exactly one annotation."""
    from repro.obs import trace as trace_mod

    made = []

    class Annotation:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_trace_annotation", lambda: Annotation)
    off = Tracer(enabled=False)
    for sp in (tspan(None, "solve"), tspan(off, "solve"), off.span("pack")):
        assert sp is NULL_SPAN
        with sp:
            pass
    off.event("resolve")
    Tracer().event("resolve")            # events stay out of the profiler
    assert made == []
    with tspan(Tracer(), "solve"):
        pass
    assert made == ["repro.solve"]


def test_obs_imports_no_jax():
    """jax is imported on the first enabled span, not by ``repro.obs``."""
    import subprocess
    import sys

    code = (
        "import sys; from repro.obs import Tracer, tspan\n"
        "with tspan(None, 'solve'): pass\n"
        "with Tracer(enabled=False).span('solve'): pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "with Tracer().span('solve'): pass\n"
        "assert 'jax.profiler' in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_spans_land_in_profiler_trace_nested(tmp_path):
    """Under ``jax.profiler.trace`` the enabled spans appear as host events
    ``repro.<name>`` whose nesting is the one the ``parent`` ids record."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("solve"):
            with tr.span("relabel"):
                pass
            with tr.span("pack"):
                with tr.span("pack_build"):
                    np.ones(1000).sum()
                with tr.span("upload"):
                    jnp.ones(8).block_until_ready()
            tr.event("resolve")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    assert sorted(n for n, _, _ in events) == [
        "repro.pack", "repro.pack_build", "repro.relabel", "repro.solve",
        "repro.upload"]

    def enclosing(name, s, e):
        outer = [(s2, n2) for n2, s2, e2 in events
                 if n2 != name and s2 <= s and e <= e2]
        return max(outer)[1] if outer else None

    by_id = {sp.id: sp.name for sp in tr.spans}
    for name, s, e in events:
        (sp,) = tr.find(name.removeprefix("repro."))
        want = None if sp.parent is None else "repro." + by_id[sp.parent]
        assert enclosing(name, s, e) == want, name


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_solve_spans_nest(gw, backend):
    """``solve`` covers the relabel both ways; ``pack_build`` sits inside
    ``pack``; on pallas one ``upload`` and one ``pack_device`` (the device
    build's dispatch) follow it inside ``pack``, on jax ``upload`` comes
    right after ``pack``."""
    algo = get_algorithm("sssp", gw, source=3)
    rank = np.random.default_rng(4).permutation(gw.n)
    tr = Tracer()
    on = solve(algo, options=EngineOptions(
        bs=BS, backend=backend, rank=rank, trace=tr,
        sweeps_per_call=4 if backend == "pallas" else 1))
    off = solve(algo, options=EngineOptions(
        bs=BS, backend=backend, rank=rank,
        sweeps_per_call=4 if backend == "pallas" else 1))
    np.testing.assert_array_equal(on.x, off.x)
    (sv,) = tr.find("solve")
    (pack,) = tr.find("pack")
    builds, ups = tr.find("pack_build"), tr.find("upload")
    devs = tr.find("pack_device")
    relabels = tr.find("relabel")
    assert [r.attrs["direction"] for r in relabels] == ["forward", "inverse"]
    assert all(r.parent == sv.id for r in relabels)
    assert sv.parent is None and pack.parent == sv.id
    assert builds and all(b.parent == pack.id for b in builds)
    # pallas sends the compact skeleton in one transfer, then builds the
    # tiles and the padded state on the device
    (up,) = ups
    assert up.parent == (pack.id if backend == "pallas" else sv.id)
    assert up.attrs["bytes"] > 0
    if backend == "pallas":
        (dev,) = devs
        assert dev.parent == pack.id
        assert builds[-1].t_end <= up.t_start and up.t_end <= dev.t_start
    else:
        assert not devs
    assert relabels[0].t_end <= pack.t_start and pack.t_end <= relabels[1].t_start


# --------------------------------------------------------------- registry


def test_counter_roundtrip_and_rollups():
    reg = MetricsRegistry()
    c = reg.counter("q_total", "queries", ("tenant",))
    c.inc(tenant="a")
    c.inc(2, tenant="b")
    assert c.value(tenant="a") == 1.0
    assert c.total() == 3.0
    assert c.per_label("tenant") == {"a": 1.0, "b": 2.0}
    with pytest.raises(ValueError):
        c.inc(-1, tenant="a")
    # get-or-create: same declaration returns the same family,
    # a mismatched one is rejected loudly
    assert reg.counter("q_total", "queries", ("tenant",)) is c
    with pytest.raises(ValueError):
        reg.counter("q_total", "queries", ("tenant", "family"))
    with pytest.raises(ValueError):
        reg.gauge("q_total", "queries", ("tenant",))


def test_gauge_set_and_inc():
    reg = MetricsRegistry()
    g = reg.gauge("occ", "occupancy")
    g.set(0.5)
    g.inc(0.25)
    assert g.value() == 0.75


def test_histogram_percentiles_and_merge():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", ("tenant",))
    for v in (1.0, 2.0, 3.0):
        h.observe(v, tenant="a")
    h.observe(100.0, tenant="b")
    assert h.percentile(50, tenant="a") == 2.0
    assert h.count(tenant="a") == 3 and h.total_count() == 4
    # label-less percentile on a labeled family merges every child window
    assert h.percentile(100) == 100.0
    assert h.per_label("tenant")["b"] == [100.0]


def test_histogram_wrong_labels_rejected():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", ("tenant",))
    with pytest.raises(ValueError):
        h.observe(1.0, nottenant="a")
    with pytest.raises(ValueError):
        h.observe(1.0)


_LABEL = r'[a-zA-Z_]+="(\\.|[^"\\])*"'
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    rf"(\{{{_LABEL}(,{_LABEL})*\}})? -?[0-9.eE+\-]+(inf)?$"
)


def test_prometheus_text_parses():
    reg = MetricsRegistry()
    reg.counter("q_total", "queries served", ("tenant",)).inc(tenant='we"ird')
    reg.gauge("occ", "occupancy").set(0.5)
    h = reg.histogram("lat", "latency seconds", ("tenant",),
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v, tenant="a")
    text = reg.prometheus_text()
    assert text.endswith("\n")
    help_seen, type_seen = set(), set()
    for line in text.splitlines():
        if line.startswith("# HELP"):
            help_seen.add(line.split()[2])
        elif line.startswith("# TYPE"):
            type_seen.add(line.split()[2])
        else:
            assert _SAMPLE_RE.match(line), line
    assert help_seen == type_seen == {"q_total", "occ", "lat"}
    # histogram buckets are cumulative and +Inf equals _count
    assert 'lat_bucket{tenant="a",le="0.1"} 1' in text
    assert 'lat_bucket{tenant="a",le="1"} 2' in text
    assert 'lat_bucket{tenant="a",le="+Inf"} 3' in text
    assert 'lat_count{tenant="a"} 3' in text
    # label escaping: the quote in the tenant name is escaped
    assert 'tenant="we\\"ird"' in text


def test_registry_summary_shapes():
    reg = MetricsRegistry()
    reg.counter("plain", "unlabeled").inc(3)
    reg.counter("labeled", "labeled", ("tenant",)).inc(tenant="a")
    h = reg.histogram("lat", "latency", ("tenant",))
    h.observe(2.0, tenant="a")
    s = reg.summary()
    assert s["plain"] == 3.0
    assert s["labeled"] == {"a": 1.0}
    assert s["lat"]["a"]["count"] == 1 and s["lat"]["a"]["p50"] == 2.0


# --------------------------------------------- EngineOptions.trace knob


def test_options_trace_validation(gw):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError):
        solve(algo, options=EngineOptions(trace="yes please"))
    res = solve(algo, options=EngineOptions(trace=Tracer()))
    assert res.converged


# ------------------------------------------------- convergence telemetry

ENGINE_SPECS = [
    ("sync", {}),
    ("async_block", {"bs": BS, "inner": 2}),
    ("async_block", {"bs": BS, "backend": "pallas"}),
    ("async_block", {"bs": BS, "backend": "pallas", "sweeps_per_call": 4}),
    ("push", {}),
]


@pytest.mark.parametrize("engine,kw", ENGINE_SPECS)
@pytest.mark.parametrize("algo_name,params", [
    ("pagerank", {}), ("sssp", {"source": 3}),
])
def test_convergence_trace_all_engines(gw, engine, kw, algo_name, params):
    if engine == "push" and algo_name == "pagerank":
        pytest.skip("push engine serves selective semirings")
    algo = get_algorithm(algo_name, gw, **params)
    res = solve(algo, engine=engine, **kw)
    tr = res.convergence_trace
    assert isinstance(tr, ConvergenceTrace)
    assert tr.rounds == res.rounds > 0
    assert len(tr.active_fraction) == len(tr.work) == tr.rounds
    assert np.all(tr.active_fraction >= 0) and np.all(tr.active_fraction <= 1)
    assert np.all(tr.work >= 0) and tr.total_work > 0
    # the trace's final residual IS the convergence decision
    assert res.converged
    assert tr.final_residual <= algo.eps
    expected_unit = {
        "sync": "swept_vertex_cols",
        "push": "pushed_vertices",
    }.get(engine, "swept_block_cells"
          if kw.get("sweeps_per_call", 1) > 1 else "swept_vertex_cols")
    assert tr.unit == expected_unit
    j = tr.to_json()
    assert j["rounds"] == tr.rounds and len(j["residual"]) == tr.rounds


def test_active_columns_per_round():
    # cols froze after 1, 3, 3 rounds -> active counts 3,2,2 then 0
    out = active_columns_per_round(np.array([1, 3, 3]), rounds=4)
    np.testing.assert_array_equal(out, [3.0, 2.0, 2.0, 0.0])
    assert active_columns_per_round(np.array([2]), rounds=0).shape == (0,)


def test_trace_final_residual_tracks_nonconvergence(gw):
    algo = get_algorithm("sssp", gw, source=3)
    res = solve(algo, engine="sync", max_iters=2)
    assert not res.converged
    assert res.convergence_trace.rounds == res.rounds == 2
    assert res.convergence_trace.final_residual > algo.eps


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("algo_name,params", [
    ("sssp", {"source": 3}),    # min semiring
    ("sswp", {"source": 3}),    # max semiring
])
def test_trace_on_is_bitwise_invisible(gw, backend, algo_name, params,
                                       transfer_guard_disallow):
    """Enabling tracing must not perturb the solve: identical states
    (bitwise — selective semirings copy, never blend), identical rounds,
    and no unaudited transfer appears (the guard faults if one does)."""
    algo = get_algorithm(algo_name, gw, **params)
    kw = dict(bs=BS, backend=backend,
              sweeps_per_call=4 if backend == "pallas" else 1)
    off = solve(algo, engine="async_block", **kw)
    sink = io.StringIO()
    on = solve(algo, engine="async_block",
               options=EngineOptions(trace=Tracer(jsonl=sink), **kw))
    assert on.rounds == off.rounds
    np.testing.assert_array_equal(on.x, off.x)
    np.testing.assert_array_equal(
        on.convergence_trace.residual, off.convergence_trace.residual
    )
    assert sink.getvalue().count('"name": "solve"') == 1


# ---------------------------------------------------------- serving wiring


def test_cache_hit_populates_wait_population():
    """The fix: a cache hit is a resolve the client waited 0s for, so it
    must appear in the wait histogram (it used to be silently skipped)."""
    st = ServerStats(slots=4)
    st.record_submit(tenant="a")
    st.record_cache_hit(tenant="a", family="sssp")
    s = st.summary()
    assert s["cache_hits"] == 1 and s["resolved"] == 1
    assert st._wait_h.total_count() == 1          # the regression bit
    assert st._latency_h.total_count() == 1
    assert s["wait_p50_s"] == 0.0


def test_stats_summary_superset_and_legacy_surface():
    st = ServerStats(slots=2)
    st.record_submit(tenant="a")
    st.record_batch(2, 8, tenant="a")
    st.record_delta("a")
    st.record_reorder("a")
    st.record_reorder_disabled("a")
    st.record_fail(tenant="b")
    legacy_keys = {
        "submitted", "resolved", "unconverged", "failed", "cache_hits",
        "batches", "rounds_total", "round_slots_total", "deltas_applied",
        "deadline_misses", "tenant_batches", "tenant_rounds", "reorders",
        "reorders_disabled", "elapsed_s", "throughput_qps", "latency_p50_s",
        "latency_p99_s", "wait_p50_s", "wait_p99_s", "rounds_p50",
        "rounds_p99", "occupancy_mean",
    }
    s = st.summary()
    assert legacy_keys <= set(s)
    assert {"per_tenant", "per_family"} <= set(s)
    assert st.rounds_total == 8 and st.round_slots_total == 16
    assert st.tenant_batches == {"a": 1}
    assert st.deltas_applied == 1 and st.failed == 1
    assert st.reorders == {"a": 1}
    assert st.reorders_disabled == {"a": True}
    assert isinstance(st.metrics_text(), str)


def _small_server(**kw):
    rng = np.random.default_rng(0)
    n, m = 150, 900
    g = gen.Graph(n, rng.integers(0, n, m), rng.integers(0, n, m),
                  rng.random(m).astype(np.float32))
    return GraphServer(g, slots=4, bs=32, rounds_per_batch=4, **kw)


def test_traced_serving_end_to_end():
    """Acceptance scenario: a traced server under the transfer sanitizer
    produces spans, Prometheus-parseable metrics, and per-ticket resolve
    events — with zero unaudited transfers."""
    sink = io.StringIO()
    tr = Tracer(jsonl=sink)
    srv = _small_server(transfer_guard="disallow", trace=tr)
    t1 = srv.submit("pagerank", {"damping": 0.85})
    t2 = srv.submit("sssp", {"source": 3})
    srv.run()
    t3 = srv.submit("sssp", {"source": 3})      # cache hit
    assert t1.converged and t2.converged and t3.from_cache
    names = {sp.name for sp in tr.spans}
    assert {"pack", "batch", "resolve"} <= names
    resolves = [json.loads(line) for line in sink.getvalue().splitlines()
                if json.loads(line)["name"] == "resolve"]
    assert len(resolves) == 3
    assert {r["algo"] for r in resolves} == {"pagerank", "sssp"}
    live = [r for r in resolves if not r.get("from_cache")]
    assert all(r["rounds"] > 0 and r["converged"] for r in live)
    # batch spans carry the family attrs _make_family stamped
    batch = tr.find("batch")[0]
    assert batch.attrs["tenant"] == "default"
    assert "family" in batch.attrs and "graph_version" in batch.attrs
    text = srv.metrics_text()
    for line in text.splitlines():
        if not line.startswith("#"):
            assert _SAMPLE_RE.match(line), line
    assert 'repro_queries_resolved_total{tenant="default"} 3' in text
    assert 'repro_cache_hits_total{tenant="default"} 1' in text
    s = srv.stats.summary()
    assert s["per_family"]["sssp"]["rounds_p50"] >= 0
    assert s["per_tenant"]["default"]["resolved"] == 3


def test_server_trace_knob_validated():
    with pytest.raises(TypeError):
        _small_server(trace="not a tracer")


def test_untraced_server_unchanged():
    srv = _small_server(transfer_guard="disallow")
    assert srv.trace is None
    t = srv.submit("sssp", {"source": 1})
    srv.run()
    assert t.converged and t.rounds > 0
