"""Continuous-batching serving layer (`repro.serving`).

The load-bearing contract: for ANY arrival schedule, batch granularity, and
admission policy, a query resolved by the server carries exactly the state
and round count a solo `run_async_block` of that query would produce on the
graph version it ran against — bitwise for min/max semirings, within eps
for sum semirings — including queries that arrive the same batch a
GraphDelta lands. Plus: region-invalidation soundness of the result cache,
admission policies, the static-batching baseline, and regression tests for
the deduplicated per-column convergence accounting (PR satellite).
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.engine import get_algorithm, personalized_pagerank, run_async_block
from repro.engine.convergence import converge_step, reinit_columns
from repro.engine import harness
from repro.graphs import generators as gen
from repro.graphs.delta import GraphDelta, random_delta
from repro.graphs.graph import Graph
from repro.serving import GraphServer, Scheduler, family_key
from repro.serving.stats import percentile

N = 350
BS = 64


def _base_graph():
    g = gen.scrambled(gen.powerlaw_cluster(N, 4, p=0.4, seed=1), seed=9)
    # weights <= 1 keep the pagerank-family spectral radius < damping, so
    # PPR and SSSP traffic can share one weighted graph
    return gen.with_random_weights(g, lo=0.1, hi=1.0, seed=2)


GW = _base_graph()
_SOLO_CACHE: dict = {}


def _solo(algo, src, graph=None, key=None):
    """Memoized solo reference run (same engine config the server uses)."""
    if graph is None:
        graph = GW
        key = (algo, src)
    if key not in _SOLO_CACHE:
        p = {"seeds": [src]} if algo == "ppr" else {"source": src}
        _SOLO_CACHE[key] = run_async_block(
            get_algorithm(algo, graph, **p), bs=BS
        )
    return _SOLO_CACHE[key]


def _check_ticket(t, solo, *, rounds=True):
    assert t.done and t.converged, (t.algo, t.params, t.status)
    is_sum = t.algo in ("ppr", "pagerank", "katz", "php", "adsorption")
    if rounds:
        assert t.rounds == solo.rounds, (t.algo, t.params, t.rounds, solo.rounds)
    if is_sum:
        np.testing.assert_allclose(t.result, solo.x, atol=1e-5, rtol=0)
    else:
        np.testing.assert_array_equal(t.result, solo.x, err_msg=str(t.params))


# ---------------------------------------------------------------------------
# swap-in equivalence: any arrival schedule == solo runs
# ---------------------------------------------------------------------------

@st.composite
def schedules(draw):
    n_q = draw(st.integers(3, 9))
    queries = sorted(
        (
            draw(st.integers(0, 5)),                       # arrival tick
            draw(st.sampled_from(["sssp", "bfs", "ppr"])),
            draw(st.integers(0, N - 1)),                   # source/seed
            draw(st.integers(0, 3)),                       # priority
        )
        for _ in range(n_q)
    )
    rpb = draw(st.sampled_from([1, 2, 3, 5]))
    slots = draw(st.sampled_from([2, 3, 4]))
    policy = draw(st.sampled_from(["fifo", "priority", "deadline"]))
    return queries, rpb, slots, policy


@given(schedules())
@settings(max_examples=6, deadline=None)
def test_any_arrival_schedule_matches_solo_runs(schedule):
    queries, rpb, slots, policy = schedule
    srv = GraphServer(
        GW, slots=slots, bs=BS, rounds_per_batch=rpb, policy=policy,
        cache=False,
    )
    pending = list(queries)
    tickets = []
    tick = 0
    while pending or srv.scheduler.total_pending() or srv._busy():
        while pending and pending[0][0] <= tick:
            _, algo, src, prio = pending.pop(0)
            p = {"seeds": [src]} if algo == "ppr" else {"source": src}
            tickets.append((algo, src, srv.submit(algo, p, priority=prio)))
        srv.step()
        tick += 1
    for algo, src, t in tickets:
        _check_ticket(t, _solo(algo, src))


def test_cached_resubmit_serves_identical_result():
    srv = GraphServer(GW, slots=2, bs=BS, rounds_per_batch=4)
    t1 = srv.submit("sssp", {"source": 3})
    srv.run()
    t2 = srv.submit("sssp", {"source": 3})
    assert t2.status == "cached" and t2.rounds == 0
    np.testing.assert_array_equal(t2.result, t1.result)
    assert srv.cache.stats()["hits"] == 1


# ---------------------------------------------------------------------------
# live delta ingestion
# ---------------------------------------------------------------------------

def _delta_setup(delta_mode, seed=5, loosening=True):
    srv = GraphServer(
        GW, slots=3, bs=BS, rounds_per_batch=2, delta_mode=delta_mode,
    )
    t_flight = srv.submit("sssp", {"source": 0})
    t_ppr = srv.submit("ppr", {"seeds": [7]})
    srv.step()
    assert t_flight.status == "running"   # mid-convergence when delta lands
    delta = random_delta(
        GW, frac_add=0.01,
        frac_del=0.003 if loosening else 0.0,
        frac_rew=0.003 if loosening else 0.0,
        n_add_vertices=3, seed=seed,
    )
    srv.apply_delta(delta)
    # arrives the same batch the delta lands: must run on the NEW graph
    t_same = srv.submit("sssp", {"source": 11})
    srv.run()
    return srv, t_flight, t_ppr, t_same


@pytest.mark.parametrize("delta_mode", ["warm", "restart"])
@pytest.mark.parametrize("loosening", [False, True])
def test_delta_in_flight_and_same_batch_arrival(delta_mode, loosening):
    srv, t_flight, t_ppr, t_same = _delta_setup(delta_mode, loosening=loosening)
    g2 = srv.g
    # the same-batch arrival is solo-exact on the mutated graph, rounds incl.
    solo_same = run_async_block(get_algorithm("sssp", g2, source=11), bs=BS)
    assert t_same.rounds == solo_same.rounds
    np.testing.assert_array_equal(t_same.result, solo_same.x)
    # the in-flight min-semiring query resolves the exact new fixpoint
    # (bitwise) in both modes; restart additionally keeps solo round counts
    solo_flight = run_async_block(get_algorithm("sssp", g2, source=0), bs=BS)
    np.testing.assert_array_equal(t_flight.result, solo_flight.x)
    if delta_mode == "restart":
        assert t_flight.rounds == solo_flight.rounds
    # the in-flight sum-semiring query lands within stopping tolerance
    solo_ppr = run_async_block(personalized_pagerank(g2, [7]), bs=BS)
    np.testing.assert_allclose(t_ppr.result, solo_ppr.x, atol=1e-5, rtol=0)


def test_delta_bumps_version_and_reruns_invalidated():
    srv = GraphServer(GW, slots=2, bs=BS, rounds_per_batch=4)
    t1 = srv.submit("sssp", {"source": 0})
    srv.run()
    delta = random_delta(GW, frac_add=0.01, seed=6)
    srv.apply_delta(delta)
    assert srv.graph_version == 1
    t2 = srv.submit("sssp", {"source": 0})
    assert t2.status != "cached"    # support intersects this dense delta
    srv.run()
    solo = run_async_block(get_algorithm("sssp", srv.g, source=0), bs=BS)
    np.testing.assert_array_equal(t2.result, solo.x)
    assert t2.rounds == solo.rounds


# ---------------------------------------------------------------------------
# result cache: region invalidation
# ---------------------------------------------------------------------------

def _two_component_graph():
    """Components in disjoint block ranges: A = blocks 0..2, B = 3..5."""
    ga = gen.powerlaw_cluster(3 * BS, 4, p=0.3, seed=3)
    gb = gen.powerlaw_cluster(3 * BS, 4, p=0.3, seed=4)
    src = np.concatenate([ga.src, gb.src + ga.n])
    dst = np.concatenate([ga.dst, gb.dst + ga.n])
    g = Graph(ga.n + gb.n, src, dst)
    return gen.with_random_weights(g, lo=0.1, hi=1.0, seed=7), ga.n


def test_cache_survives_far_delta_and_dies_on_near_delta():
    g2c, n_a = _two_component_graph()
    srv = GraphServer(g2c, slots=2, bs=BS, rounds_per_batch=4)
    ta = srv.submit("sssp", {"source": 5})           # component A
    tb = srv.submit("sssp", {"source": n_a + 5})     # component B
    srv.run()
    # delta confined to component B's blocks
    delta = GraphDelta(
        add_src=[n_a + 10, n_a + 40], add_dst=[n_a + 90, n_a + 120],
        add_w=[0.5, 0.5],
    )
    srv.apply_delta(delta)
    hit = srv.submit("sssp", {"source": 5})
    miss = srv.submit("sssp", {"source": n_a + 5})
    assert hit.status == "cached", "A-entry must survive a B-only delta"
    assert miss.status != "cached", "B-entry must be invalidated"
    srv.run()
    # the promoted answer is still the exact answer on the mutated graph
    solo_a = run_async_block(get_algorithm("sssp", srv.g, source=5), bs=BS)
    np.testing.assert_array_equal(hit.result, solo_a.x)
    solo_b = run_async_block(get_algorithm("sssp", srv.g, source=n_a + 5), bs=BS)
    np.testing.assert_array_equal(miss.result, solo_b.x)
    assert srv.cache.stats()["promoted"] >= 1
    assert ta.result is not None and tb.result is not None


def test_cache_extends_promoted_entries_over_appended_vertices():
    g2c, n_a = _two_component_graph()
    srv = GraphServer(g2c, slots=2, bs=BS, rounds_per_batch=4)
    srv.submit("sssp", {"source": 5})
    srv.run()
    # append a vertex wired into component B only
    delta = GraphDelta(n_add=1, add_src=[n_a + 3], add_dst=[g2c.n],
                       add_w=[0.5])
    srv.apply_delta(delta)
    hit = srv.submit("sssp", {"source": 5})
    assert hit.status == "cached"
    assert hit.result.shape == (g2c.n + 1,)
    solo = run_async_block(get_algorithm("sssp", srv.g, source=5), bs=BS)
    np.testing.assert_array_equal(hit.result, solo.x)


# ---------------------------------------------------------------------------
# scheduler policies
# ---------------------------------------------------------------------------

def _resolution_order(policy, submits):
    srv = GraphServer(GW, slots=1, bs=BS, rounds_per_batch=4, policy=policy,
                      cache=False)
    tickets = {}
    for label, src, kw in submits:
        tickets[label] = srv.submit("sssp", {"source": src}, **kw)
    srv.run()
    return sorted(tickets, key=lambda k: tickets[k].resolved_at)


def test_priority_policy_orders_admission():
    order = _resolution_order("priority", [
        ("lo", 3, {"priority": 0}),
        ("hi", 17, {"priority": 5}),
        ("mid", 29, {"priority": 2}),
    ])
    assert order == ["hi", "mid", "lo"]


def test_deadline_policy_is_edf():
    order = _resolution_order("deadline", [
        ("late", 3, {"deadline": 100.0}),
        ("soon", 17, {"deadline": 1.0}),
        ("none", 29, {}),
    ])
    assert order == ["soon", "late", "none"]


def test_fifo_policy_is_arrival_order():
    order = _resolution_order("fifo", [
        ("a", 3, {"priority": 0}),
        ("b", 17, {"priority": 9}),   # priority ignored under fifo
        ("c", 29, {}),
    ])
    assert order == ["a", "b", "c"]


def test_family_key_groups_structurally():
    assert family_key("sssp", {"source": 1}) == family_key("sssp", {"source": 9})
    assert family_key("sssp", {"source": 1, "eps": 0.5}) != \
        family_key("sssp", {"source": 1, "eps": 2.5})
    assert family_key("ppr", {"seeds": [3]}) == family_key("ppr", {"seeds": [8]})
    assert family_key("pagerank", {"damping": 0.85}) != \
        family_key("pagerank", {"damping": 0.5})


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        Scheduler("lifo")


def test_submit_validation():
    srv = GraphServer(GW, slots=2, bs=BS)
    with pytest.raises(KeyError, match="unknown algorithm"):
        srv.submit("dijkstra", {})
    t = srv.submit("ppr", {"seeds": [1, 2, 3]})   # d=3: one query per ticket
    srv.run()
    assert t.status == "failed" and "one query per ticket" in t.error


# ---------------------------------------------------------------------------
# continuous vs static refill (the point of the subsystem)
# ---------------------------------------------------------------------------

def _skewed_graph():
    """Hub cluster + a path tail feeding INTO the hub: hub SSSP queries
    converge in a few sweeps (they never reach the tail), tail-depth
    queries need many (the paper-Fig.-7 skew, condensed). Returns the
    served graph and the scramble rank (pre-scramble id -> served id)."""
    hub = gen.powerlaw_cluster(160, 4, p=0.3, seed=2)
    path_n = 96
    n = hub.n + path_n
    ps = np.arange(hub.n + 1, n, dtype=np.int32)   # p_k -> p_{k-1}
    pd = np.arange(hub.n, n - 1, dtype=np.int32)
    g = Graph(n, np.concatenate([hub.src, ps, [hub.n]]),
              np.concatenate([hub.dst, pd, [0]]))
    rank = np.random.default_rng(13).permutation(n).astype(np.int64)
    return gen.with_random_weights(g.relabel(rank), lo=0.1, hi=1.0, seed=3), rank


def test_continuous_batching_beats_static_on_skewed_rounds():
    gw, rank = _skewed_graph()
    rng = np.random.default_rng(0)
    # 8 fast hub sources + 4 slow tail sources, interleaved
    pre = np.concatenate([rng.integers(0, 160, size=8),
                          160 + rng.integers(48, 96, size=4)])
    rng.shuffle(pre)
    sources = [int(s) for s in rank[pre]]
    results = {}
    for refill in ("continuous", "static"):
        srv = GraphServer(gw, slots=4, bs=BS, rounds_per_batch=2,
                          refill=refill, cache=False)
        ts = [srv.submit("sssp", {"source": s}) for s in sources]
        srv.run()
        for t, s in zip(ts, sources, strict=True):
            solo = _solo("sssp", s, graph=gw, key=("skew", s))
            assert t.rounds == solo.rounds
            np.testing.assert_array_equal(t.result, solo.x)
        results[refill] = srv.stats.rounds_total
    assert results["continuous"] < results["static"], results


# ---------------------------------------------------------------------------
# pallas backends through the server
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sweeps", [1, 2])
def test_server_pallas_backend_bitwise(sweeps):
    srv = GraphServer(GW, slots=2, bs=BS, rounds_per_batch=2,
                      backend="pallas", sweeps_per_call=sweeps, cache=False)
    ts = [srv.submit("sssp", {"source": s}) for s in (0, 7, 100)]
    srv.run()
    for t in ts:
        solo = _solo("sssp", t.params["source"])
        assert t.rounds == solo.rounds, (sweeps, t.params)
        np.testing.assert_array_equal(t.result, solo.x)


# ---------------------------------------------------------------------------
# deduplicated convergence accounting (satellite regression)
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_converge_step_matches_inline_reference(seed):
    """The shared implementation reproduces the exact logic both round
    drivers previously inlined — on numpy AND jax arrays, bit-for-bit."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 9))
    res = rng.uniform(0, 2, d).astype(np.float32)
    eps = float(rng.uniform(0, 2))
    done = rng.random(d) < 0.3
    rounds = rng.integers(0, 50, d).astype(np.int32)
    # the pre-refactor inline logic, verbatim
    ref_active = ~done
    ref_newly = ref_active & (res <= eps)
    ref_done = done | ref_newly
    ref_rounds = rounds + ref_active.astype(np.int32)
    for xp in (np, jnp):
        newly, active, done2, rounds2 = converge_step(
            xp.asarray(res), eps, xp.asarray(done), xp.asarray(rounds)
        )
        np.testing.assert_array_equal(np.asarray(newly), ref_newly)
        np.testing.assert_array_equal(np.asarray(active), ref_active)
        np.testing.assert_array_equal(np.asarray(done2), ref_done)
        np.testing.assert_array_equal(np.asarray(rounds2), ref_rounds)


def test_reinit_columns_is_freeze_inverse():
    done = np.array([True, True, False, True])
    rounds = np.array([5, 9, 3, 7], np.int32)
    done2, rounds2 = reinit_columns(done, rounds, [1, 3])
    np.testing.assert_array_equal(done2, [True, False, False, False])
    np.testing.assert_array_equal(rounds2, [5, 0, 3, 0])
    # inputs untouched
    assert done[1] and rounds[1] == 9


def test_column_support_marks_inputs_and_reach():
    q = get_algorithm("sssp", GW, source=4)
    sup = harness.column_support(
        q.x0[:, 0], q.c[:, 0], q.fixed[:, 0],
        reduce="min", c_fill=q.c_pad_fill,
    )
    assert sup[4] and sup.sum() == 1          # only the source injects
    solo = _solo("sssp", 4)
    sup_x = harness.column_support(
        q.x0[:, 0], q.c[:, 0], q.fixed[:, 0],
        reduce="min", c_fill=q.c_pad_fill, x=solo.x,
    )
    reached = solo.x < 3.0e38
    np.testing.assert_array_equal(sup_x, reached | sup)


def test_percentile_nearest_rank():
    assert percentile([], 99) == 0.0
    assert percentile([5.0], 99) == 5.0
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100


def test_swap_in_column_keeps_padding():
    q = get_algorithm("sssp", GW, source=0)
    d = 3
    fam = dataclasses.replace(
        q, x0=np.zeros((q.n, d), np.float32),
        c=np.full((q.n, d), q.c_pad_fill, np.float32),
        fixed=np.ones((q.n, d), bool), exact_fn=None, params=None,
    )
    _, x0, c, fixed, npad = harness.pack(fam, BS)
    x = x0.copy()
    harness.swap_in_column(x, x0, c, fixed, 1, q.n,
                           q.x0[:, 0], q.c[:, 0], q.fixed[:, 0])
    np.testing.assert_array_equal(x0[: q.n, 1], q.x0[:, 0])
    np.testing.assert_array_equal(x[:, 1], x0[:, 1])
    # padding rows keep the reduce-identity fill in every column
    assert (x0[q.n :, :] == fam.semiring.identity).all()
    assert fixed[q.n :, :].all()


# ---------------------------------------------------------------------------
# multi-tenancy: several graphs per server, fair slots, scoped deltas
# ---------------------------------------------------------------------------

def _second_graph():
    g = gen.scrambled(gen.powerlaw_cluster(N + 40, 4, p=0.4, seed=11), seed=3)
    return gen.with_random_weights(g, lo=0.1, hi=1.0, seed=4)


GW2 = _second_graph()


def test_multi_tenant_results_match_each_tenants_graph():
    srv = GraphServer(GW, slots=2, bs=BS, rounds_per_batch=4)
    srv.add_tenant("b", GW2)
    t_a = srv.submit("sssp", {"source": 0})
    t_b = srv.submit("sssp", {"source": 0}, tenant="b")
    srv.run()
    solo_a = _solo("sssp", 0)
    solo_b = run_async_block(get_algorithm("sssp", GW2, source=0), bs=BS)
    _check_ticket(t_a, solo_a)
    assert t_b.rounds == solo_b.rounds
    np.testing.assert_array_equal(t_b.result, solo_b.x)
    assert t_a.result.shape != t_b.result.shape  # really two graphs


def test_multi_tenant_fair_round_robin():
    """Symmetric load on two tenants -> batch counts within one of each
    other: the rotating interleave gives every tenant with work a batch
    before any tenant gets a second one."""
    srv = GraphServer(graphs={"a": GW, "b": GW}, slots=2, bs=BS,
                      rounds_per_batch=2)
    for s in (0, 3, 9, 14):
        srv.submit("ppr", {"seeds": [s]}, tenant="a")
        srv.submit("ppr", {"seeds": [s]}, tenant="b")
    srv.run()
    tb = srv.stats.tenant_batches
    assert set(tb) == {"a", "b"}
    assert abs(tb["a"] - tb["b"]) <= 1, tb
    tr = srv.stats.tenant_rounds
    assert tr["a"] > 0 and tr["b"] > 0
    s = srv.stats.summary()
    assert s["tenant_batches"] == tb and s["tenant_rounds"] == tr


def test_multi_tenant_delta_scoped_to_one_tenant():
    """Tenant a's delta bumps only a's version and can only invalidate a's
    cache entries; tenant b's cached result keeps serving hits."""
    srv = GraphServer(graphs={"a": GW, "b": GW2}, slots=2, bs=BS,
                      rounds_per_batch=4)
    srv.submit("pagerank", {}, tenant="a")
    srv.submit("pagerank", {}, tenant="b")
    srv.run()
    assert len(srv.cache) == 2
    delta = random_delta(GW, frac_add=0.01, seed=5)
    srv.apply_delta(delta, tenant="a")
    assert srv.tenants["a"].graph_version == 1
    assert srv.tenants["b"].graph_version == 0
    # pagerank has global support: a's entry must die, b's must survive
    t_b = srv.submit("pagerank", {}, tenant="b")
    assert t_b.from_cache
    t_a = srv.submit("pagerank", {}, tenant="a")
    assert not t_a.from_cache
    srv.run()
    solo_a = run_async_block(get_algorithm("pagerank", srv.tenants["a"].g),
                             bs=BS)
    assert t_a.rounds == solo_a.rounds
    np.testing.assert_allclose(t_a.result, solo_a.x, atol=1e-5, rtol=0)


def test_tenant_validation():
    srv = GraphServer(GW, slots=2, bs=BS)
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.submit("sssp", {"source": 0}, tenant="nope")
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.apply_delta(random_delta(GW, frac_add=0.01, seed=1), tenant="no")
    srv.add_tenant("b", GW2)
    with pytest.raises(ValueError, match="duplicate tenant"):
        srv.add_tenant("b", GW2)
    with pytest.raises(ValueError, match="duplicate tenant"):
        GraphServer(GW, graphs={"default": GW2})
    with pytest.raises(ValueError, match="at least one graph"):
        GraphServer()


# ---------------------------------------------------------------------------
# byte-budgeted LRU result cache
# ---------------------------------------------------------------------------

def test_cache_lru_evicts_oldest_within_budget():
    from repro.serving import ResultCache

    x = np.zeros(100, np.float32)          # 400 bytes + overhead per entry
    per = x.nbytes + 256
    c = ResultCache(max_bytes=3 * per)
    for i in range(4):
        c.put(("t", "a", i), x, 1, [0], 0, x0_fill=0.0)
    assert len(c) == 3 and c.bytes <= 3 * per
    assert c.get(("t", "a", 0), 0) is None          # LRU entry evicted
    assert c.get(("t", "a", 1), 0) is not None      # ...and now refreshed
    c.put(("t", "a", 9), x, 1, [0], 0, x0_fill=0.0)
    assert c.get(("t", "a", 2), 0) is None          # 2 was the new LRU
    assert c.get(("t", "a", 1), 0) is not None
    assert c.stats()["evicted"] == 2
    # an entry bigger than the whole budget is not retained
    c.put(("t", "big", 0), np.zeros(10_000, np.float32), 1, [0], 0,
          x0_fill=0.0)
    assert c.get(("t", "big", 0), 0) is None
    with pytest.raises(ValueError, match="max_bytes"):
        ResultCache(max_bytes=-1)


def test_server_cache_budget_end_to_end():
    per_entry = GW.n * 4 + 256
    srv = GraphServer(GW, slots=2, bs=BS, rounds_per_batch=4,
                      cache_max_bytes=2 * per_entry)
    for s in (0, 3, 9, 14):
        srv.submit("ppr", {"seeds": [s]})
    srv.run()
    st = srv.cache.stats()
    assert st["entries"] <= 2 and st["bytes"] <= 2 * per_entry
    assert st["evicted"] >= 2
    # the retained (most recent) entries still serve hits
    t = srv.submit("ppr", {"seeds": [14]})
    assert t.from_cache


@pytest.mark.parametrize("backend,sweeps", [("jax", 1), ("pallas", 2)])
def test_traced_server_spans_per_ticket(backend, sweeps):
    """A traced server gives every ticket that ran one ``admit``, one
    ``swap_in`` and one ``resolve`` span, each carrying its ticket id,
    each inside a ``tick``; a cache hit stays a zero-length ``resolve``
    event; and the answers are bitwise those of an untraced server."""
    from repro.obs import Tracer

    kw = dict(slots=2, bs=BS, rounds_per_batch=2, backend=backend,
              sweeps_per_call=sweeps)
    queries = [("sssp", {"source": s}) for s in (0, 7, 100)]
    queries.append(("ppr", {"seeds": [5]}))
    tr = Tracer()
    servers = (GraphServer(GW, **kw), GraphServer(GW, trace=tr, **kw))
    runs = []
    for srv in servers:
        ts = [srv.submit(a, p) for a, p in queries]
        srv.run()
        ts.append(srv.submit("sssp", {"source": 7}))      # cache hit
        runs.append(ts)
    for plain, traced in zip(*runs):
        assert traced.rounds == plain.rounds
        np.testing.assert_array_equal(traced.result, plain.result)
    assert runs[1][-1].from_cache

    by_id = {sp.id: sp for sp in tr.spans}

    def in_tick(sp):
        while sp.parent is not None:
            sp = by_id[sp.parent]
            if sp.name == "tick":
                return True
        return False

    for t in runs[1][:-1]:
        for name in ("admit", "swap_in", "resolve"):
            (sp,) = [s for s in tr.find(name) if s.attrs["ticket"] == t.id]
            assert sp.t_end > sp.t_start and in_tick(sp), (name, t.id)
        (res,) = [s for s in tr.find("resolve") if s.attrs["ticket"] == t.id]
        assert res.attrs["rounds"] == t.rounds and res.attrs["converged"]
        (swap,) = [s for s in tr.find("swap_in") if s.attrs["ticket"] == t.id]
        if backend == "pallas":
            assert swap.attrs["support_vertices"] >= 1
            assert swap.attrs["dirty_blocks"] >= 1
    hit = runs[1][-1]
    (ev,) = [s for s in tr.find("resolve") if s.attrs["ticket"] == hit.id]
    assert ev.duration_s == 0.0 and ev.attrs["from_cache"] and ev.parent is None
    ticks = tr.find("tick")
    assert ticks and all(sp.parent is None for sp in ticks)
    assert sum(sp.attrs["batches"] for sp in ticks) == servers[1].stats.batches
