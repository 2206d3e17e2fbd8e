"""Vertex-granular residual push engine (`repro.engine.push`) — PR tentpole.

The load-bearing contract: `solve(algo, engine="push")` resolves exactly the
fixpoint `run_async_block` resolves — **bitwise** for the lattice semirings
(quiescence pins the monotone closure), within stopping tolerance for the
sum semirings — cold or warm, jax or pallas backend, for any bucket count.
Plus: the `engine="auto"` frontier-size router (both arms, knob dropping,
transfer-guard compatibility), `run_incremental(engine="push")` sparse delta
absorption with work proportional to the touched neighborhood, the
`out_closure`/`touched_vertices(closure=)` helper semantics, push_stats
accounting, option validation, and the GraphServer push-absorption path.
"""
import types

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.engine import (
    get_algorithm,
    multi_source_sssp,
    personalized_pagerank,
    remake,
    run_async_block,
    run_incremental,
    run_push,
)
from repro.engine import push as push_mod
from repro.engine.api import (
    EngineOptionsError,
    EngineUnsupportedError,
    solve,
)
from repro.engine.push import estimate_frontier_fraction
from repro.graphs import generators as gen
from repro.graphs.delta import GraphDelta, out_closure, random_delta
from repro.graphs.graph import Graph
from repro.serving import GraphServer

BS = 64
LATTICE = ["sssp", "bfs", "cc", "sswp", "reachability"]
SUM = ["pagerank", "katz", "php", "adsorption"]


@pytest.fixture(scope="module")
def graphs():
    g = gen.scrambled(gen.powerlaw_cluster(400, 4, p=0.4, seed=1), seed=9)
    # weights <= 1 keep the sum family contractive, so the same weighted
    # graph can serve sssp/sswp AND weighted-sum sanity runs
    gw = gen.with_random_weights(g, lo=0.1, hi=1.0, seed=2)
    return g, gw


def _algo(name, g, gw, **kw):
    graph = gw if name in ("sssp", "sswp", "ms_sssp") else g
    return get_algorithm(name, graph, **kw)


# ---------------------------------------------------------------------------
# equivalence with the sweep engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("name", LATTICE)
def test_lattice_cold_bitwise_equals_async_block(name, backend, graphs):
    g, gw = graphs
    algo = _algo(name, g, gw)
    r = solve(algo, engine="push", backend=backend)
    ref = run_async_block(algo, bs=BS)
    assert r.converged
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("name", SUM)
def test_sum_cold_within_eps_of_async_block(name, backend, graphs):
    g, gw = graphs
    algo = _algo(name, g, gw)
    r = solve(algo, engine="push", backend=backend)
    ref = run_async_block(algo, bs=BS)
    assert r.converged
    # push maintains r incrementally (r -= push; r += scatter), so hub rows
    # drift by float accumulation-order noise on top of the eps stopping rule
    np.testing.assert_allclose(
        np.asarray(r.x), np.asarray(ref.x), atol=20 * algo.eps, rtol=1e-5
    )


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_batched_columns_ms_sssp_bitwise(backend, graphs):
    _, gw = graphs
    algo = multi_source_sssp(gw, sources=[0, 42, 99])
    r = solve(algo, engine="push", backend=backend)
    ref = run_async_block(algo, bs=BS)
    assert r.x.shape == (gw.n, 3) and bool(r.col_converged.all())
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


def test_batched_columns_ppr_per_column_freeze(graphs):
    """Converged columns freeze out of the push: each column of a batched
    run equals its solo run within eps even when round counts diverge."""
    g, _ = graphs
    seeds = [3, 17, 40]
    algo = personalized_pagerank(g, seeds=seeds)
    r = solve(algo, engine="push")
    assert r.converged and r.x.shape == (g.n, 3)
    for j, s in enumerate(seeds):
        solo = solve(personalized_pagerank(g, seeds=[s]), engine="push")
        np.testing.assert_allclose(
            r.x[:, j], solo.x, atol=20 * algo.eps, rtol=1e-5
        )


@given(st.integers(10, 120), st.integers(0, 30))
@settings(max_examples=10, deadline=None)
def test_hypothesis_sssp_push_bitwise(n, seed):
    g = gen.with_random_weights(
        gen.erdos_renyi(n, 3.0, seed=seed), lo=0.1, hi=1.0, seed=seed
    )
    algo = get_algorithm("sssp", g, source=seed % n)
    r = solve(algo, engine="push")
    ref = run_async_block(algo, bs=32)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


@given(st.integers(10, 120), st.integers(0, 30))
@settings(max_examples=10, deadline=None)
def test_hypothesis_pagerank_push_within_eps(n, seed):
    algo = get_algorithm("pagerank", gen.erdos_renyi(n, 3.0, seed=seed))
    r = solve(algo, engine="push")
    ref = run_async_block(algo, bs=32)
    np.testing.assert_allclose(
        np.asarray(r.x), np.asarray(ref.x), atol=5 * algo.eps, rtol=1e-5
    )


@pytest.mark.parametrize("buckets", [1, 3, 8])
def test_pallas_bucket_count_does_not_change_answer(buckets, graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    r = solve(algo, engine="push", backend="pallas", buckets=buckets)
    ref = run_async_block(algo, bs=BS)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


# ---------------------------------------------------------------------------
# warm starts & incremental delta absorption (the killer application)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_incremental_push_sssp_bitwise_and_sparse(backend, graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    prior = run_async_block(algo, bs=BS)
    delta = random_delta(gw, frac_add=0.005, seed=3)
    g2 = delta.apply(gw)
    algo2 = remake(algo, g2)
    warm = run_incremental(algo2, algo, prior, engine="push", backend=backend)
    cold = run_async_block(algo2, bs=BS)
    np.testing.assert_array_equal(np.asarray(warm.x), np.asarray(cold.x))


def test_incremental_push_touches_neighborhood_not_graph(graphs):
    """A 10-edge delta's push absorption does work proportional to the
    touched neighborhood: far fewer swept-vertex relaxations than the block
    engine's rounds * n, and a strict minority of vertices touched."""
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    prior = run_async_block(algo, bs=BS)
    rng = np.random.default_rng(7)
    src = rng.integers(0, gw.n, 10).astype(np.int32)
    dst = rng.integers(0, gw.n, 10).astype(np.int32)
    keep = src != dst
    delta = GraphDelta(add_src=src[keep], add_dst=dst[keep],
                       add_w=np.full(int(keep.sum()), 0.2, np.float32))
    g2 = delta.apply(gw)
    algo2 = remake(algo, g2)
    warm_push = run_incremental(algo2, algo, prior, engine="push")
    warm_block = run_incremental(algo2, algo, prior, bs=BS)
    cold = run_async_block(algo2, bs=BS)
    np.testing.assert_array_equal(np.asarray(warm_push.x), np.asarray(cold.x))
    stats = warm_push.push_stats
    assert stats is not None
    # swept-vertex work: push settles `pushed` vertices total; the block
    # engine revisits all n every round
    assert stats["pushed"] <= 0.2 * warm_block.rounds * gw.n
    assert stats["touched_fraction"] < 0.5


def test_incremental_push_pagerank_matches_cold(graphs):
    g, _ = graphs
    algo = get_algorithm("pagerank", g)
    prior = run_async_block(algo, bs=BS)
    delta = random_delta(g, frac_add=0.01, seed=5)
    g2 = delta.apply(g)
    algo2 = remake(algo, g2)
    warm = run_incremental(algo2, algo, prior, engine="push")
    cold = run_async_block(algo2, bs=BS)
    np.testing.assert_allclose(
        np.asarray(warm.x), np.asarray(cold.x), atol=10 * algo.eps, rtol=1e-5
    )


def test_warm_restart_from_converged_state_is_noop(graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    prior = run_async_block(algo, bs=BS)
    r = solve(algo, engine="push", x_init=prior.x)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(prior.x))
    assert r.push_stats["pushed"] == 0


# ---------------------------------------------------------------------------
# frontier estimation & the auto router
# ---------------------------------------------------------------------------

def test_estimate_frontier_fraction_regimes(graphs):
    g, gw = graphs
    # cold pagerank: every vertex carries a supra-eps teleport residual
    assert estimate_frontier_fraction(get_algorithm("pagerank", g)) == 1.0
    # cold sssp: only the source holds a pending candidate
    sssp = get_algorithm("sssp", gw, source=0)
    assert estimate_frontier_fraction(sssp) == pytest.approx(1 / gw.n)
    # a converged warm start has nothing pending
    prior = run_async_block(sssp, bs=BS)
    assert estimate_frontier_fraction(sssp, x_init=np.asarray(prior.x)) == 0.0
    # cold max-semiring workloads must establish every inert 0 -> dense
    assert estimate_frontier_fraction(
        get_algorithm("reachability", g, source=0)) == 1.0


def test_auto_routes_sparse_frontier_to_push(graphs):
    g, _ = graphs
    algo = personalized_pagerank(g, seeds=[5])
    r = solve(algo, engine="auto")
    assert r.push_stats is not None  # the push arm ran
    ref = run_async_block(algo, bs=BS)
    np.testing.assert_allclose(
        np.asarray(r.x), np.asarray(ref.x), atol=20 * algo.eps, rtol=1e-5
    )


def test_auto_routes_dense_frontier_to_sweep(graphs):
    g, _ = graphs
    r = solve(get_algorithm("pagerank", g), engine="auto")
    assert r.push_stats is None and r.converged


def test_auto_threshold_zero_never_pushes(graphs):
    g, _ = graphs
    algo = personalized_pagerank(g, seeds=[5])
    r = solve(algo, engine="auto", push_threshold=0.0)
    assert r.push_stats is None and r.converged


def test_auto_drops_sweep_knobs_when_push_wins(graphs):
    """The router's contract is 'same answer, engine's choice of work':
    sweep-batching and Aitken knobs are dropped on the push route, not
    rejected."""
    g, _ = graphs
    algo = personalized_pagerank(g, seeds=[5])
    r = solve(algo, engine="auto", extrapolate_every=4)
    assert r.push_stats is not None and r.converged


@pytest.mark.parametrize("engine", ["push", "auto"])
def test_push_and_router_under_transfer_guard(engine, graphs):
    g, _ = graphs
    algo = personalized_pagerank(g, seeds=[5])
    r = solve(algo, engine=engine, transfer_guard="disallow")
    assert r.converged and r.push_stats is not None


def test_push_pallas_under_transfer_guard(graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    r = solve(algo, engine="push", backend="pallas",
              transfer_guard="disallow")
    assert r.converged


# ---------------------------------------------------------------------------
# eps_vec / beta
# ---------------------------------------------------------------------------

def test_beta_one_is_uniform_eps(graphs):
    g, _ = graphs
    algo = get_algorithm("pagerank", g)
    np.testing.assert_array_equal(
        push_mod._eps_vec(algo, 1.0), np.full(g.n, algo.eps, np.float32)
    )


def test_beta_below_one_pushes_less_and_stays_close(graphs):
    g, _ = graphs
    algo = personalized_pagerank(g, seeds=[5])
    exact = solve(algo, engine="push", beta=1.0)
    approx = solve(algo, engine="push", beta=0.5)
    assert approx.converged
    assert approx.push_stats["pushed"] <= exact.push_stats["pushed"]
    # degree-normalized thresholds loosen per-vertex stopping by at most
    # outdeg^(1-beta); the fixpoint error stays within that envelope
    deg = Graph(algo.n, algo.src, algo.dst, algo.w).out_degrees()
    envelope = 30 * algo.eps * float(np.sqrt(np.maximum(deg, 1).max()))
    np.testing.assert_allclose(
        np.asarray(approx.x), np.asarray(exact.x), atol=envelope, rtol=0
    )


# ---------------------------------------------------------------------------
# push_stats accounting
# ---------------------------------------------------------------------------

def test_push_stats_contract(graphs):
    _, gw = graphs
    r = solve(get_algorithm("sssp", gw, source=0), engine="push")
    s = r.push_stats
    assert set(s) == {"pushed", "edges", "touched", "touched_fraction",
                      "rounds"}
    assert s["rounds"] == r.rounds
    assert 0 < s["touched"] <= gw.n
    assert s["touched_fraction"] == pytest.approx(s["touched"] / gw.n)
    assert s["pushed"] >= s["touched"]
    # sweep engines don't carry push accounting
    assert run_async_block(get_algorithm("sssp", gw, source=0),
                           bs=BS).push_stats is None


def test_run_push_shim_matches_solve(graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    r1 = run_push(algo)
    r2 = solve(algo, engine="push")
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    assert r1.rounds == r2.rounds


# ---------------------------------------------------------------------------
# option validation & unsupported semirings
# ---------------------------------------------------------------------------

def test_push_option_validation(graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    with pytest.raises(EngineOptionsError, match="per-round frontier"):
        solve(algo, engine="push", sweeps_per_call=4)
    with pytest.raises(EngineOptionsError, match="per-round frontier"):
        solve(algo, engine="push", frontier=np.ones(gw.n, bool))
    with pytest.raises(EngineOptionsError, match="inner"):
        solve(algo, engine="push", inner=2)
    with pytest.raises(EngineUnsupportedError, match="sparse acceleration"):
        solve(algo, engine="push", extrapolate_every=4)
    with pytest.raises(EngineOptionsError, match="push_threshold"):
        solve(algo, engine="auto", push_threshold=1.5)
    with pytest.raises(EngineOptionsError, match="beta"):
        solve(algo, engine="push", beta=2.0)
    with pytest.raises(EngineOptionsError, match="buckets"):
        solve(algo, engine="push", buckets=0)


def test_push_rejects_unknown_semiring():
    fake = types.SimpleNamespace(
        semiring=types.SimpleNamespace(reduce="sum", edge_op="add"),
        combine="replace",
    )
    with pytest.raises(NotImplementedError, match="push engine"):
        push_mod._kernel_semiring(fake)
    # ... and so does the router's estimator (solve(engine="auto") catches
    # this and falls back to the sweep engine)
    fake2 = types.SimpleNamespace(
        semiring=types.SimpleNamespace(reduce="min", edge_op="add"),
        combine="replace",
    )
    with pytest.raises(NotImplementedError, match="push engine"):
        push_mod._kernel_semiring(fake2)


def test_push_x_init_shape_rejected(graphs):
    _, gw = graphs
    algo = get_algorithm("sssp", gw, source=0)
    with pytest.raises(ValueError):
        run_push(algo, x_init=np.zeros(gw.n + 1, np.float32))


# ---------------------------------------------------------------------------
# out_closure / touched_vertices(closure=)
# ---------------------------------------------------------------------------

def test_out_closure_depth_semantics():
    # path 0 -> 1 -> 2 -> 3
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 3], np.int32)
    for depth, want in [(0, [0]), (1, [0, 1]), (2, [0, 1, 2]),
                        (3, [0, 1, 2, 3])]:
        mask = out_closure(src, dst, np.array([0]), 4, depth=depth)
        assert np.nonzero(mask)[0].tolist() == want
    # bool-mask seeds are accepted as-is
    seed_mask = np.array([False, True, False, False])
    mask = out_closure(src, dst, seed_mask, 4, depth=1)
    assert np.nonzero(mask)[0].tolist() == [1, 2]
    with pytest.raises(ValueError, match="bool seed mask"):
        out_closure(src, dst, np.array([True, False]), 4)
    # empty seeds stay empty at any depth
    assert not out_closure(src, dst, np.empty(0, np.int64), 4, depth=2).any()


def test_touched_vertices_closure_semantics():
    g = Graph(5, np.array([0, 1, 2, 3]), np.array([1, 2, 3, 4]))
    delta = GraphDelta(rew_src=[1], rew_dst=[2], rew_w=[2.0])
    g2 = delta.apply(g)
    assert delta.touched_vertices().tolist() == [1, 2]
    assert delta.touched_vertices(g2, closure=1).tolist() == [1, 2, 3]
    assert delta.touched_vertices(g2, closure=2).tolist() == [1, 2, 3, 4]
    with pytest.raises(ValueError, match="post-apply graph"):
        delta.touched_vertices(closure=1)


# ---------------------------------------------------------------------------
# GraphServer push absorption
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("thresh", [0.0, 1.0])
def test_server_push_absorption_resolves_in_flight(thresh, graphs):
    """push_threshold=1.0 forces the absorption path for every warm delta;
    0.0 is the plain rebuild. Both must resolve in-flight queries to the
    new graph's fixpoint (bitwise for sssp, within eps for ppr)."""
    _, gw = graphs
    srv = GraphServer(gw, slots=3, bs=BS, rounds_per_batch=2,
                      delta_mode="warm", push_threshold=thresh)
    t_ppr = srv.submit("ppr", {"seeds": [7]})
    t_sssp = srv.submit("sssp", {"source": 0})
    srv.step()
    assert t_sssp.status == "running"  # genuinely in flight when delta lands
    srv.apply_delta(random_delta(gw, frac_add=0.002, seed=5))
    srv.run()
    g2 = srv.g
    solo_sssp = run_async_block(get_algorithm("sssp", g2, source=0), bs=BS)
    np.testing.assert_array_equal(np.asarray(t_sssp.result),
                                  np.asarray(solo_sssp.x))
    solo_ppr = run_async_block(personalized_pagerank(g2, [7]), bs=BS)
    np.testing.assert_allclose(np.asarray(t_ppr.result),
                               np.asarray(solo_ppr.x), atol=1e-5, rtol=0)


def test_server_push_threshold_validation(graphs):
    _, gw = graphs
    with pytest.raises(ValueError, match="push_threshold"):
        GraphServer(gw, slots=2, push_threshold=1.5)


@pytest.mark.parametrize("interpret", [True, False])
def test_push_pallas_passes_the_interpret_decision(interpret, graphs,
                                                   monkeypatch):
    """engine='push', backend='pallas' hands the scatter kernel the one
    interpret decision (`kernels.ops.interpret_mode`) instead of the
    kernel's own default, so on a TPU it runs lowered. The False case is
    stopped at the kernel boundary: the CPU cannot lower it."""
    from repro.kernels import ops, push_scatter

    class Stop(Exception):
        pass

    seen = []
    real = push_scatter.push_scatter_pallas

    def spy(*args, **kw):
        seen.append(kw["interpret"])
        if not kw["interpret"]:
            raise Stop
        return real(*args, **kw)

    monkeypatch.setattr(ops, "interpret_mode", lambda: interpret)
    monkeypatch.setattr(push_scatter, "push_scatter_pallas", spy)
    g, gw = graphs
    algo = _algo("sssp", g, gw)
    if interpret:
        r = solve(algo, engine="push", backend="pallas")
        np.testing.assert_array_equal(r.x, run_async_block(algo, bs=BS).x)
    else:
        with pytest.raises(Stop):
            solve(algo, engine="push", backend="pallas")
    assert seen and set(seen) == {interpret}
