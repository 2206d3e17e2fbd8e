"""The unified `repro.solve` entry point and `EngineOptions` (PR tentpole).

Three contracts:

1. **Parity** — `solve(algo, engine=...)` returns exactly what the legacy
   `run_sync` / `run_async_block` / `run_distributed` spellings return:
   bitwise-identical states for min/max semirings, eps-equal for sum, with
   identical round counts — because the shims ARE `solve` now, and `solve`
   dispatches to the same engine bodies.
2. **Validation in one place** — every knob is validated by
   `engine.api.validate_options` regardless of the spelling used, raising
   one exception family (`EngineOptionsError` is a `ValueError`;
   `EngineUnsupportedError` is additionally a `NotImplementedError`), so
   pre-redesign `except ValueError` / `except NotImplementedError` callers
   keep working.
3. **Device residency** — `AsyncBlockSession` keeps state, operands, and
   per-column accounting as jax arrays across batches and column swaps;
   nothing round-trips through host numpy between batches.
"""
import dataclasses

import jax
import numpy as np
import pytest

import repro
from repro import (
    EngineOptions,
    EngineOptionsError,
    EngineUnsupportedError,
    get_algorithm,
    personalized_pagerank,
    run_async_block,
    run_distributed,
    run_sync,
    solve,
)
from repro.engine.api import validate_options
from repro.engine.async_block import AsyncBlockSession
from repro.graphs import generators as gen

N = 300
BS = 64


@pytest.fixture(scope="module")
def gw():
    g = gen.scrambled(gen.powerlaw_cluster(N, 4, p=0.4, seed=1), seed=9)
    return gen.with_random_weights(g, lo=0.1, hi=1.0, seed=2)


# one algorithm per reduce direction: sum (eps-equal), min and max
# (bitwise — selective semirings copy values, never blend them)
CASES = [("pagerank", {}, "sum"), ("sssp", {"source": 3}, "min"),
         ("sswp", {"source": 3}, "max")]


def _assert_same(r_a, r_b, reduce):
    assert r_a.rounds == r_b.rounds
    assert r_a.converged and r_b.converged
    if reduce == "sum":
        np.testing.assert_allclose(r_a.x, r_b.x, rtol=0, atol=1e-6)
    else:
        np.testing.assert_array_equal(r_a.x, r_b.x)


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("algo_name,params,reduce", CASES)
def test_solve_matches_run_sync(gw, algo_name, params, reduce):
    algo = get_algorithm(algo_name, gw, **params)
    _assert_same(solve(algo, engine="sync"), run_sync(algo), reduce)


@pytest.mark.parametrize("algo_name,params,reduce", CASES)
def test_solve_matches_run_async_block(gw, algo_name, params, reduce):
    algo = get_algorithm(algo_name, gw, **params)
    _assert_same(
        solve(algo, engine="async_block", bs=BS, inner=2),
        run_async_block(algo, bs=BS, inner=2), reduce,
    )


@pytest.mark.parametrize("algo_name,params,reduce", CASES)
def test_solve_matches_run_distributed(gw, algo_name, params, reduce):
    algo = get_algorithm(algo_name, gw, **params)
    _assert_same(
        solve(algo, engine="distributed", bs=BS),
        run_distributed(algo, bs=BS), reduce,
    )


def test_solve_options_object_equals_overrides(gw):
    algo = get_algorithm("pagerank", gw)
    r_opt = solve(algo, options=EngineOptions(bs=BS, inner=2))
    r_kw = solve(algo, bs=BS, inner=2)
    _assert_same(r_opt, r_kw, "sum")


def test_solve_distributed_batched_columns(gw):
    """d>1 through the shard_map path (new in this PR) matches async_block."""
    algo = personalized_pagerank(gw, [0, 5, 17, 99])
    r_d = solve(algo, engine="distributed", bs=BS)
    r_a = solve(algo, engine="async_block", bs=BS)
    assert r_d.rounds == r_a.rounds
    np.testing.assert_allclose(r_d.x, r_a.x, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(r_d.col_rounds, r_a.col_rounds)


def test_solve_pallas_backend_bitwise(gw):
    algo = get_algorithm("sssp", gw, source=3)
    r_p = solve(algo, backend="pallas", bs=BS)
    r_j = solve(algo, backend="jax", bs=BS)
    assert r_p.rounds == r_j.rounds
    np.testing.assert_array_equal(r_p.x, r_j.x)


def test_shims_route_through_solve(gw, monkeypatch):
    """run_* are thin shims: stubbing solve() is enough to divert them."""
    calls = []

    def fake_solve(algo, engine="async_block", options=None, **kw):
        calls.append((engine, options))
        return "sentinel"

    import repro.engine.api as api
    monkeypatch.setattr(api, "solve", fake_solve)
    algo = get_algorithm("pagerank", gw)
    assert run_sync(algo) == "sentinel"
    assert run_async_block(algo, bs=BS) == "sentinel"
    assert run_distributed(algo, bs=BS) == "sentinel"
    assert [c[0] for c in calls] == ["sync", "async_block", "distributed"]
    assert all(isinstance(c[1], EngineOptions) for c in calls)


# ------------------------------------------------------------- validation


def test_unknown_engine_rejected(gw):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError, match="unknown engine"):
        solve(algo, engine="warp")


def test_unknown_backend_rejected(gw):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError, match="unknown backend"):
        solve(algo, backend="cuda")


def test_unknown_option_field_rejected(gw):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError, match="block_size"):
        solve(algo, block_size=64)  # the field is called bs


@pytest.mark.parametrize("kw,msg", [
    ({"bs": 0}, "bs must be >= 1"),
    ({"inner": 0}, "inner must be >= 1"),
    ({"max_iters": 0}, "max_iters must be >= 1"),
    ({"sweeps_per_call": 0}, "sweeps_per_call must be >= 1"),
])
def test_bad_knob_values_rejected(gw, kw, msg):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError, match=msg):
        solve(algo, **kw)


def test_pallas_knobs_rejected_on_jax_backend(gw):
    algo = get_algorithm("sssp", gw, source=3)
    with pytest.raises(EngineOptionsError, match="pallas-backend knobs"):
        solve(algo, backend="jax", sweeps_per_call=4)


def test_extrapolation_contracts(gw):
    """Extrapolation: sum-semiring only, every >= 2, not under the
    megakernel — and EngineUnsupportedError still reads as the
    NotImplementedError the old engines raised."""
    sum_algo = get_algorithm("pagerank", gw)
    min_algo = get_algorithm("sssp", gw, source=3)
    with pytest.raises(NotImplementedError, match="sum-semiring"):
        solve(min_algo, extrapolate_every=4)
    with pytest.raises(ValueError, match=">= 2"):
        solve(sum_algo, extrapolate_every=1)
    with pytest.raises(EngineUnsupportedError):
        solve(sum_algo, backend="pallas", bs=BS,
              sweeps_per_call=4, extrapolate_every=4)
    assert solve(sum_algo, extrapolate_every=4, bs=BS).converged


def test_exception_family_is_compatible():
    assert issubclass(EngineOptionsError, ValueError)
    assert issubclass(EngineUnsupportedError, EngineOptionsError)
    assert issubclass(EngineUnsupportedError, NotImplementedError)


def test_options_frozen_and_validate_direct():
    o = EngineOptions(bs=BS)
    with pytest.raises(dataclasses.FrozenInstanceError):
        o.bs = 128
    with pytest.raises(EngineOptionsError, match="unknown engine"):
        validate_options("nope", o)


def test_session_constructor_validates(gw):
    algo = get_algorithm("pagerank", gw)
    with pytest.raises(EngineOptionsError, match="bs must be >= 1"):
        AsyncBlockSession(algo, bs=0)
    with pytest.raises(EngineOptionsError, match="unknown backend"):
        AsyncBlockSession(algo, backend="cuda")


# -------------------------------------------------------- public surface


def test_top_level_public_surface():
    for name in repro.__all__:
        assert getattr(repro, name) is not None, name
    assert repro.solve is solve
    assert repro.GraphServer.__name__ == "GraphServer"
    with pytest.raises(AttributeError):
        repro.definitely_not_an_attr


# ------------------------------------------------------- device residency


def _is_device(a):
    return isinstance(a, jax.Array)


def test_session_state_stays_on_device(gw, transfer_guard_disallow):
    """The tentpole's residency contract: packed state, operands, and
    per-column accounting are jax arrays after construction, after every
    run_batch, and after a column swap — host numpy appears only when the
    caller reads a result out. Runs under the device->host transfer guard,
    so any implicit readback in the session/engine path faults."""
    algo = personalized_pagerank(gw, [2, 7, 11, 42])
    ses = AsyncBlockSession(algo, bs=BS)

    def check(where):
        for name in ("x", "x0", "c", "fixed", "col_done", "col_rounds"):
            assert _is_device(getattr(ses, name)), (where, name)
        assert _is_device(ses.state), where

    check("init")
    ses.run_batch(4)
    check("after batch 1")
    ses.run_batch(4)
    check("after batch 2")
    q = personalized_pagerank(gw, [123])
    ses.swap_in(1, q.x0[:, 0], q.c[:, 0], q.fixed[:, 0])
    check("after swap_in")
    ses.run_batch(2000)
    check("after drain")
    # and the resident computation is still correct end to end
    solo = run_async_block(q, bs=BS)
    np.testing.assert_allclose(
        jax.device_get(ses.state[:, 1]), solo.x, rtol=0, atol=1e-6
    )
    assert int(jax.device_get(ses.col_rounds)[1]) == solo.rounds


def test_session_pallas_state_stays_on_device(gw, transfer_guard_disallow):
    from repro.engine import multi_source_sssp

    # min semiring: selective updates make the resident megakernel state
    # bitwise-comparable to the solo run regardless of sweep granularity
    algo = multi_source_sssp(gw, [3, 5])
    ses = AsyncBlockSession(algo, bs=BS, backend="pallas", sweeps_per_call=2)
    ses.run_batch(4)
    assert _is_device(ses.x) and _is_device(ses.dirty)
    ses.run_batch(2000)
    assert _is_device(ses.state)
    solo = run_async_block(algo, bs=BS)
    np.testing.assert_array_equal(
        jax.device_get(ses.state), np.asarray(solo.x, np.float32)
    )


def test_server_resolution_is_the_only_host_copy(gw, transfer_guard_disallow):
    """End to end through the server: the family session's arrays remain
    device arrays across ticks/swaps; the Ticket.result is host numpy.
    The server's own sanitizer knob is also on, nested inside the fixture's
    guard — both paths must hold."""
    from repro.serving import GraphServer

    srv = GraphServer(gw, slots=2, bs=BS, rounds_per_batch=4,
                      transfer_guard="disallow")
    tickets = [srv.submit("ppr", {"seeds": [s]}) for s in (1, 2, 3, 4)]
    srv.run()
    fam = next(iter(srv._families.values()))
    assert _is_device(fam.session.x)
    assert _is_device(fam.session.col_rounds)
    for t in tickets:
        assert isinstance(t.result, np.ndarray)
        solo = run_async_block(
            personalized_pagerank(gw, t.params["seeds"]), bs=BS
        )
        assert t.rounds == solo.rounds
        np.testing.assert_allclose(t.result, solo.x, rtol=0, atol=1e-6)


# -------------------------------------------------- transfer-guard knob


def test_transfer_guard_value_validated():
    with pytest.raises(EngineOptionsError, match="transfer_guard"):
        validate_options(
            "async_block", EngineOptions(transfer_guard="everything")
        )
    for ok in (None, "allow", "log", "disallow"):
        validate_options("async_block", EngineOptions(transfer_guard=ok))


def test_mesh_rejected_outside_distributed():
    with pytest.raises(EngineOptionsError, match="mesh"):
        validate_options("async_block", EngineOptions(mesh=object()))
    with pytest.raises(EngineOptionsError, match="mesh"):
        validate_options("sync", EngineOptions(mesh=object()))


def test_x_init_rank_validated():
    with pytest.raises(EngineOptionsError, match="x_init"):
        validate_options(
            "async_block", EngineOptions(x_init=np.zeros((2, 2, 2)))
        )
    validate_options("async_block", EngineOptions(x_init=np.zeros(4)))
    validate_options("async_block", EngineOptions(x_init=np.zeros((4, 2))))


def test_axis_validated():
    with pytest.raises(EngineOptionsError, match="axis"):
        validate_options("distributed", EngineOptions(axis=""))


@pytest.mark.parametrize("algo_name,params,reduce", CASES)
def test_solve_under_transfer_guard_matches_plain(gw, algo_name, params,
                                                  reduce):
    """The engines run start-to-finish under the device->host guard: every
    transfer in the hot path is an audited jax.device_get."""
    algo = get_algorithm(algo_name, gw, **params)
    plain = solve(algo, engine="async_block", bs=BS)
    guarded = solve(algo, engine="async_block", bs=BS,
                    transfer_guard="disallow")
    _assert_same(plain, guarded, reduce)


def test_solve_pallas_under_transfer_guard(gw):
    algo = get_algorithm("sssp", gw, source=3)
    plain = solve(algo, engine="async_block", bs=BS, backend="pallas",
                  sweeps_per_call=4)
    guarded = solve(algo, engine="async_block", bs=BS, backend="pallas",
                    sweeps_per_call=4, transfer_guard="disallow")
    _assert_same(plain, guarded, "min")


def test_server_transfer_guard_rejects_bad_value(gw):
    from repro.serving import GraphServer

    with pytest.raises(ValueError, match="transfer_guard"):
        GraphServer(gw, transfer_guard="everything")


def test_pallas_block_size_must_fill_lanes_when_lowered(monkeypatch):
    """Lowered for the TPU, the megakernel's tile DMA needs bs to be a
    multiple of 128: validate_options refuses any other bs (never rounds it
    up), while interpret-mode runs keep their small test blocks."""
    from repro.kernels import ops

    algo = get_algorithm("pagerank", gen.powerlaw_cluster(50, 2, seed=0))
    validate_options("async_block", EngineOptions(backend="pallas", bs=64), algo)
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    for bs in (16, 64, 200):
        with pytest.raises(EngineOptionsError, match="multiple of 128"):
            validate_options(
                "async_block", EngineOptions(backend="pallas", bs=bs), algo)
    with pytest.raises(EngineOptionsError, match="multiple of 128"):
        AsyncBlockSession(algo, bs=64, backend="pallas", sweeps_per_call=2)
    for bs in (128, 256):
        validate_options("async_block", EngineOptions(backend="pallas", bs=bs), algo)
    # the jax backend and the push engine have no tile DMA to align
    validate_options("async_block", EngineOptions(backend="jax", bs=64), algo)
    validate_options("push", EngineOptions(backend="pallas", bs=64), algo)


def test_interpret_mode_is_decided_by_the_backend(monkeypatch):
    """One decision for every kernel call: lowered on a TPU, interpreted on
    the CPU, refused anywhere else — never silently interpreted."""
    from repro.kernels import ops

    assert ops.interpret_mode() is True  # the tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops.interpret_mode()
