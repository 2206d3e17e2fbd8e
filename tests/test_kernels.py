"""Pallas kernels vs pure-numpy oracles: flat-BSR shape/semiring sweeps,
engine parity, and the padding contract across every supported
semiring/combine pair (non-divisible n, batched d > 1, warm-start x_init)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.engine.algorithms import BIG
from repro.engine import get_algorithm, run_async_block
from repro.graphs import generators as gen
from repro.kernels import bsr_spmm, gs_sweep
from repro.kernels.ops import pack_algorithm, run_async_block_pallas
from repro.kernels.ref import ref_bsr_spmm, ref_gs_sweep

RNG = np.random.default_rng(0)

SEMIRINGS = ["plus_times", "min_plus", "max_min", "max_times"]

# every fused pair the kernels implement, with a graph workload that
# exercises it (weighted graphs where the semiring needs real weights)
PAIRS = [
    ("pagerank", False),      # plus_times / replace
    ("sssp", True),           # min_plus  / min_old
    ("sswp", True),           # max_min   / max_old
    ("reachability", False),  # max_times / max_old
]


def _rand_tiles(nnz, bs, semiring):
    """Random tiles: ~20% real entries, the rest the semiring's in-tile fill."""
    from repro.kernels.semirings import TILE_FILL

    real = RNG.random((nnz, bs, bs)) < 0.2
    vals = (RNG.random((nnz, bs, bs)) * 5).astype(np.float32)
    return np.where(real, vals, np.float32(TILE_FILL[semiring])).astype(np.float32)


def _flat_operands(bs, d, nb, kmax, dtype, semiring):
    """Random ragged flat-BSR operands: row i owns i%(kmax+1) tiles (so some
    rows are empty — the layout's whole point) with random column blocks."""
    counts = np.arange(nb) % (kmax + 1)
    rowptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    nnz = int(rowptr[-1])
    tilecols = RNG.integers(0, nb, size=max(1, nnz)).astype(np.int32)
    tilerows = (np.repeat(np.arange(nb), counts).astype(np.int32)
                if nnz else np.zeros(1, np.int32))
    tiles = _rand_tiles(max(1, nnz), bs, semiring)
    x = RNG.random((nb * bs, d)).astype(np.float32)
    return (jnp.asarray(rowptr), jnp.asarray(tilerows), jnp.asarray(tilecols),
            jnp.asarray(tiles).astype(dtype), jnp.asarray(x).astype(dtype))


@pytest.mark.parametrize("bs,d,nb,kmax", [
    (8, 8, 3, 2), (8, 128, 4, 3), (16, 16, 5, 4), (32, 64, 3, 2),
    (128, 128, 2, 2),
])
@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_bsr_spmm_shapes(bs, d, nb, kmax, semiring):
    rowptr, tilerows, tilecols, tiles, x = _flat_operands(
        bs, d, nb, kmax, jnp.float32, semiring)
    y = bsr_spmm(rowptr, tilerows, tilecols, tiles, x, semiring=semiring)
    yref = ref_bsr_spmm(rowptr, tilecols, tiles, x, semiring=semiring)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=1e-4, rtol=1e-4)


def test_bsr_spmm_empty_rows_get_identity():
    """Row-blocks with no tiles never enter the grid; the wrapper must still
    write the reduce identity into their output rows."""
    for semiring, ident in [("plus_times", 0.0), ("min_plus", BIG),
                            ("max_min", -BIG), ("max_times", -BIG)]:
        rowptr, tilerows, tilecols, tiles, x = _flat_operands(
            8, 4, 5, 2, jnp.float32, semiring)
        y = np.asarray(bsr_spmm(rowptr, tilerows, tilecols, tiles, x,
                                semiring=semiring))
        rp = np.asarray(rowptr)
        for i in range(len(rp) - 1):
            if rp[i] == rp[i + 1]:
                np.testing.assert_array_equal(
                    y[i * 8:(i + 1) * 8], np.float32(ident))


def test_bsr_spmm_bf16():
    rowptr, tilerows, tilecols, tiles, x = _flat_operands(
        16, 32, 4, 3, jnp.bfloat16, "plus_times")
    y = bsr_spmm(rowptr, tilerows, tilecols, tiles, x)
    yref = ref_bsr_spmm(rowptr, tilecols,
                        np.asarray(tiles, np.float32),
                        np.asarray(x, np.float32))
    np.testing.assert_allclose(np.asarray(y, np.float32), yref,
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("algo_name,weighted,bs", [
    ("pagerank", False, 32), ("pagerank", False, 64),
    ("sssp", True, 32), ("bfs", False, 64), ("php", False, 32),
    ("cc", False, 32), ("katz", False, 64),
    ("sswp", True, 32), ("reachability", False, 64),
])
def test_gs_sweep_vs_ref(algo_name, weighted, bs):
    g = gen.powerlaw_cluster(400, 3, seed=1)
    if weighted:
        g = gen.with_random_weights(g, seed=2)
    algo = get_algorithm(algo_name, g)
    ops = pack_algorithm(algo, bs=bs)
    args = (ops["rowptr"], ops["tilecols"], ops["tiles"], ops["c"],
            ops["x0"], ops["fixed"], ops["x"])
    kw = dict(semiring=ops["semiring"], combine=ops["combine"])
    xk = gs_sweep(*args, **kw)
    xr = ref_gs_sweep(*args, **kw)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               atol=1e-4, rtol=1e-4)


def test_pack_algorithm_tiles_are_nnz_proportional():
    """The flat layout's contract: tile memory is nnz_blocks * bs^2 * 4, not
    nb * k_max * bs^2 * 4 (the hub row-block is paid for once)."""
    g = gen.scrambled(gen.powerlaw_cluster(600, 4, seed=3), seed=7)
    ops = pack_algorithm(get_algorithm("pagerank", g), bs=16)
    s = ops["bsr_stats"]
    assert ops["tiles"].shape[0] == s["nnz_blocks"]
    assert s["tile_bytes"] == s["nnz_blocks"] * 16 * 16 * 4
    assert s["nnz_blocks"] < s["nb"] * s["k_max"]  # real skew on powerlaw
    assert s["padding_waste"] > 0.0
    assert s["tile_bytes_saved"] == s["dense_tile_bytes"] - s["tile_bytes"]


@pytest.mark.parametrize("algo_name,weighted", PAIRS)
def test_pallas_engine_matches_jax_engine(algo_name, weighted):
    g = gen.scrambled(gen.powerlaw_cluster(600, 4, seed=3), seed=7)
    graph = gen.with_random_weights(g, seed=1) if weighted else g
    algo = get_algorithm(algo_name, graph)
    r_pal = run_async_block_pallas(algo, bs=64, max_iters=300)
    r_jax = run_async_block(algo, bs=64)
    # float accumulation-order noise near eps can shift convergence by one
    assert abs(r_pal.rounds - r_jax.rounds) <= 1, algo_name
    if algo.semiring.reduce == "sum":
        # block-matmul vs edge-segment-sum accumulation order differs
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
    else:
        # min/max reductions are order-free: the kernels must be bitwise
        # equal to the pure-JAX engine
        np.testing.assert_array_equal(r_pal.x, r_jax.x)
    np.testing.assert_allclose(r_pal.x, algo.exact(), atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# the padding contract, for every supported pair: non-block-divisible n,
# batched d > 1, and warm-start x_init must ride the pallas backend without
# padding rows ever leaking into real states
# ---------------------------------------------------------------------------

def _contract_algo(algo_name, d):
    """An instance on a graph whose n (311) is not divisible by any test bs;
    d > 1 uses the batched constructors where they exist and column broadcast
    otherwise."""
    g = gen.scrambled(gen.powerlaw_cluster(311, 3, seed=9), seed=4)
    gw = gen.with_random_weights(g, seed=6)
    if d == 1:
        return get_algorithm(algo_name, gw if algo_name in ("sssp", "sswp") else g)
    if algo_name == "pagerank":
        return get_algorithm("ppr", g, seeds=list(range(d)))
    if algo_name == "sssp":
        return get_algorithm("ms_sssp", gw, sources=list(range(d)))
    # sswp / reachability have no batched constructor: run d independent
    # single-query columns by stacking the scalar instance's vectors
    import dataclasses

    algo = get_algorithm(algo_name, gw if algo_name == "sswp" else g)
    return dataclasses.replace(
        algo,
        x0=np.repeat(algo.x0, d, axis=1),
        c=np.repeat(algo.c, d, axis=1),
        fixed=np.repeat(algo.fixed, d, axis=1),
        exact_fn=None,
    )


def _assert_sum_rounds_agree(ra, rb, eps):
    """Per-column round counts of two sum-semiring runs that accumulate in
    different orders (tile matmul vs edge segment-sum). A column's stopping
    round is where its linf residual first drops to eps; when eps sits
    within rounding of the state's ulp, accumulation order alone decides
    that round. So the counts must be equal, except where every residual the
    later run measured in between lies within two ulps of eps."""
    ulp = float(np.spacing(np.float32(max(np.abs(ra.x).max(),
                                          np.abs(rb.x).max()))))
    for ka, kb in zip(ra.col_rounds, rb.col_rounds, strict=True):
        if ka == kb:
            continue
        late = ra if ka > kb else rb
        lo, hi = sorted((int(ka), int(kb)))
        between = late.residuals[lo - 1: hi - 1]
        assert np.all(between <= eps + 2 * ulp), (ka, kb, between, eps, ulp)


@pytest.mark.parametrize("algo_name,_w", PAIRS)
@pytest.mark.parametrize("d", [1, 3])
def test_padding_contract_all_pairs(algo_name, _w, d):
    """bs=64 does not divide n=311: the last block is padding-heavy, and the
    result must still match the pure-JAX engine for every fused pair."""
    algo = _contract_algo(algo_name, d)
    r_pal = run_async_block_pallas(algo, bs=64, max_iters=300)
    r_jax = run_async_block(algo, bs=64)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
        _assert_sum_rounds_agree(r_pal, r_jax, algo.eps)
    else:
        np.testing.assert_array_equal(r_pal.x, r_jax.x)
        np.testing.assert_array_equal(r_pal.col_rounds, r_jax.col_rounds)


@pytest.mark.parametrize("algo_name,_w", PAIRS)
@pytest.mark.parametrize("d", [1, 3, 8])
def test_lane_padding_is_inert(algo_name, _w, d):
    """pack_algorithm pads the state to 128 lanes; the padding columns must
    not move, report no residual, and change no real column — the kernel on
    the padded operands equals the kernel on the bare d columns (bitwise for
    the lattice semirings), and the engine returns only d columns."""
    from repro.kernels.gs_sweep import gs_multisweep_pallas
    from repro.kernels.ops import LANES

    algo = _contract_algo(algo_name, d)
    ops = pack_algorithm(algo, bs=64)
    assert ops["d"] == d and ops["x"].shape[1] == LANES
    nb = int(ops["rowptr"].shape[0]) - 1
    kw = dict(semiring=ops["semiring"], combine=ops["combine"],
              res_kind=algo.residual, eps=float(algo.eps), bs=64, sweeps=6,
              interpret=True)

    def run(cols):
        return gs_multisweep_pallas(
            *_msweep_args(ops), jnp.ones((nb,), jnp.int32), ops["tiles"],
            *(ops[k][:, cols] for k in ("c", "x0", "fixed", "x")), **kw)

    x_p, dl_p, act_p, fr_p = run(slice(None))
    x_b, dl_b, act_b, fr_b = run(slice(0, d))
    x_p, dl_p = np.asarray(x_p), np.asarray(dl_p)
    np.testing.assert_array_equal(x_p[:, d:], np.asarray(ops["x0"])[:, d:])
    np.testing.assert_array_equal(dl_p[:, d:], 0.0)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(x_p[:, :d], x_b, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(dl_p[:, :d], dl_b, rtol=1e-5, atol=1e-7)
    else:
        np.testing.assert_array_equal(x_p[:, :d], x_b)
        np.testing.assert_array_equal(dl_p[:, :d], dl_b)
        np.testing.assert_array_equal(act_p, act_b)
        np.testing.assert_array_equal(fr_p, fr_b)

    r_pal = run_async_block_pallas(algo, bs=64, max_iters=300,
                                   sweeps_per_call=4)
    r_jax = run_async_block(algo, bs=64)
    assert np.shape(r_pal.x) == np.shape(r_jax.x)
    assert r_pal.col_rounds.shape == r_pal.col_converged.shape == (d,)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
        _assert_sum_rounds_agree(r_pal, r_jax, algo.eps)
    else:
        np.testing.assert_array_equal(r_pal.x, r_jax.x)
        np.testing.assert_array_equal(r_pal.col_rounds, r_jax.col_rounds)


@pytest.mark.parametrize("algo_name,_w", PAIRS)
def test_warm_start_contract_all_pairs(algo_name, _w):
    """x_init through the pallas backend: resuming from a mid-run jax-engine
    state must land on the same fixpoint as the jax engine resumed from the
    same state, and resuming from a *converged* state must be a bitwise
    no-op verification sweep (rounds == 1)."""
    algo = _contract_algo(algo_name, 1)
    r_cold = run_async_block(algo, bs=64)
    # mid-run resume: 3 rounds cold, then both backends finish from there
    r_mid = run_async_block(algo, bs=64, max_iters=3)
    r_pal = run_async_block_pallas(algo, bs=64, x_init=r_mid.x, max_iters=300)
    r_jax = run_async_block(algo, bs=64, x_init=r_mid.x)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
    else:
        np.testing.assert_array_equal(r_pal.x, r_jax.x)
    # converged resume: one verification sweep, state bitwise unchanged
    r_resume = run_async_block_pallas(algo, bs=64, x_init=r_cold.x, max_iters=300)
    assert r_resume.rounds == 1
    np.testing.assert_array_equal(r_resume.x, r_cold.x)


# ---------------------------------------------------------------------------
# operands built on the device from the edge list: bitwise the host packers'
# ---------------------------------------------------------------------------

def _host_operands(algo, bs, d):
    """What the host packers give: `pack_bsr_flat`'s structure and tiles,
    its reverse deps, and each state matrix padded by `pad_state` (rows)
    then `pad_lanes` (columns), a single column repeated to ``d``."""
    from repro.graphs.blocked import pack_bsr_flat, pad_state
    from repro.graphs.graph import Graph
    from repro.kernels.ops import _KERNEL_SEMIRING, pad_lanes
    from repro.kernels.semirings import TILE_FILL

    semiring = _KERNEL_SEMIRING[(algo.semiring.reduce, algo.semiring.edge_op)]
    bsr = pack_bsr_flat(Graph(algo.n, algo.src, algo.dst, algo.w), bs,
                        fill=TILE_FILL[semiring])

    def padm(a, fill):
        out = pad_state(np.asarray(a, np.float32), bs, fill=fill)
        if d != algo.d:
            out = np.repeat(out, d, axis=1)
        return pad_lanes(out, fill)

    revptr, revrows = bsr.reverse_deps()
    x0 = padm(algo.x0, algo.semiring.identity)
    return {"rowptr": bsr.rowptr, "tilecols": bsr.tilecols,
            "tilerows": bsr.tilerows, "revptr": revptr, "revrows": revrows,
            "tiles": bsr.tiles, "c": padm(algo.c, algo.c_pad_fill),
            "x0": x0, "fixed": padm(algo.fixed, 1.0), "x": x0}


def _assert_operands_bitwise(ops, ref):
    for k, want in ref.items():
        got = np.asarray(ops[k])
        assert got.dtype == want.dtype and got.shape == want.shape, k
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8),
                                      err_msg=k)
    # the start state is a buffer of its own, never an alias of x0
    assert (ops["x"].unsafe_buffer_pointer()
            != ops["x0"].unsafe_buffer_pointer())


def _edge_case_algo(algo_name, graph):
    """A d = 1 instance on n = 311 whose arcs repeat with other weights, in
    shuffled order (the last of each repeated arc wins), or that has none."""
    import dataclasses

    algo = _contract_algo(algo_name, 1)
    if graph == "empty":
        return dataclasses.replace(
            algo, src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
            w=np.zeros(0, np.float32), exact_fn=None)
    rng = np.random.default_rng(11)
    rep = rng.choice(algo.m, size=algo.m // 3, replace=False)
    src = np.concatenate([algo.src, algo.src[rep], algo.src[rep]])
    dst = np.concatenate([algo.dst, algo.dst[rep], algo.dst[rep]])
    w = np.concatenate([algo.w, rng.random(2 * len(rep))]).astype(np.float32)
    perm = rng.permutation(len(src))
    return dataclasses.replace(algo, src=src[perm], dst=dst[perm], w=w[perm],
                               exact_fn=None)


@pytest.mark.parametrize("algo_name,_w", PAIRS)
@pytest.mark.parametrize("case", ["d1", "batched", "broadcast",
                                  "repeated_arcs", "empty"])
def test_device_build_matches_host_packers(algo_name, _w, case):
    """Every operand `pack_algorithm` builds on the device from the edge
    list equals the host packers' bitwise, for all four kernel semirings
    (tile fills 0 and +-BIG), on n = 311 (not a multiple of bs = 64): d = 1,
    a batched d = 3, a d = 1 instance broadcast to d = 8, repeated arcs of
    different weights, and the empty graph with its one unreferenced tile."""
    if case in ("repeated_arcs", "empty"):
        algo = _edge_case_algo(algo_name, case)
    else:
        algo = _contract_algo(algo_name, 3 if case == "batched" else 1)
    d = 8 if case == "broadcast" else algo.d
    ops = pack_algorithm(algo, bs=64, d=d)
    assert ops["d"] == d and ops["npad"] == 320
    _assert_operands_bitwise(ops, _host_operands(algo, 64, d))
    if case == "empty":
        assert ops["bsr_stats"]["nnz_blocks"] == 0
        assert ops["tiles"].shape == (1, 64, 64)


def test_pack_uploads_the_skeleton_not_the_tiles():
    """On a d = 1 PageRank instance the pallas pack sends what the device
    build needs, one transfer below a tenth of the tiles it builds."""
    from repro.obs import Tracer

    algo = _contract_algo("pagerank", 1)
    tr = Tracer()
    ops = pack_algorithm(algo, bs=64, tracer=tr)
    (up,) = tr.find("upload")
    assert 0 < up.attrs["bytes"] < ops["tiles"].nbytes / 10


@pytest.mark.parametrize("algo_name,_w", PAIRS)
@pytest.mark.parametrize("d", [1, 3])
def test_warm_start_device_overlay(algo_name, _w, d):
    """x_init overlaid on the device equals the host overlay of the host
    packers' x0 bitwise, in each accepted shape, and the pallas run from it
    gives the RunResult.x of the jax engine, which overlays on the host."""
    from repro.engine import harness

    algo = _contract_algo(algo_name, d)
    ops = pack_algorithm(algo, bs=64)
    x0_host = _host_operands(algo, 64, d)["x0"]
    mid = np.asarray(run_async_block(algo, bs=64, max_iters=3).x)
    for x_init in (mid, mid.reshape(algo.n, d)):   # (n,) or (n, d); (n, 1)
        got = harness.init_state_device(ops["x"], ops["x0"], x_init, algo.n, d)
        want = harness.init_state(x0_host, x_init, algo.n, d)
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      want.view(np.uint8))
    assert harness.init_state_device(ops["x"], ops["x0"], None, algo.n,
                                     d) is ops["x"]
    with pytest.raises(ValueError):
        harness.init_state_device(ops["x"], ops["x0"],
                                  np.zeros((algo.n, d + 1)), algo.n, d)
    r_pal = run_async_block_pallas(algo, bs=64, x_init=mid, max_iters=300,
                                   sweeps_per_call=4)
    r_jax = run_async_block(algo, bs=64, x_init=mid)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
    else:
        np.testing.assert_array_equal(r_pal.x, r_jax.x)


def test_incremental_warm_start_through_pallas_backend():
    """run_incremental(engine='async_block', backend='pallas'): the warm
    state and the delta instance both ride the flat-BSR kernel path."""
    from repro.engine import remake, run_incremental
    from repro.graphs.delta import random_delta

    g0 = gen.scrambled(gen.powerlaw_cluster(300, 3, seed=2), seed=3)
    gw = gen.with_random_weights(g0, seed=1)
    # pagerank needs the unweighted graph (random weights up to 10 make the
    # iteration matrix non-contractive); sssp needs the weighted one
    for name, g in (("pagerank", g0), ("sssp", gw)):
        algo_old = get_algorithm(name, g)
        delta = random_delta(g, frac_add=0.02, seed=5)
        algo_new = remake(algo_old, delta.apply(g))
        prior = run_async_block(algo_old, bs=64)
        r_pal = run_incremental(algo_new, algo_old, prior, bs=64,
                                backend="pallas", max_iters=300)
        r_jax = run_incremental(algo_new, algo_old, prior, bs=64)
        np.testing.assert_allclose(r_pal.x, r_jax.x, atol=1e-4, rtol=1e-4)
        r_cold = run_async_block(algo_new, bs=64)
        np.testing.assert_allclose(r_pal.x, r_cold.x, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# the persistent multi-sweep megakernel: sweep batching, in-kernel
# convergence, and active-frontier block skipping
# ---------------------------------------------------------------------------

def _msweep_args(ops):
    return (ops["rowptr"], ops["tilecols"], ops["revptr"], ops["revrows"])


@pytest.mark.parametrize("algo_name,_w", PAIRS)
def test_multisweep_matches_ref_oracle(algo_name, _w):
    """Megakernel vs the numpy sweep-batched frontier oracle: state, the
    per-sweep delta trace, active-block counts, and the exported frontier
    must all agree (bitwise for the lattice semirings)."""
    from repro.kernels.gs_sweep import gs_multisweep_pallas
    from repro.kernels.ref import ref_gs_multisweep

    algo = _contract_algo(algo_name, 1)
    ops = pack_algorithm(algo, bs=32)
    nb = int(ops["rowptr"].shape[0]) - 1
    dirty = jnp.ones((nb,), jnp.int32)
    kw = dict(semiring=ops["semiring"], combine=ops["combine"],
              res_kind=algo.residual, eps=float(algo.eps))
    xk, dk, ak, fk = gs_multisweep_pallas(
        *_msweep_args(ops), dirty, ops["tiles"], ops["c"], ops["x0"],
        ops["fixed"], ops["x"], bs=32, sweeps=6, interpret=True, **kw)
    xr, dr, ar, fr = ref_gs_multisweep(
        *_msweep_args(ops), dirty, ops["tiles"], ops["c"], ops["x0"],
        ops["fixed"], ops["x"], sweeps=6, **kw)
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(np.asarray(xk), xr, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), dr, atol=1e-4, rtol=1e-3)
    else:
        np.testing.assert_array_equal(np.asarray(xk), xr)
        np.testing.assert_array_equal(np.asarray(dk), dr)
    np.testing.assert_array_equal(np.asarray(ak)[:, 0], ar)
    np.testing.assert_array_equal(np.asarray(fk), fr)


@pytest.mark.parametrize("algo_name,_w", PAIRS)
@pytest.mark.parametrize("d", [1, 3])
def test_multisweep_engine_matches_per_sweep(algo_name, _w, d):
    """sweeps_per_call=4 must reproduce the per-sweep pallas engine on
    non-divisible n for every fused pair: same per-column round counts, and
    bitwise-equal states for the lattice semirings (skipped blocks are
    bitwise no-ops, so frontier execution IS full-sweep execution)."""
    algo = _contract_algo(algo_name, d)
    r1 = run_async_block_pallas(algo, bs=64, max_iters=300)
    rb = run_async_block_pallas(algo, bs=64, max_iters=300, sweeps_per_call=4)
    assert rb.rounds == r1.rounds
    np.testing.assert_array_equal(rb.col_rounds, r1.col_rounds)
    if algo.semiring.reduce == "sum":
        # batched sweeps keep advancing a converged column until the batch
        # stops (no per-column freezing), each step moving it < eps
        np.testing.assert_allclose(rb.x, r1.x, atol=1e-4, rtol=1e-4)
    else:
        np.testing.assert_array_equal(rb.x, r1.x)
    assert rb.active_block_fraction is not None
    assert len(rb.active_block_fraction) == rb.rounds


@pytest.mark.parametrize("algo_name,_w", PAIRS)
def test_multisweep_warm_start(algo_name, _w):
    """x_init through the sweep-batched path: resume from a 3-round state
    and land where the per-sweep engine lands; resume from a *converged*
    state and early-out in a single batch (1 verification sweep, bitwise
    no-op for the lattice semirings)."""
    algo = _contract_algo(algo_name, 1)
    r_mid = run_async_block(algo, bs=64, max_iters=3)
    r1 = run_async_block_pallas(algo, bs=64, x_init=r_mid.x, max_iters=300)
    rb = run_async_block_pallas(algo, bs=64, x_init=r_mid.x, max_iters=300,
                                sweeps_per_call=16)
    assert rb.rounds == r1.rounds
    if algo.semiring.reduce == "sum":
        np.testing.assert_allclose(rb.x, r1.x, atol=1e-4, rtol=1e-4)
    else:
        np.testing.assert_array_equal(rb.x, r1.x)
    r_cold = run_async_block(algo, bs=64)
    r_res = run_async_block_pallas(algo, bs=64, x_init=r_cold.x,
                                   max_iters=300, sweeps_per_call=16)
    assert r_res.rounds == 1
    if algo.semiring.reduce != "sum":
        np.testing.assert_array_equal(r_res.x, r_cold.x)


def test_multisweep_frontier_skip_bitwise_at_fixpoint():
    """The frontier contract, directly: re-running a converged state with
    an all-dirty frontier (every block updates once — full verification
    sweep) and with a partially-seeded frontier (most blocks skipped) must
    both leave the state bitwise unchanged — a skipped block equals an
    updated one at fixpoint."""
    algo = _contract_algo("sssp", 1)
    r_cold = run_async_block(algo, bs=64)
    # all-dirty: every block verifies
    r_full = run_async_block_pallas(algo, bs=64, x_init=r_cold.x,
                                    sweeps_per_call=4)
    np.testing.assert_array_equal(r_full.x, r_cold.x)
    assert r_full.active_block_fraction[0] == 1.0
    # partial frontier: only the first vertex's block updates, rest skipped
    fr = np.zeros(algo.n, bool)
    fr[0] = True
    r_part = run_async_block_pallas(algo, bs=64, x_init=r_cold.x,
                                    sweeps_per_call=4, frontier=fr)
    np.testing.assert_array_equal(r_part.x, r_cold.x)
    assert 0.0 < r_part.active_block_fraction[0] < 1.0


def test_multisweep_empty_frontier_early_exit():
    """An empty frontier on a converged state is the cheapest possible
    serving no-op: zero blocks touched, convergence declared after one
    batch (rounds == 1), state bitwise untouched."""
    for name in ("pagerank", "sssp"):
        algo = _contract_algo(name, 1)
        r_cold = run_async_block(algo, bs=64)
        r = run_async_block_pallas(algo, bs=64, x_init=r_cold.x,
                                   sweeps_per_call=8,
                                   frontier=np.zeros(algo.n, bool))
        assert r.rounds == 1, name
        assert r.converged
        np.testing.assert_array_equal(
            r.x, np.asarray(r_cold.x, np.float32))
        assert r.active_block_fraction[0] == 0.0


def test_multisweep_frontier_shrinks_during_convergence():
    """The active_block_fraction trace must shrink as SSSP converges (the
    frontier win the bench records): the last sweep touches strictly fewer
    blocks than the first."""
    algo = _contract_algo("sssp", 1)
    r = run_async_block_pallas(algo, bs=16, sweeps_per_call=16)
    af = r.active_block_fraction
    assert af[0] == 1.0
    assert af[-1] < af[0]


def test_incremental_frontier_seeding_through_megakernel():
    """run_incremental(backend='pallas', sweeps_per_call=4): warm-start
    frontiers seeded from the delta-touched blocks must land on the cold
    fixpoint (bitwise for sssp) while skipping untouched regions."""
    from repro.engine import remake, run_incremental
    from repro.graphs.delta import random_delta

    g0 = gen.scrambled(gen.powerlaw_cluster(300, 3, seed=2), seed=3)
    gw = gen.with_random_weights(g0, seed=1)
    for name, g in (("pagerank", g0), ("sssp", gw)):
        algo_old = get_algorithm(name, g)
        delta = random_delta(g, frac_add=0.02, seed=5)
        algo_new = remake(algo_old, delta.apply(g))
        prior = run_async_block(algo_old, bs=64)
        r_batch = run_incremental(algo_new, algo_old, prior, bs=64,
                                  backend="pallas", sweeps_per_call=4,
                                  max_iters=300)
        r_cold = run_async_block(algo_new, bs=64)
        if name == "sssp":
            np.testing.assert_array_equal(r_batch.x, r_cold.x)
            # the seeded frontier must actually skip work somewhere
            assert min(r_batch.active_block_fraction) < 1.0
        else:
            np.testing.assert_allclose(r_batch.x, r_cold.x,
                                       atol=1e-3, rtol=1e-3)


def test_multisweep_knobs_rejected_where_invalid():
    algo = _contract_algo("pagerank", 1)
    with pytest.raises(ValueError):
        run_async_block(algo, bs=64, sweeps_per_call=4)  # jax backend
    with pytest.raises(ValueError):
        run_async_block(algo, bs=64, backend="pallas", sweeps_per_call=0)
    with pytest.raises(NotImplementedError):
        run_async_block(algo, bs=64, backend="pallas", sweeps_per_call=4,
                        extrapolate_every=4)
    with pytest.raises(ValueError):
        # frontier must be vertex-level bool[n]
        run_async_block(algo, bs=64, backend="pallas", sweeps_per_call=4,
                        frontier=np.zeros(3, bool))


def test_delta_metric_matches_algorithm_residuals():
    """kernels.semirings.DELTA_METRIC must agree with the residual kinds the
    algorithm constructors assign, or in-kernel convergence decisions would
    diverge from the host drivers'."""
    from repro.kernels.ops import _KERNEL_SEMIRING
    from repro.kernels.semirings import DELTA_METRIC

    g = gen.with_random_weights(gen.powerlaw_cluster(50, 3, seed=0), seed=1)
    for name in ("pagerank", "sssp", "sswp", "reachability"):
        algo = get_algorithm(name, g)
        semiring = _KERNEL_SEMIRING[(algo.semiring.reduce,
                                     algo.semiring.edge_op)]
        assert DELTA_METRIC[semiring] == algo.residual, name


def test_gs_sweep_uses_fresh_states():
    """The defining property of the fused sweep: a block's update sees
    earlier blocks' THIS-sweep values (positive cross-block edges are fresh,
    Eq. 2 at tile granularity)."""
    from repro.graphs.graph import Graph

    n, bs = 8, 2
    g = Graph(n, np.arange(n - 1, dtype=np.int32),
              np.arange(1, n, dtype=np.int32),
              np.ones(n - 1, np.float32))
    algo = get_algorithm("sssp", g, source=0)
    ops = pack_algorithm(algo, bs=bs)
    args = (ops["rowptr"], ops["tilecols"], ops["tiles"], ops["c"],
            ops["x0"], ops["fixed"])
    kw = dict(semiring=ops["semiring"], combine=ops["combine"])
    x1 = np.asarray(gs_sweep(*args, ops["x"], **kw))[:n, 0]
    # after ONE sweep: v1 from the initial source; v2 via the cross-block
    # edge 1->2 sees v1's THIS-sweep value (pure Jacobi would leave it BIG);
    # v3's edge is intra-block -> still previous-round (BIG)
    np.testing.assert_allclose(x1[:3], [0.0, 1.0, 2.0], atol=1e-5)
    assert x1[3] >= BIG / 2
    # the chain settles one block per sweep: ceil(n/bs)=4 sweeps total,
    # vs n-1=7 Jacobi rounds
    x = ops["x"]
    for _ in range(4):
        x = gs_sweep(*args, x, **kw)
    np.testing.assert_allclose(np.asarray(x)[:n, 0], np.arange(n), atol=1e-5)
