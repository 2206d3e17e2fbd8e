"""Kernel microbench: gs_sweep wall-clock + flat-vs-dense layout accounting
+ sweep-batching (launch amortization) + active-frontier traces.

Two timing columns, labeled for what they are:

* ``us_per_sweep_interpret`` — the Pallas kernel under the CPU interpreter.
  Emulation: meaningful only relative to other interpret numbers (and for
  the structural quantities alongside it — nnz_blocks = gather DMAs per
  sweep, tile bytes moved — which are exact and transfer to TPU). The
  column only exists in full runs: ``--fast`` skips it, since interpreted
  sweeps dominate the smoke's wall clock while measuring nothing real.
* ``us_per_sweep_jit_cpu`` — the same block Gauss–Seidel sweep as a jitted
  pure-JAX (gather/segment-reduce) program on the CPU backend: a real
  compiled-code number on this host, the honest CPU baseline the interpret
  column must not be mistaken for.

``us_per_round_batched`` times the persistent megakernel at
``sweeps_per_call`` in {1, 4, 16} from the same cold state (early-out
disabled) and divides by the sweep count: the launch-amortization win the
sweep-batched driver buys. This is measured on a fixed small
(``N_LATENCY``-vertex) graph in *both* fast and full modes — launch
overhead is a fixed per-call cost, so it only shows in the latency-bound
serving regime where per-sweep device time is comparable to it; on the
full-size graph the interpreter's 8ms sweeps bury the ~0.3ms dispatch
saving in timing noise. ``active_block_fraction`` traces a full SSSP
convergence run with ``sweeps_per_call=16`` — the fraction of row-blocks
each sweep actually updates, which frontier skipping shrinks as regions
converge.

Methodology: one warmup call absorbs jit/interpret compilation, then every
reported time is the median of ``REPEATS >= 3`` steady-state runs (the old
single cold-timed call reported compile time, not sweep time).

Besides the per-run JSON under ``out_dir``, writes ``BENCH_kernels.json`` at
the repo root so the kernel perf trajectory is tracked across PRs; CI's
bench-smoke job asserts the flat layout's padding win AND the sweep-batching
win are recorded there.
"""
from __future__ import annotations

import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, save_json
from repro.core.gograph import gograph_order
from repro.engine import get_algorithm, harness, run_async_block
from repro.engine import jax_ops as J
from repro.graphs import generators as gen
from repro.kernels import gs_sweep
from repro.kernels.gs_sweep import gs_multisweep_pallas
from repro.kernels.ops import interpret_mode, pack_algorithm

REPEATS = 3
# bs=16 exposes the block-level skew (hub row-blocks vs tail) even on the
# small --fast graph; bs=64 is the TPU-native tile-friendly setting.
BLOCK_SIZES = (16, 64)
SWEEPS_PER_CALL = (1, 4, 16)
# fixed graph size for the launch-amortization measurement (see module
# docstring): the latency-bound serving point, identical in fast/full modes
# so the cross-PR BENCH_kernels.json numbers stay comparable
N_LATENCY = 200

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _median_us(fn) -> float:
    fn()  # warmup: first call pays jit + interpret lowering, not sweep work
    samples = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def _sweep_median_us(ops) -> float:
    args = (ops["rowptr"], ops["tilecols"], ops["tiles"], ops["c"],
            ops["x0"], ops["fixed"])
    kw = dict(semiring=ops["semiring"], combine=ops["combine"])
    return _median_us(
        lambda: gs_sweep(*args, ops["x"], **kw).block_until_ready()
    )


def _jax_sweep_median_us(algo, bs: int) -> float:
    """One jitted pure-JAX block GS sweep (the engine's own sweep body,
    compiled for CPU) — the non-emulated timing baseline."""
    be, x0, c, fixed, npad = harness.pack(algo, bs)
    nb = be.nb
    d = x0.shape[1]
    esrc, edst = jnp.asarray(be.esrc), jnp.asarray(be.edst)
    ew, emask = jnp.asarray(be.ew), jnp.asarray(be.emask)
    c_blk = jnp.asarray(c).reshape(nb, bs, d)
    fixed_blk = jnp.asarray(fixed).reshape(nb, bs, d)
    x0_blk = jnp.asarray(x0).reshape(nb, bs, d)
    sem, comb = algo.semiring, algo.combine
    ident = sem.identity

    @jax.jit
    def sweep(x):
        def block_update(i, xx):
            msgs = J.edge_op(sem.edge_op, xx[esrc[i]], ew[i])
            msgs = jnp.where(emask[i][:, None], msgs, ident)
            agg = J.segment_reduce(sem.reduce, msgs, edst[i], bs, ident)
            old = jax.lax.dynamic_slice(xx, (i * bs, 0), (bs, d))
            new = J.combine(comb, agg, c_blk[i], old, fixed_blk[i], x0_blk[i])
            return jax.lax.dynamic_update_slice(xx, new, (i * bs, 0))

        return jax.lax.fori_loop(0, nb, block_update, x)

    x_start = jnp.asarray(x0)
    return _median_us(lambda: sweep(x_start).block_until_ready())


def _batched_round_us(ops, sweeps: int, bs: int) -> float:
    """Per-sweep wall time of one ``sweeps``-deep megakernel launch, from
    the same cold state every call. eps=-1 disables the in-kernel early-out;
    frontier skipping stays armed (the real serving configuration), so the
    number is only the pure launch-amortization win if every sweep of the
    batch actually updates every block — true for cold-start pagerank, whose
    blocks keep moving bitwise far past 16 sweeps, and *asserted* below via
    the kernel's own active-block counts so a future workload change cannot
    silently turn this into a frontier benchmark."""
    nb = int(ops["rowptr"].shape[0]) - 1
    dirty = jnp.ones((nb,), jnp.int32)
    args = (ops["rowptr"], ops["tilecols"], ops["revptr"], ops["revrows"],
            dirty, ops["tiles"], ops["c"], ops["x0"], ops["fixed"])
    kw = dict(semiring=ops["semiring"], combine=ops["combine"], bs=bs,
              sweeps=sweeps, eps=-1.0, interpret=interpret_mode())

    active = np.asarray(gs_multisweep_pallas(*args, ops["x"], **kw)[2])
    assert np.all(active[:, 0] == nb), (
        f"us_per_round_batched requires full sweeps; frontier skipped blocks "
        f"(active={active[:, 0].tolist()}, nb={nb}) — pick a workload whose "
        f"blocks keep changing for the whole batch"
    )

    def call():
        out = gs_multisweep_pallas(*args, ops["x"], **kw)
        out[0].block_until_ready()

    return _median_us(call) / sweeps


def run(out_dir: str = "experiments/paper"):
    rows = []
    results = {}
    n = 200 if FAST else 2000
    g = gen.scrambled(gen.powerlaw_cluster(n, 4, seed=1), seed=5)
    rank = gograph_order(g)
    for label, graph in (("default", g), ("gograph", g.relabel(rank))):
        algo = get_algorithm("pagerank", graph)
        for bs in BLOCK_SIZES:
            ops = pack_algorithm(algo, bs=bs)
            # FlatBSRMatrix.stats() carries the dense-padded baseline's
            # accounting too (dense_tile_bytes / padding_waste), so no dense
            # repack is needed here (tests assert the two layouts' stats agree)
            stats = ops["bsr_stats"]
            # the interpret-mode sweep dominates the smoke's wall clock and
            # its timing is emulation, not signal — full runs keep the
            # column, --fast drops it (CI's assertion is presence-gated)
            us = None if FAST else _sweep_median_us(ops)
            us_jit = _jax_sweep_median_us(algo, bs)
            # steady-state VMEM per grid step: 2 double-buffered tiles + 7
            # (bs, d) state blocks (2 gathers, old, acc, c, x0, fixed) —
            # independent of k_max now
            d = int(ops["x"].shape[1])
            vmem_kb = (2 * bs * bs * 4 + 7 * bs * d * 4) / 1024
            cfg = {
                "us_per_sweep_jit_cpu": us_jit,
                "mean_dma_per_block": stats["mean_colblocks_per_rowblock"],
                "nnz_blocks": stats["nnz_blocks"],
                "dma_per_sweep": stats["nnz_blocks"],
                "k_max": stats["k_max"],
                "padding_waste_dense": stats["padding_waste"],
                "tile_bytes_flat": stats["tile_bytes"],
                "tile_bytes_dense": stats["dense_tile_bytes"],
                "tile_bytes_saved": stats["tile_bytes_saved"],
                "vmem_step_kb": vmem_kb,
            }
            if us is not None:
                cfg["us_per_sweep_interpret"] = us
            results[f"{label}_bs{bs}"] = cfg
            rows.append((f"kernel/gs_sweep/{label}_bs{bs}",
                         us if us is not None else us_jit,
                         f"jit_cpu={us_jit:.0f}us "
                         f"dma/blk={stats['mean_colblocks_per_rowblock']:.1f} "
                         f"waste={stats['padding_waste']:.2f} "
                         f"vmem={vmem_kb:.0f}KB"))

    # --- sweep batching: per-round cost vs sweeps_per_call (gograph, bs=64)
    # on the fixed latency-bound graph (launch overhead is per-call, so the
    # amortization win is a property of small/fast sweeps — see docstring)
    bs_b = 64
    g_lat = gen.scrambled(gen.powerlaw_cluster(N_LATENCY, 4, seed=1), seed=5)
    algo_b = get_algorithm("pagerank", g_lat.relabel(gograph_order(g_lat)))
    ops_b = pack_algorithm(algo_b, bs=bs_b)
    batched = {}
    for sweeps in SWEEPS_PER_CALL:
        batched[str(sweeps)] = _batched_round_us(ops_b, sweeps, bs_b)
        rows.append((f"kernel/gs_multisweep/round_batched{sweeps}",
                     batched[str(sweeps)],
                     f"megakernel us/round (interpret, n={N_LATENCY})"))
    results["batched_bs64"] = {"n": N_LATENCY,
                               "us_per_round_batched": batched}

    # --- active frontier: full SSSP convergence with sweeps_per_call=16;
    # bs=16 keeps enough row-blocks for a meaningful fraction on --fast
    gw = gen.with_random_weights(g.relabel(rank), seed=3)
    res_f = run_async_block(get_algorithm("sssp", gw), bs=16,
                            backend="pallas", sweeps_per_call=16)
    afrac = [float(a) for a in np.asarray(res_f.active_block_fraction)]
    results["frontier_sssp_bs16"] = {
        "rounds": res_f.rounds,
        "active_block_fraction": afrac,
        "mean_active_fraction": float(np.mean(afrac)) if afrac else 1.0,
    }
    rows.append(("kernel/gs_multisweep/frontier_sssp", 0.0,
                 f"active frac first={afrac[0]:.2f} last={afrac[-1]:.2f} "
                 f"rounds={res_f.rounds}"))

    save_json(out_dir, "kernel_bench", results)
    payload = {
        "graph": {"kind": "powerlaw_cluster", "n": n, "fast": FAST},
        "configs": {k: v for k, v in results.items()
                    if k.startswith(("default_", "gograph_"))},
        "batched": results["batched_bs64"],
        "frontier": results["frontier_sssp_bs16"],
        "max_padding_waste_dense": max(
            v["padding_waste_dense"] for k, v in results.items()
            if k.startswith(("default_", "gograph_"))
        ),
        "total_tile_bytes_saved": sum(
            v["tile_bytes_saved"] for k, v in results.items()
            if k.startswith(("default_", "gograph_"))
        ),
    }
    with open(os.path.join(_REPO_ROOT, "BENCH_kernels.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return rows
