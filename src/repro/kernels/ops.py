"""jit'd public wrappers for the Pallas kernels.

:func:`interpret_mode` is the one place that decides whether the kernels
lower for the TPU or run in the Pallas interpreter; every engine and wrapper
passes its answer through. The wrappers also provide `pack_algorithm`, which
turns an `AlgoInstance` (with its transformed edge weights) into kernel-ready
**ragged flat BSR** operands (`graphs.blocked.FlatBSRMatrix`:
tiles[nnz_blocks, bs, bs] + rowptr + tilecols) with lane-padded state
columns, and `run_async_block_pallas`, a full async engine whose per-sweep
work is the fused gs_sweep kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.algorithms import AlgoInstance
from repro.engine.convergence import RunResult
from repro.graphs.blocked import pack_bsr_flat, pad_state, padded_n
from repro.graphs.graph import Graph
from repro.kernels.bsr_spmm import bsr_spmm_pallas
from repro.kernels.gs_sweep import gs_sweep_pallas
from repro.kernels.semirings import TILE_FILL

# a vector register's lane count: the lowered kernels' row DMAs move whole
# lanes, so state widths and block sizes are multiples of it on the chip
LANES = 128


def interpret_mode() -> bool:
    """True when the Pallas kernels must run in the interpreter.

    A TPU lowers them (False); the CPU, where the tests run, interprets them
    (True). Any other backend can do neither, and is refused rather than
    silently interpreted.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas kernels lower for a TPU or run interpreted on the CPU; "
        f"the {backend!r} backend has neither"
    )


def lane_width(d: int) -> int:
    """``d`` state columns rounded up to a whole number of lanes."""
    return -(-d // LANES) * LANES


def pad_lanes(x: np.ndarray, fill) -> np.ndarray:
    """Pad an (n, d) state matrix with ``fill`` columns to `lane_width` (d).

    The padding columns are inert when ``fill`` pins them: the packers pad
    ``fixed`` with 1 and ``x0`` with the reduce identity, so a padding
    column never moves, never reports a residual, and never feeds a real
    column (columns are independent). Callers slice them off every result.
    """
    d = x.shape[1]
    return np.pad(x, [(0, 0), (0, lane_width(d) - d)], constant_values=fill)


def bsr_spmm(rowptr, tilerows, tilecols, tiles, x, *, semiring="plus_times",
             dj=None):
    bs = tiles.shape[-1]
    d = x.shape[1]
    if dj is None:
        # the broadcast semirings materialize (bs, bs, dj); keep within ~2 MiB
        dj = d if semiring == "plus_times" else max(
            1, min(d, (512 * 1024) // (bs * bs * 4))
        )
        while d % dj:
            dj -= 1
    return bsr_spmm_pallas(
        rowptr, tilerows, tilecols, tiles, x, semiring=semiring, bs=bs, dj=dj,
        interpret=interpret_mode(),
    )


def gs_sweep(rowptr, tilecols, tiles, c, x0, fixed, x, *,
             semiring="plus_times", combine="replace"):
    bs = tiles.shape[-1]
    return gs_sweep_pallas(
        rowptr, tilecols, tiles, c, x0, fixed, x, semiring=semiring,
        combine=combine, bs=bs, interpret=interpret_mode(),
    )


# ---------------------------------------------------------------------------
# AlgoInstance -> kernel operands
# ---------------------------------------------------------------------------

# (reduce, edge_op) -> kernel semiring; the in-tile fill for absent edges is
# the shared kernels.semirings.TILE_FILL table (max_times relies on states
# being nonnegative: a 0-weight product is then never above a real max_old
# combine's old/c floor).
_KERNEL_SEMIRING = {
    ("sum", "mul"): "plus_times",
    ("min", "add"): "min_plus",
    ("max", "min"): "max_min",
    ("max", "mul"): "max_times",
}


def pack_algorithm(algo: AlgoInstance, bs: int, d: int | None = None) -> dict:
    """Pack an algorithm's graph + vectors into flat-BSR kernel operands.

    The state is (n_padded, lane_width(d)). ``d`` defaults to the
    algorithm's own batch width ``algo.d`` (batched constructors carry real
    per-column vectors); a larger ``d`` broadcasts a scalar
    (``algo.d == 1``) instance across the batch — the kernel-bench path for
    filling TPU lanes with copies. Columns past ``d`` are inert padding
    (`pad_lanes`); ``ops["d"]`` is the real width.
    """
    key = (algo.semiring.reduce, algo.semiring.edge_op)
    if key not in _KERNEL_SEMIRING:
        raise NotImplementedError(
            f"no kernel semiring for reduce={key[0]!r} edge_op={key[1]!r}; "
            f"supported: {sorted(_KERNEL_SEMIRING)}"
        )
    semiring = _KERNEL_SEMIRING[key]
    g = Graph(algo.n, algo.src, algo.dst, algo.w)
    bsr = pack_bsr_flat(g, bs, fill=TILE_FILL[semiring])
    npad = padded_n(algo.n, bs)
    d = algo.d if d is None else d
    if d != algo.d and algo.d != 1:
        raise ValueError(f"cannot broadcast a d={algo.d} instance to d={d}")

    # same padding primitive + fill rules as engine.harness.pack
    def padm(a, fillv):
        out = pad_state(np.asarray(a, np.float32), bs, fill=fillv)
        if d != algo.d:
            out = np.repeat(out, d, axis=1)
        return pad_lanes(out, fillv)

    ident = algo.semiring.identity
    x0pad = padm(algo.x0, ident)
    revptr, revrows = bsr.reverse_deps()
    return {
        "rowptr": jnp.asarray(bsr.rowptr),
        "tilecols": jnp.asarray(bsr.tilecols),
        "tilerows": jnp.asarray(bsr.tilerows),
        "revptr": jnp.asarray(revptr),
        "revrows": jnp.asarray(revrows),
        "tiles": jnp.asarray(bsr.tiles),
        "c": jnp.asarray(padm(algo.c, algo.c_pad_fill)),
        "x0": jnp.asarray(x0pad),
        "x0_host": x0pad,  # host copy kept so warm-starts never read back x0
        "fixed": jnp.asarray(padm(algo.fixed, 1.0)),  # pads pinned
        "x": jnp.asarray(x0pad.copy()),
        "semiring": semiring,
        "combine": algo.combine,
        "bsr_stats": bsr.stats(),
        "npad": npad,
        "d": d,
    }


def run_async_block_pallas(
    algo: AlgoInstance, bs: int = 128, max_iters: int = 500,
    x_init: np.ndarray | None = None, sweeps_per_call: int = 1,
    frontier: np.ndarray | None = None,
) -> RunResult:
    """Async engine with the fused gs_sweep kernel doing each sweep.

    Back-compat shim: the convergence loop now lives in the engine layer —
    this is ``run_async_block(algo, backend="pallas")`` with the pallas
    backend's default ``bs``. ``sweeps_per_call > 1`` batches that many sweeps
    into one persistent megakernel launch (in-kernel convergence +
    active-frontier block skipping); ``frontier`` optionally seeds the dirty
    bitmap from a vertex-level bool[n] mask (see `engine.async_block`).
    """
    from repro.engine.async_block import _run_async_block_pallas

    return _run_async_block_pallas(
        algo, bs, max_iters, 1, x_init,
        sweeps_per_call=sweeps_per_call, frontier=frontier,
    )
