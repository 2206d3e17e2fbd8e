"""Block-sparse (BSR) x dense SpMM Pallas kernel — one synchronous round.

Walks the ragged flat layout (`graphs.blocked.FlatBSRMatrix`): the grid is
``(d // dj, nnz_blocks)`` — one step per *real* tile, not per ``(row, k_max)``
slot — with ``rowptr`` / ``tilerows`` / ``tilecols`` scalar-prefetched so the
BlockSpec index maps can DMA exactly the source-state tile and output block
each adjacency tile needs. Tiles are sorted by destination row, so all grid
steps writing one output block are consecutive: the block stays resident in
VMEM, is initialized at its row's first tile (``t == rowptr[row]``), and is
flushed when the row changes. Work and data movement are O(nnz_blocks); the
old dense-padded layout ran ``nb * k_max`` steps, paying the densest
(hub) row-block's tile count in every row.

Semirings (identities in kernels.semirings.ACC_IDENTITY):
  plus_times — y[i] = sum_t  tiles[t] @ x[tilecols[t]]            (MXU matmuls)
  min_plus   — y[i] = min_t  min_c (tiles[t][r,c] + x[tilecols[t]][c, :])
  max_min    — y[i] = max_t  max_c min(tiles[t][r,c], x[..][c, :])  (SSWP)
  max_times  — y[i] = max_t  max_c (tiles[t][r,c] * x[..][c, :])  (reachability;
               nonnegative states — absent in-tile edges contribute 0 products)

Padding contract: there are no padding tiles. Absent edges *inside* a real
tile carry the semiring's absorbing fill (0 / +BIG / -BIG / 0); row-blocks
with no tiles at all never appear in the grid, so the wrapper writes the
reduce identity into their output rows afterwards.

VMEM budget per grid step: tile (bs x bs) + x block (bs x dj) + out block
(bs x dj), all fp32 — with bs=128, dj=128 that's 192 KiB, comfortably inside
the ~16 MiB v5e VMEM even with double buffering. min_plus/max_* materialize a
(bs, bs, dj) broadcast, so they are built with a narrower dj (see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.semirings import ACC_IDENTITY


def _make_kernel(semiring: str):
    def kernel(rowptr_ref, tilerows_ref, tilecols_ref, tiles_ref, x_ref, o_ref):
        t = pl.program_id(1)
        row = tilerows_ref[t]

        @pl.when(t == rowptr_ref[row])
        def _init():
            o_ref[...] = jnp.full_like(o_ref, ACC_IDENTITY[semiring])

        tile = tiles_ref[0]
        if semiring == "plus_times":
            o_ref[...] += jnp.dot(
                tile, x_ref[...], preferred_element_type=o_ref.dtype
            )
        elif semiring == "min_plus":
            part = jnp.min(tile[:, :, None] + x_ref[...][None, :, :], axis=1)
            o_ref[...] = jnp.minimum(o_ref[...], part)
        elif semiring == "max_min":
            part = jnp.max(
                jnp.minimum(tile[:, :, None], x_ref[...][None, :, :]), axis=1
            )
            o_ref[...] = jnp.maximum(o_ref[...], part)
        elif semiring == "max_times":
            part = jnp.max(tile[:, :, None] * x_ref[...][None, :, :], axis=1)
            o_ref[...] = jnp.maximum(o_ref[...], part)
        else:
            raise ValueError(semiring)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("semiring", "bs", "dj", "interpret")
)
def bsr_spmm_pallas(
    rowptr: jnp.ndarray,    # int32[nb + 1]
    tilerows: jnp.ndarray,  # int32[nnz_blocks]
    tilecols: jnp.ndarray,  # int32[nnz_blocks]
    tiles: jnp.ndarray,     # f32[nnz_blocks, bs, bs]
    x: jnp.ndarray,         # f32[nb*bs, d]
    *,
    semiring: str = "plus_times",
    bs: int,
    dj: int,
    interpret: bool,
) -> jnp.ndarray:
    if semiring not in ACC_IDENTITY:
        raise NotImplementedError(
            f"bsr_spmm_pallas: unknown semiring {semiring!r}; "
            f"supported: {sorted(ACC_IDENTITY)}"
        )
    nb = rowptr.shape[0] - 1
    nnz = tiles.shape[0]
    n, d = x.shape
    assert d % dj == 0 and n == nb * bs
    assert tilerows.shape[0] == tilecols.shape[0] == nnz
    ident = jnp.float32(ACC_IDENTITY[semiring])
    # empty row-blocks own no grid steps, so the kernel never writes their
    # output rows: overwrite them with the reduce identity afterwards. This
    # also covers the empty-graph pack (one never-referenced pad tile with
    # rowptr all zero): every row is empty, so every row is overwritten.
    empty_row = jnp.repeat(rowptr[1:] == rowptr[:-1], bs)[:, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // dj, nnz),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda j, t, rp, tr, tc: (t, 0, 0)),
            pl.BlockSpec((bs, dj), lambda j, t, rp, tr, tc: (tc[t], j)),
        ],
        out_specs=pl.BlockSpec((bs, dj), lambda j, t, rp, tr, tc: (tr[t], j)),
    )
    y = pl.pallas_call(
        _make_kernel(semiring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(rowptr, tilerows, tilecols, tiles, x)
    return jnp.where(empty_row, ident.astype(x.dtype), y)
