"""Block packing of a (reordered) graph for the TPU engines and kernels.

The TPU adaptation of the paper's asynchronous mode works on contiguous
*blocks* of the processing order (DESIGN.md §3). Three packings are built
here:

* :class:`BlockedInEdges` — per-destination-block padded in-edge lists, used by
  the pure-JAX block Gauss–Seidel engine (`engine/async_block.py`). Gather/
  segment-reduce friendly.

* :class:`FlatBSRMatrix` — the **ragged flat** block-sparse layout the Pallas
  kernels (`kernels/gs_sweep.py`, `kernels/bsr_spmm.py`) walk: one dense
  ``(bs, bs)`` tile per *nonzero* block of the in-adjacency matrix, stored
  contiguously in CSR-of-tiles form (``tiles[nnz_blocks, bs, bs]`` +
  scalar-prefetched ``rowptr[nb+1]`` / ``tilecols[nnz_blocks]``). Memory, DMA
  count, and semiring FLOPs are all ``O(nnz_blocks)`` — the hub row-blocks
  that GoGraph's HD phase concentrates (paper §IV-A) are paid for once, in
  their own row, not replicated into every row's padding. The engines never
  build its tiles on the host: `tile_skeleton` gives the tile structure and
  each arc's position, and `kernels.ops.pack_algorithm` scatters the weights
  into the tiles on the device. `pack_bsr_flat` is the host packer, kept as
  the reference.

* :class:`BSRMatrix` — the legacy *dense-padded* BSR layout
  (``tiles[nb, k_max, bs, bs]``), kept as the comparison baseline: every
  row-block pads to the global ``k_max``, so on a powerlaw graph the densest
  (hub) row-block sets the cost of all of them. ``stats()['padding_waste']``
  reports exactly how much of the tile memory that padding is.

All packings order edges the same way so engines agree bit-for-bit in tests.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.graphs.graph import Graph


def num_blocks(n: int, bs: int) -> int:
    return (n + bs - 1) // bs


def padded_n(n: int, bs: int) -> int:
    return num_blocks(n, bs) * bs


@dataclasses.dataclass
class BlockedInEdges:
    """Padded per-destination-block in-edge lists.

    For destination block i, edge slot j:
      esrc[i, j]   global source vertex id (0 for pads)
      edst[i, j]   destination vertex id *local to the block* (0 for pads)
      ew[i, j]     edge weight (0 for pads; pads also masked)
      emask[i, j]  True for real edges
    """

    bs: int
    n: int  # real vertex count (before padding)
    esrc: np.ndarray
    edst: np.ndarray
    ew: np.ndarray
    emask: np.ndarray

    @property
    def nb(self) -> int:
        return self.esrc.shape[0]

    @property
    def e_max(self) -> int:
        return self.esrc.shape[1]


def pack_in_edges(g: Graph, bs: int) -> BlockedInEdges:
    nb = num_blocks(g.n, bs)
    blk = g.dst // bs
    order = np.argsort(blk, kind="stable")
    src_s, dst_s, w_s = g.src[order], g.dst[order], g.weights[order]
    blk_s = blk[order]
    counts = np.bincount(blk, minlength=nb)
    e_max = max(1, int(counts.max()) if len(counts) else 1)
    esrc = np.zeros((nb, e_max), dtype=np.int32)
    edst = np.zeros((nb, e_max), dtype=np.int32)
    ew = np.zeros((nb, e_max), dtype=np.float32)
    emask = np.zeros((nb, e_max), dtype=bool)
    offsets = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    # slot of edge e within its destination block = position after the stable
    # sort minus the block's first position: one scatter per array, same
    # (block, slot) <- sorted-edge assignment the per-block loop produced.
    slot = np.arange(len(blk_s), dtype=np.int64) - offsets[blk_s]
    esrc[blk_s, slot] = src_s
    edst[blk_s, slot] = dst_s - blk_s * bs
    ew[blk_s, slot] = w_s
    emask[blk_s, slot] = True
    return BlockedInEdges(bs=bs, n=g.n, esrc=esrc, edst=edst, ew=ew, emask=emask)


@dataclasses.dataclass
class TileStructure:
    """The nonzero tiles of the in-adjacency, without their payloads: a CSR
    over (bs, bs) tiles. For destination block i, tiles
    ``rowptr[i]..rowptr[i+1]`` are its nonzero column-blocks in ascending
    column order; ``tilerows`` is derived (``repeat`` of the rowptr runs)
    and carried so `bsr_spmm` can map grid step -> output block without a
    search. O(nnz_blocks) memory: what `tile_skeleton` builds on the host
    while the tiles themselves are built on the device.

    Empty graphs keep one never-referenced entry (``rowptr`` all zero) so
    downstream device buffers are never zero-sized; ``nnz_blocks`` reads the
    real count from ``rowptr[-1]``.
    """

    bs: int
    n: int
    rowptr: np.ndarray    # int32[nb + 1]
    tilecols: np.ndarray  # int32[max(nnz_blocks, 1)]
    tilerows: np.ndarray  # int32[max(nnz_blocks, 1)]  (derived)

    @property
    def nb(self) -> int:
        return self.rowptr.shape[0] - 1

    @property
    def nnz_blocks(self) -> int:
        return int(self.rowptr[-1])

    @property
    def k_max(self) -> int:
        """Densest row-block — what the dense layout pads *every* row to."""
        if self.nb == 0:
            return 1
        return max(1, int(np.diff(self.rowptr).max()))

    def reverse_deps(self) -> tuple[np.ndarray, np.ndarray]:
        """Block reverse-dependency CSR: for *source* block j,
        ``revrows[revptr[j]:revptr[j+1]]`` lists the destination blocks that
        own a tile reading j — the set of blocks whose next update can change
        when j's state moves. This is what the frontier megakernel
        (`kernels.gs_sweep`) walks to re-mark dependents dirty."""
        return block_reverse_deps(self.rowptr, self.tilecols)

    def stats(self) -> dict:
        """Locality proxies (the TPU analogue of the paper's cache-miss study)
        plus the layout win over the dense-padded baseline."""
        per_row = np.diff(self.rowptr)
        nnz = self.nnz_blocks
        k_max = self.k_max
        diag = int(
            np.count_nonzero(
                self.tilecols[: nnz] == self.tilerows[: nnz]
            )
        )
        tile_bytes = nnz * self.bs * self.bs * 4
        dense_tile_bytes = self.nb * k_max * self.bs * self.bs * 4
        return {
            "nb": self.nb,
            "k_max": k_max,
            "nnz_blocks": nnz,
            "mean_colblocks_per_rowblock": float(per_row.mean()) if self.nb else 0.0,
            "max_colblocks_per_rowblock": int(per_row.max()) if self.nb else 0,
            "diag_fraction": diag / max(1, self.nb),
            "tile_bytes": tile_bytes,
            "dense_tile_bytes": dense_tile_bytes,
            "tile_bytes_saved": dense_tile_bytes - tile_bytes,
            "padding_waste": 1.0 - nnz / max(1, self.nb * k_max),
        }


@dataclasses.dataclass
class FlatBSRMatrix(TileStructure):
    """Ragged flat BSR of the in-adjacency: a `TileStructure` with its
    dense tiles.

        y_blk[i] = REDUCE_{t in [rowptr[i], rowptr[i+1])} tiles[t] (x) x_blk[tilecols[t]]

    ``tiles[t]`` has layout (dst_local, src_local). Absent edges *inside* a
    nonzero tile carry ``fill`` — the semiring's absorbing element (0 for
    plus_times, +BIG for min_plus, -BIG for max_min) — but there are no
    padding *tiles*: memory and per-sweep DMAs are O(nnz_blocks), not
    O(nb * k_max). The empty graph's one never-referenced tile is all
    ``fill``.
    """

    tiles: np.ndarray     # float32[max(nnz_blocks, 1), bs, bs]
    fill: float


@dataclasses.dataclass
class BSRMatrix:
    """Dense-padded block-sparse rows (legacy layout, benchmark baseline).

    tiles[i, k] has layout (dst_local, src_local): row r of tile (i,k) holds
    the weights of edges into vertex i*bs+r from vertices cols[i,k]*bs + c.
    Padding tiles point at column-block 0 with `fill` values so semiring
    reduction ignores them (0 for plus_times, +inf for min_plus). Every
    row-block pays for the global k_max; `stats()['padding_waste']` is the
    fraction of tile memory that padding is.
    """

    bs: int
    n: int
    cols: np.ndarray      # int32[nb, k_max]
    colmask: np.ndarray   # bool[nb, k_max]
    tiles: np.ndarray     # float32[nb, k_max, bs, bs]
    fill: float

    @property
    def nb(self) -> int:
        return self.cols.shape[0]

    @property
    def k_max(self) -> int:
        return self.cols.shape[1]

    def stats(self) -> dict:
        """Locality proxies (the TPU analogue of the paper's cache-miss study)."""
        nnz_blocks = int(self.colmask.sum())
        per_row = self.colmask.sum(axis=1)
        diag = int(np.count_nonzero(
            np.any((self.cols == np.arange(self.nb)[:, None]) & self.colmask,
                   axis=1)
        ))
        return {
            "nb": self.nb,
            "k_max": self.k_max,
            "nnz_blocks": nnz_blocks,
            "mean_colblocks_per_rowblock": float(per_row.mean()) if self.nb else 0.0,
            "max_colblocks_per_rowblock": int(per_row.max()) if self.nb else 0,
            "diag_fraction": diag / max(1, self.nb),
            "tile_bytes": int(self.tiles.nbytes),
            "padding_waste": 1.0 - nnz_blocks / max(1, self.nb * self.k_max),
        }


def _sorted_tile_edges(g: Graph, bs: int):
    """Edges sorted by (dst block, src block); returns the per-tile grouping
    shared by the dense and flat packers so both layouts hold bitwise-identical
    tiles."""
    nb = num_blocks(g.n, bs)
    bi = (g.dst // bs).astype(np.int64)  # row (dst) block
    bk = (g.src // bs).astype(np.int64)  # col (src) block
    key = bi * nb + bk
    order = np.argsort(key, kind="stable")
    src_s, dst_s, w_s = g.src[order], g.dst[order], g.weights[order]
    key_s = key[order]
    uniq, tile_of_edge = np.unique(key_s, return_inverse=True)
    rows = (uniq // nb).astype(np.int64)
    cols_of = (uniq % nb).astype(np.int64)
    return nb, src_s, dst_s, w_s, tile_of_edge, rows, cols_of


def pack_bsr(g: Graph, bs: int, fill: float = 0.0) -> BSRMatrix:
    nb, src_s, dst_s, w_s, tile_of_edge, rows, cols_of = _sorted_tile_edges(g, bs)
    per_row = np.bincount(rows, minlength=nb)
    k_max = max(1, int(per_row.max()) if nb else 1)
    cols = np.zeros((nb, k_max), dtype=np.int32)
    colmask = np.zeros((nb, k_max), dtype=bool)
    tiles = np.full((nb, k_max, bs, bs), fill, dtype=np.float32)
    # tiles arrive sorted by (row, col), so a tile's k-slot is its index minus
    # its row's first tile index — the cumulative-count form of the old
    # per-tile `slot[i]++` bookkeeping, as scatters.
    row_start = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(per_row, out=row_start[1:])
    slot = np.arange(len(rows), dtype=np.int64) - row_start[rows]
    cols[rows, slot] = cols_of
    colmask[rows, slot] = True
    er = rows[tile_of_edge]       # per-edge destination block
    ec = cols_of[tile_of_edge]    # per-edge source block
    tiles[er, slot[tile_of_edge], dst_s - er * bs, src_s - ec * bs] = w_s
    return BSRMatrix(bs=bs, n=g.n, cols=cols, colmask=colmask, tiles=tiles, fill=fill)


def pack_bsr_flat(g: Graph, bs: int, fill: float = 0.0) -> FlatBSRMatrix:
    """Pack the in-adjacency into the ragged flat layout the kernels walk.

    Tile memory is ``nnz_blocks * bs * bs * 4`` bytes — proportional to the
    graph's real block structure, not to ``nb * k_max``.
    """
    nb, src_s, dst_s, w_s, tile_of_edge, rows, cols_of = _sorted_tile_edges(g, bs)
    nnz = len(rows)
    per_row = np.bincount(rows, minlength=nb)
    rowptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(per_row, out=rowptr[1:])
    tiles = np.full((max(1, nnz), bs, bs), fill, dtype=np.float32)
    er = rows[tile_of_edge]
    ec = cols_of[tile_of_edge]
    tiles[tile_of_edge, dst_s - er * bs, src_s - ec * bs] = w_s
    tilecols = cols_of.astype(np.int32) if nnz else np.zeros(1, np.int32)
    tilerows = rows.astype(np.int32) if nnz else np.zeros(1, np.int32)
    return FlatBSRMatrix(
        bs=bs, n=g.n, rowptr=rowptr.astype(np.int32), tilecols=tilecols,
        tilerows=tilerows, tiles=tiles, fill=fill,
    )


def tile_skeleton(
    g: Graph, bs: int
) -> tuple[TileStructure, np.ndarray, np.ndarray, np.ndarray]:
    """The host half of `pack_bsr_flat`, from one sort: the tile structure
    and, per arc that lands in the tiles, its ``(tile, offset)`` position —
    ``tiles[tile].ravel()[offset] = w`` rebuilds ``pack_bsr_flat``'s tiles
    (`kernels.ops` does that scatter on the device).

    One int64 key per arc, ``((dst//bs)*nb + src//bs) * bs*bs + (dst%bs)*bs
    + src%bs`` (below ``npad**2``), sorted stably, is tile-major, so the
    tile boundaries give the structure. Repeated ``(dst, src)`` arcs are
    adjacent and in input order; the last of each run is kept, which is
    `pack_bsr_flat`'s last-write-wins, and leaves the positions unique and
    ascending. Returns ``(structure, tile int32, offset int32, w float32)``.
    """
    nb = num_blocks(g.n, bs)
    bb = bs * bs
    bi, ri = np.divmod(g.dst, bs)
    bk, ci = np.divmod(g.src, bs)
    key = bi.astype(np.int64)
    key *= nb
    key += bk
    key *= bb
    ri *= bs
    ri += ci
    key += ri
    order = np.argsort(key, kind="stable")
    key = key[order]
    last = np.ones(len(key), bool)
    last[:-1] = key[1:] != key[:-1]
    key = key[last]
    w = g.weights[order[last]].astype(np.float32)
    tkey = key // bb
    first = np.ones(len(tkey), bool)
    first[1:] = tkey[1:] != tkey[:-1]
    tile = np.cumsum(first, dtype=np.int32)
    tile -= 1
    utk = tkey[first]
    rows = (utk // nb).astype(np.int32)
    rowptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=nb), out=rowptr[1:])
    nnz = len(utk)
    structure = TileStructure(
        bs=bs, n=g.n, rowptr=rowptr.astype(np.int32),
        tilecols=(utk % nb).astype(np.int32) if nnz else np.zeros(1, np.int32),
        tilerows=rows if nnz else np.zeros(1, np.int32),
    )
    return structure, tile, (key % bb).astype(np.int32), w


def block_reverse_deps(
    rowptr: np.ndarray, tilecols: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """CSC of the tile structure: ``(revptr[nb+1], revrows)`` where source
    (column) block j's dependents — the destination (row) blocks holding a
    tile that reads j — are ``revrows[revptr[j]:revptr[j+1]]``, in ascending
    row order. O(nnz_blocks) memory; the empty structure keeps one
    never-referenced zero entry so device buffers are never zero-sized
    (mirrors `FlatBSRMatrix.tilecols`)."""
    rowptr = np.asarray(rowptr)
    nb = len(rowptr) - 1
    nnz = int(rowptr[-1])
    cols = np.asarray(tilecols)[:nnz]
    rows = np.repeat(np.arange(nb, dtype=np.int32), np.diff(rowptr))
    order = np.argsort(cols, kind="stable")
    revrows = rows[order].astype(np.int32) if nnz else np.zeros(1, np.int32)
    revptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(np.bincount(cols, minlength=nb), out=revptr[1:])
    return revptr.astype(np.int32), revrows


def block_dependency_structure(
    src: np.ndarray, dst: np.ndarray, n: int, bs: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The nonzero block structure only — ``(rowptr, tilerows, tilecols)``
    over unique (dst block, src block) pairs, no tile payloads. This is the
    O(nnz_blocks) skeleton the priority scheduler propagates deltas over
    (``prio[tilerows] += delta[tilecols]``) instead of a dense (nb, nb)
    indicator matmul."""
    nb = num_blocks(n, bs)
    key = (np.asarray(dst, np.int64) // bs) * nb + (np.asarray(src, np.int64) // bs)
    uniq = np.unique(key)
    rows = (uniq // nb).astype(np.int32)
    cols = (uniq % nb).astype(np.int32)
    rowptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=nb), out=rowptr[1:])
    return rowptr.astype(np.int32), rows, cols


def frontier_blocks(frontier, n: int, bs: int) -> np.ndarray:
    """Pack a vertex-level dirty mask into the per-row-block bitmap the
    megakernel's frontier consumes: block i is dirty iff any of its vertices
    is. ``frontier=None`` (cold start / no self-consistency claim) marks
    every block dirty — the only always-safe default, since a clean block is
    a *contract* that its current state already satisfies its update
    equation."""
    nb = num_blocks(n, bs)
    if frontier is None:
        return np.ones(nb, np.int32)
    f = np.asarray(frontier)
    if f.shape != (n,):
        raise ValueError(
            f"frontier must be a vertex-level mask of shape ({n},), got {f.shape}"
        )
    fp = np.zeros(nb * bs, bool)
    fp[:n] = f != 0
    return fp.reshape(nb, bs).any(axis=1).astype(np.int32)


def pad_state(x: np.ndarray, bs: int, fill=0.0) -> np.ndarray:
    """Pad a per-vertex state array (n, ...) up to a whole number of blocks.

    This is the one padding primitive of the shared pack path
    (`engine.harness.pack`): batched (n, d) state matrices pad along axis 0
    only, and ``fill`` must be the semiring-appropriate value — the reduce
    identity for states, the combine-appropriate fill for constants, ``True``
    for ``fixed`` masks (padding vertices are pinned so they never move).
    """
    n = x.shape[0]
    np_ = padded_n(n, bs)
    if np_ == n:
        return x.copy()
    pad_width = [(0, np_ - n)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_width, constant_values=fill)
