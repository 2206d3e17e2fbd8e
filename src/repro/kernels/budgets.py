"""Declared per-kernel VMEM/SMEM budgets — the contract repro-lint enforces.

Every ``pl.pallas_call`` in `repro.kernels` must have an entry here; the
static checker (`tools.check.pallas_resources`) re-derives each kernel's
VMEM/SMEM footprint from its BlockSpecs, scratch_shapes, and grid at the
representative points below and fails the build when a footprint crosses its
declared budget. A budget is at most the v5e's 16 MiB of scoped VMEM, the
limit the TPU compiler enforces; the smaller ones leave headroom to widen
``d`` or ``bs`` without renegotiating the kernel's memory story.

Footprint model (all operands are 4-byte f32/int32):

* scratch ``pltpu.VMEM`` / ``pltpu.SMEM`` shapes count at face value;
* windowed BlockSpecs (shape + index map) count twice — Pallas
  double-buffers pipelined windows;
* ``memory_space=ANY`` operands live in HBM and count zero (their VMEM cost
  is whatever scratch the kernel DMAs them into, already counted);
* broadcast temporaries the kernel body materializes (the min/max
  semirings' ``(bs, bs, d)`` intermediates in `gs_sweep` and `bsr_spmm`)
  are declared per point as ``temp_bytes`` — the checker cannot see inside
  the traced body.

Points carry every dimension name the kernel's shape expressions use
(``bs``/``d``/``nb``/``sweeps``/``nnz``/``dj``; ``n`` derives as
``nb * bs``). The main-path kernels' points sit at widths the TPU compiler
takes (bs a multiple of 128, d a multiple of 128 — `tests/test_tpu_compile`
compiles them for a described v5e), bracketing the one-chip smoke and the
widest block that still fits.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class KernelBudget:
    """Declared resource ceiling for one pallas_call wrapper."""

    vmem_limit_bytes: int
    smem_limit_bytes: int
    points: tuple[dict, ...]    # representative dims (+ optional temp_bytes)
    notes: str = ""


KiB = 1024
MiB = 1024 * 1024

# Mosaic's default scoped-VMEM limit on a TPU v5e: a lowered kernel whose
# scratch, pipelined windows and internal temporaries exceed it is refused
V5E_SCOPED_VMEM = 16 * MiB

# the min/max semirings' (bs, bs, d) broadcast-reduce, as the v5e compiler
# lays it out: it strip-mines d, so the temporary depends on bs only. Scoped
# VMEM of the whole min_plus megakernel, read from the compiler's refusal
# under a lowered limit (described v5e, d = 128 and 256): 3.87 MiB at
# bs = 128, 14.25 MiB at bs = 256; these are those totals less the scratch
# and windows counted above them
_GS_BROADCAST_TEMP = {128: int(3.5 * MiB), 256: int(13.25 * MiB)}

KERNEL_BUDGETS: dict[str, KernelBudget] = {
    "gs_multisweep_pallas": KernelBudget(
        # widest point: bs=256 min/max semirings, ~15 MiB of 16 MiB — the
        # reason bs = 512 does not compile
        vmem_limit_bytes=V5E_SCOPED_VMEM,
        smem_limit_bytes=128 * KiB,
        points=(
            # the one-chip smoke's megakernel: grid_2d(1024, 1024) in id
            # order, ~5 tiles per row-block
            {"bs": 128, "d": 128, "nb": 8192, "sweeps": 16, "nnz": 41000,
             "temp_bytes": _GS_BROADCAST_TEMP[128]},
            # the largest block the broadcast semirings compile at
            {"bs": 256, "d": 128, "nb": 4096, "sweeps": 16, "nnz": 20500,
             "temp_bytes": _GS_BROADCAST_TEMP[256]},
            # two lanes of query columns
            {"bs": 128, "d": 256, "nb": 64, "sweeps": 16, "nnz": 1024,
             "temp_bytes": _GS_BROADCAST_TEMP[128]},
        ),
        notes="scratch holds 2 gather + 2 tile buffers (double-buffered "
              "DMA), old/acc blocks, the (1, d) delta row; SMEM holds the "
              "nb dirty flags, their exported copy and the done bit. Not "
              "counted: the scalar-prefetched rowptr/tilecols/revptr/"
              "revrows (8 B per block + 8 B per tile), which share the "
              "v5e's 1 MiB of SMEM: nb = 16384 with 82k tiles compiles, "
              "nb = 20000 with 100k tiles is refused (1.16 MiB used)",
    ),
    "push_scatter_pallas": KernelBudget(
        # the push kernel streams (1, d) rows, so VMEM is independent of n,
        # m, buckets, and cap
        vmem_limit_bytes=64 * KiB,
        smem_limit_bytes=16 * KiB,
        points=(
            # the one-chip smoke's delta absorption: one query, one lane
            {"ecap": 1024, "d": 128, "buckets": 4, "cap": 64, "n": 1 << 20},
            # serving-width columns, large rounds
            {"ecap": 1024, "d": 256, "buckets": 16, "cap": 1024,
             "n": 1 << 20},
        ),
        notes="scratch holds four (1, d) residual/state rows + two (1, 1) "
              "work counters; SMEM holds the two (ecap,) edge-chunk "
              "buffers (neighbor ids + weights)",
    ),
    "bsr_spmm_pallas": KernelBudget(
        # measured: ~0.38 MiB (plus_times), ~0.64 MiB (min family w/ temp)
        vmem_limit_bytes=2 * MiB,
        smem_limit_bytes=4 * KiB,
        points=(
            # plus_times runs full-width dj = d on the MXU (no broadcast temp)
            {"bs": 128, "d": 128, "dj": 128, "nb": 64, "nnz": 512,
             "temp_bytes": 0},
            # broadcast semirings: ops.bsr_spmm narrows dj so the
            # (bs, bs, dj) intermediate stays <= 512 KiB — declare it
            {"bs": 128, "d": 64, "dj": 8, "nb": 64, "nnz": 512,
             "temp_bytes": 128 * 128 * 8 * 4},
            {"bs": 16, "d": 64, "dj": 64, "nb": 256, "nnz": 4096,
             "temp_bytes": 16 * 16 * 64 * 4},
        ),
        notes="per step: one (1, bs, bs) tile window + (bs, dj) x/out "
              "windows; min/max semirings add the declared (bs, bs, dj) "
              "broadcast temporary (see ops.bsr_spmm's dj narrowing)",
    ),
}
