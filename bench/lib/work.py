"""The work a sweep needs, counted from the graph, and the device's peaks.

A sweep of an iterative graph algorithm reads every edge once (its source
id and weight, 4 + 4 bytes) and reads and writes the state of every vertex
in every useful column (4 bytes each way), and performs one multiply and
one add per edge and column. The count is taken from the graph and the
useful columns alone, never from the tiles or padding of a layout, so it
reads the same whatever layout the program packs.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def sweep_work(n: int, m: int, sweeps: int, column_sweeps: int) -> tuple[int, int]:
    """``(bytes, flops)`` of ``sweeps`` sweeps over a graph of ``n``
    vertices and ``m`` edges that together updated ``column_sweeps``
    (sweeps x useful columns) state columns."""
    nbytes = 8 * m * sweeps + 2 * 4 * n * column_sweeps
    flops = 2 * m * column_sweeps
    return nbytes, flops


def least_time_s(nbytes: int, flops: int, pk: dict) -> float:
    """The least time the chip could take: the larger of the bytes over peak
    HBM bandwidth and the operations over peak FLOP/s."""
    return max(nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_per_s"])


def roofline_share(n: int, m: int, sweeps: int, column_sweeps: int,
                   device_s: float, pk: dict):
    """Percent of its roofline the sweep program reached: least time over
    the device time it took; None where it did not run."""
    if sweeps <= 0 or device_s <= 0:
        return None
    nbytes, flops = sweep_work(n, m, sweeps, column_sweeps)
    return 100.0 * least_time_s(nbytes, flops, pk) / device_s
