"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

Each reader gets ``env``: ``record`` (what the driver recorded in the
window), ``trace`` (the reduced profiler trace), ``peaks`` (the device's
row of ``bench/peaks.json``) and ``config`` (the cell's configuration).
A reader that finds nothing to read returns None and the metric is left
out of the line; a share of a roofline is never reported as 0 for want of
data.
"""
from __future__ import annotations

import numpy as np

from lib import trace as trace_mod
from lib.work import roofline_share


def idle_share(env):
    """Percent of the traced window with no operation on the device."""
    tr = env["trace"]
    lo, hi = tr.window()
    if hi <= lo or not tr.ops:
        return None
    return 100.0 * (1.0 - trace_mod.busy_s(tr) / (hi - lo))


def solves_in_window(env) -> list:
    return [s for s in env["record"].get("solves", []) if s["in_window"]]


def sweep_roofline(env, sweeps: int, column_sweeps: int):
    """Percent of its roofline the configuration's sweep program reached
    over the traced window (see ``lib.work``)."""
    rec = env["record"]
    device_s = trace_mod.program_s(env["trace"], env["config"]["sweep_program"])
    return roofline_share(rec["n"], rec["m"], sweeps, column_sweeps,
                          device_s, env["peaks"])


def analytics_roofline(env):
    # every solve the window started ran inside the trace, to its end
    sweeps = sum(s["rounds"] for s in env["record"].get("solves", []))
    return sweep_roofline(env, sweeps, sweeps * env["record"]["columns"])


def serve_roofline(env):
    rec = env["record"]
    return sweep_roofline(env, rec.get("rounds", 0), rec.get("round_slots", 0))


def batch_occupancy(env):
    """Percent of the slot-rounds of the window's batches that held a query."""
    rec = env["record"]
    if not rec.get("rounds"):
        return None
    return 100.0 * rec["round_slots"] / (rec["rounds"] * rec["slots"])


def ran(env) -> list:
    """The window's tickets that ran on the engine (not cache hits)."""
    return [t for t in env["record"].get("tickets", []) if not t["from_cache"]]


def percentile(values, q):
    return float(np.percentile(values, q)) if len(values) else None
