"""The trace reduction, on intervals made by hand and on a trace recorded
on the chip (``bench/tests/data/small.xplane.pb``, made by
``record_trace.py``)."""
import os

import numpy as np
import pytest

from lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def hand_made() -> T.Trace:
    ops = [("%a.1 = f32[8] add(x, y)", 1.0, 2.0), ("%b = f32[8] mul(x, y)", 1.5, 3.0),
           ("%a.1 = f32[8] add(x, y)", 5.0, 6.0), ("%c = f32[8] exp(x)", 9.5, 12.0)]
    programs = [("jit_step(1)", 1.0, 3.0), ("jit_other(2)", 5.0, 6.0),
                ("jit_step(1)", 9.5, 12.0)]
    spans = [("bench.window", 0.0, 10.0), ("bench.solve", 0.0, 3.5),
             ("bench.arrival_wait", 3.5, 9.0), ("bench.step", 9.0, 12.0)]
    return T.Trace(ops=[ops], programs=[programs], spans=spans)


def test_union_merges_and_clips():
    ivs = [("x", 1, 2), ("y", 1.5, 3), ("z", 5, 6), ("w", 9, 12)]
    assert T.union(ivs, 0, 10) == [(1, 3), (5, 6), (9, 10)]


def test_busy_idle_and_programs_by_hand():
    tr = hand_made()
    assert tr.window() == (0.0, 10.0)
    assert T.busy_s(tr) == pytest.approx(2.0 + 1.0 + 0.5)
    gaps = T.idle_gaps(tr)
    # (3, 5) lies mostly in the wait, (6, 9.5) too, (0, 1) in the solve
    assert gaps == [("bench.arrival_wait", 3.5), ("bench.arrival_wait", 2.0),
                    ("bench.solve", 1.0)]
    assert T.idle_by_span(tr) == {"bench.arrival_wait": 5.5, "bench.solve": 1.0}
    assert T.program_s(tr, "jit_step(") == pytest.approx(2.5)
    b = T.breakdown(tr)
    assert b["device_ops"][0] == ["a.1", 2.0]
    assert b["idle_gaps"][0] == ["bench.arrival_wait", 3.5]


def brute_busy(ops, lo, hi, step=1e-5):
    """Busy seconds by sampling the window on a fine grid."""
    t = np.arange(lo, hi, step)
    busy = np.zeros(len(t), bool)
    for _, s, e in ops:
        busy |= (t >= s) & (t < e)
    return busy.sum() * step


@pytest.mark.skipif(not os.path.exists(DATA), reason="recorded trace missing")
def test_recorded_trace():
    tr = T.load(DATA)
    assert tr.devices == 1
    lo, hi = tr.window()
    names = {name for name, _, _ in tr.spans}
    assert {"bench.window", "bench.solve", "bench.arrival_wait", "bench.step"} <= names
    busy = T.busy_s(tr)
    assert 0.0 < busy < hi - lo
    assert busy == pytest.approx(brute_busy(tr.ops[0], lo, hi), abs=2e-4)
    # the host slept 50 ms inside the wait span: the longest gap is there
    (name, secs), *_ = T.idle_gaps(tr)
    assert name == "bench.arrival_wait" and 0.045 <= secs <= 0.2
    # the products ran as one program, five times, inside the window
    progs = {p for p, _, _ in tr.programs[0]}
    assert len(progs) >= 1
    # a program spans its operations, give or take the trace's clock steps
    assert max(T.program_s(tr, p) for p in progs) <= busy + 1e-6
    b = T.breakdown(tr)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
