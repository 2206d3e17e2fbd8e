"""The program's spans in the trace reduction, on intervals made by hand and
on a trace recorded on the chip (``bench/tests/data/program.xplane.pb``,
made by ``record_program_trace.py``)."""
import os

import pytest

from lib import program_trace as P
from lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "program.xplane.pb")
CONFIG = {"sweep_program": "jit_sweep("}


def hand_made(program=True) -> T.Trace:
    """One solve (relabel, pack, sweeps, relabel back) then one tick."""
    ops = [("%s = f32[8] sweep(x)", 6.0, 9.0), ("%u = f32[8] update(x)", 11.5, 12.0),
           ("%s = f32[8] sweep(x)", 12.0, 16.0)]
    programs = [("jit_sweep(1)", 6.0, 9.0), ("jit_swap(2)", 11.5, 12.0),
                ("jit_sweep(1)", 12.0, 16.0)]
    spans = [("bench.window", 0.0, 20.0), ("bench.solve", 0.0, 10.0),
             ("bench.step", 10.0, 20.0)]
    prog = [("repro.solve", 0.5, 9.5), ("repro.relabel", 0.5, 1.0),
            ("repro.pack", 1.0, 6.0), ("repro.pack_build", 1.0, 4.0),
            ("repro.upload", 4.0, 6.0), ("repro.relabel", 9.0, 9.5),
            ("repro.tick", 10.5, 19.5), ("repro.admit", 10.5, 11.2),
            ("repro.swap_in", 11.2, 12.0), ("repro.batch", 12.0, 16.0),
            ("repro.resolve", 16.0, 18.0)]
    if not program:
        return T.Trace(ops=[ops], programs=[programs], spans=spans)
    return P.ProgramTrace(ops=[ops], programs=[programs], spans=spans, program=prog)


def test_innermost_labelling_by_hand():
    tr = hand_made()
    # (0, 6) is mostly the host build, (16, 20) the resolve, (9, 11.5) the
    # admit; no gap is put down to a span that only encloses another
    assert P.idle_gaps(tr) == [("repro.pack_build", 6.0), ("repro.resolve", 4.0),
                               ("repro.admit", pytest.approx(2.5))]
    by = P.idle_by_program_span(tr)
    want = {"repro.pack_build": 3.0, "repro.upload": 2.0, "repro.resolve": 2.0,
            "repro.tick": 1.5, "repro.relabel": 1.0, "bench.solve": 1.0,
            "bench.step": 1.0, "repro.admit": 0.7, "repro.swap_in": 0.3}
    assert by == pytest.approx(want)
    assert sum(by.values()) == pytest.approx(20.0 - T.busy_s(tr))
    assert "repro.solve" not in by and "repro.pack" not in by
    b = P.breakdown(tr)
    assert b["idle_gaps"][0] == ["repro.pack_build", 6.0]
    assert b["device_ops"] == T.top_ops(tr)


def test_bench_spans_alone_label_as_before():
    """Without program spans the gaps get the benchmark's labels, as
    ``lib.trace.idle_gaps`` gives them; the seconds by span split the gap
    that straddles two spans between them."""
    tr = hand_made(program=False)
    assert P.idle_gaps(tr) == T.idle_gaps(tr)
    assert P.idle_by_program_span(tr) == pytest.approx(
        {"bench.solve": 7.0, "bench.step": 5.5})
    assert sum(P.idle_by_program_span(tr).values()) == pytest.approx(
        sum(T.idle_by_span(tr).values()))


def test_readers_by_hand():
    env = {"trace": hand_made(), "config": CONFIG}
    got = {name: read(env) for name, read in P.METRICS.items()}
    assert got == pytest.approx({
        "admit_s.serve": 0.7, "swapin_host_s.serve": 0.8, "resolve_s.serve": 2.0,
        "nonsweep_device_s.serve": 0.5, "pack_build_s.analytics": 3.0,
        "upload_s.analytics": 2.0, "relabel_s.analytics": 1.0})


@pytest.mark.parametrize("name", sorted(P.METRICS))
def test_readers_none_without_program_spans(name):
    read = P.METRICS[name]
    assert read({"trace": hand_made(program=False), "config": CONFIG}) is None
    empty = hand_made()
    empty.program = []
    assert read({"trace": empty, "config": CONFIG}) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="recorded trace missing")
def test_recorded_program_spans_on_device_clock():
    tr = P.load(DATA)
    assert tr.devices == 1
    lo, hi = tr.window()
    names = {n for n, _, _ in tr.program}
    assert names == {"repro.tick", "repro.batch", "repro.resolve"}
    assert all(lo <= s <= e <= hi for _, s, e in tr.program)
    (step,) = [(s, e) for n, s, e in tr.spans if n == "bench.step"]
    (tick,) = P.program_spans(tr, "tick")
    (batch,) = P.program_spans(tr, "batch")
    (resolve,) = P.program_spans(tr, "resolve")
    assert step[0] <= tick[0] <= batch[0] <= batch[1] <= resolve[0] <= tick[1] <= step[1]
    # one clock, to within the trace's skew (device events read about 1 ms
    # early against the host spans, in this trace and in small.xplane.pb):
    # the window's last program is the product the batch span waited for
    _, start, end = max(tr.programs[0], key=lambda iv: iv[1])
    assert batch[0] - 2e-3 <= start and end <= batch[1] + 2e-3
    # the host slept 50 ms in the innermost span: the longest gap is its own
    (name, secs), *_ = P.idle_gaps(tr)
    assert name == "repro.resolve" and 0.045 <= secs <= 0.2
    assert T.idle_gaps(tr)[0][0] == "bench.step"   # what the bench spans alone say
    by = P.idle_by_program_span(tr)
    assert by["repro.resolve"] >= 0.045
