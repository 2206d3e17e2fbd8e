"""The program's own spans in a profiler trace, and device idle time put
down to the innermost span the host was in.

An enabled ``repro.obs.Tracer`` opens a ``jax.profiler.TraceAnnotation``
named ``repro.<span>`` for each span, so a traced window holds the
program's spans on the device's clock beside the benchmark's own
``bench.*`` spans. :func:`load` reads them into a :class:`ProgramTrace`:
a ``lib.trace.Trace`` with one more list, ``program``, of
``(name, start, end)`` host intervals. Every function of ``lib.trace``
takes it as it is.

:func:`idle_gaps` labels each idle gap by the innermost span, program or
benchmark, that was open during most of it; ``bench.*`` labels a gap only
where no program span covers it. The readers at the end compute the
per-layer metrics the program's spans make possible; each returns None
where the trace holds none of the spans it reads, as a trace of a program
without them does.
"""
from __future__ import annotations

import dataclasses

from lib import trace as T

PREFIX = "repro."


@dataclasses.dataclass
class ProgramTrace(T.Trace):
    """A :class:`lib.trace.Trace` with the program's spans, ``program``."""

    program: list = dataclasses.field(default_factory=list)


def load(path: str) -> ProgramTrace:
    """Read an ``.xplane.pb`` with the program's ``repro.*`` host spans."""
    from jax.profiler import ProfileData

    base = T.load(path)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program += [iv for iv in T._intervals(line)
                            if iv[0].startswith(PREFIX)]
    return ProgramTrace(ops=base.ops, programs=base.programs, spans=base.spans,
                        program=sorted(program, key=lambda iv: iv[1]))


def program_spans(tr, name: str) -> list:
    """``(start, end)`` of the program's ``name`` spans that lie inside the
    window; empty for a trace that has no program spans."""
    lo, hi = tr.window()
    full = PREFIX + name
    return [(s, e) for n, s, e in getattr(tr, "program", ())
            if n == full and lo <= s and e <= hi]


# ---------------------------------------------------------------- idle time

def _gaps(tr, device: int) -> list:
    lo, hi = tr.window()
    busy = T.union(tr.ops[device], lo, hi) if tr.ops else []
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def idle_partition(tr, device: int = 0) -> list:
    """Each idle gap of ``device`` as ``(start, end, {label: seconds})``:
    every instant of the gap goes to the innermost span open then (the one
    opened last; "none" where no span is open)."""
    lo, hi = tr.window()
    gaps = _gaps(tr, device)
    spans = [iv for iv in tr.spans if iv[0] != "bench.window"]
    spans += list(getattr(tr, "program", ()))
    marks = []
    for k, (_, s, e) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks += [(s, 1, k), (e, 0, k)]
    marks.sort()          # at one instant, spans close before others open
    parts: list = [{} for _ in gaps]
    active: dict = {}
    first = 0             # the first gap not yet wholly behind the sweep
    t_prev = lo
    for t, opens, k in marks + [(hi, 0, None)]:
        if t > t_prev:
            # innermost: opened last; of two opened together, the shorter
            name = max(active.values(), key=lambda iv: (iv[1], -iv[2]))[0] \
                if active else "none"
            while first < len(gaps) and gaps[first][1] <= t_prev:
                first += 1
            i = first
            while i < len(gaps) and gaps[i][0] < t:
                ov = min(t, gaps[i][1]) - max(t_prev, gaps[i][0])
                if ov > 0:
                    parts[i][name] = parts[i].get(name, 0.0) + ov
                i += 1
            t_prev = t
        if k is not None:
            if opens:
                active[k] = spans[k]
            else:
                active.pop(k, None)
    return [(gs, ge, p) for (gs, ge), p in zip(gaps, parts)]


def idle_gaps(tr, device: int = 0) -> list:
    """``(label, seconds)`` of each idle gap, longest first, labelled by the
    innermost span that holds most of it."""
    out = [(max(p, key=p.get) if p else "none", ge - gs)
           for gs, ge, p in idle_partition(tr, device)]
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_program_span(tr, device: int = 0) -> dict:
    """Idle seconds of ``device`` summed by the innermost span open."""
    out: dict = {}
    for _, _, p in idle_partition(tr, device):
        for name, secs in p.items():
            out[name] = out.get(name, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def breakdown(tr, k: int = 10) -> dict:
    """``lib.trace.breakdown`` with the gaps labelled by :func:`idle_gaps`."""
    return {"device_ops": T.top_ops(tr, k),
            "idle_gaps": [[n, s] for n, s in idle_gaps(tr)[:k]]}


# ------------------------------------------------------------------ readers

def mean_span_s(env, name: str):
    """Mean seconds of the program's ``name`` spans in the window."""
    spans = program_spans(env["trace"], name)
    return sum(e - s for s, e in spans) / len(spans) if spans else None


def per_solve_s(env, name: str):
    """Seconds of the program's ``name`` spans per ``solve`` span."""
    tr = env["trace"]
    spans, solves = program_spans(tr, name), program_spans(tr, "solve")
    if not spans or not solves:
        return None
    return sum(e - s for s, e in spans) / len(solves)


def nonsweep_device_s(env):
    """Device seconds in the window of every program other than the
    configuration's sweep program, per ``swap_in`` span in the window."""
    tr = env["trace"]
    swaps = program_spans(tr, "swap_in")
    if not swaps or not tr.programs:
        return None
    lo, hi = tr.window()
    sweep = env["config"]["sweep_program"]
    per = [sum(e - s for s, e in T.union(
        [iv for iv in progs if sweep not in iv[0]], lo, hi))
        for progs in tr.programs]
    return sum(per) / len(per) / len(swaps)


# per-layer metric -> reader of ``env`` (``lib.readers`` describes ``env``)
METRICS = {
    "admit_s.serve": lambda env: mean_span_s(env, "admit"),
    "swapin_host_s.serve": lambda env: mean_span_s(env, "swap_in"),
    "resolve_s.serve": lambda env: mean_span_s(env, "resolve"),
    "nonsweep_device_s.serve": nonsweep_device_s,
    "pack_build_s.analytics": lambda env: per_solve_s(env, "pack_build"),
    "upload_s.analytics": lambda env: per_solve_s(env, "upload"),
    "relabel_s.analytics": lambda env: per_solve_s(env, "relabel"),
}
