"""Reduction of a JAX profiler trace to device busy time, program time and
idle gaps attributed to what the host was doing.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into a :class:`Trace` of plain intervals:

* ``ops``: operations that ran on the device (the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane),
* ``programs``: compiled programs that ran there (the ``XLA Modules``
  line), by program name,
* ``spans``: the benchmark's own host spans (``jax.profiler.
  TraceAnnotation`` names listed in :data:`HOST_SPANS`).

Device timestamps in the trace are already on the host's clock, so host
spans and device intervals compare directly. Everything after :func:`load`
is plain arithmetic on intervals and is checked on a recorded trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

# what the benchmark wraps in TraceAnnotation spans around its calls
HOST_SPANS = ("bench.solve", "bench.submit", "bench.step", "bench.arrival_wait",
              "bench.window")

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    """Intervals in seconds on one clock: ``(name, start, end)`` tuples.
    ``ops`` and ``programs`` are per device (index = device number)."""

    ops: list
    programs: list
    spans: list

    @property
    def devices(self) -> int:
        return len(self.ops)

    def window(self) -> tuple[float, float]:
        """The traced window: the benchmark's ``bench.window`` span."""
        w = [(s, e) for name, s, e in self.spans if name == "bench.window"]
        if not w:
            raise ValueError("the trace holds no 'bench.window' span")
        return min(s for s, _ in w), max(e for _, e in w)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return files[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, programs, spans = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            idx = int(name[len(DEVICE_PREFIX):].split()[0].split("/")[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[idx] = _intervals(line)
                elif line.name == PROGRAMS_LINE:
                    programs[idx] = _intervals(line)
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans += [iv for iv in _intervals(line) if iv[0] in HOST_SPANS]
    idxs = sorted(set(ops) | set(programs))
    return Trace(ops=[ops.get(i, []) for i in idxs],
                 programs=[programs.get(i, []) for i in idxs],
                 spans=sorted(spans, key=lambda iv: iv[1]))


def _intervals(line) -> list:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def op_name(hlo: str) -> str:
    """``fusion.12`` of an XLA Ops event named ``%fusion.12 = f32[...] ...``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran, averaged over the
    devices in the trace."""
    lo, hi = trace.window()
    per = [sum(e - s for s, e in union(ops, lo, hi)) for ops in trace.ops]
    return sum(per) / len(per) if per else 0.0


def idle_gaps(trace: Trace, device: int = 0) -> list:
    """Gaps of the window with no operation on ``device``:
    ``(host_span, seconds)``, where ``host_span`` is the benchmark span that
    overlaps the gap most ("none" where no span does), longest first."""
    lo, hi = trace.window()
    busy = union(trace.ops[device], lo, hi) if trace.ops else []
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    # the benchmark's own spans run one after another on one thread
    spans = [iv for iv in trace.spans if iv[0] != "bench.window"]
    starts = [s for _, s, _ in spans]
    out = []
    for gs, ge in gaps:
        best, best_ov = "none", 0.0
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and spans[i][2] > gs:
            name, s, e = spans[i]
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
            i -= 1
        out.append((best, ge - gs))
    out.sort(key=lambda g: -g[1])
    return out


def program_s(trace: Trace, name_part: str) -> float:
    """Device seconds in the window of the programs whose name holds
    ``name_part``, averaged over the devices."""
    lo, hi = trace.window()
    per = [sum(e - s for s, e in union(
        [iv for iv in progs if name_part in iv[0]], lo, hi))
        for progs in trace.programs]
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: Trace, k: int = 10, device: int = 0) -> list:
    """``[name, seconds]`` of the ``k`` device operations that took most
    time in the window."""
    lo, hi = trace.window()
    tot: dict = {}
    for name, s, e in trace.ops[device] if trace.ops else []:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + d
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def breakdown(trace: Trace, k: int = 10) -> dict:
    """The result line's ``breakdown``: the top device operations and the
    longest idle gaps, each labelled by what the host was doing."""
    return {"device_ops": top_ops(trace, k),
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace)[:k]]}


def idle_by_span(trace: Trace) -> dict:
    """Idle seconds of device 0 summed by the host span they fell in."""
    out: dict = {}
    for name, s in idle_gaps(trace):
        out[name] = out.get(name, 0.0) + s
    return out
