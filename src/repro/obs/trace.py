"""Structured span tracing for the engines and the serving loop.

The paper's quantity of interest is *rounds*, and rounds are only visible
from the host side of the batch boundary — so the tracer records exactly
the host-side control-flow edges where round/convergence information
already surfaces, never anything per-round on device:

``solve``         one engine entry (`repro.engine.api.solve`), the whole
                  call: relabel, pack, rounds and readout
``relabel``       the relabel into a processing order (``direction=
                  "forward"``) or the gather of the answer back to vertex
                  ids (``"inverse"``), inside ``solve``
``pack``          operand packing (block padding / flat-BSR layout)
``pack_build``    host construction of the packed operands (on the pallas
                  backend the tile skeleton), inside ``pack``
``upload``        device placement of the packed operands (``bytes``);
                  inside ``pack`` on the pallas backend, right after it on
                  the jax backend
``pack_device``   dispatch of the pallas backend's device build of the tiles
                  and padded state from what ``upload`` sent, inside ``pack``
``batch``         one bounded-round session batch (`AsyncBlockSession`)
``sweep_call``    one megakernel dispatch inside a batch (dispatch-side
                  duration: the launch is asynchronous, the following
                  batch-granular readout is the real sync point)
``tick``          one `GraphServer.step`
``admit``         building and checking one queued ticket's query
                  (``ticket``), inside ``tick``
``swap_in``       installing one query in a slot (`GraphServer._install`:
                  rank gather, padding, frontier seeding, the column
                  update's dispatch; ``ticket``, and on the megakernel
                  ``support_vertices`` / ``dirty_blocks``)
``resolve``       a ticket resolved (`GraphServer._resolve`: the column
                  readout, the gather to vertex ids, the cache insert;
                  ``ticket`` / tenant / algo / rounds / converged — the
                  per-query round histogram source). A cache hit in
                  ``submit`` is a zero-length ``resolve`` event
                  (``from_cache=True``)
``delta_apply``   one `GraphServer.apply_delta` ingestion
``reorder_swap``  instantaneous event: one online order swap
                  (`GraphServer._set_rank`)

Every span gets an ``id`` and the ``parent`` id of the span of the same
tracer that was open when it started (``None`` at the top); the tracer
keeps the stack of open spans, so use one tracer from one thread. The
spans of one query carry its ``ticket`` id.

Spans carry flat attribute dicts (``tenant`` / ``algo`` / ``engine`` /
``graph_version`` / ...). Attribute values MUST be host scalars, strings,
or small lists of host scalars — never jax arrays: an implicit coercion
(``float(jnp_scalar)``) at a recording call site is a hidden device->host
sync, exactly the bug class the host-sync checker (HS001) flags; this
module and every module with recording hooks sit in repro-lint's hot-path
globs.

One clock: an enabled span also opens a
``jax.profiler.TraceAnnotation("repro.<name>")`` for its extent, so under
``jax.profiler.trace`` every span lands in the profiler's trace on the
same clock as the device's operations, and device idle time can be put
down to the innermost span the host was in. ``jax.profiler`` is imported
on the first enabled span, never at module load. Events (zero-length)
stay in the ring and the sink only.

Cost model (the "zero-cost-when-disabled" contract): a disabled tracer's
:meth:`Tracer.span` returns the shared :data:`NULL_SPAN` singleton — no
span object, no timestamp, no annotation, no import, no buffer traffic;
the only cost at a disabled call site is building the keyword dict.
Enabled spans pay two ``perf_counter`` reads, one small object, one
annotation (a no-op while no profiler records) and (when a JSONL sink is
configured) one serialized line. All spans are batch-granular or coarser,
so even enabled tracing is O(batches), never O(rounds).

Finished spans land in an in-memory ring buffer (``deque(maxlen=ring)`` —
a long-lived server keeps the most recent window) and, optionally, in a
JSONL sink: one JSON object per finished span, written and flushed at span
exit so a live reader (``examples/observe_serving.py``) can tail the file
mid-run.
"""
from __future__ import annotations

import collections
import functools
import json
import time
from types import TracebackType
from typing import IO, Any, Optional, Union

SPAN_NAMES = (
    "solve", "relabel", "pack", "pack_build", "upload", "pack_device",
    "batch", "sweep_call",
    "tick", "admit", "swap_in", "resolve", "delta_apply", "reorder_swap",
)


@functools.cache
def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class _NullSpan:
    """The shared do-nothing span a disabled tracer hands out.

    One module-level instance serves every disabled call site: entering,
    exiting, and :meth:`set` are all no-ops, so ``with tracer.span(...)``
    costs nothing measurable when tracing is off.
    """

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(
        self,
        exc_type: Optional[type[BaseException]],
        exc: Optional[BaseException],
        tb: Optional[TracebackType],
    ) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One finished-or-open span: a named, timed, attributed interval."""

    __slots__ = ("name", "attrs", "t_start", "t_end", "id", "parent",
                 "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: dict[str, Any]) -> None:
        self.name = name
        self.attrs = attrs
        self.t_start: float = 0.0
        self.t_end: Optional[float] = None
        self.id: int = -1          # assigned on entry (or on record, for events)
        self.parent: Optional[int] = None
        self._tracer = tracer
        self._annotation: Any = None

    @property
    def duration_s(self) -> float:
        """Wall duration; 0.0 while the span is still open."""
        return 0.0 if self.t_end is None else self.t_end - self.t_start

    def set(self, **attrs: Any) -> "Span":
        """Attach attributes discovered mid-span (e.g. the batch's round
        count, known only after the batch-granular readout)."""
        self.attrs.update(attrs)
        return self

    def to_json(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "t_start": self.t_start,
            "duration_s": self.duration_s,
            **self.attrs,
        }

    def __enter__(self) -> "Span":
        self._tracer._open(self)
        self._annotation = _trace_annotation()(f"repro.{self.name}")
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(
        self,
        exc_type: Optional[type[BaseException]],
        exc: Optional[BaseException],
        tb: Optional[TracebackType],
    ) -> bool:
        self.t_end = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        self._tracer._close(self)
        self._tracer._record(self)
        return False

    def __repr__(self) -> str:  # debugging/REPL aid only
        return f"Span({self.name!r}, {self.duration_s * 1e3:.3f}ms, {self.attrs})"


class Tracer:
    """Span recorder: ring buffer + optional JSONL sink.

    Parameters
    ----------
    enabled : master switch. Disabled tracers hand out :data:`NULL_SPAN`
        and record nothing — the zero-cost path.
    ring : finished spans kept in memory (oldest evicted first).
    jsonl : optional sink — a filesystem path (opened lazily, append mode)
        or any object with ``write``; each finished span becomes one JSON
        line, flushed immediately.
    """

    def __init__(self, enabled: bool = True, ring: int = 4096,
                 jsonl: Union[str, IO[str], None] = None) -> None:
        self.enabled = enabled
        self.spans: collections.deque[Span] = collections.deque(maxlen=ring)
        self._jsonl = jsonl
        self._sink: Optional[IO[str]] = None
        self._owns_sink = False
        self._stack: list[Span] = []   # open spans, innermost last
        self._next_id = 0

    def span(self, name: str, **attrs: Any) -> Union[Span, _NullSpan]:
        """Open a span; use as ``with tracer.span("batch", tenant=t) as sp``."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, attrs)

    def event(self, name: str, **attrs: Any) -> None:
        """Record an instantaneous (zero-duration) span."""
        if not self.enabled:
            return
        sp = Span(self, name, attrs)
        self._assign_id(sp)
        sp.t_start = time.perf_counter()
        sp.t_end = sp.t_start
        self._record(sp)

    def find(self, name: str) -> list[Span]:
        """Recorded spans with the given name, oldest first."""
        return [s for s in self.spans if s.name == name]

    def close(self) -> None:
        """Close a path-opened sink (file-like sinks stay the caller's)."""
        if self._sink is not None and self._owns_sink:
            self._sink.close()
        self._sink = None

    # ------------------------------------------------------------ internal

    def _assign_id(self, span: Span) -> None:
        span.id = self._next_id
        self._next_id += 1
        span.parent = self._stack[-1].id if self._stack else None

    def _open(self, span: Span) -> None:
        self._assign_id(span)
        self._stack.append(span)

    def _close(self, span: Span) -> None:
        self._stack.remove(span)   # the innermost, under context managers

    def _record(self, span: Span) -> None:
        self.spans.append(span)
        sink = self._ensure_sink()
        if sink is not None:
            sink.write(json.dumps(span.to_json()) + "\n")
            sink.flush()

    def _ensure_sink(self) -> Optional[IO[str]]:
        if self._sink is None and self._jsonl is not None:
            if isinstance(self._jsonl, str):
                self._sink = open(self._jsonl, "a", encoding="utf-8")
                self._owns_sink = True
            else:
                self._sink = self._jsonl
        return self._sink


def tspan(tracer: Optional[Tracer], name: str,
          **attrs: Any) -> Union[Span, _NullSpan]:
    """``tracer.span(...)`` that also accepts ``tracer=None`` (tracing off).

    The one helper every instrumented call site uses, so ``None`` /
    disabled / enabled all read identically:
    ``with tspan(o.trace, "pack", algo=algo.name): ...``
    """
    if tracer is None or not tracer.enabled:
        return NULL_SPAN
    return Span(tracer, name, attrs)
