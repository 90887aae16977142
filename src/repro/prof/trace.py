"""Request-level span traces for the serve engine.

cf4ocl's profiler shows *device* lanes — one row per command queue.  The
serve engine adds a second actor the queue view cannot express: the
**request**.  This module gives every :class:`~repro.serve.engine.request.Sequence`
a trace of typed spans covering its whole lifetime:

==========  ==========================================================
kind        interval
==========  ==========================================================
QUEUED      submission → admission (waiting for a slot / pages)
PREFILL     admission's prompt prefill + relayout + slot/page insert
DECODE      service interval of one emitted token: ``token_index`` i
            spans emission of token i → emission of token i+1 (the
            last token's span closes at retirement; a preemption
            splits a token's interval into two DECODE spans)
PREEMPTED   evicted from the paged pool, swapped out, requeued
SWAP        resumption's swap-in (pages rebound, blocks scattered)
COW         *marker* (zero length): copy-on-write page copies charged
            to this request this tick
FAILED      *marker*: terminal failure, ``detail`` = error string
==========  ==========================================================

**Invariants** (by construction, not convention): the lifecycle spans
(everything except the COW/FAILED markers) of one request are
contiguous and non-overlapping — each transition closes the open span
and opens the next at the same ``(tick, ns)`` instant — and partition
``[submitted, terminal]``.  Spans carry *both* coordinates: engine
ticks (deterministic, used by every metric) and ``now_ns`` wall
instants (used only for timeline rendering/export, where they line up
with the device events' clocks).

**Event linking**: the engine attaches the
:class:`~repro.core.event.Event` objects that served each span
(``PREFILL_KERNEL``, ``DECODE_KERNEL``, ``ALIGN_CACHE``, ``SWAP_IN``,
``PAGE_COW``, ``TRACE_COMPILE``, …) via :meth:`TraceCollector.link`, so
a slow request points straight at the device work that made it slow —
the cf4ocl event-timeline idea extended across the request boundary.

**Host phases on the profiler's clock**: :func:`host_span` names one
stretch of the engine's host work (``serve.step``, ``serve.decode``,
``serve.sample``, …) as a ``jax.profiler.TraceAnnotation``.  The span
lands in the profiler's own host trace, on the clock the device events
are aligned to, so a gap in which the device sat idle can be put down to
what the host was doing.  The ``now_ns`` stamps above are
``perf_counter`` instants and do not line up with that trace; a span
that belongs to a request carries its ``rid`` as the join key.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from ..core.event import Event, now_ns


def host_span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` on the profiler's clock, used as a context
    manager; ``stats`` (numbers or strings) are recorded with it, and
    ``set_metadata`` adds more before it closes.  With no profiler
    session running the span records nothing and costs about a
    microsecond."""
    return jax.profiler.TraceAnnotation(name, **stats)


class SpanKind(enum.Enum):
    QUEUED = "QUEUED"
    PREFILL = "PREFILL"
    DECODE = "DECODE"
    PREEMPTED = "PREEMPTED"
    SWAP = "SWAP"
    COW = "COW"          # marker: CoW copies charged this tick
    FAILED = "FAILED"    # marker: terminal failure

    @property
    def lifecycle(self) -> bool:
        """True for the mutually-exclusive states that partition a
        request's lifetime; False for the instantaneous markers."""
        return self not in (SpanKind.COW, SpanKind.FAILED)


@dataclasses.dataclass
class Span:
    """One typed interval (or instantaneous marker) of a request."""
    kind: SpanKind
    rid: int
    tick0: int                      # engine tick coordinates (metrics)
    t0: int                         # now_ns coordinates (rendering only)
    tick1: Optional[int] = None     # None while open
    t1: Optional[int] = None
    token_index: Optional[int] = None   # DECODE: which emitted token
    detail: str = ""
    events: List[Event] = dataclasses.field(default_factory=list)

    @property
    def open(self) -> bool:
        return self.t1 is None

    @property
    def duration_ticks(self) -> Optional[int]:
        return None if self.tick1 is None else self.tick1 - self.tick0

    @property
    def duration_ns(self) -> Optional[int]:
        return None if self.t1 is None else self.t1 - self.t0

    def __repr__(self) -> str:
        tok = f" tok={self.token_index}" if self.token_index is not None \
            else ""
        return (f"<Span {self.kind.value} rid={self.rid} "
                f"ticks=[{self.tick0},{self.tick1}]{tok} "
                f"events={len(self.events)}>")


class RequestTrace:
    """All spans of one request, in emission order, with at most one
    lifecycle span open at a time."""

    def __init__(self, rid: int, tick: int):
        self.rid = rid
        self.spans: List[Span] = []
        self._open: Optional[Span] = None
        self._transition(SpanKind.QUEUED, tick, now_ns())

    def _transition(self, kind: SpanKind, tick: int, t: int,
                    token_index: Optional[int] = None,
                    detail: str = "") -> Span:
        if self._open is not None:
            self._open.tick1 = tick
            self._open.t1 = t
        span = Span(kind, self.rid, tick, t, token_index=token_index,
                    detail=detail)
        self.spans.append(span)
        self._open = span
        return span

    def transition(self, kind: SpanKind, tick: int,
                   token_index: Optional[int] = None,
                   detail: str = "") -> Span:
        """Close the open lifecycle span and open the next one at the
        same instant (contiguity by construction)."""
        assert kind.lifecycle, f"{kind} is a marker — use mark()"
        return self._transition(kind, tick, now_ns(), token_index, detail)

    def link(self, *events: Event) -> None:
        """Attach device events to the open span (no-op once closed —
        e.g. a release-path scrub after the trace already terminated)."""
        if self._open is not None:
            self._open.events.extend(events)

    def mark(self, kind: SpanKind, tick: int, detail: str = "",
             events: Sequence[Event] = ()) -> Span:
        """Append an instantaneous marker span (COW / FAILED) without
        disturbing the open lifecycle span."""
        assert not kind.lifecycle, f"{kind} is a lifecycle kind"
        t = now_ns()
        span = Span(kind, self.rid, tick, t, tick1=tick, t1=t,
                    detail=detail, events=list(events))
        self.spans.append(span)
        return span

    def close(self, tick: int) -> None:
        """Terminate the trace: close the open span (idempotent)."""
        if self._open is not None:
            self._open.tick1 = tick
            self._open.t1 = now_ns()
            self._open = None

    def fail(self, tick: int, detail: str = "") -> None:
        """Terminate with a FAILED marker carrying the error string."""
        self.close(tick)
        self.mark(SpanKind.FAILED, tick, detail=detail)

    # -- queries ---------------------------------------------------------
    def lifecycle_spans(self) -> List[Span]:
        return [s for s in self.spans if s.kind.lifecycle]

    def markers(self) -> List[Span]:
        return [s for s in self.spans if not s.kind.lifecycle]

    def contiguous(self) -> bool:
        """True iff the lifecycle spans are all closed and partition the
        trace's lifetime — each starts exactly where its predecessor
        ended, in both tick and ns coordinates."""
        life = self.lifecycle_spans()
        if any(s.open for s in life):
            return False
        for a, b in zip(life, life[1:]):
            if b.tick0 != a.tick1 or b.t0 != a.t1:
                return False
        return True


class TraceCollector:
    """Per-request traces for one engine run, keyed by rid."""

    def __init__(self):
        self.traces: Dict[int, RequestTrace] = {}

    def begin(self, rid: int, tick: int) -> RequestTrace:
        assert rid not in self.traces, f"duplicate trace for rid {rid}"
        rt = RequestTrace(rid, tick)
        self.traces[rid] = rt
        return rt

    def transition(self, rid: int, kind: SpanKind, tick: int,
                   token_index: Optional[int] = None,
                   detail: str = "") -> None:
        self.traces[rid].transition(kind, tick, token_index, detail)

    def link(self, rid: int, *events: Event) -> None:
        self.traces[rid].link(*events)

    def mark(self, rid: int, kind: SpanKind, tick: int, detail: str = "",
             events: Sequence[Event] = ()) -> None:
        self.traces[rid].mark(kind, tick, detail, events)

    def close(self, rid: int, tick: int) -> None:
        self.traces[rid].close(tick)

    def fail(self, rid: int, tick: int, detail: str = "") -> None:
        self.traces[rid].fail(tick, detail)

    def __iter__(self) -> Iterator[RequestTrace]:
        return iter(self.traces.values())

    def __len__(self) -> int:
        return len(self.traces)

    def time_range_ns(self) -> Optional[Tuple[int, int]]:
        ts = [t for rt in self for s in rt.spans
              for t in (s.t0, s.t1) if t is not None]
        return (min(ts), max(ts)) if ts else None


__all__ = ["SpanKind", "Span", "RequestTrace", "TraceCollector",
           "host_span"]
