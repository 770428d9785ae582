"""Dapper-style request tracing for the serving, ingest, and training
paths (Sigelman et al., 2010; docs/observability.md).

A :class:`Trace` is one request's (or one train run's) span tree:
flat records of ``(name, parent, start offset, duration)`` appended
under a lock, so spans measured on OTHER threads — the QueryBatcher's
dispatcher recording queue-wait and device time, the deadline pool
running a non-batched predict — land on the same trace safely.

Propagation has two legs:

- **ambient** — a contextvar carries the active trace on the current
  thread; ``span(name)`` opens a child span against it and is a shared
  no-op when no trace is active (one contextvar read, no allocation —
  the near-free disabled path). ``contextvars.copy_context`` captures
  it, so the engine server's deadline-dispatch pool threads
  (``EngineService._query_with_deadline``) inherit the trace for free.
- **explicit** — queue handoffs (QueryBatcher.submit) carry the trace
  object on the queue entry; the dispatcher thread calls
  ``Trace.add_span`` with externally measured intervals. One dispatch
  serves several requests, so the dispatcher binds ONE ambient trace of
  its own around ``query_batch`` (only when a traced request rides in
  the batch) and copies what ``span()`` recorded there onto each
  request's trace (``Trace.add_spans_from``).

A trace may carry a **root span** over its whole life
(``Trace.open_root`` / ``close_root``): the engine server's ``request``,
from the request line in hand to the response flushed. Every span
recorded without a parent becomes its child, the trace's origin is the
root's start and its duration the root's.

Traces are sampled into a bounded :class:`TraceLog` ring per server,
served as JSON on ``GET /traces.json``. The ring holds two kinds: one
trace a request, and (engine server, batching on) one ``dispatch``
trace a dispatcher cycle (serving/batcher.py).
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import os
import re
import threading
import time
import uuid
from collections import deque
from contextvars import ContextVar
from typing import Any, Iterator, Mapping

#: process-unique trace-id scheme: one random prefix per process plus a
#: sequence — same uniqueness story as uuid4 for correlation purposes,
#: without an os.urandom read (a syscall) on every traced request.
#: itertools.count threads safely under the GIL (a single C call).
_TRACE_ID_PREFIX = uuid.uuid4().hex[:16]
_TRACE_ID_SEQ = itertools.count(1)

#: span ids carry a per-SEGMENT prefix (fleet PR): a trace that crosses
#: the router hop collects spans from several trace segments, and bare
#: per-trace sequences ("s0", "s1") would collide between the router's
#: segment and each replica's when the stitcher joins them — cycling
#: the stitched parent links. The prefix is a per-process random part
#: (unique across the fleet's processes w.h.p., no syscall per span)
#: plus a per-process segment counter (unique across the many servers
#: an e2e test runs in ONE process).
_SPAN_ID_PREFIX = uuid.uuid4().hex[:6]
_SPAN_SEG_SEQ = itertools.count(1)

#: cross-process trace context headers (docs/observability.md): the
#: router forwards the trace id plus the span id of ITS attempt span,
#: so the replica's trace segment nests under the right attempt when
#: the trees are stitched back together.
TRACE_ID_HEADER = "X-PIO-Trace-Id"
PARENT_SPAN_HEADER = "X-PIO-Parent-Span"

#: inbound trace context is adopted only when it looks like ids this
#: framework (or a well-behaved peer) mints — anything else (spaces,
#: quotes, control bytes, unbounded length) is DROPPED and a fresh
#: local trace is started instead: a hostile header must never inject
#: into trace documents nor 500 the request.
_TRACE_CTX_RE = re.compile(r"^[A-Za-z0-9._:-]{1,128}$")


def parse_trace_context(
        headers: Mapping[str, str]) -> tuple[str | None, str | None]:
    """``(trace_id, parent_span_id)`` from inbound headers, each None
    when absent OR malformed/oversized (never raises — the caller
    falls back to fresh local ids). ``headers`` may be an
    ``email.Message`` (case-insensitive get) or a lowercased dict."""

    def clean(name: str) -> str | None:
        raw = headers.get(name) or headers.get(name.lower())
        if raw and _TRACE_CTX_RE.match(raw):
            return raw
        return None

    return clean(TRACE_ID_HEADER), clean(PARENT_SPAN_HEADER)


def tracing_default() -> bool:
    """The process-wide default for servers whose config leaves
    ``tracing`` unset: the ``PIO_TRACE`` env var. Read at CALL time
    (server construction), never frozen at import."""
    return os.environ.get("PIO_TRACE", "").strip().lower() in (
        "1", "true", "yes", "on")


_current: ContextVar["Trace | None"] = ContextVar("pio_trace", default=None)

#: span record slots: (name, parent_span_id, span_id, start_s, dur_s)
_ROOT_PARENT = ""


class Trace:
    """One request's spans. Cheap to create (an id, a list); creation
    is gated behind the server's tracing flag so the disabled path
    never allocates.

    Concurrency contract (why there is NO lock): span records are
    appended with ``list.append`` — atomic under the GIL — and every
    read (``to_dict``/``stage_seconds``) first takes an atomic
    ``list(...)`` copy, so a reader can never see a half-written
    record (tuples are immutable and fully built before the append).
    In the serving wiring the writers seldom overlap: the handler
    thread is blocked on its future while the batcher's dispatcher
    records queue-wait/device spans; only the phases the dispatcher
    copies onto a rider after its future is set may land while the
    handler appends its own, and an append is all either does. A lock
    here would add
    two GIL handoff points per span on a 24-thread serving path for a
    race that cannot corrupt anything — measured as a real qps cost
    in the tracing-overhead bench phase."""

    __slots__ = ("trace_id", "name", "request_id", "parent_span_id",
                 "service", "tags", "_t0", "_wall_start", "_spans",
                 "_span_seq", "_span_prefix", "_duration", "_root",
                 "observer")

    def __init__(self, name: str, request_id: str | None = None,
                 trace_id: str | None = None,
                 parent_span_id: str | None = None,
                 service: str | None = None,
                 start_perf: float | None = None):
        self.trace_id = (trace_id
                         or f"{_TRACE_ID_PREFIX}{next(_TRACE_ID_SEQ):012x}")
        self.name = name
        self.request_id = request_id
        #: the REMOTE span this whole segment nests under (the router's
        #: attempt span id, forwarded via X-PIO-Parent-Span); None for
        #: a root segment
        self.parent_span_id = parent_span_id
        #: which server recorded this segment ("router"/"engine"/...)
        self.service = service
        self.tags: dict[str, Any] = {}
        #: the origin span offsets count from: now, or a
        #: ``time.perf_counter`` reading the caller took earlier (the
        #: handler's stamp at the request line, the end of the
        #: dispatcher's previous cycle), so no span starts before it
        now = time.perf_counter()
        self._t0 = now if start_perf is None else start_perf
        self._wall_start = time.time() - (now - self._t0)
        #: flat records: (name, parent_id, span_id, start_off_s, dur_s)
        self._spans: list[tuple[str, str, str, float, float]] = []
        #: per-trace span-id sequence — ids must survive pre-allocation
        #: (reserve_span_id) and concurrent hedge-thread appends, so a
        #: counter, not len(self._spans) (GIL-atomic single C call)
        self._span_seq = itertools.count()
        self._span_prefix = f"{_SPAN_ID_PREFIX}{next(_SPAN_SEG_SEQ):x}"
        self._duration: float | None = None
        #: (name, span id) of the root span, once ``open_root`` ran
        self._root: tuple[str, str] | None = None
        #: optional span-completion callback ``(name, start_off_s,
        #: dur_s)`` — the train profiler samples device memory as each
        #: DASE stage closes (obs/device.TrainProfiler). Exceptions are
        #: swallowed: an observer must never fail the traced work.
        self.observer = None

    # -- span recording ------------------------------------------------------
    def span(self, name: str, parent_id: str = _ROOT_PARENT) -> "_ActiveSpan":
        """Context manager timing one in-thread stage."""
        return _ActiveSpan(self, name, parent_id)

    def reserve_span_id(self) -> str:
        """A span id usable BEFORE its span is recorded — the router
        must put its attempt span's id on the forward headers before
        the attempt runs, then record the span with the reserved id
        once the exchange finishes (``add_span(span_id=...)``)."""
        return f"s{self._span_prefix}.{next(self._span_seq):x}"

    def add_span(self, name: str, start_perf: float, end_perf: float,
                 parent_id: str = _ROOT_PARENT,
                 span_id: str | None = None) -> str:
        """Record an interval measured elsewhere (e.g. the batcher's
        dispatcher thread timing queue-wait with its own clock reads).
        ``start_perf``/``end_perf`` are ``time.perf_counter`` values.
        Returns the new span id (usable as a parent link).

        Span ids are a process prefix + per-trace sequence, not uuids:
        the sequence keeps them unique within the trace, the prefix
        across the processes a stitched fleet trace spans, and the hot
        path never pays an os.urandom read per span."""
        if span_id is None:
            span_id = f"s{self._span_prefix}.{next(self._span_seq):x}"
        if not parent_id and self._root is not None:
            parent_id = self._root[1]
        self._spans.append(
            (name, parent_id, span_id,
             start_perf - self._t0, max(0.0, end_perf - start_perf)))
        observer = self.observer
        if observer is not None:
            try:
                observer(name, start_perf - self._t0,
                         max(0.0, end_perf - start_perf))
            except Exception:
                pass
        return span_id

    def add_spans_from(self, other: "Trace",
                       parent_id: str = _ROOT_PARENT) -> None:
        """Copy every span of ``other`` onto this trace as children of
        ``parent_id`` — the batcher's per-dispatch trace, recorded once
        on the dispatcher thread, lands on each coalesced request's own
        trace (both clocks are ``time.perf_counter``)."""
        origin = other.start_perf
        for name, _, _, start, dur in other.spans():
            self.add_span(name, origin + start, origin + start + dur,
                          parent_id)

    def open_root(self, name: str) -> None:
        """Open the span that covers the whole trace, from its origin:
        from here on a span recorded without a parent is its child.
        Its own record is written by ``close_root``, which may run
        after the trace is finished and in the ring; until then
        ``to_dict`` shows it as long as the trace has lasted."""
        self._root = (name, self.reserve_span_id())

    def close_root(self, end_perf: float) -> None:
        """Record the root span, origin to ``end_perf``, and end the
        trace there: ``durationMs`` is the root's."""
        name, span_id = self._root
        self.finish(end_perf=end_perf)
        self._spans.append(
            (name, _ROOT_PARENT, span_id, 0.0, end_perf - self._t0))

    def finish(self, end_perf: float | None = None, **tags: Any) -> None:
        """End the trace now, or at ``end_perf`` (a clock reading the
        caller also closed a span with)."""
        # pio: lint-ignore[shared-state-race]: one float swapped by reference (GIL-atomic); a trace is finished by the one thread that owns it (a handler its request's, the dispatcher its cycle's record) and to_dict reads either the old or the new duration, both valid
        self._duration = (time.perf_counter() if end_perf is None
                          else end_perf) - self._t0
        if tags:
            self.tags.update(tags)

    # -- views ---------------------------------------------------------------
    @property
    def start_perf(self) -> float:
        """The ``time.perf_counter`` origin span offsets are relative
        to — lets external clock readings (the recompile sentinel's
        compile events) be binned into this trace's spans."""
        return self._t0

    def spans(self) -> list[tuple[str, str, str, float, float]]:
        """Atomic copy of the raw span records ``(name, parent_id,
        span_id, start_off_s, dur_s)`` (the Trace read contract)."""
        return list(self._spans)

    def stage_seconds(self) -> dict[str, float]:
        """Total seconds per span name, insertion-ordered — the
        ``pio train`` stage breakdown."""
        out: dict[str, float] = {}
        for name, _, _, _, dur in list(self._spans):
            out[name] = out.get(name, 0.0) + dur
        return out

    def to_dict(self) -> dict:
        spans = list(self._spans)
        duration = self._duration
        tags = dict(self.tags)
        root = self._root
        if root is not None and not any(s[2] == root[1] for s in spans):
            # the root is still open (the response is not flushed yet):
            # its children must not point at a span the document lacks
            spans.append((root[0], _ROOT_PARENT, root[1], 0.0,
                          duration or 0.0))
        doc: dict[str, Any] = {
            "traceId": self.trace_id,
            "name": self.name,
            "startTime": self._wall_start,
            "durationMs": (round(duration * 1e3, 3)
                           if duration is not None else None),
            "spans": [
                {
                    "name": name,
                    "spanId": span_id,
                    **({"parentId": parent} if parent else {}),
                    "startMs": round(start * 1e3, 3),
                    "durationMs": round(dur * 1e3, 3),
                }
                for name, parent, span_id, start, dur in sorted(
                    spans, key=lambda s: (s[3], -s[4]))
            ],
        }
        if self.request_id:
            doc["requestId"] = self.request_id
        if self.parent_span_id:
            doc["parentSpanId"] = self.parent_span_id
        if self.service:
            doc["service"] = self.service
        if tags:
            doc["tags"] = tags
        return doc


class _ActiveSpan:
    """The in-thread span context manager (``Trace.span``)."""

    __slots__ = ("_trace", "_name", "_parent", "_start", "span_id")

    def __init__(self, trace: Trace, name: str, parent_id: str):
        self._trace = trace
        self._name = name
        self._parent = parent_id
        self._start = 0.0
        self.span_id = ""

    def __enter__(self) -> "_ActiveSpan":
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.span_id = self._trace.add_span(
            self._name, self._start, time.perf_counter(), self._parent)


class _NullSpan:
    """Shared no-op for the disabled path: ``span()`` with no active
    trace returns THIS singleton — no allocation, two no-op calls."""

    __slots__ = ()
    span_id = ""

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


def start_trace(name: str, request_id: str | None = None,
                trace_id: str | None = None,
                parent_span_id: str | None = None,
                service: str | None = None,
                start_perf: float | None = None) -> Trace:
    """A new root trace (or, with ``trace_id``/``parent_span_id`` from
    :func:`parse_trace_context`, a CHILD SEGMENT of a cross-process
    trace). Call sites gate this behind their tracing flag — the flag
    check is the whole cost of the disabled path."""
    return Trace(name, request_id=request_id, trace_id=trace_id,
                 parent_span_id=parent_span_id, service=service,
                 start_perf=start_perf)


def active_trace() -> Trace | None:
    return _current.get()


@contextlib.contextmanager
def use_trace(trace: Trace | None) -> Iterator[Trace | None]:
    """Bind ``trace`` as the ambient trace for the current context.
    ``contextvars.copy_context()`` carries the binding onto pool
    threads (the deadline-dispatch path)."""
    token = _current.set(trace)
    try:
        yield trace
    finally:
        _current.reset(token)


def span(name: str):
    """Ambient child span: records against the current trace, or is a
    shared no-op when none is active (one contextvar read, zero
    allocation)."""
    trace = _current.get()
    if trace is None:
        return _NULL_SPAN
    return trace.span(name)


class GcPauseSpans:
    """A ``gc.callbacks`` hook: a collection of generation 1 or 2 that
    runs on a thread with an ambient trace lands on it as the span
    ``gc.pause.gen<n>`` (the collector holds the GIL, so the pause is
    every thread's; generation 0 runs every few hundred allocations
    and takes microseconds: not recorded). One collection runs at a
    time, so one start stamp is enough. A server installs it when it
    starts with tracing on and removes it when it stops."""

    def __init__(self):
        self._start: float | None = None

    def __call__(self, phase: str, info: Mapping[str, int]) -> None:
        if info["generation"] == 0:
            return
        if phase == "start":
            self._start = time.perf_counter()
            return
        trace, start, self._start = _current.get(), self._start, None
        if trace is not None and start is not None:
            trace.add_span(f"gc.pause.gen{info['generation']}", start,
                           time.perf_counter())

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)


class TraceLog:
    """Bounded ring of recently finished traces (newest first on
    read; a trace may still receive spans after it is recorded: the
    handler's ``request`` root, the dispatcher's four). Recording is
    one deque append under the ring's lock — serialization to JSON-able dicts happens at READ time, relying on
    the lock-free :class:`Trace` read contract (``to_dict`` copies the
    span list atomically under the GIL; see the Trace docstring for
    why the trace itself carries no lock), so the request hot path
    never pays for a trace nobody is looking at. The ring's one lock
    guards the deque at writers and readers."""

    def __init__(self, maxlen: int = 64):
        self._lock = threading.Lock()
        self._ring: deque[Trace] = deque(maxlen=maxlen)

    def record(self, trace: Trace) -> None:
        with self._lock:
            self._ring.append(trace)

    def snapshot(self) -> list[dict]:
        with self._lock:
            traces = list(reversed(self._ring))
        return [t.to_dict() for t in traces]

    def find(self, trace_id: str) -> list[dict]:
        """Every recorded segment of one trace (a hedged request can
        leave several segments with the same id in ONE ring)."""
        with self._lock:
            traces = [t for t in self._ring if t.trace_id == trace_id]
        return [t.to_dict() for t in traces]
