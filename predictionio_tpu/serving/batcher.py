"""The query micro-batcher: coalesces concurrent queries into one
device dispatch — the TPU-first serving feature a per-query dispatch
model can't offer (beyond reference; the reference's spray actor served
queries strictly one predict per request, CreateServer.scala:495-497).

Handler threads ``submit()`` and block on a future; one dispatcher
thread drains the queue. After a batch's first query arrives the
configured :class:`~predictionio_tpu.serving.batch_policy.BatchPolicy`
decides how long to wait for companions and how many to take (the
adaptive policy waits near-zero when idle, coalesces under load; the
fixed policy is the legacy constant window), then the whole batch runs
through ``DeployedEngine.query_batch``.

Hot-path guarantees, each carried by a counter in
:class:`~predictionio_tpu.api.stats.ServingStats`:

- queries whose resilience deadline already expired are FAILED at
  dequeue time (``QueryDeadlineExceeded`` → the server's 503) instead
  of being scored and discarded — a timed-out client must not consume
  a device slot;
- identical concurrent queries (same canonical-JSON key) dedup to ONE
  slot in the dispatched batch, every waiter sharing the result;
- a failing batch is retried query-by-query so one poisoned query 500s
  alone, skipping entries whose deadline expired during the batch
  attempt.

``get_deployed`` is read fresh per batch, so /reload hot-swaps apply
from the next batch on.

With a traced query in the batch the dispatcher also keeps a record of
its own: one ``dispatch`` trace a cycle in the server's ``TraceLog``,
whose four spans partition the thread's time from the end of the
previous cycle to the end of this one (``_close_cycle``;
docs/observability.md). What tracing itself costs runs after the
futures are set.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Any, NamedTuple

from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.obs.trace import Trace, use_trace
from predictionio_tpu.serving.batch_policy import BatchPolicy, FixedBatchPolicy
from predictionio_tpu.utils.resilience import (
    deadline_scope,
    record_fallback,
    remaining_deadline,
)

logger = logging.getLogger(__name__)


class QueryDeadlineExceeded(RuntimeError):
    """A query's time budget expired while WAITING for its result — as
    distinct from the work itself raising TimeoutError (which, on
    Python 3.11+, is the same class as concurrent.futures.TimeoutError
    and must not be misreported as a blown deadline)."""

    def __init__(self, budget: float):
        super().__init__(f"query deadline exceeded ({budget:.3f}s budget)")
        self.budget = budget


class _Pending(NamedTuple):
    query: Any
    fut: Future
    #: absolute monotonic deadline (None = unbounded)
    deadline: float | None
    #: the budget that produced the deadline, for error messages
    budget: float | None
    #: canonical dedup key (None = never deduped)
    key: str | None
    #: perf_counter at enqueue — the queue-wait component of serving
    #: latency is measured from here to the dispatch (obs histograms)
    t_enq: float
    #: the caller's trace, carried EXPLICITLY across the thread handoff
    #: (contextvars do not follow queue entries); None when tracing is
    #: off — the dispatcher's whole tracing cost is this None check
    trace: Any = None


#: the spans that end where the dispatcher hands a result back
_DISPATCH_SPANS = ("batcher.device_dispatch", "batcher.fallback_predict")


def _record_wake(trace) -> None:
    """``batcher.wake`` on the caller's trace: from the end of its
    dispatch (read back from the trace the dispatcher wrote before
    ``set_result``) to this thread running again."""
    now = time.perf_counter()
    for name, _, _, start, dur in reversed(trace.spans()):
        if name in _DISPATCH_SPANS:
            trace.add_span("batcher.wake", trace.start_perf + start + dur,
                           now)
            return


class QueryBatcher:
    """Policy-driven coalescing dispatcher (module docstring)."""

    def __init__(self, get_deployed, policy: BatchPolicy | None = None,
                 stats: ServingStats | None = None, batch_max: int = 64,
                 batch_wait_ms: float = 5.0, trace_log: Any = None):
        import queue as _queue

        self._get_deployed = get_deployed
        #: the server's TraceLog: where a traced cycle's ``dispatch``
        #: record goes (None: the record is made and not kept)
        self._trace_log = trace_log
        #: perf_counter at the end of the last cycle, if it was traced
        #: (dispatcher thread only): where ``dispatcher.idle`` starts
        self._cycle_end: float | None = None
        # legacy ctor shape (batch_max/batch_wait_ms) builds the fixed
        # policy PR 1 shipped with
        self._policy = policy or FixedBatchPolicy(
            batch_max=batch_max, wait_ms=batch_wait_ms)
        self.stats = stats or ServingStats()
        self._queue: "_queue.Queue" = _queue.Queue()
        self._stopped = False
        # callers currently blocked in submit — the closed-loop load
        # signal the policy uses to avoid holding the door for
        # companions that cannot exist (BatchPolicy.plan docstring)
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, name="pio-query-batcher", daemon=True)
        self._thread.start()

    @property
    def policy(self) -> BatchPolicy:
        return self._policy

    # counters kept as read-only views for the status page (the writers
    # live in ServingStats, lock-guarded at both ends)
    @property
    def batches(self) -> int:
        return self.stats.count("dispatches")

    @property
    def batched_queries(self) -> int:
        return self.stats.count("batched_queries")

    def submit(self, query: Any, timeout: float = 300.0,
               key: str | None = None, trace: Any = None) -> Any:
        """Enqueue and wait; raises whatever the predict path raised.

        The caller's ambient resilience deadline (deadline_scope) rides
        along into the dispatcher thread — contextvars do not cross
        threads, so the remaining budget is captured here and re-entered
        around the batch dispatch and any per-query fallbacks. A budget
        that is ALREADY exhausted fails here, before the queue. The
        caller's ``trace`` (obs/trace.py) rides the queue entry the
        same way: the dispatcher records this query's queue-wait, hold
        and device-dispatch spans (with the dispatch's own phases as
        children) onto it, and the caller adds ``batcher.wake`` when it
        runs again."""
        if self._stopped:
            raise RuntimeError("query batcher is stopped")
        rem = remaining_deadline()
        if rem is not None and rem <= 0:
            self.stats.bump("expired")
            raise QueryDeadlineExceeded(max(rem, 0.0))
        with self._inflight_lock:
            self._inflight += 1
        try:
            self._policy.observe_arrival()
            deadline = time.monotonic() + rem if rem is not None else None
            fut: Future = Future()
            self._queue.put(_Pending(query, fut, deadline, rem, key,
                                     time.perf_counter(), trace))
            if self._stopped and not fut.done():
                # close() raced the enqueue: the dispatcher (or close's
                # drain) may never see this entry — fail fast instead of
                # letting the handler hang out the timeout (done() guards
                # the benign double-completion race)
                try:
                    fut.set_exception(
                        RuntimeError("query batcher is stopped"))
                except Exception:
                    pass
            try:
                result = fut.result(timeout=timeout)
                if trace is not None:
                    _record_wake(trace)
                return result
            except FuturesTimeoutError:
                if not fut.done():
                    # the WAIT expired (a blown budget) — not an
                    # exception from the predict path, which fut.done()
                    # distinguishes even on 3.11 where the two classes
                    # are aliased
                    raise QueryDeadlineExceeded(timeout) from None
                raise
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    def close(self) -> None:
        self._stopped = True
        self._queue.put(None)
        self._thread.join(timeout=5)
        self._fail_pending()

    def _fail_pending(self) -> None:
        """Fail anything still queued after the dispatcher exited —
        a blocked submit must get its 500 now, not at timeout."""
        import queue as _queue

        while True:
            try:
                item = self._queue.get_nowait()
            except _queue.Empty:
                return
            if item is None:
                continue
            if not item.fut.done():
                try:
                    item.fut.set_exception(
                        RuntimeError("query batcher is stopped"))
                except Exception:
                    pass

    # -- dispatcher ---------------------------------------------------------
    def _run(self) -> None:
        import queue as _queue

        while True:
            item = self._queue.get()
            if item is None:
                return
            # the dispatcher is free from here on: what a query waits
            # after this is the policy holding the door (batcher.hold),
            # what it waited before was the previous dispatch
            t_first = time.perf_counter()
            batch = [item]
            # the policy decides how long to hold the door for FUTURE
            # arrivals and how many to wait for (snapped to the
            # jit-signature menu); queries that ALREADY queued while
            # the previous batch dispatched always ride along for free
            # (up to the menu cap) — under closed-loop load the queue
            # depth, not the inter-arrival EWMA, carries the signal
            # (blocked clients space their arrivals out exactly when
            # coalescing pays most)
            with self._inflight_lock:
                inflight = self._inflight
            wait_s, target = self._policy.plan(inflight=inflight)
            stop = False
            while len(batch) < self._policy.batch_max:
                try:
                    nxt = self._queue.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                batch.append(nxt)
            if not stop:
                deadline = time.perf_counter() + wait_s
                while len(batch) < target:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=remaining)
                    except _queue.Empty:
                        break
                    if nxt is None:
                        stop = True
                        break
                    batch.append(nxt)
            self._finish(batch, t_first)
            if stop:
                return

    @staticmethod
    def _scope(deadline_abs: float | None):
        """Re-enter a caller's deadline (absolute monotonic) on the
        dispatcher thread; nested scopes only ever shrink."""
        if deadline_abs is None:
            return contextlib.nullcontext()
        return deadline_scope(max(0.0, deadline_abs - time.monotonic()))

    def _expire(self, entry: _Pending) -> None:
        self.stats.bump("expired")
        if not entry.fut.done():
            try:
                entry.fut.set_exception(QueryDeadlineExceeded(
                    entry.budget if entry.budget is not None else 0.0))
            except Exception:
                pass

    def _finish(self, batch: list[_Pending], t_first: float) -> None:
        """One dispatcher cycle from its first dequeue on. A cycle with
        a traced rider is recorded (module docstring): the record is in
        the ring before the first future is set, so whoever has an
        answer finds the ring in the order dispatch, then request; it
        is filled in after the last. The dispatcher's whole tracing
        cost for an untraced batch is the None checks here."""
        traced = [e for e in batch if e.trace is not None]
        if not traced:
            self._cycle_end = None
            self._serve(batch, t_first, None)
            return
        record = Trace("dispatch", service="engine",
                       start_perf=(t_first if self._cycle_end is None
                                   else self._cycle_end))
        if self._trace_log is not None:
            self._trace_log.record(record)
        self._close_cycle(record, traced, t_first,
                          *self._serve(batch, t_first, record))

    def _close_cycle(self, record: Trace, traced: list[_Pending],
                     t_first: float, live: int, groups: int,
                     t0: float | None, t1: float | None) -> None:
        """Fill the cycle's record in: the four spans meet end to start,
        from the previous traced cycle's end (none: a first cycle) to
        this instant; a cycle whose riders all expired is all
        ``dispatcher.collect``."""
        t_end = time.perf_counter()
        if self._cycle_end is not None:
            record.add_span("dispatcher.idle", self._cycle_end, t_first)
        if t0 is None:
            record.add_span("dispatcher.collect", t_first, t_end)
        else:
            record.add_span("dispatcher.collect", t_first, t0)
            record.add_span("dispatcher.dispatch", t0, t1)
            record.add_span("dispatcher.handoff", t1, t_end)
        for e in traced:
            e.trace.tags["dispatch"] = record.trace_id
        record.finish(end_perf=t_end, batch=live, groups=groups,
                      traced=len(traced),
                      requests=[e.trace.trace_id for e in traced])
        self._cycle_end = t_end

    def _serve(self, batch: list[_Pending], t_first: float,
               record: Trace | None):
        """Expire, dedup, dispatch, hand the results back. Returns
        ``(live queries, groups, dispatch start, query_batch returned)``
        on the perf_counter clock, the last two None when nothing was
        dispatched; read only where ``record`` says the cycle is
        traced."""
        # 1. fail anything already past its deadline — dispatching it
        # would burn a device slot on a client that stopped waiting
        now = time.monotonic()
        live: list[_Pending] = []
        for entry in batch:
            if entry.deadline is not None and now >= entry.deadline:
                self._expire(entry)
            else:
                live.append(entry)
        if not live:
            return 0, 0, None, None
        # 2. dedup identical concurrent queries (same canonical key):
        # one device slot, every waiter shares the result
        groups: list[list[_Pending]] = []
        by_key: dict[str, int] = {}
        for entry in live:
            if entry.key is not None and entry.key in by_key:
                groups[by_key[entry.key]].append(entry)
            else:
                if entry.key is not None:
                    by_key[entry.key] = len(groups)
                groups.append([entry])
        deployed = self._get_deployed()
        deadlines = [e.deadline for e in live if e.deadline is not None]
        # the batch shares one dispatch: honor its tightest deadline
        t0 = time.perf_counter()
        try:
            # queue-wait attribution (enqueue -> dispatch start): one
            # lock acquisition for the whole batch's samples, plus the
            # per-entry trace spans when tracing rode along;
            # batcher.hold is the part of the wait spent with the
            # dispatcher free (from the batch's first dequeue, or this
            # entry's own arrival if later)
            self.stats.observe_queue_waits([t0 - e.t_enq for e in live])
            traced = ([e for e in live if e.trace is not None]
                      if record is not None else ())
            for e in traced:
                e.trace.add_span(
                    "batcher.hold", max(t_first, e.t_enq), t0,
                    e.trace.add_span("batcher.queue_wait", e.t_enq, t0))
            # one ambient trace for the dispatch, only when a traced
            # query rides in it: ``span()`` calls under query_batch
            # (dispatch.* phases, obs/compile's xla_compile, a
            # gc.pause) record on it and are copied below onto each
            # traced entry's trace. The cycle's record takes none of
            # them: in the ring its names are its own four
            phases = Trace("dispatch") if traced else None
            ambient = (use_trace(phases) if phases is not None
                       else contextlib.nullcontext())
            with self._scope(min(deadlines) if deadlines else None), ambient:
                results = deployed.query_batch([g[0].query for g in groups])
            t1 = time.perf_counter()
            dt = t1 - t0
            self.stats.observe_device_time(dt)
            # results before bookkeeping: a traced rider gets the one
            # span its wake-up reads (_record_wake) ahead of its
            # result, the phases under it once every waiter has its own
            parents = [e.trace.add_span("batcher.device_dispatch", t0, t1)
                       for e in traced]
            # query_batch records request bookkeeping only for the
            # group leaders it saw; the deduped waiters were answered
            # by the same dispatch and must count as served requests
            # too (same invariant the server applies to cache hits)
            for _ in range(len(live) - len(groups)):
                deployed.record_served(dt)
            for group, served in zip(groups, results):
                for entry in group:
                    if not entry.fut.done():
                        try:
                            entry.fut.set_result(served)
                        except Exception:
                            pass
            self.stats.record_batch(len(groups), len(live))
            for e, parent in zip(traced, parents):
                e.trace.add_spans_from(phases, parent)
        except Exception:
            logger.exception(
                "batched predict failed; retrying %d quer(ies) individually",
                len(groups))
            record_fallback("serving/query-batcher")
            for group in groups:
                self._fallback_group(group)
            # the cycle's dispatch covers the failed batch and the
            # retries, which set their futures themselves
            t1 = time.perf_counter() if record is not None else None
        return len(live), len(groups), t0, t1

    _UNSET = object()

    def _fallback_group(self, group: list[_Pending]) -> None:
        """Per-query retry of one dedup group after a failed batch: one
        predict shared by the group's waiters; entries whose deadline
        expired during the batch attempt are failed, not retried."""
        outcome: Any = self._UNSET
        err: Exception | None = None
        for entry in group:
            if entry.fut.done():
                continue
            if entry.deadline is not None and time.monotonic() >= entry.deadline:
                self._expire(entry)
                continue
            if outcome is self._UNSET and err is None:
                t0 = time.perf_counter()
                try:
                    # re-resolve per query: a /reload mid-batch must not
                    # pin the whole fallback pass to the dead instance
                    # the batch dispatch captured
                    with self._scope(entry.deadline):
                        outcome = self._get_deployed().query(entry.query)
                except Exception as e:          # noqa: BLE001
                    err = e
                if entry.trace is not None:
                    entry.trace.add_span("batcher.fallback_predict", t0,
                                         time.perf_counter())
            try:
                if err is not None:
                    entry.fut.set_exception(err)
                else:
                    entry.fut.set_result(outcome)
            except Exception:
                pass
