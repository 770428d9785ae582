"""Event-server bookkeeping: per-app counts of status codes and
(entityType, targetEntityType, event) triples, kept in hourly buckets.

Parity: data/src/main/scala/.../data/api/{Stats.scala:30-82,
StatsActor.scala} — the reference rotates a ``Stats`` per hour inside
``StatsActor``; here ``StatsKeeper`` owns the rotation under a lock
instead of an actor mailbox.

Beyond reference: :func:`resilience_snapshot` surfaces the per-backend
retry/circuit-breaker counters (utils/resilience registry) so both
servers' stats/status documents show backend health alongside traffic,
and :class:`ServingStats` carries the engine server's hot-path counters
(batch-size histogram, adaptive-wait EWMA input, result-cache hit/miss/
eviction, per-batch dedup) for ``GET /stats.json``.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import Counter
from datetime import datetime, timezone

from predictionio_tpu.core.event import Event
from predictionio_tpu.core.json_codec import format_datetime
from predictionio_tpu.core.wire import snake_to_camel
from predictionio_tpu.obs.histogram import LatencyHistogram


def resilience_snapshot() -> dict:
    """Per-backend resilience counters: attempts, retries, failures,
    short-circuits, breaker state/opens — keyed by policy name
    (``<backend>/<source>``). Empty until a resilient backend is used."""
    from predictionio_tpu.utils.resilience import registry_snapshot

    return registry_snapshot()


class ServingStats:
    """Counters for the engine server's query hot path, written by the
    batcher dispatcher (batch records), the result cache (hit/miss/
    eviction), and handler threads (expiries) — one lock guards every
    field at writers AND readers, the same discipline as
    :class:`StatsKeeper`/``ResilienceMetrics``, so no reader ever sees a
    torn histogram and the lock-discipline lint needs no suppressions."""

    COUNTER_FIELDS = (
        "dispatches", "topk_two_stage_dispatches",
        "batched_queries", "deduped", "expired",
        "cache_hits", "cache_misses", "cache_evictions",
        "cache_expirations", "cache_invalidations",
        "cache_user_invalidations",
        "ann_queries", "ann_rescored",
        # the session engine's dispatches (templates/sessionrec)
        "seq_programs", "seq_tokens", "seq_padded_tokens",
        "seq_split_dispatches",
        "seq_moe_assignments", "seq_moe_tokens", "seq_moe_max_expert_load",
        "seq_sparse_rows", "seq_sparse_blocks_selected",
        "seq_sparse_keys_scored",
        "seq_ssm_tokens", "seq_ssm_scan_steps", "seq_window_rows",
        "seq_window_keys_scored",
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self.COUNTER_FIELDS, 0)
        #: dispatched (post-dedup) batch size -> count
        self._batch_hist: Counter[int] = Counter()
        #: ANN shortlist width (candidate columns rescored per query,
        #: pad included — the static jit width) -> query count
        self._ann_hist: Counter[int] = Counter()
        #: byte width of an entry of the table the brute-force
        #: recommend programs score from (2: the model's bfloat16
        #: serving copy; 4: a float32 table); 0 until a server wires a
        #: model that has one, and while it answers through ANN
        self._score_table_bytes = 0
        #: the Pallas kernels the session engine's serving programs
        #: engage (models/seqrec.kernels); empty until a server wires a
        #: session model
        self._seq_kernels: tuple[str, ...] = ()
        #: latency attribution (obs/histogram.py; each histogram owns
        #: its own lock): queue component vs device component of the
        #: batched serving path — the Clipper-style split GET /metrics
        #: and /traces.json surface (docs/observability.md)
        self.queue_wait = LatencyHistogram()
        self.device_time = LatencyHistogram()

    def bump(self, field: str, n: int = 1) -> None:
        with self._lock:
            self._counts[field] += n

    def observe_queue_waits(self, waits) -> None:
        """Per-entry enqueue→dispatch waits for one batch (one lock
        acquisition for the whole batch)."""
        self.queue_wait.observe_many(waits)

    def observe_device_time(self, dt: float) -> None:
        """One batch's query_batch walltime."""
        self.device_time.observe(dt)

    def record_batch(self, dispatched: int, coalesced: int) -> None:
        """One device dispatch: ``dispatched`` unique queries actually
        scored, ``coalesced`` queries answered by it (>= dispatched when
        the dedup pass folded identical concurrent queries)."""
        with self._lock:
            self._counts["dispatches"] += 1
            self._counts["batched_queries"] += coalesced
            self._counts["deduped"] += coalesced - dispatched
            self._batch_hist[dispatched] += 1

    def record_ann(self, shortlist_width: int, queries: int = 1) -> None:
        """One ANN retrieval dispatch: ``queries`` queries answered from
        a ``shortlist_width``-candidate rescore each (the ALSModel
        observer hook — models/als.set_ann_observer)."""
        with self._lock:
            self._counts["ann_queries"] += queries
            self._counts["ann_rescored"] += shortlist_width * queries
            self._ann_hist[shortlist_width] += queries

    def record_two_stage_topk(self) -> None:
        """One brute-force top-k dispatch whose program selected in two
        stages (group maxima, then the winning groups): the ALSModel
        observer hook, models/als.set_topk_observer."""
        self.bump("topk_two_stage_dispatches")

    def set_score_table_bytes(self, width: int) -> None:
        """The byte width of an entry of the item table brute-force
        dispatches read (``ALSModel.score_table_bytes_per_entry``): a
        gauge the engine server sets when it wires its models."""
        with self._lock:
            self._score_table_bytes = int(width)

    def score_table_bytes(self) -> int:
        with self._lock:
            return self._score_table_bytes

    def set_seq_kernels(self, names) -> None:
        """The Pallas kernels the session models' serving programs
        engage (``models/seqrec.kernels`` at ``max_len``): a gauge the
        engine server sets when it wires its models."""
        with self._lock:
            self._seq_kernels = tuple(sorted(set(names)))

    def seq_kernels(self) -> tuple[str, ...]:
        with self._lock:
            return self._seq_kernels

    def record_seq_dispatch(self, report) -> None:
        """One ``batch_predict`` of the session engine, as the record
        ``templates/sessionrec.SeqDispatch`` (the SeqRecEngineModel
        observer hook): each of its fields adds to the counter
        ``seq_<field>`` (``split`` to ``seq_split_dispatches``)."""
        with self._lock:
            for field in dataclasses.fields(report):
                name = "split_dispatches" if field.name == "split" \
                    else field.name
                self._counts["seq_" + name] += getattr(report, field.name)

    def ann_histogram(self) -> dict[int, int]:
        """Shortlist width -> query count, read under the lock."""
        with self._lock:
            return dict(self._ann_hist)

    def count(self, field: str) -> int:
        with self._lock:
            return self._counts[field]

    def raw_counts(self) -> dict[str, int]:
        """All counters under ONE lock acquisition (snake_case keys) —
        the metric-registry adapter's read (obs/registry.py)."""
        with self._lock:
            return dict(self._counts)

    def batch_histogram(self) -> dict[int, int]:
        """Dispatched batch-size -> count, read under the lock."""
        with self._lock:
            return dict(self._batch_hist)

    def snapshot(self) -> dict:
        with self._lock:
            counts = dict(self._counts)
            hist = {str(k): v for k, v in sorted(self._batch_hist.items())}
            ann_hist = {str(k): v
                        for k, v in sorted(self._ann_hist.items())}
            table_bytes = self._score_table_bytes
            seq_kernels = list(self._seq_kernels)
        hits, misses = counts["cache_hits"], counts["cache_misses"]
        looked = hits + misses
        return {
            **{snake_to_camel(k): v for k, v in counts.items()},
            "scoreTableBytesPerEntry": table_bytes,
            "seqKernels": seq_kernels,
            "batchSizeHistogram": hist,
            "annShortlistHistogram": ann_hist,
            "cacheHitRatio": round(hits / looked, 4) if looked else None,
            "queueWait": self.queue_wait.snapshot().summary_ms(),
            "deviceDispatch": self.device_time.snapshot().summary_ms(),
        }


class IngestStats:
    """Counters for the event server's ingest path, written by the
    request handlers after each successful insert/insert_batch — the
    same one-lock-at-writers-AND-readers discipline as
    :class:`ServingStats`, so a ``GET /stats.json`` reader never sees a
    torn histogram and the lock-discipline lint needs no suppressions.

    ``events_per_sec_ewma`` smooths the instantaneous batch rate
    (batch size / time since the previous batch) with EWMA_ALPHA.
    Caveat (bench discipline): under a closed-loop load generator the
    EWMA tracks the generator's issue rate, not server capacity — treat
    it as an observability signal, not a benchmark number. The
    windowed rate below does NOT share that bias: a ring of per-second
    monotonic buckets counts what actually landed each wall second, so
    ``eventsPerSecWindowed`` is a true recent-throughput number
    (complete seconds only — the current partial second is excluded so
    a mid-second read never underreports)."""

    EWMA_ALPHA = 0.2
    #: SKIP (not clamp) the EWMA update for gaps below this: two
    #: handler threads landing in the same instant would otherwise
    #: divide by ~zero and fold a meaningless multi-million-events/sec
    #: spike into the average
    _MIN_DT = 1e-6
    #: per-second ring span: the windowed rate covers up to this many
    #: complete seconds (Prometheus-style "last minute" semantics)
    WINDOW_SECONDS = 60

    def __init__(self, clock=None):
        import time

        self._now = clock or time.monotonic
        self._lock = threading.Lock()
        self._batches = 0
        self._events = 0
        #: inserted batch size -> count (1 = single-event posts)
        self._batch_hist: Counter[int] = Counter()
        self._last_t: float | None = None
        self._ewma_rate: float | None = None
        #: per-second event counts: slot i holds the count for the
        #: monotonic second recorded in _ring_sec[i]; a slot whose
        #: second moved on is reset lazily at the next write
        self._ring = [0] * self.WINDOW_SECONDS
        self._ring_sec = [-1] * self.WINDOW_SECONDS
        self._first_sec: int | None = None
        #: storage insert/insert_batch walltime (obs/histogram.py;
        #: owns its own lock) — fed by the event server's ingest paths
        self.insert_latency = LatencyHistogram()

    def record_batch(self, n: int) -> None:
        """One successful storage insert of ``n`` events."""
        if n <= 0:
            return
        with self._lock:
            # clock read INSIDE the lock: a thread that read the clock
            # before losing the lock race would otherwise compute a
            # negative-then-clamped dt and spike the EWMA
            now = self._now()
            self._batches += 1
            self._events += n
            self._batch_hist[n] += 1
            sec = int(now)
            idx = sec % self.WINDOW_SECONDS
            if self._ring_sec[idx] != sec:
                self._ring[idx] = 0
                self._ring_sec[idx] = sec
            self._ring[idx] += n
            if self._first_sec is None:
                self._first_sec = sec
            if self._last_t is not None:
                dt = now - self._last_t
                if dt >= self._MIN_DT:
                    inst = n / dt
                    self._ewma_rate = (
                        inst if self._ewma_rate is None
                        else self.EWMA_ALPHA * inst
                        + (1.0 - self.EWMA_ALPHA) * self._ewma_rate)
            self._last_t = now

    def _windowed_rate_locked(self) -> tuple[float | None, int]:
        """(events/sec over complete seconds, window length) — caller
        holds the lock. None until one full second has elapsed."""
        if self._first_sec is None:
            return None, 0
        now_sec = int(self._now())
        # complete seconds only: [now_sec - window, now_sec)
        window = min(self.WINDOW_SECONDS - 1, now_sec - self._first_sec)
        if window <= 0:
            return None, 0
        lo = now_sec - window
        total = sum(
            count
            for count, sec in zip(self._ring, self._ring_sec)
            if lo <= sec < now_sec
        )
        return total / window, window

    def totals(self) -> tuple[int, int]:
        """(batches, events) under one lock — the registry adapter."""
        with self._lock:
            return self._batches, self._events

    def batch_histogram(self) -> dict[int, int]:
        with self._lock:
            return dict(self._batch_hist)

    def rates(self) -> tuple[float | None, float | None, int]:
        """(ewma, windowed, window_seconds) under one lock."""
        with self._lock:
            windowed, window = self._windowed_rate_locked()
            return self._ewma_rate, windowed, window

    def snapshot(self) -> dict:
        with self._lock:
            batches, events = self._batches, self._events
            hist = {str(k): v for k, v in sorted(self._batch_hist.items())}
            rate = self._ewma_rate
            windowed, window = self._windowed_rate_locked()
        return {
            "batches": batches,
            "events": events,
            "meanBatchSize": round(events / batches, 2) if batches else None,
            "batchSizeHistogram": hist,
            "eventsPerSecEwma": round(rate, 1) if rate is not None else None,
            "eventsPerSecWindowed": (
                round(windowed, 1) if windowed is not None else None),
            "windowSeconds": window,
            "insertLatency": self.insert_latency.snapshot().summary_ms(),
        }


@dataclasses.dataclass(frozen=True)
class EntityTypesEvent:
    """Parity: EntityTypesEvent (Stats.scala:30-39)."""
    entity_type: str
    target_entity_type: str | None
    event: str

    @staticmethod
    def of(e: Event) -> "EntityTypesEvent":
        return EntityTypesEvent(e.entity_type, e.target_entity_type, e.event)


class Stats:
    """One bucket of counts. Parity: Stats (Stats.scala:51-82)."""

    def __init__(self, start_time: datetime):
        self.start_time = start_time
        self.end_time: datetime | None = None
        self.status_code_count: Counter[tuple[int, int]] = Counter()
        self.ete_count: Counter[tuple[int, EntityTypesEvent]] = Counter()

    def cutoff(self, end_time: datetime) -> None:
        self.end_time = end_time

    def update(self, app_id: int, status_code: int, event: Event) -> None:
        self.status_code_count[(app_id, status_code)] += 1
        self.ete_count[(app_id, EntityTypesEvent.of(event))] += 1

    def get(self, app_id: int) -> dict:
        """JSON snapshot for one app (Stats.get -> StatsSnapshot)."""
        return {
            "startTime": format_datetime(self.start_time),
            "endTime": format_datetime(self.end_time) if self.end_time else None,
            "basic": [
                {
                    "key": {
                        "entityType": k[1].entity_type,
                        "targetEntityType": k[1].target_entity_type,
                        "event": k[1].event,
                    },
                    "value": v,
                }
                for k, v in sorted(self.ete_count.items(), key=lambda kv: repr(kv[0]))
                if k[0] == app_id
            ],
            "statusCode": [
                {"key": k[1], "value": v}
                for k, v in sorted(self.status_code_count.items())
                if k[0] == app_id
            ],
        }


def _hour_floor(t: datetime) -> datetime:
    return t.replace(minute=0, second=0, microsecond=0)


class StatsKeeper:
    """Thread-safe hourly rotation: current hour + previous hour.
    Parity: StatsActor's Bookkeeping/GetStats handling."""

    def __init__(self):
        now = datetime.now(timezone.utc)
        self._lock = threading.Lock()
        self._current = Stats(_hour_floor(now))
        self._previous = Stats(_hour_floor(now))

    def _rotate(self, now: datetime) -> None:
        hour = _hour_floor(now)
        if hour > self._current.start_time:
            self._current.cutoff(hour)
            self._previous = self._current
            self._current = Stats(hour)

    def update(self, app_id: int, status_code: int, event: Event) -> None:
        now = datetime.now(timezone.utc)
        with self._lock:
            self._rotate(now)
            self._current.update(app_id, status_code, event)

    def get(self, app_id: int) -> dict:
        """Both buckets, keyed like the reference's Map[String, StatsSnapshot]."""
        with self._lock:
            self._rotate(datetime.now(timezone.utc))
            return {
                "time": format_datetime(datetime.now(timezone.utc)),
                "currentHour": self._current.get(app_id),
                "prevHour": self._previous.get(app_id),
            }
