"""The Engine Server: prediction serving on :8000.

Route and behavior parity with the reference deploy server
(reference: core/src/main/scala/.../workflow/CreateServer.scala):

- ``GET /``              status document (:442-469 — twirl HTML page;
                         here JSON, plus HTML when Accept asks for it)
- ``POST /queries.json`` the query path (:470-621): bind query JSON →
                         ``serving.supplement`` → sequential per-algorithm
                         ``predict`` → ``serving.serve`` → optional
                         feedback events → output-blocker plugins →
                         latency bookkeeping
- ``GET|POST /reload``   hot-swap to the latest completed instance
                         (:316-342; key-authenticated)
- ``POST /stop``         shutdown (:633-646; key-authenticated)
- ``GET /plugins.json``  plugin listing (:648-671)
- ``GET /healthz``       liveness (beyond reference; k8s-style contract)
- ``GET /readyz``        readiness: model loaded + storage reachable
- ``GET /stats.json``    serving hot-path internals (beyond reference):
                         batch-size histogram, adaptive-wait EWMA,
                         cache hit ratio, dedup count, resilience
- ``POST /retrieval``    runtime retrieval reconfig (brute <-> ann,
                         nprobe/rescore; key-authenticated)

Prefork worker pool (``pio deploy --workers N``; docs/
serving-performance.md "Multi-process serving"): N of these servers
run as separate processes sharing one SO_REUSEPORT listen port. Each
holds its own model/batcher/cache/registry; a ``/metrics`` or
``/stats.json`` scrape landing on any worker merges every sibling
(fleet/workers.WorkerHub + obs/aggregate.merge_sources),
``/traces.json`` folds sibling rings in, and the admin surfaces
(``/reload``, ``/drain``, ``POST /retrieval``) publish a sequenced
admin-state document every sibling's sync loop applies
(serving/workers.WorkerCoherence) — so a reload bumps the result-cache
generation on ALL workers, not the 1/N the connection hash happened to
pick.

Graceful degradation (beyond reference, docs/operations-resilience.md):
storage-unavailable failures map to ``503`` + ``Retry-After`` instead of
``500``; a failed ``/reload`` keeps serving the last-known-good model;
``ServerConfig.request_deadline_ms`` (or an ``X-PIO-Deadline-Ms``
request header) bounds each query's time budget, propagated to the
micro-batcher and the storage resilience layer.

The reference's MasterActor/ServerActor pair collapses to
``EngineServer`` (HTTP lifecycle, bind retry ×3 — :347-357) over
``EngineService`` (transport-free request logic). The feedback loop
(:514-576) POSTs ``predict`` events to the event server from a
fire-and-forget thread, tagging responses with a ``prId``.

Serving hot path (docs/serving-performance.md): the query envelope
binds/encodes through the precompiled codecs (core/json_codec.
compile_wire_decoder / encode_wire) instead of the per-request
reflective binder; an opt-in result cache (ServerConfig.cache_enabled)
answers repeated queries without a dispatch and invalidates atomically
on /reload; the micro-batcher is policy-driven
(ServerConfig.batch_policy — adaptive EWMA wait by default) with
per-batch dedup of identical concurrent queries.
"""

from __future__ import annotations

import abc
import contextlib
import contextvars
import dataclasses
import json
import logging
import queue
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeoutError
from http.server import BaseHTTPRequestHandler
from typing import Any, Mapping
from urllib.parse import parse_qs, urlparse

from predictionio_tpu.api.http_base import (
    REQUEST_ID_HEADER,
    PlainTextPayload,
    RestServer,
    access_log_enabled,
    bounded_probe,
    emit_access_log,
    ensure_access_log_handler,
    parse_deadline_budget,
    resolve_request_id,
    retry_after_header,
)
from predictionio_tpu.api.stats import ServingStats, resilience_snapshot
from predictionio_tpu.core.json_codec import (
    canonical_json,
    compile_wire_decoder,
    encode_wire,
)
from predictionio_tpu.obs.aggregate import (
    ExpositionParseError,
    merge_sources,
    parse_exposition,
    source_count_metric,
)
from predictionio_tpu.obs.compile import compile_metrics_collector
from predictionio_tpu.obs.compile import recorder as compile_recorder
from predictionio_tpu.obs.device import (
    device_memory_collector,
    train_report_collector,
)
from predictionio_tpu.obs.exporter import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from predictionio_tpu.obs.exporter import render_metrics, render_prometheus
from predictionio_tpu.obs.registry import (
    HistogramFamily,
    Metric,
    MetricRegistry,
    online_collector,
    resilience_collector,
    server_info_collector,
    serving_collector,
)
from predictionio_tpu.obs.slo import SLOEngine, serving_pressure_collector
from predictionio_tpu.obs.trace import (
    PARENT_SPAN_HEADER,
    TRACE_ID_HEADER,
    GcPauseSpans,
    TraceLog,
    active_trace,
    parse_trace_context,
    span,
    start_trace,
    tracing_default,
    use_trace,
)
from predictionio_tpu.serving.batch_policy import make_batch_policy
from predictionio_tpu.serving.result_cache import ResultCache
from predictionio_tpu.serving.workers import WorkerCoherence
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.utils.resilience import (
    STORAGE_UNAVAILABLE_ERRORS,
    deadline_scope,
    record_fallback,
    retry_after_hint,
)
from predictionio_tpu.workflow.context import EngineContext
from predictionio_tpu.workflow.deploy import (
    DeployedEngine,
    QueryBatcher,
    QueryDeadlineExceeded,
    ServerConfig,
    apply_retrieval_config,
    load_deployed_engine,
    retrieval_targets,
)

logger = logging.getLogger(__name__)

OUTPUT_BLOCKER = "outputblocker"
OUTPUT_SNIFFER = "outputsniffer"


@dataclasses.dataclass(frozen=True)
class QueryInfo:
    """What engine-server plugins observe per query
    (EngineServerPlugin.scala:33-41)."""
    query: Any
    prediction: Any
    engine_instance_id: str


class EngineServerPlugin(abc.ABC):
    """Parity: EngineServerPlugin (workflow/EngineServerPlugin.scala:22-41).
    Output blockers run synchronously and may transform (or reject, by
    raising) the prediction; sniffers observe asynchronously."""

    plugin_name: str = "plugin"
    plugin_description: str = ""
    plugin_type: str = OUTPUT_SNIFFER

    @abc.abstractmethod
    def process(self, info: QueryInfo, context: "EngineServerPluginContext") -> Any:
        """Blockers return the (possibly transformed) prediction."""


class EngineServerPluginContext:
    """Parity: EngineServerPluginContext.scala:39-91 +
    EngineServerPluginsActor (async sniffer fan-out as a worker thread)."""

    def __init__(self, plugins: list[EngineServerPlugin] | None = None):
        plugins = list(plugins or [])
        self.output_blockers = {
            p.plugin_name: p for p in plugins if p.plugin_type == OUTPUT_BLOCKER
        }
        self.output_sniffers = {
            p.plugin_name: p for p in plugins if p.plugin_type == OUTPUT_SNIFFER
        }
        # one daemon worker drains sniffer notifications off the serving
        # hot path (the EngineServerPluginsActor role)
        self._queue: "queue.Queue[QueryInfo | None]" = queue.Queue()
        self._worker: threading.Thread | None = None
        if self.output_sniffers:
            self._worker = threading.Thread(
                target=self._drain, name="pio-output-sniffers", daemon=True
            )
            self._worker.start()

    def run_blockers(self, info: QueryInfo) -> Any:
        """Fold the prediction through all blockers
        (CreateServer.scala:578-581). Exceptions propagate and reject the
        query (the caller maps them to an HTTP error)."""
        prediction = info.prediction
        for blocker in self.output_blockers.values():
            prediction = blocker.process(
                dataclasses.replace(info, prediction=prediction), self
            )
        return prediction

    def notify_sniffers(self, info: QueryInfo) -> None:
        if self._worker is not None:
            self._queue.put(info)

    def _drain(self) -> None:
        while True:
            info = self._queue.get()
            if info is None:
                return
            for sniffer in self.output_sniffers.values():
                try:
                    sniffer.process(info, self)
                except Exception:
                    logger.exception("output sniffer %s failed", sniffer.plugin_name)

    def close(self) -> None:
        if self._worker is not None:
            self._queue.put(None)
            self._worker.join(timeout=5)
            self._worker = None

    def describe(self) -> dict:
        def block(plugins: dict[str, EngineServerPlugin]) -> dict:
            return {
                name: {
                    "name": p.plugin_name,
                    "description": p.plugin_description,
                    "class": type(p).__qualname__,
                }
                for name, p in plugins.items()
            }

        return {
            "plugins": {
                "outputblockers": block(self.output_blockers),
                "outputsniffers": block(self.output_sniffers),
            }
        }


class _HtmlPage(str):
    """Marker: payload is a rendered HTML page, not JSON."""


class _Reject(Exception):
    def __init__(self, status: int, message: str,
                 headers: dict[str, str] | None = None):
        self.status = status
        self.message = message
        self.headers = headers


class EngineService:
    """Transport-free request logic — the ServerActor routes
    (CreateServer.scala:405-683)."""

    def __init__(
        self,
        deployed: DeployedEngine,
        config: ServerConfig | None = None,
        storage: Storage | None = None,
        ctx: EngineContext | None = None,
        plugin_context: EngineServerPluginContext | None = None,
    ):
        # built at CALL time: a module-level default instance would
        # freeze the PIO_SERVING_* env reads at import
        config = config if config is not None else ServerConfig()
        self.deployed = deployed
        self.config = config
        self.storage = storage
        self.ctx = ctx
        self.plugins = plugin_context or EngineServerPluginContext()
        #: set by the HTTP wrapper; called on authorized POST /stop
        self.on_stop = lambda: None
        #: set by the HTTP wrapper; mid-request client-disconnect count
        self.client_disconnects = lambda: 0
        #: one counter set shared by batcher + cache (GET /stats.json)
        self.serving_stats = ServingStats()
        #: opt-in result cache: canonical-query-JSON -> prediction,
        #: invalidated on successful /reload (ResultCache docs). With
        #: --shm-cache the pool shares ONE seqlock-slotted segment
        #: (serving/shm_cache) behind the same interface; a platform
        #: without shared memory warns and falls back to the private
        #: LRU — same contract, worker-local warmth
        self.cache = None
        if config.cache_enabled:
            if config.shm_cache:
                from predictionio_tpu.serving.shm_cache import open_shm_cache

                self.cache = open_shm_cache(config,
                                            stats=self.serving_stats)
                if self.cache is not None:
                    # the pool-reload put fence (ShmResultCache
                    # docstring): between a sibling's /reload bump and
                    # THIS worker's own model swap (up to one admin
                    # sync interval), local computations are old-model
                    # results — the cache must refuse to publish them
                    # into the new generation
                    self.cache.model_generation_fn = (
                        lambda: self.model_generation)
            if self.cache is None:
                self.cache = ResultCache(
                    max_entries=config.cache_max_entries,
                    ttl_s=config.cache_ttl_s,
                    stats=self.serving_stats)
        #: the ring behind /traces.json: one trace a traced request
        #: and, with batching, one ``dispatch`` trace a dispatcher cycle
        self.trace_log = TraceLog()
        #: opt-in micro-batching: concurrent queries coalesce into one
        #: device dispatch (ServerConfig.batching; QueryBatcher docs);
        #: the wait/target per batch comes from the configured policy
        self.batcher: QueryBatcher | None = (
            QueryBatcher(lambda: self.deployed,
                         policy=make_batch_policy(config.batch_policy,
                                                  config.batch_max,
                                                  config.batch_wait_ms),
                         stats=self.serving_stats,
                         trace_log=self.trace_log)
            if config.batching else None
        )
        #: precompiled query binder — refreshed on /reload with the new
        #: instance's query class (core/json_codec fast path)
        self._query_decoder = (
            compile_wire_decoder(qc)
            if (qc := deployed.query_class) is not None else None)
        #: observability plane (docs/observability.md): per-request
        #: tracing (opt-in; config wins, else PIO_TRACE), structured
        #: access logs (config wins, else PIO_ACCESS_LOG), and the
        #: per-server metric registry GET /metrics renders
        self.tracing = (config.tracing if config.tracing is not None
                        else tracing_default())
        self.access_log = access_log_enabled(config.access_log)
        if self.access_log:
            ensure_access_log_handler()
        #: collector pauses as spans on whichever traced thread they
        #: ran on (obs/trace.GcPauseSpans): installed when an
        #: EngineServer is built around this service, removed when it
        #: closes
        self.gc_pauses = GcPauseSpans() if self.tracing else None
        self.request_latency = HistogramFamily(
            "pio_http_request_seconds",
            "HTTP request walltime by route (handler-measured)",
            "route", ("queries", "stats", "metrics", "status"))
        self.registry = MetricRegistry()
        self.registry.register(self.request_latency.collect)
        self.registry.register(serving_collector(self.serving_stats))
        self.registry.register(resilience_collector())
        self.registry.register(server_info_collector("engine"))
        #: SLO burn-rate gauges + the queue-pressure autoscaler signal
        #: (obs/slo.py; docs/fleet.md): outcomes recorded per query by
        #: the handler, evaluated at scrape time only
        self.slo = SLOEngine()
        self.registry.register(self.slo.collector())
        self.registry.register(
            serving_pressure_collector(self.serving_stats))
        #: sublinear-retrieval observability (docs/serving-performance.md):
        #: ANN-capable models report their dispatches into ServingStats
        #: (pio_serving_ann_* on /metrics, annShortlistHistogram on
        #: /stats.json); re-wired on every /reload since the swap brings
        #: fresh model objects
        self._wire_ann_observers()
        self.registry.register(self._ann_mode_collector)
        #: device/compiler observability (docs/observability.md "Device
        #: and compiler observability"): the recompile sentinel's
        #: counters (pio_jit_compiles_total / pio_serving_recompile_total),
        #: device memory gauges (absent on backends without
        #: memory_stats), and the last profiled train's MFU/HBM gauges.
        #: Warmup is marked when this deployment answers its FIRST
        #: query — every later compile is a live request paying the
        #: XLA cliff and counts as a serving recompile with a WARN
        #: (operators warm their batch widths before fronting traffic;
        #: runbook in docs/observability.md)
        self.registry.register(compile_metrics_collector())
        self.registry.register(device_memory_collector())
        self.registry.register(train_report_collector())
        self._compile_warmup_marked = False
        #: deadline enforcement for the NON-batched path: the query runs
        #: on a pool thread so a blown budget returns 503 instead of
        #: holding the socket (threads spawn lazily; idle pool is free)
        self._query_pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="pio-query-deadline")
        #: /reload-in-flight count: while > 0, /readyz reports 503 so a
        #: fleet router's membership loop stops routing here mid-model-
        #: swap instead of racing the hot swap (docs/fleet.md); queries
        #: already in flight still answer (last-known-good semantics on
        #: reload failure are unchanged). Lock-guarded at writer and
        #: readers (handler threads on both sides).
        self._reload_lock = threading.Lock()
        self._reloads_in_flight = 0
        #: drain latch (POST /drain): while set, /readyz answers 503
        #: "draining" so every router's membership loop stops routing
        #: here — the fleet supervisor's drain-before-SIGTERM step
        #: (fleet/supervisor.py, docs/fleet.md "Supervision"). Queries
        #: already in flight still answer; the latch only refuses NEW
        #: placement. Guarded by _reload_lock at writer and readers.
        self._draining = False
        #: `pio deploy --workers N` peering + shared admin state
        #: (fleet/workers.py spool + serving/workers.WorkerCoherence;
        #: docs/serving-performance.md "Multi-process serving"): a
        #: /metrics or /stats.json scrape landing on THIS worker
        #: reports fleet-of-workers truth, /traces.json folds sibling
        #: rings in, and /reload, /drain, POST /retrieval landing
        #: anywhere reach every sibling through the sequenced
        #: admin.state document
        self.worker_hub = None
        self.coherence: WorkerCoherence | None = None
        #: base-model generation: bumped on every successful /reload
        #: (to the pool's shared reload sequence under --workers, so
        #: generations are comparable across siblings). The online
        #: fold-in plane fences on it: a delta computed against
        #: generation G is discarded, never applied, once a reload
        #: lands G+1 (online/overlay.py; docs/freshness.md)
        self.model_generation = 0
        if config.worker_spool_dir:
            from predictionio_tpu.fleet.workers import WorkerHub

            self.worker_hub = WorkerHub(
                config.worker_spool_dir,
                metrics_text=lambda: render_prometheus(self.registry),
                traces_snapshot=self.trace_log.snapshot,
                timeout_s=config.worker_peer_timeout_s,
                # LOCAL stats for sibling fan-out: a peer callback that
                # itself fanned out would recurse across the pool
                extra_paths={"/stats.json":
                             lambda: self.stats_doc(include_workers=False)})
            self.coherence = WorkerCoherence(
                self.worker_hub, on_state=self._on_admin_state,
                interval_s=config.admin_sync_interval_s)
            adopted = self.coherence.adopt()
            # respawn adoption: a fresh boot already loaded the latest
            # completed instance, so reloadSeq is history (the cache —
            # empty anyway — aligns its generation with the pool's);
            # the drain latch and retrieval config apply for real
            if self.cache is not None and adopted["reloadSeq"] > 0:
                self.cache.invalidate(generation=adopted["reloadSeq"])
            self.model_generation = adopted["reloadSeq"]
            if adopted["draining"]:
                with self._reload_lock:
                    self._draining = True
            if adopted["retrieval"]:
                # guarded like the sync path: an unappliable adopted
                # doc (index-less model, version skew) must degrade,
                # not abort boot — under --supervise a boot abort
                # respawns into the same document until the
                # crash-loop latch permanently shrinks the pool
                try:
                    self._apply_retrieval_doc(adopted["retrieval"])
                except Exception:
                    logger.exception(
                        "adopted retrieval config %s failed to "
                        "apply; serving %s retrieval",
                        adopted["retrieval"], self.config.retrieval)
            self.coherence.start()
        #: real-time freshness plane (`pio deploy --online`; online/,
        #: docs/freshness.md): tails the event store, folds touched
        #: users' ALS vectors closed-form between retrains, publishes
        #: generation-fenced deltas into the serving overlay with
        #: per-user result-cache invalidation, and propagates across
        #: `--workers` siblings over the spool plane
        self.online = None
        if config.online:
            from predictionio_tpu.online.service import OnlineFoldIn

            self.online = OnlineFoldIn(
                storage=storage,
                deployed_fn=lambda: self.deployed,
                generation_fn=lambda: self.model_generation,
                interval_s=config.online_interval_s,
                overlay_max=config.online_overlay_max,
                state_dir=config.online_state_dir or None,
                invalidate_user=self._invalidate_user_results,
                trace_log=self.trace_log,
                tracing=self.tracing,
                worker_hub=self.worker_hub,
            )
            self.online.start()
            self.registry.register(online_collector(self.online))

    def _invalidate_user_results(self, user_id: str) -> None:
        """Drop exactly one user's result-cache entries after their
        vector was re-folded — targeted, instead of the pool-wide
        generation bump a /reload takes (every OTHER user's warm
        entries stay warm; the whole point of a speed layer is that
        freshness does not cost the fleet its cache)."""
        if self.cache is not None:
            from predictionio_tpu.online.service import user_key_fragment

            self.cache.invalidate_matching(user_key_fragment(user_id))

    @property
    def worker_id(self) -> str | None:
        """This worker's spool identity (None outside a worker pool) —
        stamped into access-log lines so per-worker skew is visible."""
        return self.worker_hub.worker_id if self.worker_hub else None

    def _publish_admin(self, applied_note: str, **changes) -> None:
        """Publish admin ``changes`` to the worker pool and VERIFY they
        committed: ``WorkerCoherence.publish`` swallows spool I/O
        failures (returning the previous state), and answering 200
        while N-1 siblings silently stay on the old state would
        contradict the coherence contract. The local mutation stands
        either way — the 500 tells the operator the pool is split and
        a retry (every admin mutation here is idempotent) heals it."""
        if self.coherence is None:
            return
        published = self.coherence.publish(**changes)
        for key, value in changes.items():
            if published.get(key) != value:
                raise _Reject(
                    500, f"{applied_note}, but publishing to the "
                         "worker pool failed; sibling workers are "
                         "unchanged — check the spool directory and "
                         "retry")

    def _on_admin_state(self, new: dict, prev: dict) -> None:
        """WorkerCoherence apply callback: perform whatever changed
        between two cumulative admin states (serving/workers.py). A
        sibling's /reload becomes a local reload adopting the shared
        sequence as the cache generation — a failed local reload keeps
        last-known-good exactly like a direct /reload failure (the
        sibling that succeeded is ahead; this one answers /readyz
        truthfully and retries on the next seq bump)."""
        if new["draining"] != prev["draining"]:
            with self._reload_lock:
                self._draining = new["draining"]
            logger.info("adopted sibling drain latch: %s",
                        "set" if new["draining"] else "cleared")
        # reload BEFORE retrieval: a cumulative document can carry both
        # (operator reloaded onto an index-bearing model, then flipped
        # to ann, inside one sync interval) — a lagging sibling that
        # applied retrieval against the still-deployed OLD model would
        # reject the mode and never retry it
        if new["reloadSeq"] > prev["reloadSeq"]:
            try:
                self.reload(generation=new["reloadSeq"])
                logger.info("adopted sibling reload (seq %d): now "
                            "serving %s", new["reloadSeq"],
                            self.deployed.instance.id)
            except Exception:
                record_fallback("serving/reload")
                logger.exception(
                    "sibling-triggered reload failed; still serving "
                    "instance %s", self.deployed.instance.id)
        if new["retrieval"] != prev["retrieval"] and new["retrieval"]:
            # guarded like the reload above: a failed local apply must
            # not abort the remaining deltas in this document (the
            # sequence has already advanced — an aborted callback would
            # silently desync this worker from the pool forever)
            try:
                self._apply_retrieval_doc(new["retrieval"])
                logger.info("adopted sibling retrieval config: %s",
                            new["retrieval"])
            except Exception:
                logger.exception(
                    "sibling retrieval config %s failed to apply; "
                    "still serving %s retrieval", new["retrieval"],
                    self.config.retrieval)

    # -- sublinear retrieval wiring (ops/ann) -------------------------------
    def _wire_ann_observers(self) -> None:
        # getattr: test doubles and minimal deployments may not carry a
        # models list — they simply have no ANN-capable targets
        table_bytes = 0
        for target in retrieval_targets(
                getattr(self.deployed, "models", ())):
            if hasattr(target, "set_ann_observer"):
                target.set_ann_observer(self.serving_stats.record_ann)
            if hasattr(target, "set_topk_observer"):
                target.set_topk_observer(
                    self.serving_stats.record_two_stage_topk)
            if (hasattr(target, "score_table_bytes_per_entry")
                    and not getattr(target, "ann_enabled", False)):
                # reading it makes the brute path's serving copy of the
                # item table now, ahead of the first query
                table_bytes = max(table_bytes,
                                  target.score_table_bytes_per_entry)
        self.serving_stats.set_score_table_bytes(table_bytes)
        # the session engine's models report programs and tokens the
        # same way (pio_serving_seq_* on /metrics, seq* on /stats.json)
        for model in getattr(self.deployed, "models", ()):
            if hasattr(model, "set_dispatch_observer"):
                model.set_dispatch_observer(
                    self.serving_stats.record_seq_dispatch)

    def _missing_index_targets(self) -> list:
        """ANN-capable deployed models WITHOUT a ready index — the
        runtime-switch blocker: configure-time fallback builds (fine at
        deploy) would run a full k-means on whatever thread applies the
        change, and on the single admin-sync thread that stalls every
        later /drain//reload for minutes."""
        return [t for t in retrieval_targets(
                    getattr(self.deployed, "models", ()))
                if getattr(t, "ann_index", None) is None]

    def _apply_retrieval_doc(self, doc: Mapping[str, Any]) -> None:
        """Apply a runtime retrieval reconfiguration (POST /retrieval,
        a sibling's admin document, or respawn adoption): push the
        knobs onto every ANN-capable model, re-wire the dispatch
        observers, invalidate the cache — ann and brute answer the
        same query with (potentially) different rankings, so entries
        computed under the old mode must die with it — and only then
        commit the new ServerConfig (a mid-apply failure must not
        leave the config claiming a mode the models don't serve)."""
        mode = str(doc.get("retrieval", self.config.retrieval))
        if mode not in ("brute", "ann"):
            raise ValueError(f"invalid retrieval mode {mode!r}")

        def _int(key: str, current: int) -> int:
            value = doc.get(key, current)
            if not isinstance(value, int) or value < 0:
                raise ValueError(f"invalid {key}: {value!r}")
            return value

        if mode == "ann" and self._missing_index_targets():
            # guarded HERE so every apply path (HTTP, sibling sync,
            # respawn adoption) refuses the build — this worker may be
            # on an older last-known-good model without an index even
            # when the publishing sibling had one
            raise ValueError(
                "no persisted ANN index on the deployed model: build "
                "it at train/persist time (PIO_SERVING_ANN_BUILD) or "
                "deploy with --retrieval ann; the runtime switch only "
                "flips between ready modes")
        candidate = dataclasses.replace(
            self.config, retrieval=mode,
            ann_nprobe=_int("annNprobe", self.config.ann_nprobe),
            ann_rescore=_int("annRescore", self.config.ann_rescore),
            ann_nlist=_int("annNlist", self.config.ann_nlist))
        apply_retrieval_config(getattr(self.deployed, "models", ()),
                               candidate)
        self._wire_ann_observers()
        if self.cache is not None:
            self.cache.invalidate()
        self.config = candidate

    def retrieval_admin(self, body: Any) -> tuple:
        """``POST /retrieval`` — runtime retrieval reconfig without a
        restart: ``{"retrieval": "ann"|"brute"[, "annNprobe": N,
        "annRescore": N, "annNlist": N]}``. Key-authenticated like
        /reload; under ``--workers N`` the change publishes to the
        admin spool so every sibling reconfigures too."""
        if not isinstance(body, dict) or "retrieval" not in body:
            raise _Reject(400, 'expected {"retrieval": "ann"|"brute", ...}')
        if body.get("retrieval") == "ann" and self._missing_index_targets():
            # a state conflict, not a malformed request: the model has
            # no ready index to flip onto (the same guard inside
            # _apply_retrieval_doc protects the sibling/adoption paths)
            raise _Reject(
                409, "no persisted ANN index on the deployed model: "
                     "build it at train/persist time "
                     "(PIO_SERVING_ANN_BUILD) or deploy with "
                     "--retrieval ann; the runtime switch only flips "
                     "between ready modes")
        try:
            self._apply_retrieval_doc(body)
        except ValueError as exc:
            raise _Reject(400, str(exc))
        self._publish_admin("retrieval applied on this worker",
                            retrieval={
                                "retrieval": self.config.retrieval,
                                "annNprobe": self.config.ann_nprobe,
                                "annRescore": self.config.ann_rescore,
                                "annNlist": self.config.ann_nlist,
                            })
        logger.info("retrieval reconfigured: %s (nprobe=%d rescore=%d)",
                    self.config.retrieval, self.config.ann_nprobe,
                    self.config.ann_rescore)
        return (200, {"retrieval": self.config.retrieval,
                      "annEnabled": self.ann_enabled()})

    def ann_enabled(self) -> bool:
        """True when any deployed model answers queries through its ANN
        index (retrieval mode applied AND an index present)."""
        return any(getattr(t, "ann_enabled", False)
                   for t in retrieval_targets(
                       getattr(self.deployed, "models", ())))

    def _ann_mode_collector(self) -> list:
        return [Metric(
            name="pio_serving_ann_enabled", kind="gauge",
            help="1 when queries are served through the ANN MIPS index, "
                 "0 for brute-force retrieval",
            samples=[({}, 1.0 if self.ann_enabled() else 0.0)],
        )]

    # -- auth (KeyAuthentication.withAccessKeyFromFile) ---------------------
    def _check_server_key(self, params: Mapping[str, str]) -> None:
        if self.config.server_key is None:
            return
        if params.get("accessKey") != self.config.server_key:
            raise _Reject(401, "invalid accessKey")

    # -- routes -------------------------------------------------------------
    def handle(
        self,
        method: str,
        path: str,
        params: Mapping[str, str],
        headers: Mapping[str, str],
        body: Any,
    ) -> tuple:
        """Returns ``(status, payload)`` or ``(status, payload, headers)``
        (the 3-tuple form carries e.g. ``Retry-After`` on 503s)."""
        try:
            if method == "GET" and path == "/":
                if "text/html" in headers.get("accept", ""):
                    return (200, _HtmlPage(self.status_html()))
                return (200, self.status_doc())
            if method == "POST" and path == "/queries.json":
                return self.handle_query(body, headers)
            if method == "GET" and path == "/plugins.json":
                return (200, self.plugins.describe())
            if method == "GET" and path == "/stats.json":
                return (200, self.stats_doc())
            if method == "GET" and path == "/metrics":
                # Prometheus exposition: serving counters + latency
                # histograms + resilience state (docs/observability.md);
                # under `--workers N` merged with every live sibling
                return (200, PlainTextPayload(
                    self.metrics_text(), PROMETHEUS_CONTENT_TYPE))
            if method == "GET" and path == "/traces.json":
                return (200, {"tracing": self.tracing,
                              "traces": self.traces_merged()})
            if method == "GET" and path == "/healthz":
                # liveness: the process answers; nothing else implied
                return (200, {"status": "ok"})
            if method == "GET" and path == "/readyz":
                return self.readyz()
            if path == "/reload" and method in ("GET", "POST"):
                self._check_server_key(params)
                # the shared reload sequence doubles as the new cache
                # generation, so every sibling's private cache lands on
                # the SAME generation (serving/workers.py); reload
                # FIRST, publish only on success — a failed swap keeps
                # last-known-good and announces nothing to the pool
                reload_seq = (self.coherence.next_reload_seq()
                              if self.coherence is not None else None)
                try:
                    self.reload(generation=reload_seq)
                except LookupError as e:
                    raise _Reject(404, str(e))
                except Exception as e:
                    # keep serving the last-known-good model instead of
                    # wedging: the old instance stays deployed
                    keep = self.deployed.instance.id
                    logger.exception(
                        "reload failed; still serving instance %s", keep)
                    record_fallback("serving/reload")
                    raise _Reject(
                        503,
                        f"reload failed ({e}); still serving instance {keep}",
                        {"Retry-After": retry_after_header(retry_after_hint(e))})
                self._publish_admin("reloaded on this worker",
                                    **({"reloadSeq": reload_seq}
                                       if reload_seq is not None else {}))
                return (200, {"message": "Reloading"})
            if method == "POST" and path == "/retrieval":
                self._check_server_key(params)
                return self.retrieval_admin(body)
            if method == "POST" and path == "/drain":
                self._check_server_key(params)
                return self.drain(body)
            if method == "POST" and path == "/stop":
                self._check_server_key(params)
                threading.Thread(target=self.on_stop, daemon=True).start()
                return (200, {"message": "Shutting down"})
            return (404, {"message": f"no route for {method} {path}"})
        except _Reject as r:
            if r.headers:
                return (r.status, {"message": r.message}, r.headers)
            return (r.status, {"message": r.message})
        except STORAGE_UNAVAILABLE_ERRORS as e:
            logger.warning("storage unavailable in %s %s: %s", method, path, e)
            return (503, {"message": f"storage unavailable: {e}"},
                    {"Retry-After": retry_after_header(retry_after_hint(e))})
        except Exception as e:
            logger.exception("unhandled error in %s %s", method, path)
            return (500, {"message": f"internal error: {e}"})

    _ROUTE_LABELS = {
        "/queries.json": "queries",
        "/stats.json": "stats",
        "/metrics": "metrics",
        "/": "status",
    }

    def observe_request(self, path: str, dt: float,
                        status: int | None = None) -> None:
        """Handler-measured request walltime into the per-route
        latency family (unknown paths fold into ``other``); query
        outcomes additionally feed the SLO ring (5xx = error-budget
        spend; a shed 503 is budget spend too — the SLO measures what
        callers experienced, not who was at fault)."""
        self.request_latency.observe(
            self._ROUTE_LABELS.get(path, "other"), dt)
        if status is not None and path == "/queries.json":
            self.slo.record(ok=status < 500, latency_s=dt)

    def drain(self, body: Any = None) -> tuple:
        """``POST /drain`` — flip this replica's readiness off so the
        fleet drains it before a planned stop (the supervisor's
        drain-before-SIGTERM step; docs/fleet.md "Supervision"):
        ``/readyz`` answers 503 "draining" while the latch holds, every
        router's membership loop stops routing here within its
        ``down_after`` probes, and in-flight queries still answer.
        ``{"action": "undrain"}`` clears the latch (an operator who
        drained for a look and changed their mind)."""
        undrain = isinstance(body, dict) and body.get("action") == "undrain"
        with self._reload_lock:
            self._draining = not undrain
        # workers share ONE public port, so an operator draining "the
        # deployment" cannot address one process — the latch propagates
        # to every sibling through the admin spool (verified: a
        # swallowed spool failure must not read as a drained pool)
        self._publish_admin(
            f"drain latch {'cleared' if undrain else 'set'} on this "
            "worker", draining=not undrain)
        logger.info("drain latch %s", "cleared" if undrain else "set")
        return (200, {"status": "ready" if undrain else "draining"})

    def readyz(self) -> tuple:
        """Readiness: a deployed model AND reachable storage. 503 (with
        Retry-After) until both hold — load balancers drain, clients
        back off, and a wedged dependency never looks like a live
        replica."""
        with self._reload_lock:
            reloading = self._reloads_in_flight > 0
            draining = self._draining
        if draining:
            # a planned drain (POST /drain): deliberately not-ready
            # until the supervisor stops the process or an operator
            # undrains — routers must NOT send new work here (deployed
            # may be None: the missing-model state readyz handles below
            # can be drained too)
            return (503, {"status": "draining",
                          "model": (self.deployed.instance.id
                                    if self.deployed is not None
                                    else "missing")},
                    {"Retry-After": retry_after_header(1.0)})
        if reloading:
            # a replica mid-model-swap must drain from routers/load
            # balancers: not-ready (NOT ready-with-stale) until the
            # swap commits or fails back to last-known-good
            return (503, {"status": "reloading",
                          "model": self.deployed.instance.id},
                    {"Retry-After": retry_after_header(1.0)})
        checks: dict[str, str] = {}
        ready = True
        if self.deployed is not None:
            checks["model"] = self.deployed.instance.id
        else:
            checks["model"] = "missing"
            ready = False
        if self.storage is not None:
            probe_id = checks["model"]  # a cheap keyed metadata read

            def probe() -> None:
                # inner deadline stops retry sleeps; bounded_probe walls
                # off a blackholed backend's socket timeout
                with deadline_scope(1.0):
                    self.storage.get_meta_data_engine_instances().get(probe_id)

            err = bounded_probe(probe, timeout=1.0)
            if err is None:
                checks["storage"] = "ok"
            else:
                checks["storage"] = f"unavailable: {err}"
                ready = False
        else:
            checks["storage"] = "skipped"
        if ready:
            return (200, {"status": "ready", **checks})
        return (503, {"status": "unavailable", **checks},
                {"Retry-After": retry_after_header(1.0)})

    def status_doc(self) -> dict:
        """The GET / status page content (CreateServer.scala:442-469)."""
        d = self.deployed
        inst = d.instance
        return {
            "status": "alive",
            "engineInstanceId": inst.id,
            "engineFactory": inst.engine_factory,
            "engineVariant": inst.engine_variant,
            "startTime": inst.start_time.isoformat(),
            "completionTime": inst.completion_time.isoformat(),
            "algorithms": [type(a).__name__ for a in d.algorithms],
            "serving": type(d.serving).__name__,
            "requestCount": d.request_count,
            "avgServingSec": d.avg_serving_sec,
            "lastServingSec": d.last_serving_sec,
            "clientDisconnects": self.client_disconnects(),
            **({"batching": {
                "batches": self.batcher.batches,
                "batchedQueries": self.batcher.batched_queries,
                # batchMax comes from the policy snapshot below — the
                # EFFECTIVE (menu-clamped) value, not the raw config
                "batchWaitMs": self.config.batch_wait_ms,
                **self.batcher.policy.snapshot(),
            }} if self.batcher is not None else {}),
            **({"resilience": snap} if (snap := resilience_snapshot()) else {}),
        }

    # -- `--workers N` scrape-time aggregation ------------------------------
    def metrics_text(self) -> str:
        """This worker's exposition — merged with every live sibling's
        when the worker pool is on (counters summed, histograms
        bucket-merged, gauges labeled ``worker=<id>`` per the
        merge_sources convention), plus the ``pio_serving_workers``
        gauge, so a scrape landing on one SO_REUSEPORT worker reports
        fleet-of-workers truth instead of a 1/N sample."""
        own = self.registry.collect()
        hub = self.worker_hub
        if hub is None:
            return render_metrics(own + [source_count_metric(
                "pio_serving_workers",
                "Live engine-server worker processes folded into this "
                "scrape (1 outside a worker pool)", 1)])
        sources: list[tuple[str, list]] = [(hub.worker_id, own)]
        for worker_id, body in hub.fetch_peer_bodies("/metrics"):
            try:
                sources.append((worker_id,
                                parse_exposition(body.decode())))
            except (ExpositionParseError, UnicodeDecodeError) as exc:
                logger.warning("worker %s exposition unparseable: %s",
                               worker_id, exc)
        merged = merge_sources(sources, source_label="worker")
        merged.append(source_count_metric(
            "pio_serving_workers",
            "Live engine-server worker processes folded into this "
            "scrape (1 outside a worker pool)", len(sources)))
        return render_metrics(merged)

    def traces_merged(self) -> list:
        """The local trace ring, with every live sibling's ring folded
        in (tagged ``source: worker:<id>``) under the worker pool —
        one ``GET /traces.json`` sees the whole pool's recent traces
        wherever the SO_REUSEPORT hash landed it."""
        traces = self.trace_log.snapshot()
        hub = self.worker_hub
        if hub is None:
            return traces
        for worker_id, body in hub.fetch_peer_bodies("/traces.json"):
            try:
                docs = json.loads(body).get("traces", [])
            except (json.JSONDecodeError, UnicodeDecodeError):
                continue
            for doc in docs:
                doc.setdefault("source", f"worker:{worker_id}")
                traces.append(doc)
        return traces

    def _workers_doc(self) -> dict:
        """The /stats.json ``workers`` section: per-worker request
        counts (this worker's live, siblings' fetched) plus pool
        totals — the sum is the number an operator wants, the split is
        where SO_REUSEPORT skew shows."""
        hub = self.worker_hub
        per_worker: dict[str, int] = {
            hub.worker_id: self.deployed.request_count}
        for worker_id, body in hub.fetch_peer_bodies("/stats.json"):
            try:
                doc = json.loads(body)
                per_worker[worker_id] = int(doc.get("requestCount", 0))
            except (json.JSONDecodeError, UnicodeDecodeError,
                    TypeError, ValueError):
                continue
        return {
            "worker": hub.worker_id,
            "count": len(per_worker),
            "requestCount": sum(per_worker.values()),
            "perWorker": per_worker,
        }

    def stats_doc(self, include_workers: bool = True) -> dict:
        """GET /stats.json — the serving hot path's internals (beyond
        reference; docs/serving-performance.md): batch-size histogram,
        the adaptive policy's inter-arrival EWMA and last plan, cache
        hit/miss/eviction counters and dedup count, per-backend
        resilience state. All counters are read under their own locks
        (ServingStats), so a concurrent burst never tears the doc.
        Under ``--workers N`` a ``workers`` section reports pool-wide
        request totals; ``include_workers=False`` is the sibling
        fan-out view (fetching peers from a peer callback would recurse
        across the pool)."""
        d = self.deployed
        return {
            **({"workers": self._workers_doc()}
               if include_workers and self.worker_hub is not None else {}),
            "engineInstanceId": d.instance.id,
            "requestCount": d.request_count,
            "avgServingSec": d.avg_serving_sec,
            "lastServingSec": d.last_serving_sec,
            "clientDisconnects": self.client_disconnects(),
            "annEnabled": self.ann_enabled(),
            "retrieval": self.config.retrieval,
            # the recompile sentinel's view (docs/observability.md):
            # compiles, cumulative compile seconds, post-warmup
            # serving recompiles — per-process like the jit caches
            "compile": compile_recorder().stats_doc(),
            "serving": self.serving_stats.snapshot(),
            "batching": (
                {"enabled": True, **self.batcher.policy.snapshot()}
                if self.batcher is not None else {"enabled": False}),
            "cache": (
                {"enabled": True, **self.cache.snapshot()}
                if self.cache is not None else {"enabled": False}),
            # the freshness plane's view (docs/freshness.md): overlay
            # occupancy, fold counters, event→serving lag, tail cursor
            **({"online": self.online.stats_doc()}
               if self.online is not None else {}),
            **({"resilience": snap} if (snap := resilience_snapshot()) else {}),
        }

    def status_html(self) -> str:
        """Browser-facing status page — the Twirl html.index render of the
        reference engine server (core/src/main/twirl/.../index.scala.html,
        served at CreateServer.scala:442-469)."""
        import html

        doc = self.status_doc()
        rows = "".join(
            f"<tr><th>{html.escape(str(k))}</th>"
            f"<td>{html.escape(str(v))}</td></tr>"
            for k, v in doc.items()
        )
        return (
            "<!DOCTYPE html><html><head><title>predictionio_tpu engine "
            f"server</title></head><body><h1>Engine instance "
            f"{html.escape(str(doc['engineInstanceId']))}</h1>"
            f"<table>{rows}</table></body></html>"
        )

    def _deadline_budget(self, headers: Mapping[str, str]) -> float | None:
        """Per-request budget (seconds) via the shared contract
        (http_base.parse_deadline_budget — the fleet router applies the
        same parse, so both tiers agree on every header): the
        X-PIO-Deadline-Ms header may only TIGHTEN the configured
        request_deadline_ms; malformed values are a 400."""
        try:
            return parse_deadline_budget(self.config.request_deadline_ms,
                                         headers)
        except ValueError as exc:
            raise _Reject(400, str(exc))

    def handle_query(self, body: Any,
                     headers: Mapping[str, str] = {}) -> tuple[int, Any]:
        """POST /queries.json (CreateServer.scala:470-621)."""
        if body is None or not isinstance(body, dict):
            raise _Reject(400, "the request body must be a JSON object")
        # prId is feedback-loop metadata carried alongside any query
        # (CreateServer.scala:506-512), not a query field — strip before
        # binding so the strict binder doesn't reject it
        body = dict(body)
        pr_id_in = body.pop("prId", None)
        decoder = self._query_decoder
        try:
            # span() is the ambient-trace helper: a shared no-op when
            # the handler started no trace (the near-free disabled path)
            with span("bind"):
                query = decoder(body) if decoder is not None else body
        except (ValueError, TypeError) as e:
            raise _Reject(400, f"invalid query: {e}")

        budget = self._deadline_budget(headers)
        # one canonical key serves both the result cache and the
        # batcher's dedup pass; None when neither wants it. Keyed on
        # the BOUND query's wire form, not the raw body, so camelCase
        # and snake_case spellings of the same query share an entry
        # (the ResultCache contract)
        with span("codec_key"):
            key = (canonical_json(encode_wire(query))
                   if (self.cache is not None or self.batcher is not None)
                   else None)
        hit, generation = False, None
        if self.cache is not None:
            t0 = time.perf_counter()
            with span("cache_lookup"):
                hit, cached, generation = self.cache.lookup(key)
        if hit:
            prediction = cached
            # a hit IS an answered query: requestCount / serving-time
            # bookkeeping must not report a hot cache as an idle server
            self.deployed.record_served(time.perf_counter() - t0)
        else:
            try:
                with deadline_scope(budget) if budget is not None \
                        else contextlib.nullcontext():
                    if self.batcher is not None:
                        # the ambient trace rides the queue entry: the
                        # dispatcher thread records queue-wait and
                        # device-dispatch spans onto it (batcher.py)
                        prediction = self.batcher.submit(
                            query,
                            timeout=budget if budget is not None else 300.0,
                            key=key, trace=active_trace())
                    elif budget is not None:
                        # _query_with_deadline copies this request's
                        # contextvars, so the ambient trace follows
                        # onto the pool thread by construction
                        with span("predict"):
                            prediction = self._query_with_deadline(
                                query, budget)
                    else:
                        with span("predict"):
                            prediction = self.deployed.query(query)
            except QueryDeadlineExceeded as e:
                # a blown deadline is overload/degradation, not an
                # application error: 503 so the client retries later
                raise _Reject(503, str(e), {"Retry-After": retry_after_header(1.0)})
            except STORAGE_UNAVAILABLE_ERRORS as e:
                logger.warning("query failed on unavailable storage: %s", e)
                raise _Reject(503, f"storage unavailable: {e}",
                              {"Retry-After": retry_after_header(retry_after_hint(e))})
            except Exception as e:
                logger.exception("query failed")
                raise _Reject(500, f"query failed: {e}")
            if self.cache is not None:
                # generational put: a result computed against a model
                # that /reload swapped out mid-flight is dropped, not
                # cached into the new model's generation
                self.cache.put(key, prediction, generation=generation)

        info = QueryInfo(
            query=query,
            prediction=prediction,
            engine_instance_id=self.deployed.instance.id,
        )
        try:
            prediction = self.plugins.run_blockers(info)
        except Exception as e:
            # a raising blocker rejects the prediction (plugin contract);
            # same mapping the event server uses for input blockers
            logger.warning("output blocker rejected query: %s", e)
            raise _Reject(403, f"prediction rejected: {e}")
        self.plugins.notify_sniffers(info)

        with span("encode"):
            response = encode_wire(prediction)
        if not isinstance(response, dict):
            response = {"result": response}
        # experiment attribution (experiment/controller.py): the router
        # stamps the assigned variant on the forwarded request; echo it
        # as prId-style response fields so the client can attach the
        # ids to conversion events — the loop serving → event store →
        # online score closes on exactly these two fields
        attribution = None
        experiment_id = headers.get("x-pio-experiment")
        if experiment_id:
            attribution = {"experimentId": experiment_id,
                           "variantId": headers.get("x-pio-variant", "")}
            response.update(attribution)
        if self.config.feedback:
            # feedback loop (CreateServer.scala:514-576): tag the response
            # with a prId and post the (query, prediction) as events
            pr_id = pr_id_in or uuid.uuid4().hex
            response["prId"] = pr_id
            self._post_feedback(pr_id, body, response,
                                attribution=attribution)
        if not self._compile_warmup_marked:
            # the first answered query ends serving warmup: from here
            # on, any jit compile under a request is an incident the
            # recompile sentinel WARNs about (a benign double-mark race
            # is fine — mark_warmup_complete is idempotent)
            self._compile_warmup_marked = True
            compile_recorder().mark_warmup_complete()
        return (200, response)

    def _query_with_deadline(self, query: Any, budget: float) -> Any:
        """Non-batched predict under a hard budget: run on a pool thread
        (copying this request's contextvars so the ambient deadline
        still reaches storage retries) and 503 when the wait expires —
        an in-flight slow predict cannot be interrupted, but it must
        not hold the client socket past the budget."""
        ctx = contextvars.copy_context()
        fut = self._query_pool.submit(ctx.run, self.deployed.query, query)
        try:
            return fut.result(timeout=budget)
        except FuturesTimeoutError:
            if not fut.done():
                fut.cancel()
                raise QueryDeadlineExceeded(budget) from None
            raise  # the work itself raised a TimeoutError (3.11 alias)

    def reload(self, generation: int | None = None) -> None:
        """Hot-swap to the latest completed instance
        (CreateServer.scala:316-342). While the reload is in flight
        /readyz reports not-ready (503 "reloading") so fleet membership
        drains this replica; failure semantics are unchanged — the
        last-known-good model keeps serving and the caller maps the
        error to 503. ``generation`` pins the post-swap result-cache
        generation (the shared reload sequence under ``--workers N``,
        so sibling caches stay generationally comparable)."""
        with self._reload_lock:
            self._reloads_in_flight += 1
        try:
            new = load_deployed_engine(
                storage=self.storage,
                config=dataclasses.replace(self.config,
                                           engine_instance_id=None),
                ctx=self.ctx,
                engine=self.deployed.engine,
            )
            old_id = self.deployed.instance.id
            self.deployed = new
            # the swap brought fresh model objects: re-install the
            # ServingStats ANN dispatch counter on each of them
            self._wire_ann_observers()
            self._query_decoder = (
                compile_wire_decoder(qc)
                if (qc := new.query_class) is not None else None)
            if self.cache is not None:
                # swap THEN invalidate: entries computed against the old
                # model die with its generation (ResultCache docstring); a
                # FAILED reload never reaches here, so last-known-good
                # keeps its warm cache
                self.cache.invalidate(generation=generation)
            # the generation fence: advance BEFORE the online plane
            # hears about the swap, so any fold-in racing this reload
            # publishes against a generation that no longer exists and
            # is discarded (overlay.put_* returns False)
            self.model_generation = (generation if generation is not None
                                     else self.model_generation + 1)
            if self.online is not None:
                self.online.on_model_swapped(self.model_generation)
            logger.info("reloaded: instance %s -> %s", old_id, new.instance.id)
        finally:
            with self._reload_lock:
                self._reloads_in_flight -= 1

    # -- feedback loop ------------------------------------------------------
    def _post_feedback(self, pr_id: str, query_json: dict, response: dict,
                       attribution: dict | None = None) -> None:
        """Fire-and-forget POST to the event server
        (CreateServer.scala:550-566). Forwards the ambient trace
        context (captured HERE, on the handler thread — the posting
        thread has no contextvars) so the event server's segment nests
        under this query's feedback span in the stitched tree."""
        trace = active_trace()
        feedback_span_id = trace.reserve_span_id() if trace else None

        def post() -> None:
            import urllib.request

            from predictionio_tpu.utils.ssl_config import client_transport

            scheme, ssl_ctx = client_transport()
            url = (
                f"{scheme}://{self.config.event_server_ip}:{self.config.event_server_port}"
                f"/events.json?accessKey={self.config.access_key}"
            )
            event = {
                "event": "predict",
                "entityType": "pio_pr",
                "entityId": pr_id,
                # attribution rides as top-level properties so the
                # conversion-count sweep (`pio experiment conversions`)
                # never has to dig through prediction payloads
                "properties": {"query": query_json, "prediction": response,
                               **(attribution or {})},
            }
            headers = {"Content-Type": "application/json"}
            if trace is not None:
                headers[TRACE_ID_HEADER] = trace.trace_id
                headers[PARENT_SPAN_HEADER] = feedback_span_id
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url,
                    data=json.dumps(event).encode(),
                    headers=headers,
                    method="POST",
                )
                with urllib.request.urlopen(
                        req, timeout=self.config.feedback_timeout_s,
                        context=ssl_ctx):
                    pass
            except Exception as e:
                logger.warning("feedback event POST failed: %s", e)
            finally:
                if trace is not None:
                    # best-effort: the handler has usually finished the
                    # trace by now, but TraceLog serializes at READ time
                    # and list.append is atomic, so the span still lands
                    # in later scrapes (Trace's lock-free contract)
                    trace.add_span("feedback", t0, time.perf_counter(),
                                   span_id=feedback_span_id)

        threading.Thread(target=post, name="pio-feedback", daemon=True).start()


class _Handler(BaseHTTPRequestHandler):
    service: EngineService  # bound per server

    # HTTP/1.1 keep-alive: the stdlib default (1.0) closes the socket
    # after every response, so each query paid a TCP connect + a fresh
    # ThreadingHTTPServer thread. Persistent
    # connections make the per-request cost one read/write on a
    # long-lived thread. Requires the Content-Length header on every
    # response, which _respond always sends.
    protocol_version = "HTTP/1.1"

    # ...and a read timeout, or every idle persistent connection pins
    # its handler thread (and fd) for the life of the process —
    # handle_one_request treats the timeout as close_connection, so an
    # idle client is simply hung up on and reconnects transparently.
    # 75 s (nginx's keepalive_timeout): at 30 a pooled client of the
    # session engine (half a second a query, under one query a second
    # over 16 connections) found the connection it picked hung up on a
    # fifth of the time, and a client that does not retry counts that
    # as a failed request (PERF.md section 6, PR 27)
    timeout = 75

    # buffer the response: the stdlib default (wbufsize=0) issues one
    # write() syscall PER HEADER LINE, and with Nagle enabled those
    # small segments can stall behind delayed ACKs; one buffered write
    # per response (handle_one_request flushes) + TCP_NODELAY keeps a
    # response to a single segment
    wbufsize = 64 * 1024
    disable_nagle_algorithm = True

    def _params(self) -> dict[str, str]:
        return {k: v[0] for k, v in parse_qs(urlparse(self.path).query).items()}

    #: perf_counter with the request line in hand (tracing on only):
    #: where a traced query's ``request`` and ``request.read`` start
    _t_request: float | None = None

    def parse_request(self) -> bool:
        if self.service.tracing:
            self._t_request = time.perf_counter()
        return super().parse_request()

    def _dispatch(self, method: str) -> None:
        """Observability envelope around the real dispatch: request-id
        resolution (echoed by _respond), optional trace creation for
        the query hot path, handler-measured route latency, and the
        structured access log (all docs/observability.md)."""
        t_start = time.perf_counter()
        path = urlparse(self.path).path
        self._request_id = resolve_request_id(self.headers)
        self._last_status = 0
        self._trace = None
        if (method == "POST" and path == "/queries.json"
                and self.service.tracing):
            # adopt inbound cross-process context (the router's trace
            # id + its attempt span id) when well-formed; malformed or
            # oversized headers fall back to fresh local ids — never a
            # rejected request (obs/trace.parse_trace_context). The
            # trace counts from the stamp at the request line, and its
            # root span ``request`` is open from there
            inbound_id, inbound_parent = parse_trace_context(self.headers)
            self._trace = start_trace(
                "queries.json", request_id=self._request_id,
                trace_id=inbound_id, parent_span_id=inbound_parent,
                service="engine", start_perf=self._t_request)
            self._trace.open_root("request")
        try:
            self._dispatch_inner(method, path)
        finally:
            dt = time.perf_counter() - t_start
            self.service.observe_request(path, dt, self._last_status)
            if self._trace is not None:
                self._trace.finish(status=self._last_status)
                self.service.trace_log.record(self._trace)
            if self.service.access_log:
                # the worker id (satellite of the prefork pool): with N
                # processes behind one port, per-worker skew is only
                # visible when each line says WHICH worker served it
                wid = self.service.worker_id
                emit_access_log(
                    "engine", method, path, self._last_status, dt,
                    self._request_id, client=self.address_string(),
                    **({"worker": wid} if wid else {}))
        if self._trace is not None:
            # the trace is finished and in the ring (whoever has the
            # response finds it); now the flush the stdlib would make
            # one statement later, timed: ``request.flush`` is the
            # bookkeeping above, run while the response waited in the
            # buffer, and the socket write. The root closes with it
            # and the trace ends there
            self.wfile.flush()
            t_end = time.perf_counter()
            self._trace.add_span("request.flush", self._t_responded, t_end)
            self._trace.close_root(t_end)

    def _dispatch_inner(self, method: str, path: str) -> None:
        body: Any = None
        if self.headers.get("Transfer-Encoding"):
            # chunked bodies are not decoded here; on a keep-alive
            # (HTTP/1.1) connection the unread chunks would desync
            # every later request on the socket — 411 and CLOSE
            # (RFC 9112 §6.3 allows rejecting chunked with 411)
            self.close_connection = True
            self._respond(411, {
                "message": "chunked request bodies are not supported; "
                           "send Content-Length"},
                {"Connection": "close"})
            return
        # drain a Content-Length body for EVERY method: on a keep-alive
        # connection unread body bytes would be parsed as the next
        # request line (non-POST bodies are drained and ignored). A
        # malformed/negative length cannot be drained reliably — 400
        # and CLOSE (read(-1) would block to EOF and pin the thread)
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            length = -1
        if length < 0:
            self.close_connection = True
            self._respond(400, {"message": "invalid Content-Length"},
                          {"Connection": "close"})
            return
        raw = self.rfile.read(length) if length else b""
        if self._trace is not None:
            # the stdlib's header parse, request id, trace creation and
            # the body read: the request line in hand -> the body in hand
            self._trace.add_span("request.read", self._trace.start_perf,
                                 time.perf_counter())
        if method == "POST" and raw:
            try:
                if self._trace is not None:
                    with self._trace.span("parse"):
                        body = json.loads(raw)
                else:
                    body = json.loads(raw)
            except json.JSONDecodeError:
                self._respond(400, {"message": "the request body is not valid JSON"})
                return
        # header names are case-insensitive (RFC 9110); normalise once
        headers = {k.lower(): v for k, v in self.headers.items()}
        if self._trace is not None:
            # ambient binding: spans opened anywhere under handle()
            # (bind, cache lookup, predict, encode) land on this trace
            with use_trace(self._trace):
                result = self.service.handle(
                    method, path, self._params(), headers, body)
        else:
            result = self.service.handle(
                method, path, self._params(), headers, body)
        self._respond(*result)

    def _respond(self, status: int, payload: Any,
                 extra_headers: Mapping[str, str] | None = None) -> None:
        trace = getattr(self, "_trace", None)
        if trace is None:
            self._write_response(status, payload, extra_headers)
            return
        # the last stretch of a traced query that does work: body
        # encode, header lines, the buffered write; where it ends
        # ``request.flush`` starts (_dispatch)
        t0 = time.perf_counter()
        self._write_response(status, payload, extra_headers)
        self._t_responded = time.perf_counter()
        trace.add_span("respond", t0, self._t_responded)

    def _write_response(self, status: int, payload: Any,
                        extra_headers: Mapping[str, str] | None) -> None:
        self._last_status = status
        if isinstance(payload, _HtmlPage):
            data = str(payload).encode()
            ctype = "text/html; charset=UTF-8"
        elif isinstance(payload, PlainTextPayload):
            data = str(payload).encode()
            ctype = payload.content_type
        else:
            data = json.dumps(payload).encode()
            ctype = "application/json; charset=UTF-8"
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        # every response carries the correlation id (inbound
        # X-PIO-Request-Id propagated, else minted — http_base)
        if getattr(self, "_request_id", None):
            self.send_header(REQUEST_ID_HEADER, self._request_id)
        if getattr(self, "_trace", None) is not None:
            self.send_header("X-PIO-Trace-Id", self._trace.trace_id)
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self) -> None:  # noqa: N802
        self._dispatch("GET")

    def do_POST(self) -> None:  # noqa: N802
        self._dispatch("POST")

    def log_message(self, format: str, *args) -> None:
        logger.debug("%s - %s", self.address_string(), format % args)


def undeploy(ip: str, port: int, server_key: str | None = None) -> bool:
    """POST /stop to a running engine server on (ip, port) — the
    MasterActor undeploy of a previous instance (CreateServer.scala:260-294)
    and the CLI `pio undeploy` (commands/Engine.scala:240-276)."""
    import urllib.error
    import urllib.request

    from predictionio_tpu.utils.ssl_config import client_transport

    scheme, ssl_ctx = client_transport()
    host = "127.0.0.1" if ip == "0.0.0.0" else ip
    url = f"{scheme}://{host}:{port}/stop"
    if server_key:
        url += f"?accessKey={server_key}"
    try:
        req = urllib.request.Request(url, data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=5, context=ssl_ctx):
            return True
    except (urllib.error.URLError, OSError):
        return False


class EngineServer(RestServer):
    """HTTP lifecycle around EngineService — the MasterActor
    (CreateServer.scala:247-382): undeploys any previous server on the
    port, binds with retry ×3, owns shutdown."""

    log_label = "Engine Server"
    thread_name = "pio-engineserver"
    bind_retries = 3

    def __init__(
        self,
        deployed: DeployedEngine,
        config: ServerConfig | None = None,
        storage: Storage | None = None,
        ctx: EngineContext | None = None,
        plugin_context: EngineServerPluginContext | None = None,
    ):
        config = config if config is not None else ServerConfig()
        self.config = config
        super().__init__(
            _Handler,
            EngineService(deployed, config, storage, ctx, plugin_context),
            config.ip, config.port,
            # N prefork workers share one listen port (`pio deploy
            # --workers N`); the CLI pool path sets the flag explicitly
            # — deliberately NOT derived from config.workers, which is
            # env-overridable: a standalone server constructed under a
            # stray PIO_SERVING_WORKERS=2 must not bind SO_REUSEPORT
            # (a later unrelated bind would silently siphon traffic)
            reuse_port=config.reuse_port,
        )
        self.service.on_stop = self.stop
        self.service.client_disconnects = lambda: self.client_disconnects
        if self.service.gc_pauses is not None:
            self.service.gc_pauses.install()

    def _on_bind_failure(self, attempt: int, ip: str, port: int) -> None:
        if attempt == 0 and port:
            # a previous instance may hold the port — undeploy it
            undeploy(ip, port, self.config.server_key)

    def _on_close(self) -> None:
        if self.service.gc_pauses is not None:
            self.service.gc_pauses.remove()
        if self.service.online is not None:
            self.service.online.close()
        if self.service.coherence is not None:
            self.service.coherence.close()
        if self.service.worker_hub is not None:
            self.service.worker_hub.close()
        # the shm cache detaches (and unlinks iff this process created
        # the segment — the standalone case; pool workers only attach,
        # the deploy CLI owns the pool segment's lifetime) strictly
        # AFTER the online fold-in thread and the coherence loop stop:
        # both call into the cache (per-user invalidation, reload
        # adoption), and releasing the segment buffer under a live
        # caller raises mid-shutdown
        cache_close = getattr(self.service.cache, "close", None)
        if cache_close is not None:
            cache_close()
        if self.service.batcher is not None:
            self.service.batcher.close()
        self.service._query_pool.shutdown(wait=False)
        self.service.plugins.close()


def create_engine_server(
    storage: Storage | None = None,
    config: ServerConfig | None = None,
    ctx: EngineContext | None = None,
    engine: Any = None,
    plugin_context: EngineServerPluginContext | None = None,
) -> EngineServer:
    """Load the engine instance and bind the server — CreateServer.main
    (CreateServer.scala:105-180)."""
    config = config if config is not None else ServerConfig()
    storage = storage or Storage.default()
    deployed = load_deployed_engine(storage=storage, config=config, ctx=ctx, engine=engine)
    return EngineServer(deployed, config, storage, ctx, plugin_context)
