"""Deployment: load a trained engine instance and answer queries.

Parity: core/src/main/scala/.../workflow/CreateServer.scala —
``createServerActorWithEngine`` (:186-244): look up the EngineInstance
(latest completed if unspecified, commands/Engine.scala:224-228),
deserialize the persisted models, run ``Engine.prepare_deploy`` (retrain
Unit models / reload manifests, Engine.scala:199-257), instantiate the
algorithms and serving from the stored params, and expose the steady-state
query path (supplement → per-algo predict → serve, CreateServer.scala:
470-500).

TPU-first: models stay resident (host or HBM) between requests, and the
query path re-uses each algorithm's jitted predict functions — there is
no per-query compilation or device handoff beyond the query tensors.
The micro-batching machinery lives in :mod:`predictionio_tpu.serving`
(batcher + adaptive policy + result cache); ``QueryBatcher`` and
``QueryDeadlineExceeded`` are re-exported here for compatibility.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Sequence

from predictionio_tpu.controller.engine import Engine, resolve_engine_factory
from predictionio_tpu.serving.batcher import (  # noqa: F401  (re-export)
    QueryBatcher,
    QueryDeadlineExceeded,
)
from predictionio_tpu.storage.base import EngineInstance
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.workflow.context import EngineContext, WorkflowParams
from predictionio_tpu.workflow.persistence import load_models

logger = logging.getLogger(__name__)


def _env_field(key: str, default: Any, cast: Callable[[str], Any]):
    """A frozen-dataclass default overridable via ``PIO_SERVING_<KEY>``
    — the serving-plane analogue of the ``PIO_RESILIENCE_*`` fallbacks
    (utils/resilience._prop), so a deployment tunes the batcher/cache
    without a code change. A malformed value falls back to the coded
    default rather than killing the server at config time (shared
    implementation in utils/envcfg.py)."""
    from predictionio_tpu.utils.envcfg import env_field

    return env_field("PIO_SERVING_", key, default, cast)


def _cast_bool(raw: str) -> bool:
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _online_field(key: str, default: Any, cast: Callable[[str], Any]):
    """``PIO_ONLINE_<KEY>``-overridable defaults for the freshness
    plane's knobs (docs/freshness.md), same degrade-don't-die contract
    as the serving fields."""
    from predictionio_tpu.utils.envcfg import env_field

    return env_field("PIO_ONLINE_", key, default, cast)


def _cast_policy(raw: str) -> str:
    # validated HERE so a typo'd env value degrades to the default with
    # a warning (the _env_field contract) instead of killing the server
    # when make_batch_policy() rejects it at EngineService construction
    value = raw.strip().lower()
    if value not in ("adaptive", "fixed"):
        raise ValueError(value)
    return value


def _cast_retrieval(raw: str) -> str:
    # same degrade-don't-die contract as _cast_policy: a typo'd
    # PIO_SERVING_RETRIEVAL serves brute force with a warning
    value = raw.strip().lower()
    if value not in ("brute", "ann"):
        raise ValueError(value)
    return value


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Parity: ServerConfig (CreateServer.scala:74-103)."""

    ip: str = "0.0.0.0"
    port: int = 8000
    engine_instance_id: str | None = None
    #: defaults match run_train's engine.json fallbacks (train.py:93-95)
    engine_id: str | None = None
    engine_version: str | None = None
    engine_variant: str | None = None
    #: feedback loop: POST prediction events back to the event server
    feedback: bool = False
    event_server_ip: str = "0.0.0.0"
    event_server_port: int = 7070
    access_key: str = ""
    #: socket timeout for the fire-and-forget feedback POST — bounds how
    #: long a stalled event server can pin a pio-feedback thread (the
    #: untimed-blocking-io lint invariant; threads are daemonic but each
    #: stuck one leaks a socket until the peer answers)
    feedback_timeout_s: float = 10.0
    #: when set, /stop and /reload require ?accessKey=<server_key>
    #: (common KeyAuthentication, KeyAuthentication.scala:33-60)
    server_key: str | None = None
    #: TPU-first micro-batching (beyond reference): coalesce concurrent
    #: queries into ONE device dispatch through the algorithms'
    #: batch_predict hook. N concurrent clients served individually
    #: serialize at one dispatch each, while the same model scores
    #: thousands of queries per dispatch batched. Opt-in; with
    #: the adaptive policy a lone query pays (near) zero added latency.
    batching: bool = _env_field("BATCHING", False, _cast_bool)
    #: "adaptive" (EWMA-driven wait, serving/batch_policy.py) or
    #: "fixed" (the legacy constant window)
    batch_policy: str = _env_field("BATCH_POLICY", "adaptive", _cast_policy)
    batch_max: int = _env_field("BATCH_MAX", 64, int)
    #: for "adaptive": the CAP on the coalescing wait; for "fixed": the
    #: constant window
    batch_wait_ms: float = _env_field("BATCH_WAIT_MS", 5.0, float)
    #: result cache (serving/result_cache.py): LRU+TTL over canonical
    #: query JSON, invalidated on /reload. Off by default — only enable
    #: for engines whose predictions depend on nothing but the query
    #: and the deployed model (a custom Serving reading live state per
    #: request would serve stale results from a cache)
    cache_enabled: bool = _env_field("CACHE_ENABLED", False, _cast_bool)
    cache_max_entries: int = _env_field("CACHE_MAX_ENTRIES", 4096, int)
    cache_ttl_s: float = _env_field("CACHE_TTL_S", 30.0, float)
    #: shared-memory result cache (`pio deploy --shm-cache`;
    #: serving/shm_cache, docs/serving-performance.md "Shared-memory
    #: serving plane"): back the result cache with ONE
    #: multiprocessing.shared_memory segment all pool workers attach —
    #: a key warmed by any worker is hot for every sibling, and a
    #: /reload re-warms once instead of N times. Requires
    #: ``cache_enabled``; platforms without POSIX shm warn and fall
    #: back to the private LRU (degrade-don't-die)
    shm_cache: bool = _env_field("SHM", False, _cast_bool)
    #: slot count of the direct-mapped table (also the entry cap the
    #: snapshot reports); colliding keys overwrite — it's a cache
    shm_slots: int = _env_field("SHM_SLOTS", 4096, int)
    #: bytes per slot: header + canonical key + pickled prediction;
    #: oversized entries simply stay uncached
    shm_slot_bytes: int = _env_field("SHM_SLOT_BYTES", 4096, int)
    #: segment name shared by the pool (the deploy CLI generates and
    #: owns one per pool); empty = a private per-process segment
    shm_segment: str = _env_field("SHM_SEGMENT", "", str)
    #: graceful degradation (beyond reference): per-request time budget
    #: for /queries.json. Propagated as the ambient resilience deadline
    #: (utils/resilience.deadline_scope — storage retries stop sleeping
    #: when the budget can't cover them) and into QueryBatcher.submit.
    #: Clients may lower it per request with an X-PIO-Deadline-Ms
    #: header; exhaustion maps to 503 + Retry-After, not a hung socket.
    #: 0 disables (legacy behavior: 300s batcher wait, no deadline).
    request_deadline_ms: float = _env_field("REQUEST_DEADLINE_MS", 0.0, float)
    #: sublinear retrieval (ops/ann; docs/serving-performance.md):
    #: "brute" scores the full item table per query, "ann" probes the
    #: IVF-flat MIPS index persisted beside the model (built at deploy
    #: when missing) and exact-rescores the shortlist — O(sqrt(catalog))
    #: instead of O(catalog) per query, recall measured by the quality
    #: harness. Applies to every model exposing ``configure_retrieval``
    #: (the ALS family behind the recommendation / similarproduct /
    #: ecommerce templates); other models ignore it.
    retrieval: str = _env_field("RETRIEVAL", "brute", _cast_retrieval)
    #: IVF cell count for a deploy-time index build (0 = auto
    #: ~4*sqrt(n)); persisted indexes keep their build-time geometry
    ann_nlist: int = _env_field("ANN_NLIST", 0, int)
    #: cells probed per query (0 = auto nlist/64, floored at 16);
    #: higher = better recall, more rescore work
    ann_nprobe: int = _env_field("ANN_NPROBE", 0, int)
    #: cap on shortlist candidates exact-rescored per query (0 = all
    #: probed candidates)
    ann_rescore: int = _env_field("ANN_RESCORE", 0, int)
    #: observability plane (docs/observability.md). ``tracing`` turns
    #: on per-request span collection for /queries.json (served back on
    #: GET /traces.json); None defers to the PIO_TRACE env var at
    #: server construction. Off by default — the disabled path is one
    #: flag check per request, which is what the serving bench runs.
    tracing: bool | None = None
    #: structured JSON access logs on the ``pio.access`` logger; None
    #: defers to the PIO_ACCESS_LOG env var (api/http_base.py)
    access_log: bool | None = None
    #: prefork worker pool (docs/serving-performance.md "Multi-process
    #: serving"): ``pio deploy --workers N`` runs N engine-server
    #: processes sharing ONE SO_REUSEPORT listen port — one CPython
    #: process tops out on its GIL long before a multi-core host does.
    #: Each worker holds its own model replica (mmap-share it via
    #: PIO_CHECKPOINT_MMAP=r; utils/checkpoint), batcher, cache, and
    #: registry; cross-worker truth/coherence ride worker_spool_dir.
    workers: int = _env_field("WORKERS", 1, int)
    #: spool directory for worker peering + shared admin state
    #: (fleet/workers.WorkerHub, serving/workers.WorkerCoherence); the
    #: CLI mkdtemps it and passes it to every worker. None = no pool.
    worker_spool_dir: str | None = None
    #: this worker's ordinal in the pool (0 = the parent process; the
    #: CLI stamps 1..N-1 onto each sibling spawn) — drives best-effort
    #: CPU-affinity placement (serving/placement): contiguous stripes
    #: of the available cores, degrade-don't-die on hosts with fewer
    #: cores than workers
    worker_index: int = 0
    #: the pool-wide allowed-CPU set, captured by the deploy CLI
    #: BEFORE the parent pins itself to stripe 0 and threaded to every
    #: worker spawn: a supervisor respawn inherits the parent's
    #: already-narrowed affinity mask, so the child must carve its
    #: stripe from this snapshot, not from sched_getaffinity. None =
    #: carve from the process's own inherited mask.
    cpu_allowlist: tuple[int, ...] | None = None
    #: bind with SO_REUSEPORT so the N worker processes share the port
    #: (set by the CLI when workers > 1)
    reuse_port: bool = False
    #: socket bound per sibling fetch on the scrape fan-out paths
    #: (/metrics, /stats.json, /traces.json merging) — a wedged worker
    #: costs the scrape its timeout, never a hang (the untimed-
    #: blocking-io contract)
    worker_peer_timeout_s: float = _env_field("WORKER_PEER_TIMEOUT_S",
                                              2.0, float)
    #: cadence of the shared-admin-state sync loop: a /reload, /drain,
    #: or retrieval reconfig landing on ANY worker reaches every
    #: sibling within about this many seconds
    admin_sync_interval_s: float = _env_field("ADMIN_SYNC_INTERVAL_S",
                                              0.5, float)
    #: real-time freshness plane (`pio deploy --online`; online/,
    #: docs/freshness.md): tail the event store between retrains and
    #: fold touched users' ALS vectors into the deployed model with the
    #: closed-form rank x rank solve — event→recommendation freshness
    #: in seconds instead of a retrain cadence. ALS-family engines
    #: only; others log a warning and serve batch-only.
    online: bool = _online_field("ENABLED", False, _cast_bool)
    #: tail polling interval: the upper bound the speed layer adds on
    #: top of ingest latency (freshness lag ≈ interval + solve time)
    online_interval_s: float = _online_field("INTERVAL_S", 1.0, float)
    #: bounded overlay: at most this many folded USERS held between
    #: retrains (items cap at a quarter of it); LRU-evicted users fall
    #: back to their base vector — the pre-online behavior
    online_overlay_max: int = _online_field("OVERLAY_MAX", 4096, int)
    #: directory for the durable tail cursor (exactly-once resume
    #: across restarts); empty = in-memory cursor, re-tailed from
    #: deploy time after a restart (correct — fold-in is idempotent —
    #: just fresh-start)
    online_state_dir: str = _online_field("STATE_DIR", "", str)


class DeployedEngine:
    """A loaded engine instance ready to serve queries — the ServerActor
    state (CreateServer.scala:384-401)."""

    def __init__(
        self,
        engine: Engine,
        instance: EngineInstance,
        algorithms: Sequence[Any],
        serving: Any,
        models: Sequence[Any],
    ):
        self.engine = engine
        self.instance = instance
        self.algorithms = list(algorithms)
        self.serving = serving
        self.models = list(models)
        self.start_time = time.time()
        # request bookkeeping (CreateServer.scala:399-401, 583-590);
        # ThreadingHTTPServer serves queries concurrently — the reference
        # serialized these updates through an actor, here a lock
        self._stats_lock = threading.Lock()
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0

    @property
    def query_class(self) -> type | None:
        for component in [*self.algorithms, self.serving]:
            qc = getattr(component, "query_class", None)
            if qc is not None:
                return qc
        return None

    def query(self, query: Any) -> Any:
        """The steady-state predict path (CreateServer.scala:479-500)."""
        t0 = time.perf_counter()
        supplemented = self.serving.supplement(query)
        predictions = [
            algo.predict(model, supplemented)
            for algo, model in zip(self.algorithms, self.models)
        ]
        served = self.serving.serve(query, predictions)
        self.record_served(time.perf_counter() - t0)
        return served

    def query_batch(self, queries: Sequence[Any]) -> list[Any]:
        """N queries, ONE device dispatch per algorithm: the serving
        analogue of the eval batch path — supplement each, route the
        whole batch through ``batch_predict`` (vectorized matmul+top_k
        for the ALS algorithms; the base default maps ``predict``, so
        every engine is batchable), then serve each query with its own
        predictions. Used by the opt-in micro-batcher
        (ServerConfig.batching)."""
        t0 = time.perf_counter()
        supplemented = [self.serving.supplement(q) for q in queries]
        indexed = list(enumerate(supplemented))
        per_algo: list[dict[int, Any]] = []
        for algo, model in zip(self.algorithms, self.models):
            per_algo.append(dict(algo.batch_predict(model, indexed)))
        served = [
            self.serving.serve(q, [preds[i] for preds in per_algo])
            for i, q in enumerate(queries)
        ]
        dt = time.perf_counter() - t0
        for _ in queries:           # bookkeeping counts every query
            self.record_served(dt)
        return served

    def record_served(self, dt: float) -> None:
        """Count one answered query in the request bookkeeping. The
        predict paths call it internally; the serving layer calls it
        for queries answered WITHOUT their own dispatch (cache hits,
        deduped batch waiters) so a hot cache never reads as an idle
        server. Public API — stand-ins for DeployedEngine must carry
        it."""
        with self._stats_lock:
            self.request_count += 1
            self.avg_serving_sec += (dt - self.avg_serving_sec) / self.request_count
            self.last_serving_sec = dt


def retrieval_targets(models: Sequence[Any]):
    """The models a deployment's retrieval knobs apply to: anything
    exposing ``configure_retrieval`` directly (ALSModel) or through an
    ``als`` attribute (the similarproduct/ecommerce wrappers). One
    resolver so the deploy wiring and the serving stats agree on the
    target set."""
    for model in models:
        if hasattr(model, "configure_retrieval"):
            yield model
        elif hasattr(getattr(model, "als", None), "configure_retrieval"):
            yield model.als


def apply_retrieval_config(models: Sequence[Any],
                           config: "ServerConfig") -> None:
    """Push the ServerConfig retrieval knobs onto every capable model
    (no-op for engines without an ANN-capable model)."""
    for target in retrieval_targets(models):
        target.configure_retrieval(
            config.retrieval, nprobe=config.ann_nprobe,
            rescore=config.ann_rescore, nlist=config.ann_nlist)


def resolve_engine_instance(
    storage: Storage,
    config: ServerConfig,
) -> EngineInstance:
    """By id when given, else the latest completed matching
    (engine_id, engine_version, variant) — commands/Engine.scala:224-228."""
    instances = storage.get_meta_data_engine_instances()
    if config.engine_instance_id:
        instance = instances.get(config.engine_instance_id)
        if instance is None:
            raise LookupError(f"engine instance {config.engine_instance_id!r} not found")
        return instance
    if config.engine_id is not None:
        instance = instances.get_latest_completed(
            config.engine_id,
            config.engine_version or "1",
            config.engine_variant or config.engine_id,
        )
    else:
        # no identity given: latest COMPLETED instance overall
        completed = [i for i in instances.get_all() if i.status == "COMPLETED"]
        instance = max(completed, key=lambda i: i.start_time, default=None)
    if instance is None:
        raise LookupError(
            "no completed engine instance found; run `pio train` first "
            f"(engine_id={config.engine_id}, variant={config.engine_variant!r})"
        )
    return instance


def load_deployed_engine(
    storage: Storage | None = None,
    config: ServerConfig | None = None,
    ctx: EngineContext | None = None,
    engine: Engine | None = None,
) -> DeployedEngine:
    """createServerActorWithEngine (CreateServer.scala:186-244)."""
    # built at CALL time: a module-level default instance would freeze
    # the PIO_SERVING_* env reads at import
    config = config if config is not None else ServerConfig()
    storage = storage or Storage.default()
    ctx = ctx or EngineContext(workflow_params=WorkflowParams(), storage=storage)
    instance = resolve_engine_instance(storage, config)
    if engine is None:
        engine = resolve_engine_factory(instance.engine_factory)()
    engine_params = engine.params_from_instance_json(
        instance.data_source_params,
        instance.preparator_params,
        instance.algorithms_params,
        instance.serving_params,
    )
    persisted = load_models(storage, instance.id)
    # one set of algorithm instances for BOTH load_model and serving:
    # load hooks stash serve-time state (e.g. the context for live
    # constraint reads) on the instance
    _, _, algorithms, serving = engine.make_components(engine_params)
    models = engine.prepare_deploy(ctx, engine_params, persisted,
                                   algorithms=algorithms)
    # retrieval mode is deployment config, not model data: applied on
    # every load (including the /reload path, which swaps the whole
    # DeployedEngine — the new model arrives with the same knobs)
    apply_retrieval_config(models, config)
    logger.info(
        "deployed engine instance %s (%s; %d algorithm(s))",
        instance.id, instance.engine_factory, len(algorithms),
    )
    return DeployedEngine(engine, instance, algorithms, serving, models)
