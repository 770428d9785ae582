"""CLI subcommands backed by the workflow and tools layers: train, eval,
deploy, undeploy, dashboard, adminserver, export, import, build, run,
upgrade, template.

Parity: tools/.../console/Console.scala build:147/train:177/eval:227/
deploy:255/undeploy:313/dashboard:326/adminserver:354/run:367/upgrade:396/
template:546/export:561/import:578 and commands/Engine.scala:37-318. The
reference spawned `spark-submit` of CreateWorkflow/CreateServer
(Runner.scala:185-307); here training and serving run in-process on the
JAX runtime — there is no assembly jar or process boundary to cross, so
`pio build` reduces to the checks the reference's compile step enforced
(factory resolves, engine.json params bind).
"""

from __future__ import annotations

import argparse
import json
import os

from predictionio_tpu.cli.pio import find_channel, register_command
from predictionio_tpu.workflow.context import WorkflowParams


def _load_variant(path: str) -> dict | None:
    """Parse an engine variant file. {} when the file is absent; None
    (with a printed error) when it exists but is not valid JSON — every
    subcommand gets the same clean diagnostic instead of a traceback."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as exc:
            print(f"[ERROR] {path} is not valid JSON: {exc}")
            return None


def _check_template_min_version(template_json: str = "template.json") -> bool:
    """template.json {"pio": {"version": {"min": "X.Y.Z"}}} gate on
    train/deploy. Parity: Template.verifyTemplateMinVersion
    (tools/.../commands/Template.scala:31-69). Returns False (with an
    error printed) when this framework is older than the template needs."""
    if not os.path.exists(template_json):
        return True
    try:
        with open(template_json) as f:
            spec = json.load(f)
        min_version = spec.get("pio", {}).get("version", {}).get("min")
    except (json.JSONDecodeError, AttributeError):
        print(f"[WARN] {template_json} is malformed; skipping version check.")
        return True
    if not min_version:
        return True
    from predictionio_tpu import __version__

    def vtuple(v):
        return tuple(int(p) for p in str(v).split(".") if p.isdigit())

    if not vtuple(min_version):
        print(f"[WARN] {template_json} min version {min_version!r} is not "
              "a version string; skipping version check.")
        return True
    if vtuple(__version__) < vtuple(min_version):
        print(f"[ERROR] This template requires predictionio_tpu >= {min_version} "
              f"(current: {__version__}).")
        return False
    return True


def _claim_devices(launcher: str = "", processes: int = 1) -> bool:
    """The start-of-command device rule of every compute command
    (utils/accelerator): place the compile cache, claim the devices and
    print which they are — or, for a ``launcher`` that fans out over
    ``processes`` JAX processes, refuse when those would share a chip
    and otherwise leave the claim to each child (a process that has
    touched JAX must not fork workers). False, with the error printed,
    when there is no accelerator and JAX_PLATFORMS did not ask for cpu."""
    from predictionio_tpu.utils import accelerator

    try:
        accelerator.refuse_shared_chip(launcher, processes)
        if processes == 1:
            accelerator.start_compute()
    except RuntimeError as exc:
        # NoAcceleratorError / SharedChipError, or the start-up error of
        # the backend JAX_PLATFORMS names
        print(f"[ERROR] {exc}")
        return False
    return True


def _serve(server, label: str, ip: str) -> int:
    """Print the bound address and block until interrupt — shared by every
    server-launching subcommand."""
    print(f"[INFO] {label} listening on {ip}:{server.port}")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


# ---------------------------------------------------------------------------
# pio train
# ---------------------------------------------------------------------------

def _configure_train(sub) -> None:
    p = sub.add_parser("train", help="train an engine variant")
    p.add_argument("--engine-json", default="engine.json",
                   help="engine variant file (default: ./engine.json)")
    p.add_argument("--engine-factory", default="",
                   help="override engineFactory from engine.json")
    p.add_argument("--batch", default="", help="batch label")
    p.add_argument("--skip-sanity-check", action="store_true")
    p.add_argument("--stop-after-read", action="store_true")
    p.add_argument("--stop-after-prepare", action="store_true")
    p.add_argument("--no-save-model", action="store_true", dest="no_save_model")
    p.add_argument("--profile", action="store_true",
                   help="profile the run: per-stage wall/compile/execute "
                        "split, MFU, HBM peaks and the recompile table, "
                        "written to TRAIN_REPORT.json (docs/observability.md "
                        "'Device and compiler observability')")
    p.add_argument("--profile-dir", default="",
                   help="with --profile: also dump a jax.profiler trace "
                        "into this directory for deep dives (TensorBoard/"
                        "Perfetto); implies --profile")
    p.add_argument("--profile-out", default="TRAIN_REPORT.json",
                   help="where --profile writes the report "
                        "(default: ./TRAIN_REPORT.json)")


def _cmd_train(args, storage) -> int:
    from predictionio_tpu.workflow.train import run_train

    if not _claim_devices() or not _check_template_min_version():
        return 1
    variant = _load_variant(args.engine_json)
    if variant is None:
        return 1
    if not variant and not args.engine_factory:
        print(f"[ERROR] {args.engine_json} not found and no --engine-factory given.")
        return 1
    wp = WorkflowParams(
        batch=args.batch,
        save_model=not args.no_save_model,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
    )
    profiler = None
    if args.profile or args.profile_dir:
        from predictionio_tpu.obs.device import TrainProfiler

        profiler = TrainProfiler(profile_dir=args.profile_dir or None)
    outcome = run_train(
        engine_factory=args.engine_factory,
        variant=variant,
        workflow_params=wp,
        storage=storage,
        profiler=profiler,
    )
    print(f"[INFO] Training finished: engine instance {outcome.instance_id} "
          f"({outcome.status})")
    if outcome.stage_seconds:
        from predictionio_tpu.workflow.train import format_stage_times

        # per-DASE-stage walltimes (docs/observability.md): where a
        # slow train actually spent its time
        print(f"[INFO] Stage times: {format_stage_times(outcome.stage_seconds)}")
    if outcome.report is not None:
        import json as _json

        from predictionio_tpu.obs.device import summarize_train_report

        print(f"[INFO] Train profile: {summarize_train_report(outcome.report)}")
        try:
            with open(args.profile_out, "w") as f:
                _json.dump(outcome.report, f, indent=2)
        except OSError as e:
            # the train itself succeeded and the summary already
            # printed — an unwritable report path must not turn a
            # completed (and persisted) run into a failing exit code
            print(f"[WARN] could not write {args.profile_out}: {e}")
        else:
            print(f"[INFO] Train report written to {args.profile_out}")
        if args.profile_dir:
            print(f"[INFO] jax.profiler trace in {args.profile_dir}")
    return 0 if outcome.status in ("COMPLETED", "INTERRUPTED") else 1


# ---------------------------------------------------------------------------
# pio eval
# ---------------------------------------------------------------------------

def _configure_eval(sub) -> None:
    p = sub.add_parser("eval", help="evaluate an engine over a params grid")
    p.add_argument("evaluation", help="Evaluation class spec, e.g. pkg.mod.MyEval")
    p.add_argument("params_generator", nargs="?", default="",
                   help="EngineParamsGenerator class spec (defaults to the "
                        "evaluation module's own generator if omitted)")
    p.add_argument("--batch", default="")
    p.add_argument("--parallel", type=int, default=None, metavar="N",
                   help="fan grid points over N eval worker processes "
                        "(default: PIO_EVAL_PARALLEL or 1 = sequential)")


def _cmd_eval(args, storage) -> int:
    from predictionio_tpu.workflow.evaluation import (
        resolve_parallel,
        run_evaluation,
    )

    parallel = resolve_parallel(args.parallel)
    if not _claim_devices(f"pio eval --parallel {parallel}", parallel):
        return 1
    generator = args.params_generator or _default_generator(args.evaluation)
    try:
        outcome = run_evaluation(
            args.evaluation,
            generator,
            workflow_params=WorkflowParams(batch=args.batch),
            storage=storage,
            parallel=parallel,
        )
    except Exception as exc:
        # the instance row already says FAILED (workflow/evaluation.py)
        print(f"[ERROR] Evaluation failed: {exc}")
        return 1
    print(f"[INFO] Evaluation finished: instance {outcome.instance_id}")
    print(f"[INFO] {outcome.result.to_one_liner()}")
    return 0


def _default_generator(evaluation_spec: str):
    """When no generator spec is given, look for an EngineParamsGenerator
    subclass/instance in the evaluation's module (the reference required
    both classes; this is a convenience on top)."""
    import importlib

    from predictionio_tpu.controller.evaluation import EngineParamsGenerator
    from predictionio_tpu.utils.reflection import resolve_attr

    evaluation = resolve_attr(evaluation_spec)
    module = importlib.import_module(type(evaluation).__module__
                                     if not isinstance(evaluation, type)
                                     else evaluation.__module__)
    for name in dir(module):
        obj = getattr(module, name)
        if isinstance(obj, EngineParamsGenerator):
            return obj
        if (isinstance(obj, type) and issubclass(obj, EngineParamsGenerator)
                and obj is not EngineParamsGenerator):
            return obj()
    raise ValueError(
        f"no EngineParamsGenerator found in {module.__name__}; "
        "pass one explicitly: pio eval <evaluation> <generator>"
    )


# ---------------------------------------------------------------------------
# pio deploy / undeploy
# ---------------------------------------------------------------------------

def _configure_deploy(sub) -> None:
    p = sub.add_parser("deploy", help="deploy the latest trained engine instance")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    # prefork worker pool (docs/serving-performance.md "Multi-process
    # serving"): N engine-server processes share one SO_REUSEPORT
    # listen port — the serving plane's escape from the single-process
    # GIL floor. None defers to PIO_SERVING_WORKERS.
    p.add_argument("--workers", type=int, default=None,
                   help="engine-server worker processes sharing the "
                        "listen port via SO_REUSEPORT; /metrics, "
                        "/stats.json and /traces.json report the whole "
                        "pool from any worker, and /reload//drain/"
                        "/retrieval reach every sibling")
    p.add_argument("--supervise", action="store_true",
                   help="own the worker siblings: respawn on death "
                        "with damped backoff, latch crash loops, stop "
                        "the whole pool on SIGTERM (fleet/supervisor)")
    p.add_argument("--model-mmap", action="store_true", dest="model_mmap",
                   help="load npz model checkpoints with mmap so the "
                        "worker processes share one physical copy of "
                        "the factor tables (sets PIO_CHECKPOINT_MMAP=r; "
                        "utils/checkpoint has the verification "
                        "trade-off)")
    p.add_argument("--engine-instance-id", default=None)
    p.add_argument("--engine-json", default="engine.json")
    p.add_argument("--feedback", action="store_true")
    p.add_argument("--event-server-ip", default="0.0.0.0")
    p.add_argument("--event-server-port", type=int, default=7070)
    p.add_argument("--accesskey", default="", help="access key for feedback events")
    p.add_argument("--server-key", default=None,
                   help="when set, /stop and /reload require this key")
    # serving knobs default to None so an absent flag falls through to
    # ServerConfig's PIO_SERVING_* env-aware defaults instead of
    # re-hard-coding them here; the boolean pairs (--batching /
    # --no-batching) exist so the CLI can force either state over a
    # fleet-wide env setting (docs/serving-performance.md)
    p.add_argument("--batching", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="coalesce concurrent queries into one device "
                        "dispatch (micro-batching; the adaptive policy "
                        "waits near-zero when idle)")
    p.add_argument("--batch-policy", choices=("adaptive", "fixed"),
                   default=None)
    p.add_argument("--batch-max", type=int, default=None)
    p.add_argument("--batch-wait-ms", type=float, default=None,
                   help="adaptive: wait cap; fixed: the constant window")
    p.add_argument("--cache", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="LRU+TTL result cache over canonical query "
                        "JSON, invalidated on /reload")
    p.add_argument("--cache-max-entries", type=int, default=None)
    p.add_argument("--cache-ttl-s", type=float, default=None)
    p.add_argument("--shm-cache", action=argparse.BooleanOptionalAction,
                   default=None, dest="shm_cache",
                   help="back the result cache with ONE shared-memory "
                        "segment all --workers siblings attach (a key "
                        "warmed by any worker is hot pool-wide; "
                        "serving/shm_cache). Implies --cache; falls "
                        "back to the private LRU where the platform "
                        "lacks shm")
    p.add_argument("--shm-slots", type=int, default=None,
                   dest="shm_slots",
                   help="slot count of the shared cache table "
                        "(PIO_SERVING_SHM_SLOTS)")
    p.add_argument("--shm-slot-bytes", type=int, default=None,
                   dest="shm_slot_bytes",
                   help="bytes per shared-cache slot "
                        "(PIO_SERVING_SHM_SLOT_BYTES)")
    # sublinear retrieval (ops/ann; docs/serving-performance.md):
    # None defers to the PIO_SERVING_ANN_* env-aware ServerConfig
    # defaults, matching the other serving knobs
    p.add_argument("--retrieval", choices=("brute", "ann"), default=None,
                   help="'ann' probes the IVF-flat MIPS index persisted "
                        "beside the model (built at deploy when missing) "
                        "and exact-rescores the shortlist; 'brute' "
                        "scores the full item table per query")
    p.add_argument("--ann-nlist", type=int, default=None, dest="ann_nlist",
                   help="IVF cell count for a deploy-time index build "
                        "(0 = auto ~4*sqrt(catalog))")
    p.add_argument("--ann-nprobe", type=int, default=None,
                   dest="ann_nprobe",
                   help="cells probed per query (0 = auto nlist/64, "
                        "floored at 16); higher = better recall, more "
                        "rescore work")
    p.add_argument("--ann-rescore", type=int, default=None,
                   dest="ann_rescore",
                   help="cap on shortlist candidates exact-rescored per "
                        "query (0 = all probed candidates)")
    # real-time freshness plane (online/; docs/freshness.md): None
    # defers to the PIO_ONLINE_* env-aware ServerConfig defaults
    p.add_argument("--online", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="fold new events into the deployed ALS model "
                        "between retrains: tail the event store and "
                        "recompute touched users' vectors closed-form "
                        "— event→recommendation freshness in seconds, "
                        "no retrain, no restart")
    p.add_argument("--online-interval-s", type=float, default=None,
                   dest="online_interval_s",
                   help="tail polling interval (the freshness lag "
                        "floor; default 1.0)")
    p.add_argument("--online-overlay-max", type=int, default=None,
                   dest="online_overlay_max",
                   help="max folded users held in the serving overlay "
                        "(LRU; evicted users fall back to their base "
                        "vector until the next retrain)")
    p.add_argument("--online-state-dir", default=None,
                   dest="online_state_dir",
                   help="directory for the durable tail cursor "
                        "(restart resumes exactly-once; default: "
                        "in-memory, re-tails from deploy time)")
    # observability (docs/observability.md): None defers to the
    # PIO_TRACE / PIO_ACCESS_LOG env vars; the boolean pairs let the
    # CLI force either state over a fleet-wide env setting
    p.add_argument("--tracing", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="per-request span collection for /queries.json "
                        "(served on GET /traces.json)")
    p.add_argument("--access-log", action=argparse.BooleanOptionalAction,
                   default=None, dest="access_log",
                   help="structured JSON access logs (method, path, "
                        "status, latency_ms, request_id)")


def _deploy_worker(config) -> None:
    """One extra `pio deploy --workers N` sibling process: a full
    engine server on the shared SO_REUSEPORT port, with its OWN storage
    connection and model replica (mmap-share the factor tables via
    --model-mmap / PIO_CHECKPOINT_MMAP=r)."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.serving.placement import apply_worker_affinity
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.utils.accelerator import start_compute

    start_compute()
    # before the model loads, so its pages fault in on the pinned
    # cores; a respawn re-applies (the index rides the config, and the
    # stripe is carved from the CLI's pre-pin CPU snapshot — a respawn
    # inherits the PINNED parent's mask, which must not narrow ours)
    apply_worker_affinity(config.worker_index, max(1, config.workers),
                          cpus=config.cpu_allowlist)
    server = create_engine_server(storage=Storage.default(), config=config)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()


def _cmd_deploy(args, storage) -> int:
    import dataclasses

    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.workflow.deploy import ServerConfig

    if not _check_template_min_version():
        return 1
    variant = _load_variant(args.engine_json)
    if variant is None:
        return 1
    if args.model_mmap:
        # before any model load, and inherited by every worker spawn:
        # N processes map the same checkpoint pages instead of holding
        # N heap copies (utils/checkpoint module docstring)
        os.environ["PIO_CHECKPOINT_MMAP"] = "r"
    config = ServerConfig(
        ip=args.ip,
        port=args.port,
        engine_instance_id=args.engine_instance_id,
        engine_id=variant.get("id"),
        engine_version=variant.get("version"),
        engine_variant=variant.get("variantId"),
        feedback=args.feedback,
        event_server_ip=args.event_server_ip,
        event_server_port=args.event_server_port,
        access_key=args.accesskey,
        server_key=args.server_key,
        **{k: v for k, v in {
            "batching": args.batching,
            "batch_policy": args.batch_policy,
            "batch_max": args.batch_max,
            "batch_wait_ms": args.batch_wait_ms,
            # --shm-cache without --cache means "cache, shared": the
            # shm flag implies the cache it backs
            "cache_enabled": (True if (args.cache is None
                                       and args.shm_cache)
                              else args.cache),
            "cache_max_entries": args.cache_max_entries,
            "cache_ttl_s": args.cache_ttl_s,
            "shm_cache": args.shm_cache,
            "shm_slots": args.shm_slots,
            "shm_slot_bytes": args.shm_slot_bytes,
            "retrieval": args.retrieval,
            "ann_nlist": args.ann_nlist,
            "ann_nprobe": args.ann_nprobe,
            "ann_rescore": args.ann_rescore,
            "tracing": args.tracing,
            "access_log": args.access_log,
            "workers": args.workers,
            "online": args.online,
            "online_interval_s": args.online_interval_s,
            "online_overlay_max": args.online_overlay_max,
            "online_state_dir": args.online_state_dir,
        }.items() if v is not None},
    )
    workers = max(1, config.workers)
    if not _claim_devices(f"pio deploy --workers {workers}", workers):
        return 1
    if workers == 1:
        if args.supervise:
            # nothing to supervise: the supervisor owns worker
            # SIBLINGS, and a 1-worker deploy is just this process —
            # say so instead of silently dropping the flag
            print("[WARN] --supervise has no effect with --workers 1 "
                  "(it respawns worker siblings); use an external "
                  "supervisor for a single process.")
        server = create_engine_server(storage=storage, config=config)
        return _serve(
            server,
            f"Engine instance {server.service.deployed.instance.id}",
            args.ip,
        )

    # prefork pool: N-1 sibling processes + this one share the
    # SO_REUSEPORT port; the spool carries peering + shared admin state
    # (docs/serving-performance.md "Multi-process serving")
    import multiprocessing
    import shutil
    import signal
    import tempfile

    from predictionio_tpu.cli.pio import resolve_concrete_port

    config = dataclasses.replace(
        config,
        port=resolve_concrete_port(config.ip, config.port),
        reuse_port=True,
        worker_spool_dir=tempfile.mkdtemp(prefix="pio-deploy-workers-"))

    # ONE shared-memory cache segment for the whole pool: the parent
    # creates and owns it (unlinked in the teardown below), workers
    # attach by name. Creation failure degrades the pool to private
    # per-worker LRUs — same serving semantics, worker-local warmth.
    shm_owner = None
    if config.shm_cache and config.cache_enabled and not config.shm_segment:
        from predictionio_tpu.serving.shm_cache import ShmResultCache

        segment = f"pio-shm-{os.getpid()}"
        try:
            shm_owner = ShmResultCache(
                segment, nslots=config.shm_slots,
                slot_bytes=config.shm_slot_bytes,
                ttl_s=config.cache_ttl_s, create="create")
            config = dataclasses.replace(config, shm_segment=segment)
        except Exception as exc:
            print(f"[WARN] shared-memory cache unavailable "
                  f"({type(exc).__name__}: {exc}); workers fall back "
                  f"to private result caches")
            config = dataclasses.replace(config, shm_cache=False)

    # capture the pool's allowed-CPU set BEFORE the parent pins itself
    # to stripe 0: a supervisor respawn happens after that pin, and the
    # child would inherit (and carve from) the parent's one-stripe
    # mask — every respawn piling onto worker 0's cores is the exact
    # opposite of the placement intent
    from predictionio_tpu.serving.placement import apply_worker_affinity

    getaffinity = getattr(os, "sched_getaffinity", None)
    try:
        allowed_cpus = (tuple(sorted(getaffinity(0)))
                        if getaffinity is not None else None)
    except OSError:
        allowed_cpus = None
    config = dataclasses.replace(config, cpu_allowlist=allowed_cpus)

    def sibling(index: int):
        return multiprocessing.Process(
            target=_deploy_worker,
            args=(dataclasses.replace(config, worker_index=index),),
            daemon=True)

    # SIGTERM's default action would kill this parent without running
    # any finally, orphaning the SO_REUSEPORT siblings on the shared
    # port; route it through KeyboardInterrupt (the `pio router`
    # discipline) BEFORE the first sibling spawns — a stop landing
    # mid-model-load must tear the pool down too, so everything from
    # the spawns on runs inside the cleanup try
    def _on_sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_sigterm)
    supervisor = None
    worker_procs: list = []
    server = None
    try:
        if args.supervise:
            from predictionio_tpu.fleet.supervisor import (
                WORKER,
                FleetSupervisor,
                ProcessHandle,
                SpawnSpec,
            )

            supervisor = FleetSupervisor([
                SpawnSpec(id=f"worker:{i}",
                          spawn=lambda i=i: ProcessHandle(sibling(i)),
                          role=WORKER)
                for i in range(1, workers)
            ])
            supervisor.start()
        else:
            for i in range(1, workers):
                proc = sibling(i)
                proc.start()
                worker_procs.append(proc)
        # the parent is worker 0 of the pool: pin it to its own stripe
        # (carved from the same pre-pin snapshot the workers use)
        apply_worker_affinity(0, workers, cpus=config.cpu_allowlist)
        # only now that every sibling has forked may this process touch
        # JAX (_claim_devices)
        from predictionio_tpu.utils.accelerator import start_compute

        start_compute()
        server = create_engine_server(storage=storage, config=config)
        print(f"[INFO] Engine instance "
              f"{server.service.deployed.instance.id} listening on "
              f"{args.ip}:{server.port} ({workers} worker(s)"
              + (", supervised" if supervisor is not None else "") + ")")
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if supervisor is not None:
            supervisor.shutdown()
        if server is not None:
            server.stop()
        for proc in worker_procs:
            proc.terminate()
        for proc in worker_procs:
            proc.join(timeout=5)
        # terminate() is SIGTERM: siblings die without running
        # WorkerHub.close, leaving spool entries behind — the parent
        # mkdtemp'd the dir, the parent removes it
        shutil.rmtree(config.worker_spool_dir, ignore_errors=True)
        if shm_owner is not None:
            # same ownership story as the spool: the parent created
            # the segment, the parent unlinks it
            shm_owner.close(unlink=True)
    return 0


def _configure_undeploy(sub) -> None:
    p = sub.add_parser("undeploy", help="stop a deployed engine server")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--server-key", default=None)


def _cmd_undeploy(args, storage) -> int:
    from predictionio_tpu.api.engine_server import undeploy

    if undeploy(args.ip, args.port, args.server_key):
        print(f"[INFO] Undeployed engine server at {args.ip}:{args.port}")
        return 0
    print(f"[ERROR] No engine server running at {args.ip}:{args.port}")
    return 1


# ---------------------------------------------------------------------------
# pio dashboard / adminserver
# ---------------------------------------------------------------------------

def _configure_dashboard(sub) -> None:
    p = sub.add_parser("dashboard", help="launch the evaluation dashboard")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9000)
    p.add_argument("--access-log", action=argparse.BooleanOptionalAction,
                   default=None, dest="access_log",
                   help="structured JSON access logs "
                        "(docs/observability.md)")


def _cmd_dashboard(args, storage) -> int:
    from predictionio_tpu.tools.dashboard import Dashboard

    return _serve(Dashboard(storage, ip=args.ip, port=args.port,
                            access_log=args.access_log),
                  "Dashboard", args.ip)


def _configure_adminserver(sub) -> None:
    p = sub.add_parser("adminserver", help="launch the admin REST API")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7071)


def _cmd_adminserver(args, storage) -> int:
    from predictionio_tpu.tools.admin import AdminServer

    return _serve(AdminServer(storage, ip=args.ip, port=args.port),
                  "Admin API", args.ip)


# ---------------------------------------------------------------------------
# pio export / import
# ---------------------------------------------------------------------------

def _configure_export(sub) -> None:
    p = sub.add_parser(
        "export", help="export an app's events to a JSON-lines or Parquet file"
    )
    p.add_argument("--appid", type=int, required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--channel", default=None)
    # EventsToFile.scala:97-105 format option
    p.add_argument("--format", choices=("json", "parquet"), default="json")


def _resolve_app_channel(storage, app_id: int, channel_name: str | None):
    """Validate --appid refers to a real app (unlike raw DAO access, which
    would silently auto-init an orphan event table) and resolve --channel.
    Returns (ok, channel_id)."""
    if storage.get_meta_data_apps().get(app_id) is None:
        print(f"[ERROR] App id {app_id} does not exist.")
        return False, None
    if channel_name is None:
        return True, None
    chan = find_channel(storage, app_id, channel_name)
    if chan is None:
        print(f"[ERROR] Channel {channel_name} does not exist.")
        return False, None
    return True, chan.id


def _cmd_export(args, storage) -> int:
    from predictionio_tpu.tools.export_import import (
        export_events,
        export_events_parquet,
    )

    ok, channel_id = _resolve_app_channel(storage, args.appid, args.channel)
    if not ok:
        return 1
    if getattr(args, "format", "json") == "parquet":
        try:
            n = export_events_parquet(storage, args.appid, args.output, channel_id)
        except ImportError:
            print("[ERROR] Parquet support requires pyarrow "
                  "(pip install 'predictionio-tpu[parquet]').")
            return 1
    else:
        with open(args.output, "w") as f:
            n = export_events(storage, args.appid, f, channel_id)
    print(f"[INFO] Exported {n} events to {args.output}")
    return 0


def _configure_import(sub) -> None:
    p = sub.add_parser(
        "import", help="import events from a JSON-lines or Parquet file"
    )
    p.add_argument("--appid", type=int, required=True)
    p.add_argument("--input", required=True)
    p.add_argument("--channel", default=None)
    p.add_argument("--format", choices=("json", "parquet"), default=None,
                   help="default: parquet for .parquet files, else json")


def _cmd_import(args, storage) -> int:
    from predictionio_tpu.tools.export_import import (
        ImportFormatError,
        import_events,
        import_events_parquet,
    )

    ok, channel_id = _resolve_app_channel(storage, args.appid, args.channel)
    if not ok:
        return 1
    if not os.path.exists(args.input):
        print(f"[ERROR] {args.input} not found.")
        return 1
    fmt = getattr(args, "format", None) or (
        "parquet" if args.input.endswith(".parquet") else "json"
    )
    try:
        if fmt == "parquet":
            n = import_events_parquet(storage, args.appid, args.input, channel_id)
        else:
            with open(args.input) as f:
                n = import_events(storage, args.appid, f, channel_id)
    except ImportFormatError as e:
        print(f"[ERROR] {args.input}: {e}")
        return 1
    except ImportError:
        print("[ERROR] Parquet support requires pyarrow "
              "(pip install 'predictionio-tpu[parquet]').")
        return 1
    print(f"[INFO] Imported {n} events from {args.input}")
    return 0


# ---------------------------------------------------------------------------
# pio build / run / upgrade / template
# ---------------------------------------------------------------------------

def _configure_build(sub) -> None:
    p = sub.add_parser("build", help="verify an engine variant is runnable")
    p.add_argument("--engine-json", default="engine.json",
                   help="engine variant file (default: ./engine.json)")
    p.add_argument("--engine-factory", default="",
                   help="override engineFactory from engine.json")


def _cmd_build(args, storage) -> int:
    """Verify the engine variant: template version gate + engineFactory
    import + instantiation. Parity: commands/Engine.scala build:65-163 —
    the reference generated pio.sbt and ran sbt package/assembly; Python
    engines import directly, so "build" reduces to the same checks the
    reference's compile step enforced (factory resolves, params bind)."""
    from predictionio_tpu.controller.engine import resolve_engine_factory

    if not _check_template_min_version():
        return 1
    variant = _load_variant(args.engine_json)
    if variant is None:
        return 1
    factory_path = args.engine_factory or variant.get("engineFactory", "")
    if not factory_path:
        if os.path.exists(args.engine_json):
            print(f"[ERROR] {args.engine_json} has no engineFactory and "
                  "no --engine-factory given.")
        else:
            print(f"[ERROR] {args.engine_json} not found and no "
                  "--engine-factory given.")
        return 1
    try:
        factory = resolve_engine_factory(factory_path)
        engine = factory()
    except Exception as exc:
        print(f"[ERROR] engineFactory {factory_path!r} failed: {exc}")
        return 1
    try:
        engine.params_from_variant_json(variant)
    except Exception as exc:
        print(f"[ERROR] engine.json params do not bind: {exc}")
        return 1
    print(f"[INFO] Build successful: {factory_path} "
          f"({type(engine).__name__}) binds {args.engine_json}.")
    return 0


def _configure_run(sub) -> None:
    p = sub.add_parser(
        "run", help="run an arbitrary main function with storage wired up")
    p.add_argument("main", help="dotted path module[:function] (default function: main)")
    import argparse

    p.add_argument("args", nargs=argparse.REMAINDER,
                   help="arguments passed through verbatim")


def _cmd_run(args, storage) -> int:
    """Launch an arbitrary user main with the PIO environment prepared.
    Parity: commands/Engine.scala run:278 (spark-submit of a user class);
    here the user names ``pkg.module[:function]`` and it runs in-process
    with storage initialised."""
    import importlib

    if not _claim_devices():
        return 1
    target = args.main
    mod_name, _, fn_name = target.partition(":")
    fn_name = fn_name or "main"
    try:
        module = importlib.import_module(mod_name)
        fn = getattr(module, fn_name)
    except (ImportError, AttributeError) as exc:
        print(f"[ERROR] cannot resolve {target!r}: {exc}")
        return 1
    result = fn(*args.args)
    # bool subclasses int; a main returning True means success, not rc=1
    if isinstance(result, bool):
        return 0 if result else 1
    return int(result) if isinstance(result, int) else 0


def _configure_upgrade(sub) -> None:
    sub.add_parser("upgrade", help="(no longer supported)")


def _cmd_upgrade(args, storage) -> int:
    # Parity: Console.scala:664-666 — upgrade is a hard error upstream too.
    print("[ERROR] Upgrade is no longer supported")
    return 1


def _configure_template(sub) -> None:
    p = sub.add_parser("template", help="(no longer supported; use git)")
    p.add_argument("subcommand", nargs="*")


def _cmd_template(args, storage) -> int:
    # Parity: Console.scala:691-694 — template gallery was retired upstream;
    # engine templates ship in predictionio_tpu.templates instead.
    print("[ERROR] template commands are no longer supported.")
    print("[ERROR] Built-in engine templates live in predictionio_tpu.templates "
          "(recommendation, similarproduct, ecommerce, classification).")
    return 1


register_command("train", _configure_train, _cmd_train)
register_command("eval", _configure_eval, _cmd_eval)
register_command("deploy", _configure_deploy, _cmd_deploy)
register_command("undeploy", _configure_undeploy, _cmd_undeploy)
register_command("dashboard", _configure_dashboard, _cmd_dashboard)
register_command("adminserver", _configure_adminserver, _cmd_adminserver)
register_command("export", _configure_export, _cmd_export)
register_command("import", _configure_import, _cmd_import)
register_command("build", _configure_build, _cmd_build)
register_command("run", _configure_run, _cmd_run)
register_command("upgrade", _configure_upgrade, _cmd_upgrade)
register_command("template", _configure_template, _cmd_template)

# `pio experiment` registers itself on import, same extension point
import predictionio_tpu.experiment.cli  # noqa: E402,F401
