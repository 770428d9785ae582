"""`pio` CLI — app/key/channel administration and server launch.

Subcommand surface mirrors the reference console
(reference: tools/.../console/Console.scala:78-768, Pio.scala:62-340).
Train/eval/deploy subcommands are wired in by the workflow layer as it
lands; this module keeps the registry.
"""

from __future__ import annotations

import argparse
import json
import sys

from predictionio_tpu import __version__
from predictionio_tpu.storage.base import AccessKey, App, Channel
from predictionio_tpu.storage.registry import Storage


def find_channel(storage: Storage, app_id: int, channel_name: str):
    """Channel-by-name within an app, or None — shared by app/channel
    subcommands and export/import."""
    channels = storage.get_meta_data_channels().get_by_app_id(app_id)
    return next((c for c in channels if c.name == channel_name), None)


def _cmd_version(args, storage: Storage) -> int:
    print(__version__)
    return 0


def _cmd_status(args, storage: Storage) -> int:
    """Parity: commands/Management.scala:99-181 (pio status). With
    ``--router host:port`` it inspects a running fleet router instead:
    the registered engine table (name, group sizes, up/down counts,
    canary weight, quota) from ``GET /fleet/engines`` — storage-free,
    like the router itself (docs/fleet.md "Multi-engine routing")."""
    if getattr(args, "router", None):
        return _status_router(args)
    print("[INFO] Inspecting predictionio_tpu...")
    try:
        storage.verify_all_data_objects()
        print("[INFO] Storage: all repositories verified (metadata/eventdata/modeldata)")
    except Exception as exc:
        print(f"[ERROR] Storage check failed: {exc}")
        return 1
    from predictionio_tpu import native
    from predictionio_tpu.utils.accelerator import describe_devices

    try:
        print(f"[INFO] JAX devices: {describe_devices()}")
    except RuntimeError as exc:
        # NoAcceleratorError, or the named backend's own start-up error
        print(f"[ERROR] JAX backend check failed: {exc}")
        return 1
    # which host paths are native: without g++ both quietly run their
    # pure-Python twins, several times slower
    print("[INFO] Native components: "
          f"eventlog={'loaded' if native.load_eventlog() else 'python'} "
          f"packer={'loaded' if native.load_bucketize() else 'numpy'}")
    print("[INFO] Your system is all ready to go.")
    return 0


def _status_router(args) -> int:
    """`pio status --router host:port` — print the router's registered
    engines."""
    import json
    import urllib.error
    import urllib.request

    url = f"http://{args.router}/fleet/engines"
    try:
        with urllib.request.urlopen(
                url, timeout=getattr(args, "timeout", None) or 10.0) as r:
            doc = json.loads(r.read())
    except (urllib.error.URLError, OSError, ValueError) as exc:
        print(f"[ERROR] router {args.router} unreachable: {exc}")
        return 1
    engines = doc.get("engines", [])
    default = doc.get("defaultEngine")
    print(f"[INFO] Fleet router {args.router}: {len(engines)} engine(s)"
          f" (default: {default})")
    for eng in engines:
        name = eng.get("name")
        marker = "*" if name == default else " "
        parts = []
        for group, counts in sorted((eng.get("groups") or {}).items()):
            parts.append(f"{group} {counts.get('up', 0)}/"
                         f"{counts.get('size', 0)} up")
        canary = eng.get("canary") or {}
        weight = canary.get("weightPct", 0.0)
        state = (f"canary {weight:g}%"
                 + (" ABORTED" if canary.get("aborted") else ""))
        quota = eng.get("quota") or {}
        if quota.get("limited"):
            state += (f" | quota qps={quota.get('qps') or 'inf'}"
                      f" inflight<={quota.get('maxInflight') or 'inf'}")
        scale = eng.get("scale")
        if scale:
            last = scale.get("lastDecision")
            reason = scale.get("lastReason")
            state += (f" | replicas {scale.get('actualReplicas')}"
                      f" (desired {scale.get('desiredReplicas')},"
                      f" bounds {scale.get('minReplicas')}-"
                      f"{scale.get('maxReplicas')}"
                      + (", dry-run" if scale.get("dryRun") else "")
                      + ")"
                      + (f" | last {last}:{reason}" if last else ""))
        print(f"[INFO]  {marker} {name}: "
              f"{'; '.join(parts) or 'no backends'} | {state}")
    experiment = doc.get("experiment")
    if experiment:
        decision = experiment.get("decision") or {}
        verdict = (f" — winner {decision.get('winner')}"
                   if decision.get("winner") else "")
        print(f"[INFO] Experiment {experiment.get('name')}: "
              f"{experiment.get('state')}{verdict}")
        for v in experiment.get("variants", []):
            flag = "ABORTED" if v.get("aborted") else \
                f"score {v.get('onlineScore')}"
            print(f"[INFO]    {v.get('name')} ({v.get('weightPct'):g}%): "
                  f"{v.get('requests')} req, {v.get('errors')} err, "
                  f"{v.get('conversions')} conv | {flag}")
    return 0


def _cmd_eventserver(args, storage: Storage) -> int:
    from predictionio_tpu.api.event_server import EventServer, EventServerConfig

    # None/absent flags fall through to the PIO_EVENTSERVER_WAL_* env
    # defaults in EventServerConfig (the ServerConfig discipline)
    wal_overrides = {
        k: v for k, v in {
            "wal_dir": args.wal_dir,
            "wal_fsync": args.wal_fsync,
            "wal_max_bytes": args.wal_max_bytes,
            "wal_policy": args.wal_policy,
        }.items() if v is not None
    }
    server = EventServer(
        storage,
        EventServerConfig(ip=args.ip, port=args.port, stats=args.stats,
                          tracing=args.tracing, access_log=args.access_log,
                          **wal_overrides),
    )
    print(f"[INFO] Event Server listening on {args.ip}:{server.port}")
    if server.service.wal is not None:
        cfg = server.service.config
        print(f"[INFO] Durable ingest: WAL at {cfg.wal_dir} "
              f"(fsync={cfg.wal_fsync}, budget={cfg.wal_max_bytes} bytes, "
              f"policy={cfg.wal_policy}, "
              f"{server.service.wal.pending_records()} pending)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    return 0


def _wal_dir_from(args) -> str | None:
    import os

    return args.wal_dir or os.environ.get("PIO_EVENTSERVER_WAL_DIR") or None


def _cmd_wal(args, storage: Storage) -> int:
    """`pio wal` — operate the durable-ingest journal
    (docs/operations-resilience.md "The ingest durability ladder"):

    - ``status``      non-mutating scan (safe against a LIVE server)
    - ``replay``      foreground drain into storage (server STOPPED)
    - ``dead-letter`` inspect / requeue quarantined records
    """
    from predictionio_tpu.data.wal import (
        WalDrainer,
        WalError,
        WriteAheadLog,
        scan_status,
    )

    wal_dir = _wal_dir_from(args)
    if not wal_dir:
        print("[ERROR] --wal-dir (or PIO_EVENTSERVER_WAL_DIR) is required.")
        return 1
    try:
        if args.wal_command == "status":
            doc = scan_status(wal_dir)
            if args.format == "json":
                print(json.dumps(doc, indent=2))
            else:
                print(f"[INFO] WAL at {doc['dir']}")
                print(f"[INFO]   pending: {doc['depth']} record(s), "
                      f"{doc['bytes']} byte(s) in {doc['segments']} "
                      f"segment(s)")
                print(f"[INFO]   cursor: segment {doc['cursor']['segment']} "
                      f"offset {doc['cursor']['offset']} "
                      f"({doc['replayedTotal']} replayed lifetime)")
                print(f"[INFO]   dead letters: {doc['deadLetterPending']} "
                      f"pending ({doc['deadLetterTotal']} lifetime), "
                      f"corrupt: {doc['corruptRecords']}")
                if doc["tornTail"]:
                    print("[WARN]   torn tail detected (crash artifact; "
                          "recovered on next server start or replay)")
            return 0

        if args.wal_command == "replay":
            # opening the journal RECOVERS it (torn tail truncated) —
            # only safe with the owning event server stopped
            if storage is None:
                storage = Storage.default()
            wal = WriteAheadLog(wal_dir)
            events = storage.get_events()
            drainer = WalDrainer(wal, events.insert_batch,
                                 max_replay_attempts=args.max_attempts)
            start_depth = wal.pending_records()
            print(f"[INFO] replaying {start_depth} journaled record(s) "
                  f"from {wal_dir} ...")
            while True:
                verdict = drainer.drain_once()
                if verdict == "empty":
                    break
                if verdict == "unavailable":
                    print("[ERROR] storage unavailable "
                          f"({wal.pending_records()} record(s) still "
                          "pending) — fix the backend and re-run.")
                    return 1
                # "progress"/"blocked" keep going: blocked records
                # escalate to the dead-letter series after
                # --max-attempts passes
            stats = wal.stats()
            wal.close()
            print(f"[INFO] replay complete: {stats['replayedTotal']} "
                  f"replayed lifetime, {stats['deadLetterTotal']} "
                  f"dead-letter record(s).")
            return 0

        if args.wal_command == "dead-letter":
            wal = WriteAheadLog(wal_dir)
            try:
                if args.requeue:
                    n, kept = wal.requeue_dead_letters()
                    print(f"[INFO] requeued {n} dead-letter record(s) "
                          "into the journal; run `pio wal replay` (or "
                          "start the event server) to drain them.")
                    if kept:
                        print(f"[WARN] kept {kept} undecodable "
                              "envelope(s) in the dead-letter series "
                              "(inspect with `pio wal dead-letter`).")
                    return 0
                shown = 0
                for env_doc in wal.dead_letters():
                    if shown >= args.show:
                        print(f"[INFO] ... (--show {args.show} cap; "
                              "use --show N for more)")
                        break
                    print(json.dumps(env_doc))
                    shown += 1
                if shown == 0:
                    print("[INFO] no dead-letter records.")
                return 0
            finally:
                wal.close()
    except WalError as exc:
        print(f"[ERROR] {exc}")
        return 1
    print(f"[ERROR] Unknown wal command {args.wal_command}")
    return 1


def resolve_concrete_port(ip: str, port: int) -> int:
    """A concrete listen port for a prefork worker pool: every
    SO_REUSEPORT sibling must bind the SAME number, so an ephemeral
    request (``port=0``) is resolved by a throwaway bind BEFORE any
    worker forks — shared by ``pio router --workers N`` and
    ``pio deploy --workers N``."""
    import socket

    if port:
        return port
    probe = socket.socket()
    probe.bind((ip, 0))
    port = probe.getsockname()[1]
    probe.close()
    return port


def _router_worker(config) -> None:
    """One extra `pio router --workers N` worker process: a full
    RouterServer on the shared SO_REUSEPORT listen port."""
    from predictionio_tpu.api.router_server import RouterServer

    server = RouterServer(config)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()


def _scaling_requested(args) -> bool:
    return any(v is not None for v in (
        args.min_replicas, args.max_replicas, args.scale_interval_s,
        args.scale_pressure_up, args.scale_burn_up,
        args.scale_up_sustain_s, args.scale_down_sustain_s,
        args.scale_cooldown_s)) or args.scale_dry_run


def _is_pio_deploy(argv: list[str]) -> bool:
    """Whether a replica command line launches ``pio deploy`` (through
    bin/pio or ``python -m predictionio_tpu.cli.pio``) — a JAX process,
    unlike the storage- and JAX-free router that spawns it."""
    import os

    return "deploy" in argv and any(
        os.path.basename(a) == "pio" or a == "predictionio_tpu.cli.pio"
        for a in argv)


def _cmd_router(args, storage: Storage) -> int:
    """`pio router` — the fleet tier (docs/fleet.md): a thin router
    fronting N engine-server replicas with health-driven membership,
    weighted canary rollout, hedged retries, and bounded admission.
    With ``--supervise`` the router also OWNS its children: worker
    siblings and ``--replica-cmd`` replicas are respawned on death with
    damped backoff (crash loops latch instead of spinning), SIGTERM
    drains the whole fleet, and the scale controller
    (``--min-replicas``/``--max-replicas``/``--scale-*``) adds/removes
    replicas against the autoscaling signals. Storage-free: the router
    talks HTTP to its replicas, never to the event/metadata stores."""
    import dataclasses
    import itertools
    import shlex
    import subprocess

    from predictionio_tpu.api.router_server import RouterServer
    from predictionio_tpu.fleet.router import RouterConfig

    supervise = args.supervise
    scaling = _scaling_requested(args)
    replica_cmd = args.replica_cmd
    if (replica_cmd is not None or scaling) and not supervise:
        print("[ERROR] --replica-cmd and --min/--max-replicas/--scale-* "
              "require --supervise (the supervisor owns the replicas "
              "the controller scales).")
        return 1

    # template replicas (docs/fleet.md "Supervision"): {port} in the
    # command is substituted per replica; ports allocate sequentially
    # from --replica-port-base for initial AND scale-up spawns
    replica_specs = []
    next_replica_spec = None
    if replica_cmd is not None:
        from predictionio_tpu.fleet.supervisor import REPLICA, SpawnSpec

        port_counter = itertools.count(args.replica_port_base)

        def next_replica_spec(_index=None):
            port = next(port_counter)
            argv = [a.format(port=port)
                    for a in shlex.split(replica_cmd)]
            return SpawnSpec(
                id=f"replica:{port}",
                spawn=lambda: subprocess.Popen(argv),
                role=REPLICA,
                address=f"127.0.0.1:{port}")

        min_replicas = args.min_replicas if args.min_replicas is not None \
            else 1
        initial = args.replicas if args.replicas is not None \
            else max(1, min_replicas)
        replica_specs = [next_replica_spec() for _ in range(initial)]

    # named engine groups (docs/fleet.md "Multi-engine routing"):
    # each --engine declares an independent backend group with its own
    # membership/breakers/canary/quota; replicas=N spawns supervised
    # engine replicas from the --replica-cmd template on ports from
    # that engine's port-base
    engine_specs = []
    engine_replica_specs: list[tuple[str, object]] = []
    if args.engine:
        from predictionio_tpu.fleet.gateway import (
            EngineSpec,
            parse_engine_flag,
        )

        try:
            flags = [parse_engine_flag(text) for text in args.engine]
        except ValueError as exc:
            print(f"[ERROR] {exc}")
            return 1
        for flag in flags:
            spawned: list[str] = []
            if flag["replicas"]:
                if replica_cmd is None or not supervise:
                    print(f"[ERROR] --engine {flag['name']}: replicas= "
                          "requires --supervise --replica-cmd (the "
                          "supervisor owns engine replicas).")
                    return 1
                if flag["port_base"] is None:
                    print(f"[ERROR] --engine {flag['name']}: replicas= "
                          "needs port-base= (each engine owns its own "
                          "port range).")
                    return 1
                from predictionio_tpu.fleet.supervisor import (
                    REPLICA,
                    SpawnSpec,
                )

                for i in range(flag["replicas"]):
                    port = flag["port_base"] + i
                    argv = [a.format(port=port)
                            for a in shlex.split(replica_cmd)]
                    engine_replica_specs.append((flag["name"], SpawnSpec(
                        id=f"replica:{flag['name']}:{port}",
                        spawn=(lambda argv=argv:
                               subprocess.Popen(argv)),
                        role=REPLICA,
                        address=f"127.0.0.1:{port}")))
                    spawned.append(f"127.0.0.1:{port}")
            try:
                engine_specs.append(EngineSpec(
                    name=flag["name"],
                    backends=flag["backends"] + tuple(spawned),
                    canary_backends=flag["canary_backends"],
                    canary_weight_pct=flag["weight"] or 0.0,
                    quota_qps=flag["qps"],
                    quota_burst=flag["burst"],
                    max_inflight=flag["max_inflight"],
                    burst_credits=flag["credits"],
                    min_replicas=flag["min_replicas"],
                    max_replicas=flag["max_replicas"]))
            except ValueError as exc:
                print(f"[ERROR] {exc}")
                return 1
        if any(f["min_replicas"] is not None
               or f["max_replicas"] is not None for f in flags):
            # per-engine bounds arm scaling like the global flags do
            if not supervise:
                print("[ERROR] --engine min-replicas=/max-replicas= "
                      "require --supervise (the supervisor owns the "
                      "replicas the per-engine controllers scale).")
                return 1
            scaling = True

    if replica_cmd is not None and _is_pio_deploy(shlex.split(replica_cmd)):
        # each `pio deploy` replica opens the accelerator for itself
        from predictionio_tpu.utils.accelerator import (
            SharedChipError,
            refuse_shared_chip,
        )

        most = max([len(replica_specs) + len(engine_replica_specs),
                    args.max_replicas or 0]
                   + [spec.max_replicas or 0 for spec in engine_specs])
        try:
            refuse_shared_chip(
                "pio router --supervise --replica-cmd 'pio deploy ...' "
                f"with up to {most} replicas", most)
        except SharedChipError as exc:
            print(f"[ERROR] {exc}")
            return 1

    backends = tuple(args.backend or ()) + tuple(
        s.address for s in replica_specs)
    if not backends and not engine_specs:
        print("[ERROR] at least one --backend host:port, --engine "
              "name=...,backend=..., or --supervise --replica-cmd is "
              "required.")
        return 1
    workers = max(1, args.workers or 1)
    config = RouterConfig(
        ip=args.ip,
        port=args.port,
        backends=backends,
        canary_backends=tuple(args.canary_backend or ()),
        engines=tuple(engine_specs),
        router_key=args.router_key,
        access_log=args.access_log,
        tracing=args.tracing,
        reuse_port=workers > 1,
        **{k: v for k, v in {
            "probe_interval_s": args.probe_interval_s,
            "probe_timeout_s": args.probe_timeout_s,
            "down_after": args.down_after,
            "up_after": args.up_after,
            "max_inflight": args.max_inflight,
            "request_deadline_ms": args.request_deadline_ms,
            "hedge": args.hedge,
            "canary_weight_pct": args.canary_weight,
            "default_engine": args.default_engine,
        }.items() if v is not None},
    )
    worker_procs = []
    worker_specs = []
    if workers > 1:
        import multiprocessing
        import tempfile

        config = dataclasses.replace(
            config, port=resolve_concrete_port(config.ip, config.port))
        # worker peering spool (fleet/workers.py): each worker
        # registers its loopback peer endpoint here, so a /metrics
        # scrape landing on ONE SO_REUSEPORT worker reports ALL of
        # them — and the shared canary/admin state document rides the
        # same spool (docs/fleet.md)
        config = dataclasses.replace(
            config,
            worker_spool_dir=tempfile.mkdtemp(prefix="pio-router-workers-"))
        if supervise:
            from predictionio_tpu.fleet.supervisor import (
                WORKER,
                ProcessHandle,
                SpawnSpec,
            )

            def worker_spawn():
                return ProcessHandle(multiprocessing.Process(
                    target=_router_worker, args=(config,), daemon=True))

            worker_specs = [
                SpawnSpec(id=f"worker:{i}", spawn=worker_spawn,
                          role=WORKER)
                for i in range(1, workers)
            ]
        else:
            for _ in range(workers - 1):
                proc = multiprocessing.Process(
                    target=_router_worker, args=(config,), daemon=True)
                proc.start()
                worker_procs.append(proc)

    supervisor = None
    controller = None
    scale_set = None
    if supervise:
        from predictionio_tpu.fleet.supervisor import (
            FleetSupervisor,
            SupervisorConfig,
        )

        supervisor = FleetSupervisor(
            replica_specs + [s for _, s in engine_replica_specs]
            + worker_specs,
            SupervisorConfig(**({"drain_key": args.replica_key}
                                if args.replica_key else {})))
        supervisor.start()
    try:
        server = RouterServer(config)
    except ValueError as exc:
        # gateway-level validation (duplicate --engine name, a name
        # colliding with the default engine built from --backend):
        # a pointed error like every other flag check — and any
        # already-spawned supervised children must not be orphaned
        if supervisor is not None:
            supervisor.shutdown()
        print(f"[ERROR] {exc}")
        return 1
    if supervisor is not None:
        server.service.attach_supervisor(supervisor)
        for engine_name, spec in (
                [(None, s) for s in replica_specs]
                + engine_replica_specs):
            # template replicas are still booting (importing jax):
            # join them DOWN so the probe loop gates traffic onto them
            # when they actually serve — the same invariant the
            # scale-up actuator establishes for identical cold spawns.
            # Engine replicas live in THEIR engine's membership
            group = (server.gateway.get(engine_name)
                     if engine_name else None)
            membership = (group.router.membership if group is not None
                          else server.router.membership)
            backend = membership.by_id(spec.address)
            if backend is not None:
                backend.mark_down("starting")
    if supervise and (scaling or replica_cmd is not None) and engine_specs:
        # per-tenant elasticity (docs/fleet.md "Per-tenant
        # elasticity"): one ScaleController per engine group, each with
        # its own bounds/hysteresis/cooldown, scale-ups arbitrated
        # against the shared --replica-budget. Engines with supervised
        # replicas actuate; engines fronting only static backends run
        # dry (verdicts exported, nothing to spawn).
        import os

        from predictionio_tpu.fleet.controller import (
            CapacityArbiter,
            EngineScaleSet,
            MembershipCountActuator,
            ScalePolicy,
            SupervisedFleetActuator,
            engine_scale_policy,
        )
        from predictionio_tpu.fleet.supervisor import REPLICA, SpawnSpec

        budget = args.replica_budget
        if budget is None:
            raw = os.environ.get("PIO_FLEET_REPLICA_BUDGET")
            try:
                budget = int(raw) if raw else 0
            except ValueError:
                print("[WARN] ignoring unparseable "
                      f"PIO_FLEET_REPLICA_BUDGET={raw!r}")
                budget = 0
        dry_run = bool(args.scale_dry_run) or not scaling
        if dry_run and not args.scale_dry_run:
            print("[INFO] per-engine scale controllers in DRY-RUN (no "
                  "scale bounds given): verdicts exported only; add "
                  "min-replicas=/max-replicas= per engine or --scale-* "
                  "to arm actuation (docs/fleet.md rollout runbook).")
        #: the global --scale-* flags become each tenant's base layer;
        #: PIO_FLEET_ENGINE_<NAME>_* env and per-engine flag keys
        #: override (engine_scale_policy precedence)
        base_policy = {
            "min_replicas": args.min_replicas,
            "max_replicas": args.max_replicas,
            "interval_s": args.scale_interval_s,
            "pressure_up": args.scale_pressure_up,
            "burn_up": args.scale_burn_up,
            "up_sustain_s": args.scale_up_sustain_s,
            "down_sustain_s": args.scale_down_sustain_s,
            "cooldown_s": args.scale_cooldown_s,
        }
        arbiter = CapacityArbiter(budget)
        interval = (args.scale_interval_s
                    if args.scale_interval_s is not None
                    else ScalePolicy().interval_s)
        scale_set = EngineScaleSet(server.service, arbiter,
                                   interval_s=interval)
        supervised: dict[str, list] = {}
        for engine_name, spec in engine_replica_specs:
            supervised.setdefault(engine_name, []).append(spec)
        for flag in flags:
            name = flag["name"]
            group = server.gateway.get(name)
            if group is None:
                continue
            owned = supervised.get(name)
            engine_dry = dry_run
            if owned and replica_cmd is not None:
                # this engine's scale-up ports continue past its
                # initial spawns, inside its own port-base range
                counter = itertools.count(
                    flag["port_base"] + flag["replicas"])

                def make_engine_spec(_index=None, name=name,
                                     counter=counter):
                    port = next(counter)
                    argv = [a.format(port=port)
                            for a in shlex.split(replica_cmd)]
                    return SpawnSpec(
                        id=f"replica:{name}:{port}",
                        spawn=lambda: subprocess.Popen(argv),
                        role=REPLICA,
                        address=f"127.0.0.1:{port}")

                actuator = SupervisedFleetActuator(
                    supervisor, group.router.membership,
                    make_spec=make_engine_spec,
                    breaker_threshold=config.breaker_threshold,
                    breaker_reset_s=config.breaker_reset_s)
                for spec in owned:
                    actuator.adopt(spec.id)
            else:
                actuator = MembershipCountActuator(
                    group.router.membership)
                engine_dry = True
            scale_set.add_engine(
                name,
                engine_scale_policy(
                    name, dry_run=engine_dry, base=base_policy,
                    min_replicas=flag["min_replicas"],
                    max_replicas=flag["max_replicas"]),
                actuator)
        # the default engine built from --backend / --replica-cmd
        # participates too when it exists alongside the named engines
        default_name = server.gateway.default_engine
        if backends and scale_set.get(default_name) is None \
                and server.gateway.get(default_name) is not None:
            engine_dry = dry_run
            if next_replica_spec is not None:
                actuator = SupervisedFleetActuator(
                    supervisor, server.router.membership,
                    make_spec=next_replica_spec,
                    breaker_threshold=config.breaker_threshold,
                    breaker_reset_s=config.breaker_reset_s)
                for spec in replica_specs:
                    actuator.adopt(spec.id)
            else:
                actuator = MembershipCountActuator(
                    server.router.membership)
                engine_dry = True
            scale_set.add_engine(
                default_name,
                engine_scale_policy(default_name, dry_run=engine_dry,
                                    base=base_policy),
                actuator)
        scale_set.start()
        server.service.attach_scale_set(scale_set)
    elif supervise and (scaling or replica_cmd is not None):
        from predictionio_tpu.fleet.controller import (
            MembershipCountActuator,
            ScaleController,
            ScalePolicy,
            SupervisedFleetActuator,
            fleet_signals_reader,
        )

        # actuation must be REQUESTED: --replica-cmd alone runs the
        # controller in dry-run (verdicts exported, nothing spawned) —
        # the documented rollout posture. Passing any --scale-* or
        # --min/--max-replicas flag without --scale-dry-run arms it.
        dry_run = bool(args.scale_dry_run) or not scaling
        if dry_run and not args.scale_dry_run:
            print("[INFO] scale controller in DRY-RUN (no --scale-* "
                  "flags given): verdicts exported only; add "
                  "--min/--max-replicas or --scale-* to arm actuation "
                  "(docs/fleet.md rollout runbook).")
        if next_replica_spec is not None:
            actuator = SupervisedFleetActuator(
                supervisor, server.router.membership,
                make_spec=next_replica_spec,
                breaker_threshold=config.breaker_threshold,
                breaker_reset_s=config.breaker_reset_s)
            for spec in replica_specs:
                actuator.adopt(spec.id)
        else:
            print("[WARN] scale flags without --replica-cmd: the "
                  "controller has nothing to actuate — forcing "
                  "--scale-dry-run (decisions exported only).")
            actuator = MembershipCountActuator(server.router.membership)
            dry_run = True
        policy = ScalePolicy(
            dry_run=dry_run,
            **{k: v for k, v in {
                "min_replicas": args.min_replicas,
                "max_replicas": args.max_replicas,
                "interval_s": args.scale_interval_s,
                "pressure_up": args.scale_pressure_up,
                "burn_up": args.scale_burn_up,
                "up_sustain_s": args.scale_up_sustain_s,
                "down_sustain_s": args.scale_down_sustain_s,
                "cooldown_s": args.scale_cooldown_s,
            }.items() if v is not None})
        controller = ScaleController(
            policy, fleet_signals_reader(server.service), actuator)
        controller.start()
        server.service.attach_controller(controller)
    print(f"[INFO] Fleet Router listening on {args.ip}:{server.port} "
          f"({len(config.backends)} stable / "
          f"{len(config.canary_backends)} canary backend(s), "
          f"{workers} worker(s)"
          + (f", {len(server.gateway.engine_names())} engines "
             f"[default: {server.gateway.default_engine}]"
             if engine_specs else "")
          + (", supervised" if supervise else "")
          + (", scale controller "
             + ("dry-run" if controller is not None
                and controller.policy.dry_run else "active")
             if controller is not None else "")
          + (f", per-engine elasticity x{len(scale_set.controllers())}"
             + (f" budget={scale_set.arbiter.budget}"
                if scale_set.arbiter.budget else "")
             if scale_set is not None else "")
          + ")")
    if worker_procs or supervisor is not None:
        # SIGTERM's default action kills the parent without running
        # finally/atexit, orphaning the SO_REUSEPORT workers on the
        # shared port (they keep serving with a stale spool). Route it
        # through KeyboardInterrupt so the finally always runs — under
        # --supervise that means a graceful FULL-FLEET drain (replicas
        # drained via /readyz before SIGTERM, then workers), fixing
        # the old "stop from the shell stops one worker" quirk.
        import signal

        def _on_sigterm(signum, frame):
            raise KeyboardInterrupt

        signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if controller is not None:
            controller.stop()
        if scale_set is not None:
            scale_set.stop()
        if supervisor is not None:
            supervisor.shutdown()
        server.stop()
        for proc in worker_procs:
            proc.terminate()
        for proc in worker_procs:
            proc.join(timeout=5)
        if config.worker_spool_dir:
            # terminate() is SIGTERM: workers die without running
            # WorkerHub.close, leaving their spool entries behind —
            # the parent mkdtemp'd the dir, the parent removes it
            import shutil

            shutil.rmtree(config.worker_spool_dir, ignore_errors=True)
    return 0


def _cmd_trace(args, storage: Storage) -> int:
    """`pio trace <trace_id>` — fetch the stitched cross-process tree
    of one fleet request from the router's merge endpoint
    (GET /traces.json?trace_id=) and render it as a text tree or
    Chrome trace-viewer JSON (docs/observability.md)."""
    import urllib.error
    import urllib.parse
    import urllib.request

    from predictionio_tpu.obs.stitch import render_tree, to_chrome_trace

    url = (f"http://{args.router}/traces.json?"
           f"trace_id={urllib.parse.quote(args.trace_id)}")
    try:
        with urllib.request.urlopen(url, timeout=args.timeout) as r:
            doc = json.load(r)
    except urllib.error.HTTPError as e:
        try:
            doc = json.load(e)
        except json.JSONDecodeError:
            doc = {}
        print(f"[ERROR] trace {args.trace_id} not found "
              f"({doc.get('message', f'HTTP {e.code}')})")
        return 1
    except OSError as e:
        print(f"[ERROR] router {args.router} unreachable: {e}")
        return 1
    tree = doc.get("trace")
    if not doc.get("found") or tree is None:
        print(f"[ERROR] trace {args.trace_id} not found")
        return 1
    if args.chrome:
        payload = json.dumps(to_chrome_trace(tree), indent=2)
        if args.out:
            with open(args.out, "w") as f:
                f.write(payload)
            print(f"[INFO] Chrome trace written to {args.out} "
                  f"(open chrome://tracing or ui.perfetto.dev)")
        else:
            print(payload)
    else:
        print(render_tree(tree))
        if doc.get("scrapeErrors"):
            print(f"[WARN] {doc['scrapeErrors']} replica trace ring(s) "
                  "unreachable; the tree may be missing segments")
    return 0


def _cmd_app(args, storage: Storage) -> int:
    """Parity: commands/App.scala:25-365."""
    apps = storage.get_meta_data_apps()
    keys = storage.get_meta_data_access_keys()
    channels = storage.get_meta_data_channels()
    events = storage.get_events()
    if args.app_command == "new":
        if args.access_key and keys.get(args.access_key) is not None:
            print(f"[ERROR] Access key {args.access_key} already exists.")
            return 1
        app_id = apps.insert(App(args.id or 0, args.name, args.description))
        if app_id is None:
            print(f"[ERROR] App {args.name} already exists.")
            return 1
        events.init(app_id)
        key = keys.insert(AccessKey(args.access_key or "", app_id, ()))
        if key is None:
            print(f"[ERROR] Access key {args.access_key} already exists.")
            return 1
        print(f"[INFO] Created a new app:")
        print(f"[INFO]         Name: {args.name}")
        print(f"[INFO]           ID: {app_id}")
        print(f"[INFO]   Access Key: {key}")
        return 0
    if args.app_command == "list":
        for app in apps.get_all():
            app_keys = keys.get_by_app_id(app.id)
            key_str = app_keys[0].key if app_keys else ""
            print(f"[INFO]   {app.name} (id={app.id}) key={key_str}")
        return 0
    if args.app_command == "show":
        app = apps.get_by_name(args.name)
        if app is None:
            print(f"[ERROR] App {args.name} does not exist.")
            return 1
        print(f"[INFO]     App Name: {app.name}")
        print(f"[INFO]       App ID: {app.id}")
        print(f"[INFO]  Description: {app.description or ''}")
        for k in keys.get_by_app_id(app.id):
            allowed = ",".join(k.events) if k.events else "(all)"
            print(f"[INFO]   Access Key: {k.key} | {allowed}")
        for c in channels.get_by_app_id(app.id):
            print(f"[INFO]      Channel: {c.name} (id={c.id})")
        return 0
    if args.app_command == "delete":
        app = apps.get_by_name(args.name)
        if app is None:
            print(f"[ERROR] App {args.name} does not exist.")
            return 1
        for c in channels.get_by_app_id(app.id):
            events.remove(app.id, c.id)
            channels.delete(c.id)
        events.remove(app.id)
        for k in keys.get_by_app_id(app.id):
            keys.delete(k.key)
        apps.delete(app.id)
        print(f"[INFO] App {args.name} deleted.")
        return 0
    if args.app_command == "data-delete":
        app = apps.get_by_name(args.name)
        if app is None:
            print(f"[ERROR] App {args.name} does not exist.")
            return 1
        if args.channel:
            chan = find_channel(storage, app.id, args.channel)
            if chan is None:
                print(f"[ERROR] Channel {args.channel} does not exist.")
                return 1
            events.remove(app.id, chan.id)
            events.init(app.id, chan.id)
        else:
            events.remove(app.id)
            events.init(app.id)
        print(f"[INFO] Data of app {args.name} deleted.")
        return 0
    if args.app_command == "channel-new":
        app = apps.get_by_name(args.name)
        if app is None:
            print(f"[ERROR] App {args.name} does not exist.")
            return 1
        channel_id = channels.insert(Channel(0, args.channel, app.id))
        if channel_id is None:
            print(f"[ERROR] Invalid channel name: {args.channel}")
            return 1
        events.init(app.id, channel_id)
        print(f"[INFO] Channel {args.channel} (id={channel_id}) created.")
        return 0
    if args.app_command == "channel-delete":
        app = apps.get_by_name(args.name)
        if app is None:
            print(f"[ERROR] App {args.name} does not exist.")
            return 1
        chan = find_channel(storage, app.id, args.channel)
        if chan is None:
            print(f"[ERROR] Channel {args.channel} does not exist.")
            return 1
        events.remove(app.id, chan.id)
        channels.delete(chan.id)
        print(f"[INFO] Channel {args.channel} deleted.")
        return 0
    print(f"[ERROR] Unknown app command {args.app_command}")
    return 1


def _cmd_accesskey(args, storage: Storage) -> int:
    """Parity: commands/AccessKey.scala:26-66."""
    apps = storage.get_meta_data_apps()
    keys = storage.get_meta_data_access_keys()
    if args.ak_command == "new":
        app = apps.get_by_name(args.app_name)
        if app is None:
            print(f"[ERROR] App {args.app_name} does not exist.")
            return 1
        key = keys.insert(
            AccessKey(args.access_key or "", app.id, tuple(args.event or ()))
        )
        if key is None:
            print(f"[ERROR] Access key {args.access_key} already exists.")
            return 1
        print(f"[INFO] Created new access key: {key}")
        return 0
    if args.ak_command == "list":
        app = apps.get_by_name(args.app_name) if args.app_name else None
        for k in keys.get_all():
            if args.app_name and (app is None or k.appid != app.id):
                continue
            allowed = ",".join(k.events) if k.events else "(all)"
            print(f"[INFO]   {k.key} | app={k.appid} | {allowed}")
        return 0
    if args.ak_command == "delete":
        keys.delete(args.key)
        print(f"[INFO] Deleted access key {args.key}")
        return 0
    print(f"[ERROR] Unknown accesskey command {args.ak_command}")
    return 1


def _git_changed_relpaths(pkg: str) -> set[str]:
    """Package-relative paths of .py files git sees as modified, staged
    or untracked — the `pio lint --changed` reporting scope. Raises
    RuntimeError when git is unavailable (the caller exits 2: a CI hook
    must fail loudly, not silently lint nothing)."""
    import os.path
    import subprocess

    try:
        top = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"],
            cwd=pkg, capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired) as exc:
        raise RuntimeError(f"--changed needs git: {exc}")
    if top.returncode != 0:
        raise RuntimeError("--changed: package is not inside a git work tree")
    root = top.stdout.strip()
    out = subprocess.run(
        ["git", "status", "--porcelain", "--untracked-files=all"],
        cwd=root, capture_output=True, text=True, timeout=10)
    if out.returncode != 0:
        raise RuntimeError(
            f"--changed: git status failed: {out.stderr.strip()}")
    changed: set[str] = set()
    for line in out.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:]
        if " -> " in path:  # rename: the new side is what gets linted
            path = path.split(" -> ", 1)[1]
        path = path.strip().strip('"')
        if not path.endswith(".py"):
            continue
        abspath = os.path.abspath(os.path.join(root, path))
        if abspath.startswith(pkg + os.sep):
            changed.add(os.path.relpath(abspath, pkg).replace(os.sep, "/"))
    return changed


def _cmd_lint(args, storage: Storage) -> int:
    """`pio lint` — AST invariant checker for the serving/compute paths
    (docs/static-analysis.md). Exit 0 clean, 1 on findings."""
    import os.path

    import predictionio_tpu
    from predictionio_tpu.analysis import (
        all_rules,
        default_config,
        format_findings,
        lint_paths_report,
    )

    if args.list_rules:
        policy = default_config()
        for rule_id, rule in sorted(all_rules().items()):
            # the EFFECTIVE repo-policy scope, not the rule's built-in
            # default — the listing must match what a run checks
            paths = ", ".join(p or "<all>" for p in policy.rule_paths(rule))
            print(f"{rule_id:24s} {rule.description} [{paths}]")
        return 0

    pkg = os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
    changed = None
    if args.changed:
        try:
            changed = _git_changed_relpaths(pkg)
        except RuntimeError as exc:
            print(f"[ERROR] {exc}", file=sys.stderr)
            return 2
    cache = None
    if not args.no_cache:
        from predictionio_tpu.analysis.cache import (
            LintCache,
            default_cache_path,
            rules_fingerprint,
        )

        cache = LintCache(default_cache_path(pkg),
                          rules_fingerprint(default_config(), args.rules))
    project = not args.no_project

    try:
        if not args.paths:
            findings, stats = lint_paths_report(
                [pkg], rel_root=pkg, rule_ids=args.rules, cache=cache,
                project=project, changed=changed)
        else:
            # paths inside the package keep the policy's package-relative
            # scoping; ad-hoc files outside it (fixtures, snippets) run
            # every requested rule unscoped — `pio lint some_file.py
            # --rule X` must never silently skip X for scope reasons
            in_pkg = [
                p for p in args.paths
                if os.path.abspath(p) == pkg
                or os.path.abspath(p).startswith(pkg + os.sep)
            ]
            external = [p for p in args.paths if p not in in_pkg]
            findings, stats = [], None
            if in_pkg:
                findings, stats = lint_paths_report(
                    in_pkg, rel_root=pkg, rule_ids=args.rules, cache=cache,
                    project=project, changed=changed)
            if external:
                ext_findings, ext_stats = lint_paths_report(
                    external, config=default_config().unscoped(),
                    rule_ids=args.rules, project=project)
                findings += ext_findings
                stats = _merge_lint_stats(stats, ext_stats)
            findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
    except (KeyError, OSError) as exc:
        # stderr: stdout must stay machine-parseable under --format json
        print(f"[ERROR] {exc.args[0] if isinstance(exc, KeyError) else exc}",
              file=sys.stderr)
        return 2

    from predictionio_tpu.analysis.report import (
        apply_baseline,
        load_baseline,
        write_baseline,
    )

    if args.write_baseline:
        n = write_baseline(args.write_baseline, findings)
        print(f"[INFO] wrote {n} finding(s) to {args.write_baseline}",
              file=sys.stderr)
        return 0
    if args.baseline:
        try:
            accepted = load_baseline(args.baseline)
        except (OSError, ValueError) as exc:
            print(f"[ERROR] {exc}", file=sys.stderr)
            return 2
        findings, suppressed = apply_baseline(findings, accepted)
        if suppressed:
            print(f"[INFO] baseline suppressed {suppressed} finding(s)",
                  file=sys.stderr)
    print(format_findings(
        findings, fmt=args.format,
        stats=stats if args.format == "json" else None))
    return 1 if findings else 0


def _merge_lint_stats(a, b):
    """Fold two LintStats (in-package + external path runs) into one
    JSON report; rule lists union, counters and timings add."""
    if a is None:
        return b
    a.files += b.files
    a.cache_hits += b.cache_hits
    a.cache_misses += b.cache_misses
    a.parse_s += b.parse_s
    a.module_rules_s += b.module_rules_s
    a.project_rules_s += b.project_rules_s
    a.total_s += b.total_s
    a.module_rules = sorted(set(a.module_rules) | set(b.module_rules))
    a.project_rules = sorted(set(a.project_rules) | set(b.project_rules))
    return a


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="pio",
        description="predictionio_tpu: TPU-native machine-learning server framework",
    )
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version", help="show version")
    p = sub.add_parser("status", help="verify environment and storage")
    p.add_argument("--router", default=None, metavar="HOST:PORT",
                   help="inspect a running fleet router instead: print "
                        "its registered engine table (name, group "
                        "sizes, up/down counts, canary weight, quota) "
                        "from GET /fleet/engines — storage-free")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="HTTP timeout for the --router fetch")

    p = sub.add_parser("eventserver", help="launch the event server")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7070)
    p.add_argument("--stats", action="store_true")
    # observability (docs/observability.md): None defers to the
    # PIO_TRACE / PIO_ACCESS_LOG env vars
    p.add_argument("--tracing", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="per-request span collection for the ingest "
                        "paths (served on GET /traces.json)")
    p.add_argument("--access-log", action=argparse.BooleanOptionalAction,
                   default=None, dest="access_log",
                   help="structured JSON access logs (method, path, "
                        "status, latency_ms, request_id)")
    # durable ingest (docs/operations-resilience.md "The ingest
    # durability ladder"); None defers to PIO_EVENTSERVER_WAL_* env
    p.add_argument("--wal-dir", default=None, dest="wal_dir",
                   help="write-ahead journal directory: storage outages "
                        "ride through as 202-journaled events replayed "
                        "by a background drainer (default: WAL off, "
                        "outages shed 503s)")
    p.add_argument("--wal-fsync", default=None, dest="wal_fsync",
                   choices=("always", "interval", "off"),
                   help="journal fsync policy: always = every 202 is "
                        "crash-durable; interval (default) = bounded "
                        "loss window, near-direct throughput; off = OS "
                        "page cache only")
    p.add_argument("--wal-max-bytes", type=int, default=None,
                   dest="wal_max_bytes",
                   help="journal disk budget; past it ingest reverts to "
                        "503 backpressure with a drain-aware Retry-After")
    p.add_argument("--wal-policy", default=None, dest="wal_policy",
                   choices=("ride-through", "write-through"),
                   help="ride-through (default): journal only during "
                        "outages; write-through: journal EVERY accepted "
                        "event (always 202, storage written by the "
                        "drainer; reads lag by the drain depth)")

    p = sub.add_parser(
        "router",
        help="launch the fleet router fronting N engine-server replicas "
             "(docs/fleet.md)",
    )
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8100)
    p.add_argument("--backend", action="append", metavar="HOST:PORT",
                   help="stable replica address (repeatable; required)")
    p.add_argument("--canary-backend", action="append", metavar="HOST:PORT",
                   dest="canary_backend",
                   help="canary replica address (repeatable)")
    p.add_argument("--canary-weight", type=float, default=None,
                   dest="canary_weight", metavar="PCT",
                   help="initial %% of traffic routed to the canary group")
    # None falls through to RouterConfig's PIO_ROUTER_* env-aware
    # defaults (the ServerConfig discipline — no re-hard-coding here)
    p.add_argument("--probe-interval-s", type=float, default=None,
                   dest="probe_interval_s")
    p.add_argument("--probe-timeout-s", type=float, default=None,
                   dest="probe_timeout_s",
                   help="per-probe socket bound; size for the replica's "
                        "p99 under load, NOT idle latency — a saturated "
                        "CPython replica can sit >1s on /healthz "
                        "(docs/fleet.md runbooks)")
    p.add_argument("--down-after", type=int, default=None, dest="down_after",
                   help="consecutive failed probes before mark-down")
    p.add_argument("--up-after", type=int, default=None, dest="up_after",
                   help="consecutive good probes before mark-up")
    p.add_argument("--max-inflight", type=int, default=None,
                   dest="max_inflight",
                   help="bounded admission: concurrent in-flight requests")
    p.add_argument("--request-deadline-ms", type=float, default=None,
                   dest="request_deadline_ms")
    p.add_argument("--hedge", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="tail-latency hedging: fire a second attempt on "
                        "another replica after a p99-derived delay")
    p.add_argument("--router-key", default=None, dest="router_key",
                   help="when set, /fleet/canary and /stop require this key")
    p.add_argument("--workers", type=int, default=1,
                   help="router worker processes sharing the listen "
                        "port via SO_REUSEPORT (one CPython process "
                        "tops out on its GIL long before the fleet "
                        "does); each worker probes and holds canary "
                        "state independently — see docs/fleet.md")
    p.add_argument("--engine", action="append", metavar="SPEC",
                   help="a named engine group behind this router "
                        "(repeatable; docs/fleet.md \"Multi-engine "
                        "routing\"): comma-separated key=value pairs — "
                        "name=rec,backend=h:p+h:p[,canary=h:p]"
                        "[,weight=10][,qps=100][,burst=200]"
                        "[,max-inflight=64][,replicas=2,port-base=8300]"
                        "[,min-replicas=1,max-replicas=4][,credits=50]"
                        " (replicas= spawns supervised engine replicas "
                        "from --replica-cmd; min/max-replicas= bound "
                        "that engine's OWN scale controller under the "
                        "shared --replica-budget; credits= caps its "
                        "burst-credit reservoir). Requests route by path "
                        "/engines/<name>/queries.json or the "
                        "X-PIO-Engine header; bare /queries.json keeps "
                        "hitting the default engine")
    p.add_argument("--default-engine", default=None, dest="default_engine",
                   metavar="NAME",
                   help="engine bare /queries.json routes to (default: "
                        "the --backend group, else the first --engine; "
                        "PIO_ROUTER_DEFAULT_ENGINE)")
    p.add_argument("--access-log", action=argparse.BooleanOptionalAction,
                   default=None, dest="access_log",
                   help="structured JSON access logs")
    p.add_argument("--tracing", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="root span per routed query (admission, pick, "
                        "attempt/retry/hedge) with trace context "
                        "forwarded to replicas for cross-process "
                        "stitching; see `pio trace`")
    # self-healing (docs/fleet.md "Supervision" / "Autoscaling"):
    # PIO_FLEET_* env tunes the supervisor backoff/crash-loop and the
    # scale policy defaults; None here falls through to those
    p.add_argument("--supervise", action="store_true",
                   help="own the worker siblings (and --replica-cmd "
                        "replicas): respawn on death with damped "
                        "backoff, latch crash loops, drain the whole "
                        "fleet on SIGTERM")
    p.add_argument("--replica-cmd", default=None, dest="replica_cmd",
                   metavar="CMD",
                   help="shell-style command template spawning one "
                        "engine-server replica; {port} is substituted "
                        "(e.g. 'pio deploy --port {port}'); requires "
                        "--supervise")
    p.add_argument("--replica-key", default=None, dest="replica_key",
                   help="accessKey the supervisor sends on POST /drain "
                        "when the --replica-cmd replicas run with a "
                        "server key (PIO_FLEET_DRAIN_KEY)")
    p.add_argument("--replica-port-base", type=int, default=8200,
                   dest="replica_port_base",
                   help="first replica port for --replica-cmd spawns "
                        "(sequential from here, scale-ups included)")
    p.add_argument("--replicas", type=int, default=None,
                   help="initial --replica-cmd replica count (default: "
                        "max(1, --min-replicas))")
    p.add_argument("--min-replicas", type=int, default=None,
                   dest="min_replicas",
                   help="scale controller floor (PIO_FLEET_MIN_REPLICAS)")
    p.add_argument("--max-replicas", type=int, default=None,
                   dest="max_replicas",
                   help="scale controller ceiling (PIO_FLEET_MAX_REPLICAS)")
    p.add_argument("--scale-dry-run", action="store_true",
                   dest="scale_dry_run",
                   help="evaluate the scale policy but only EXPORT "
                        "verdicts (pio_fleet_desired_replicas vs "
                        "actual + decision counters) — the rollout "
                        "posture; see docs/fleet.md")
    p.add_argument("--scale-interval-s", type=float, default=None,
                   dest="scale_interval_s")
    p.add_argument("--scale-pressure-up", type=float, default=None,
                   dest="scale_pressure_up",
                   help="scale up when pio_fleet_pressure sustains "
                        "at/above this (PIO_FLEET_PRESSURE_UP)")
    p.add_argument("--scale-burn-up", type=float, default=None,
                   dest="scale_burn_up",
                   help="scale up when the fast-window SLO burn rate "
                        "reaches this (PIO_FLEET_BURN_UP)")
    p.add_argument("--scale-up-sustain-s", type=float, default=None,
                   dest="scale_up_sustain_s")
    p.add_argument("--scale-down-sustain-s", type=float, default=None,
                   dest="scale_down_sustain_s",
                   help="quiet cooldown before a scale-in "
                        "(PIO_FLEET_DOWN_SUSTAIN_S)")
    p.add_argument("--scale-cooldown-s", type=float, default=None,
                   dest="scale_cooldown_s",
                   help="minimum gap between scale actions "
                        "(PIO_FLEET_COOLDOWN_S)")
    p.add_argument("--replica-budget", type=int, default=None,
                   dest="replica_budget",
                   help="fleet-wide replica budget across ALL engines "
                        "(device/HBM slots; 0 = unlimited, "
                        "PIO_FLEET_REPLICA_BUDGET). Contention is "
                        "burn-weighted; a hot tenant may preempt an "
                        "idle tenant's above-min replica "
                        "(docs/fleet.md \"Per-tenant elasticity\")")

    p = sub.add_parser(
        "trace",
        help="fetch and render one stitched fleet trace from the "
             "router (docs/observability.md)",
    )
    p.add_argument("trace_id", help="the X-PIO-Trace-Id of the request")
    p.add_argument("--router", default="127.0.0.1:8100",
                   metavar="HOST:PORT",
                   help="router address serving /traces.json (default "
                        "127.0.0.1:8100)")
    p.add_argument("--chrome", action="store_true",
                   help="emit Chrome trace-viewer JSON instead of the "
                        "text tree (open in chrome://tracing or "
                        "ui.perfetto.dev)")
    p.add_argument("--out", default=None,
                   help="write --chrome JSON to this file")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="HTTP timeout for the router fetch")

    p = sub.add_parser("app", help="app administration")
    app_sub = p.add_subparsers(dest="app_command", required=True)
    pn = app_sub.add_parser("new")
    pn.add_argument("name")
    pn.add_argument("--id", type=int)
    pn.add_argument("--description")
    pn.add_argument("--access-key", dest="access_key")
    for name in ("list",):
        app_sub.add_parser(name)
    ps = app_sub.add_parser("show")
    ps.add_argument("name")
    pd = app_sub.add_parser("delete")
    pd.add_argument("name")
    pdd = app_sub.add_parser("data-delete")
    pdd.add_argument("name")
    pdd.add_argument("--channel")
    pcn = app_sub.add_parser("channel-new")
    pcn.add_argument("name")
    pcn.add_argument("channel")
    pcd = app_sub.add_parser("channel-delete")
    pcd.add_argument("name")
    pcd.add_argument("channel")

    p = sub.add_parser(
        "lint",
        help="AST invariant checker for the serving/compute paths "
             "(docs/static-analysis.md)",
    )
    p.add_argument(
        "paths", nargs="*",
        help="files or directories (default: the installed predictionio_tpu package)",
    )
    p.add_argument(
        "--rule", action="append", dest="rules", metavar="RULE_ID",
        help="run only this rule (repeatable; see --list-rules)",
    )
    p.add_argument("--list-rules", action="store_true",
                   help="list registered rules and exit")
    p.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text",
                   help="json includes run stats (files, cache hits, "
                        "phase timings); sarif emits SARIF 2.1.0")
    p.add_argument("--baseline", metavar="FILE",
                   help="report (and fail on) only findings NOT in this "
                        "baseline snapshot — lets a stricter rule land "
                        "before the tree is fully clean")
    p.add_argument("--write-baseline", metavar="FILE",
                   dest="write_baseline",
                   help="snapshot the current findings to FILE and exit 0")
    p.add_argument("--changed", action="store_true",
                   help="report only findings in files git sees as "
                        "modified/untracked (the whole tree is still "
                        "analyzed, so cross-module passes stay sound)")
    p.add_argument("--no-project", action="store_true", dest="no_project",
                   help="skip whole-program passes (shared-state-race, "
                        "lock-order, jit-recompile-risk)")
    p.add_argument("--no-cache", action="store_true", dest="no_cache",
                   help="neither read nor write the per-file result cache")

    p = sub.add_parser(
        "wal",
        help="operate the durable-ingest write-ahead journal "
             "(docs/operations-resilience.md)",
    )
    wal_sub = p.add_subparsers(dest="wal_command", required=True)
    ws = wal_sub.add_parser(
        "status", help="non-mutating journal scan (safe against a "
                       "running event server)")
    ws.add_argument("--wal-dir", default=None, dest="wal_dir",
                    help="journal directory (default: "
                         "PIO_EVENTSERVER_WAL_DIR)")
    ws.add_argument("--format", choices=("text", "json"), default="text")
    wr = wal_sub.add_parser(
        "replay", help="foreground drain into storage — run with the "
                       "owning event server STOPPED (opening the "
                       "journal recovers torn tails)")
    wr.add_argument("--wal-dir", default=None, dest="wal_dir")
    wr.add_argument("--max-attempts", type=int, default=5,
                    dest="max_attempts",
                    help="application-failure passes per record before "
                         "dead-letter quarantine")
    wd = wal_sub.add_parser(
        "dead-letter", help="inspect or requeue quarantined records")
    wd.add_argument("--wal-dir", default=None, dest="wal_dir")
    wd.add_argument("--show", type=int, default=20,
                    help="print at most this many envelopes")
    wd.add_argument("--requeue", action="store_true",
                    help="move every dead-letter record back into the "
                         "live journal (after fixing the cause — see "
                         "the runbook)")

    p = sub.add_parser("accesskey", help="access key administration")
    ak_sub = p.add_subparsers(dest="ak_command", required=True)
    an = ak_sub.add_parser("new")
    an.add_argument("app_name")
    an.add_argument("--access-key", dest="access_key")
    an.add_argument("--event", action="append")
    al = ak_sub.add_parser("list")
    al.add_argument("app_name", nargs="?")
    ad = ak_sub.add_parser("delete")
    ad.add_argument("key")

    parser.subparsers = sub  # handle for late-bound subcommand registration
    return parser


#: commands that never touch storage — they must work (CI lint hooks,
#: version probes, the storage-free fleet router and its trace viewer)
#: even when PIO_STORAGE_* env is broken or absent. `wal` rides here
#: because status/dead-letter operate on the journal directory alone;
#: its replay subcommand builds Storage.default() itself.
STORAGE_FREE_COMMANDS = frozenset({"version", "lint", "router", "trace",
                                   "wal"})

_COMMANDS = {
    "version": _cmd_version,
    "status": _cmd_status,
    "eventserver": _cmd_eventserver,
    "router": _cmd_router,
    "trace": _cmd_trace,
    "wal": _cmd_wal,
    "app": _cmd_app,
    "accesskey": _cmd_accesskey,
    "lint": _cmd_lint,
}


def register_command(name: str, configure_parser, run) -> None:
    """Extension point used by the workflow layer to add train/eval/deploy."""
    _COMMANDS[name] = run
    _EXTRA_PARSERS.append((name, configure_parser))


_EXTRA_PARSERS: list = []


def main(argv: list[str] | None = None) -> int:
    # late-bound subcommands (train/deploy/eval) register on import
    try:
        import predictionio_tpu.workflow.cli_commands  # noqa: F401
    except ImportError:
        pass
    parser = build_parser()
    for name, configure in _EXTRA_PARSERS:
        configure(parser.subparsers)
    args = parser.parse_args(argv)
    if not args.command:
        parser.print_help()
        return 1
    if args.command in STORAGE_FREE_COMMANDS or (
            args.command == "status" and getattr(args, "router", None)):
        # `pio status --router` inspects a running router over HTTP —
        # storage-free like the router itself, so it works from an
        # operator box with no PIO_STORAGE_* configured
        return _COMMANDS[args.command](args, None)
    storage = Storage.default()
    return _COMMANDS[args.command](args, storage)


if __name__ == "__main__":
    # Re-resolve main through the canonical module name: under
    # ``python -m predictionio_tpu.cli.pio`` this file executes as
    # ``__main__`` while workflow.cli_commands registers train/deploy/...
    # into the ``predictionio_tpu.cli.pio`` instance — calling the local
    # main() would silently drop those subcommands.
    from predictionio_tpu.cli.pio import main as _canonical_main

    sys.exit(_canonical_main())
