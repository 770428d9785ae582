"""A cell's closed-loop traffic against a real ``pio deploy --batching``
child, to set beside the in-process host of ``run.py``.

    python3 benchmarks/tools/deploy_child.py --workload <cell> [--manifest M] --seconds 30

JAX-free parent (``chip_smoke.py``'s process model): ``pio app new``,
seed the configuration's ratings as ``rate`` events, ``pio train``,
``pio deploy --batching``, then the cell's generator children against
the child's port: ``--warm`` seconds untimed (the child compiles its
signatures on live requests; nothing else can warm it from outside),
then the window. Prints one JSON line. The model is trained, not
seeded, so answers are not compared; shapes, traffic and seen lists are
the cell's.
"""

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
PIO = os.path.join(ROOT, "bin", "pio")
WORK = os.path.join(ROOT, "benchmarks", ".cache", "deploy_child")
PORT = 18433
APP = "BenchDeployChild"


def child_env() -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith("PIO_")}
    env.update({
        "PYTHONPATH": ROOT, "PYTHONUNBUFFERED": "1",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PIO_SQLITE",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PIO_BIN",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "PIO_FS",
        "PIO_STORAGE_SOURCES_PIO_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_PIO_SQLITE_PATH": os.path.join(WORK, "pio.db"),
        "PIO_STORAGE_SOURCES_PIO_BIN_TYPE": "binevents",
        "PIO_STORAGE_SOURCES_PIO_BIN_PATH": os.path.join(WORK, "events"),
        "PIO_STORAGE_SOURCES_PIO_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_PIO_FS_PATH": os.path.join(WORK, "models"),
        "PIO_FS_BASEDIR": WORK,
    })
    return env


def run(label: str, argv: list, timeout: float) -> str:
    t = time.monotonic()
    p = subprocess.run(argv, cwd=WORK, env=child_env(), text=True,
                       capture_output=True, timeout=timeout)
    print(f"[deploy_child] {label}: exit {p.returncode} in "
          f"{time.monotonic() - t:.1f}s", file=sys.stderr)
    if p.returncode != 0:
        raise SystemExit(f"{label} failed\n{p.stdout[-2000:]}\n{p.stderr[-2000:]}")
    return p.stdout


def seed(config_file: str, seed_: int, app_id: int) -> int:
    """In a child of its own: the storage layer's bulk path."""
    from datetime import datetime, timedelta, timezone

    from benchmarks.harness import traffic as tr
    from predictionio_tpu.core.datamap import DataMap
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.registry import Storage

    with open(config_file) as f:
        config = json.load(f)
    users, items, vals = tr.make_ratings(config, seed_)
    events = Storage.default().get_events()
    events.init(app_id)
    t0 = datetime(2020, 1, 1, tzinfo=timezone.utc)
    for lo in range(0, len(users), 5000):
        hi = min(len(users), lo + 5000)
        events.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap({"rating": float(v)}),
                  event_time=t0 + timedelta(seconds=k))
            for k, u, i, v in zip(range(lo, hi), users[lo:hi].tolist(),
                                  items[lo:hi].tolist(), vals[lo:hi].tolist())
        ], app_id)
    return 0


def get(path: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{PORT}{path}",
                                timeout=timeout) as r:
        return r.read().decode()


def recompiles() -> float:
    m = re.search(r"^pio_serving_recompile_total(?:\{[^}]*\})? (\S+)$",
                  get("/metrics"), re.M)
    return float(m.group(1)) if m else 0.0


def load(cell, seed_: int, seconds: float):
    """The cell's generator children against PORT; their merged records."""
    from benchmarks.harness import serve

    class Port:
        port = PORT

    work = serve.workdir()
    children = serve.spawn_generators(cell, seed_, seconds, work)
    try:
        parts, _ = serve.run_window(children, Port, seconds, None)
        return serve.merge(parts)
    finally:
        serve.stop_children(children)
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "_seed":
        return seed(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warm", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from benchmarks.harness import serve
    from benchmarks.harness.manifest import load_cell

    cell = load_cell(args.workload, args.manifest)
    cfg = cell.config
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    cfg_file = os.path.join(WORK, "config.json")
    with open(cfg_file, "w") as f:
        json.dump(cfg, f)
    out = run("app new", [PIO, "app", "new", APP], 300)
    app_id = int(re.search(r"ID: (\d+)", out).group(1))
    run("seed", [sys.executable, os.path.abspath(__file__), "_seed", cfg_file,
                 str(args.seed), str(app_id)], 3000)
    with open(os.path.join(WORK, "engine.json"), "w") as f:
        json.dump({
            "id": "bench-deploy-child",
            "engineFactory":
                "predictionio_tpu.templates.recommendation.engine_factory",
            "datasource": {"params": {"app_name": APP}},
            "algorithms": [{"name": "als", "params": {
                "rank": cfg["rank"], "numIterations": cfg["num_iterations"],
                "lambda": cfg["lambda"], "seed": 3}}],
        }, f)
    out = run("train", [PIO, "train"], 3000)
    stages = re.search(r"Stage times: (.*)", out)
    with open(os.path.join(WORK, "deploy.log"), "w") as logf:
        server = subprocess.Popen(
            [PIO, "deploy", "--batching", "--ip", "127.0.0.1",
             "--port", str(PORT)], cwd=WORK, env=child_env(), stdout=logf,
            stderr=subprocess.STDOUT, start_new_session=True)
    try:
        t0 = time.monotonic()
        while True:
            if server.poll() is not None:
                raise SystemExit(f"deploy exited {server.returncode}")
            try:
                get("/", timeout=5)
                break
            except (urllib.error.URLError, OSError):
                if time.monotonic() - t0 > 600:
                    raise SystemExit("deploy not ready in 600 s")
                time.sleep(0.5)
        ready_s = time.monotonic() - t0
        load(cell, args.seed + 1000, args.warm)
        before = recompiles()
        rec = load(cell, args.seed, args.seconds)
        m = serve.latency_metrics(rec, args.seconds)
        compiled = recompiles() - before
        platform = re.search(r"JAX devices: (.*?) \|",
                             open(os.path.join(WORK, "deploy.log")).read())
        run("undeploy", [PIO, "undeploy", "--ip", "127.0.0.1",
                         "--port", str(PORT)], 60)
        server.wait(timeout=60)
    finally:
        if server.poll() is None:
            os.killpg(server.pid, signal.SIGKILL)
            server.wait()
    print(json.dumps({
        "host": "pio deploy --batching child", "cell": cell.name,
        "attempted": m["attempted"], "failed": m["failed"],
        "served_qps": m["served_qps"], "query_p50_ms": m["query_p50_ms"],
        "query_p99_ms": m["query_p99_ms"], "deploy_ready_s": ready_s,
        "window_recompiles": compiled,
        "train_stages": stages.group(1) if stages else None,
        "device": platform.group(1) if platform else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
