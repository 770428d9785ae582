"""Host the system under test in this process: what ``pio deploy
--batching`` runs after argument parsing, on a real loopback socket,
with the load generator as exec'ed JAX-free children."""

from __future__ import annotations

import collections
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.harness import stats, traffic as tr
from benchmarks.harness.loadgen import read_response
from benchmarks.harness.manifest import BENCH_DIR

#: the traced run records at most this many seconds of device activity
TRACE_SECONDS = 4.0
TRACE_AFTER = 2.0
#: from GO to the first due request: the children open their connections
GO_LEAD = 0.5


def spawn_generators(cell, seed: int, seconds: float, workdir: str) -> list:
    """Start the children now, before this process touches JAX; they
    build their requests and wait on stdin for GO."""
    cfg = os.path.join(workdir, "config.json")
    trf = os.path.join(workdir, "traffic.json")
    with open(cfg, "w") as f:
        json.dump(cell.config, f)
    with open(trf, "w") as f:
        json.dump(cell.traffic, f)
    n = int(cell.traffic["generators"])
    children = []
    for k in range(n):
        out = os.path.join(workdir, f"gen{k}.npz")
        p = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
             "--config", cfg, "--traffic", trf, "--seed", str(seed),
             "--seconds", str(seconds), "--index", str(k), "--of", str(n),
             "--out", out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        children.append((p, out))
    return children


def stop_children(children) -> None:
    for p, _ in children:
        if p.poll() is None:
            p.kill()
        p.wait()
        for pipe in (p.stdin, p.stdout):
            if pipe:
                pipe.close()


def start_server(deployed, tracing: bool):
    """``ServerConfig(batching=True)`` and every other field at its
    default; tracing only in the traced run."""
    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.workflow.deploy import ServerConfig

    server = EngineServer(deployed, ServerConfig(
        ip="127.0.0.1", port=0, batching=True, tracing=tracing))
    if tracing:
        # the ring keeps 64 traces for /traces.json; this run reads all
        server.service.trace_log._ring = collections.deque(maxlen=1 << 20)
    server.start()
    return server


def signatures(seen: dict, pool: np.ndarray, batch_max: int) -> list:
    """Every (batch width, seen width) the batcher can hand
    ``batch_predict`` for this pool: the batch menu up to ``batch_max``
    times the seen-width classes the pool's users fall in. Each is
    (B, heavy user of that class, light users)."""
    from predictionio_tpu.ops import topk

    def width_class(n: int) -> int:
        pad = topk._SEEN_WIDTHS[0]
        for cap in topk._SEEN_WIDTHS:
            pad = cap
            if n <= cap:
                break
        while pad < n:
            pad *= 2
        return pad

    known = [int(u) for u in dict.fromkeys(pool.tolist()) if u >= 0]
    by_class: dict[int, int] = {}
    for u in known:
        by_class.setdefault(width_class(len(seen.get(u, ()))), u)
    lightest = min(by_class)
    light = [u for u in known
             if width_class(len(seen.get(u, ()))) == lightest]
    out = []
    for b in topk.BATCH_WIDTHS:
        if b > batch_max:
            break
        for cls, heavy in sorted(by_class.items()):
            fill = [u for u in light if u != heavy][:b - 1]
            if len(fill) == b - 1:
                out.append((b, cls, [heavy] + fill))
    return out


def warm_up(deployed, server, seen, pool, num: int, batch_max: int) -> int:
    """Run every signature once through ``query_batch`` (compile or
    cache load), then a few requests over the socket, which is also what
    marks the program's own warm-up complete."""
    from predictionio_tpu.templates import recommendation as rec

    sigs = signatures(seen, pool, batch_max)
    for _, _, users in sigs:
        deployed.query_batch([rec.Query(user=f"u{u}", num=num) for u in users])
    with socket.create_connection(("127.0.0.1", server.port), timeout=30) as s:
        buf = bytearray()
        for u in [int(x) for x in pool[:8]]:
            body = tr.request_body(u, num)
            s.sendall(b"POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      b"Content-Type: application/json\r\nContent-Length: "
                      + str(len(body)).encode() + b"\r\n\r\n" + body)
            status, _ = read_response(s, buf)
            if status != 200:
                raise RuntimeError(f"warm-up request answered {status}")
    return len(sigs)


def batch_counters(server) -> dict:
    snap = server.service.serving_stats.snapshot()
    hist = {int(k): int(v) for k, v in snap["batchSizeHistogram"].items()}
    return {"dispatches": sum(hist.values()),
            "dispatched_queries": sum(k * v for k, v in hist.items())}


def compile_count() -> int:
    from predictionio_tpu.obs.compile import recorder

    return recorder().totals()[0]


def run_window(children, server, seconds: float, trace_dir: str | None,
               mark=None):
    """GO, wait for the children, return their records and the window's
    clock facts. With ``trace_dir`` the profiler covers TRACE_SECONDS of
    the live window, and ``mark`` (``device.clock_marker``) ties its
    clock to the host's."""
    for p, _ in children:
        if p.stdout.readline().strip() != "READY":
            raise RuntimeError("a load generator did not start")
    t0 = time.monotonic() + GO_LEAD
    for p, _ in children:
        p.stdin.write(f"GO {server.port} {t0!r}\n")
        p.stdin.flush()
    clock = {"t0_monotonic": t0}
    if trace_dir:
        import jax

        from benchmarks.harness import device

        time.sleep(max(0.0, t0 + min(TRACE_AFTER, seconds / 4)
                       - time.monotonic()))
        device.start_trace(trace_dir)
        clock["trace_start"] = time.monotonic() - t0
        clock["marker_perf"] = mark()
        time.sleep(min(TRACE_SECONDS, seconds / 2))
        clock["trace_stop"] = time.monotonic() - t0
        jax.profiler.stop_trace()
    parts = []
    for p, out in children:
        rc = p.wait(timeout=seconds + 60)
        if rc != 0:
            raise RuntimeError(f"load generator exit {rc}")
        parts.append(dict(np.load(out)))
    return parts, clock


def merge(parts) -> dict:
    out = {k: np.concatenate([p[k] for p in parts])
           for k in ("due", "sent", "done", "status", "ix", "body_len")}
    blob = np.concatenate([p["bodies"] for p in parts]).tobytes()
    ends = np.cumsum(out["body_len"])
    out["body"] = [blob[e - n:e] for e, n in zip(ends, out["body_len"])]
    return out


def latency_metrics(rec: dict, seconds: float) -> dict:
    """Client latency from the instant a request was due to its last
    byte. A non-200, a timeout or a malformed body is failed and is
    counted as missing: it enters the percentiles at +inf, so it can
    only push them up."""
    ok = rec["status"] == 200
    lat_ms = np.where(ok, (rec["done"] - rec["due"]) * 1e3, np.inf)
    n = len(lat_ms)
    tail = stats.tail_percentile(n)
    in_window = ok & (rec["done"] <= seconds)
    return {
        "attempted": int(n), "failed": int((~ok).sum()),
        "query_p50_ms": stats.percentile(lat_ms, 50.0),
        "query_p90_ms": stats.percentile(lat_ms, 90.0),
        "query_p95_ms": stats.percentile(lat_ms, 95.0),
        "query_p99_ms": stats.percentile(lat_ms, 99.0),
        "tail_percentile": tail,
        "query_tail_ms": stats.percentile(lat_ms, tail),
        "served_qps": float(in_window.sum()) / seconds,
        "gen_late_p99_ms": stats.percentile(
            (rec["sent"] - rec["due"]) * 1e3, 99.0),
        "slices": slices(rec["due"], lat_ms, seconds),
    }


def slices(due, lat_ms, seconds: float, width_s: float = 1.0) -> dict:
    """Median and 95th percentile of each ``width_s`` of the window, by
    the instant a request was due: where in a run a stall fell and how
    long the queue took to drain. A note, not a metric."""
    which = np.minimum((np.asarray(due) // width_s).astype(np.int64),
                       max(int(np.ceil(seconds / width_s)) - 1, 0))
    out = {"width_s": width_s, "n": [], "p50_ms": [], "p95_ms": []}
    for k in range(int(which.max()) + 1 if len(which) else 0):
        part = lat_ms[which == k]
        out["n"].append(int(part.size))
        for p in (50, 95):
            v = stats.percentile(part, float(p)) if part.size else None
            out[f"p{p}_ms"].append(
                None if v is None or not np.isfinite(v) else round(v, 2))
    return out


def workdir() -> str:
    return tempfile.mkdtemp(prefix="pio-bench-")
