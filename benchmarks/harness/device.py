"""Claim the chip, say what it is, and read what only it can report."""

from __future__ import annotations

import json
import os
import sys

from benchmarks.harness.manifest import BENCH_DIR


def claim(cell) -> dict:
    """Start JAX through the program's own entry rule (which also places
    the compile cache), then hold the cell to its contract: a TPU with
    the chips it asks for, unless the configuration is a marked
    rehearsal. Exits non-zero otherwise, before any result line."""
    from predictionio_tpu.utils.accelerator import start_compute

    start_compute()
    import jax

    # programs that compile in under a second are the serving menu:
    # without this none of them would be found again by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = bool(cell.config.get("rehearsal"))
    if platform != "tpu" and not rehearsal:
        print(f"refusing to measure on platform {platform!r}: cell "
              f"{cell.name} needs a TPU", file=sys.stderr)
        raise SystemExit(3)
    if platform == "tpu" and len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip; 0 where the backend does not say."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def peaks_for(kind: str) -> dict:
    """The one table of peaks. A kind that is not in it is an error."""
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def clock_marker():
    """A compiled no-op named ``xplane.MARKER``; call it, wait, read the
    host's clock: see ``xplane.host_offset_ns``. Returns the callable
    that does that and gives the clock reading."""
    import time

    import jax
    import jax.numpy as jnp

    def benchmark_clock_marker(x):
        return x + 1

    fn = jax.jit(benchmark_clock_marker)
    x = jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(fn(x))

    def mark() -> float:
        jax.block_until_ready(fn(x))
        return time.perf_counter()

    return mark


def start_trace(trace_dir: str) -> None:
    """The profiler with the host and Python tracers off: device events
    only, which is what the reduction reads and what keeps a window of
    a live server small enough to bring back."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def traced(planes: list, window_s: float, marker_done_perf: float,
           host_spans=()) -> tuple[dict, dict]:
    """(device facts, breakdown) of a traced window: busy seconds
    averaged over the chips, the operations that took most time, and
    the idle gaps named by what the host was doing."""
    from benchmarks.harness import xplane

    offsets = [xplane.host_offset_ns(p, marker_done_perf) for p in planes]
    offset = next((o for o in offsets if o is not None), 0.0)
    planes = [xplane.without_marker(p) for p in planes]
    busiest = max(planes, key=lambda p: xplane.busy_seconds(p["ops"]))
    spans = [(n, a * 1e9 - offset, b * 1e9 - offset) for n, a, b in host_spans]
    facts = {"busy_s": sum(xplane.busy_seconds(p["ops"])
                           for p in planes) / len(planes),
             "window_s": window_s}
    ops = busiest["ops"]
    return facts, {"device_ops": xplane.top_ops(ops),
                   "idle_gaps": xplane.idle_gaps(ops, spans)}
