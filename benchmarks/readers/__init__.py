"""Readers of per-layer metrics, one module per kind of source, found by
the name a ``layer_metrics/<metric>.json`` gives under ``reader``.

Each has ``read(spec, evidence) -> float | None``. ``evidence`` is what
a traced run collected: ``spans`` (name -> durations in seconds),
``requests`` (per request, name -> seconds), ``counters``, ``values``
(the kind's own clocks and counts), ``planes`` (the reduced device
trace), ``window_s``, ``config``, ``peaks``, and ``trace_edges`` where
the kind knows the device was idle when the profile started and
stopped. A reader that finds nothing to read returns None and the
metric is left out of the line.
"""

from __future__ import annotations

import importlib
import os

from benchmarks.harness.manifest import BENCH_DIR, load_json


def read_metric(name: str, evidence: dict):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".json")
    if not os.path.exists(path):
        return None
    spec = load_json(path)
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(spec, evidence)


def device_events(evidence: dict, spec: dict):
    """(ops, count of module runs) on the busiest device plane, cut to
    the module and op patterns the spec names. Whole runs only
    (``xplane.whole_runs``): a run the profile's edge cut goes, with
    its ops, before runs and device time are counted, so a window with
    no whole run reads nothing. A kind that starts and stops the
    profiler around work of its own, with nothing in flight at either
    edge, says ``"trace_edges": "idle"`` and every run counts."""
    from benchmarks.harness import xplane

    planes = evidence.get("planes") or []
    if not planes:
        return [], 0
    plane = max(planes, key=lambda p: xplane.busy_seconds(p["ops"]))
    ops, runs = plane["ops"], 0
    if spec.get("module"):
        whole = xplane.matching(plane["modules"], spec["module"]) \
            if evidence.get("trace_edges") == "idle" \
            else xplane.whole_runs(plane, spec["module"])
        runs = len(whole)
        ops = xplane.within(ops, whole, spec["module"])
    if spec.get("op"):
        ops = xplane.matching(ops, spec["op"])
    return ops, runs
