"""Readers of per-layer metrics, one module per kind of source, found by
the name a ``layer_metrics/<metric>.json`` gives under ``reader``.

Each has ``read(spec, evidence) -> float | None``. ``evidence`` is what
a traced run collected: ``spans`` (name -> durations in seconds),
``requests`` (per request, name -> seconds), ``counters``, ``values``
(the kind's own clocks and counts), ``planes`` (the reduced device
trace), ``window_s``, ``config``, ``peaks``. A reader that finds nothing
to read returns None and the metric is left out of the line.
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
    the module and op patterns the spec names."""
    from benchmarks.harness import xplane

    planes = evidence.get("planes") or []
    if not planes:
        return [], 0
    plane = max(planes, key=lambda p: xplane.busy_seconds(p["ops"]))
    ops, runs = plane["ops"], 0
    if spec.get("module"):
        runs = len(xplane.matching(plane["modules"], spec["module"]))
        ops = xplane.within(ops, plane["modules"], spec["module"])
    if spec.get("op"):
        ops = xplane.matching(ops, spec["op"])
    return ops, runs
