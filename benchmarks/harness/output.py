"""The one line a run ends with."""

from __future__ import annotations

import json


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                breakdown: dict | None = None, notes: dict | None = None) -> str:
    """``metrics`` maps name -> value; only names with a unit in
    ``units`` (the cell's manifest entries) are printed, each value as
    measured. ``notes`` rides along under a key the driver ignores."""
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(metrics[name]), "unit": units[name]}
                    for name in units if metrics.get(name) is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    return json.dumps(line)


def end_to_end_line(cell, metrics: dict, **facts) -> str:
    """The ``--trace 0`` line: the cell's end-to-end metrics."""
    units = {e["name"]: e["unit"] for e in cell.end_to_end}
    return result_line(metrics=metrics, units=units, **facts)


def per_layer_line(cell, evidence: dict, planes: list, window_s: float,
                   marker_done_perf: float, host_spans, *, device: dict,
                   **facts) -> str:
    """The ``--trace 1`` line: each of the cell's per-layer metrics as
    its reader finds it in ``evidence`` (``readers/__init__.py`` says
    what that holds), and from the device trace the busy time and the
    breakdown."""
    from benchmarks.harness import device as dev
    from benchmarks.readers import read_metric

    evidence.update(
        planes=planes, window_s=window_s, config=cell.config,
        peaks=dev.peaks_for(device["kind"]) if planes else None)
    metrics = {e["name"]: read_metric(e["name"], evidence)
               for e in cell.per_layer}
    units = {e["name"]: e["unit"] for e in cell.per_layer}
    breakdown = None
    if planes:
        busy, breakdown = dev.traced(planes, window_s, marker_done_perf,
                                     host_spans)
        device.update(busy)
    return result_line(metrics=metrics, units=units, device=device,
                       breakdown=breakdown, **facts)
