"""Device time of a program (and optionally of some of its operations)
in the trace, per run of the program (``"per": "module_runs"``) or per a
count the kind gives under ``values``."""

from benchmarks.harness import xplane
from benchmarks.readers import device_events


def read(spec, ev):
    ops, runs = device_events(ev, spec)
    per = runs if spec["per"] == "module_runs" else ev["values"].get(spec["per"])
    if not ops or not per:
        return None
    return xplane.busy_seconds(ops) / per * spec.get("scale", 1.0)
