"""Host share of a step: the kind's clock around the call, minus the
device time of the program it ran, per run of the program."""

from benchmarks.harness import xplane
from benchmarks.readers import device_events


def read(spec, ev):
    clock = ev.get("values", {}).get(spec["clock"])
    ops, runs = device_events(ev, spec)
    if clock is None or not ops or not runs:
        return None
    return float(clock) - xplane.busy_seconds(ops) / runs
