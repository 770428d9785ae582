"""Share of the traced window in which no operation ran on the device."""

from benchmarks.harness import xplane


def read(spec, ev):
    planes, window = ev.get("planes") or [], ev.get("window_s")
    if not planes or not window:
        return None
    busy = sum(xplane.busy_seconds(p["ops"]) for p in planes) / len(planes)
    return 100.0 * max(0.0, 1.0 - busy / window)
