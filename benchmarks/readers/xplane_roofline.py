"""A kernel's share of its roofline: the least time the chip could take
for the work the shapes require (``harness/costs.py``), over the device
time the trace shows. ``values['<metric>_bound']`` says which bound."""

from benchmarks.harness import costs, xplane
from benchmarks.readers import device_events


def read(spec, ev):
    ops, runs = device_events(ev, spec)
    if not ops or not runs:
        return None
    seconds = xplane.busy_seconds(ops)
    args = {k: ev["values"][v] for k, v in spec.get("cost_args", {}).items()}
    cost = getattr(costs, spec["cost"])(ev["config"], **args)
    times = ev["values"].get(spec["times"], 1) if "times" in spec else 1
    total = {k: v * times * runs for k, v in cost.items()}
    roof = costs.roofline(total, seconds, ev["peaks"])
    ev.setdefault("notes", {})[spec["cost"] + "_bound"] = roof["bound"]
    return roof["share_pct"]
