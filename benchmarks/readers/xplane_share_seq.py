"""A share of the chip's peak for the session engine's programs: the
work the shapes require (``harness/costs_seq.py``) over the device time
the trace shows for the module, or for some of its operations.

``"of": "roofline"`` divides by the least time the chip could take (the
larger of operations over peak FLOP/s and bytes over peak bytes/s: a
kernel's share of its roofline); ``"of": "flops"`` by operations over
peak FLOP/s alone (a whole step's share of the matrix unit's peak, an
MFU). The cost is per run of the program, at the window's mean of the
``cost_args`` the kind gives under ``values``."""

from benchmarks.harness import costs, costs_seq, xplane
from benchmarks.readers import device_events


def read(spec, ev):
    ops, runs = device_events(ev, spec)
    if not ops or not runs:
        return None
    try:
        args = {k: ev["values"][v] for k, v in spec["cost_args"].items()}
    except KeyError:
        return None
    seconds = xplane.busy_seconds(ops)
    cost = getattr(costs_seq, spec["cost"])(ev["config"], **args)
    total = {k: v * runs for k, v in cost.items()}
    if spec["of"] == "flops":
        return 100.0 * total["flops"] / ev["peaks"]["flops_per_s"] / seconds
    roof = costs.roofline(total, seconds, ev["peaks"])
    ev.setdefault("notes", {})[spec["cost"] + "_bound"] = roof["bound"]
    return roof["share_pct"]
