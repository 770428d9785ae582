"""``readers/xplane_share_seq.py`` for a configuration that names its
own cost module (``"costs"`` in its file, a module under
``benchmarks/harness/``): the work the shapes require over the device
time the trace shows for the module, or for some of its operations, as a
share of the roofline (``"of": "roofline"``) or of the matrix unit's
peak (``"of": "flops"``). The cost is per run of the program, at the
window's means of the ``cost_args`` the kind gives under ``values``. A
configuration without such a module, a program without such operations
or a window without such values reads nothing."""

import importlib

from benchmarks.harness import costs, xplane
from benchmarks.readers import device_events


def read(spec, ev):
    ops, runs = device_events(ev, spec)
    name = ev.get("config", {}).get("costs")
    if not ops or not runs or not name:
        return None
    try:
        args = {k: ev["values"][v] for k, v in spec["cost_args"].items()}
    except KeyError:
        return None
    cost_fn = getattr(importlib.import_module(f"benchmarks.harness.{name}"),
                      spec["cost"], None)
    if cost_fn is None:
        return None
    seconds = xplane.busy_seconds(ops)
    total = {k: v * runs for k, v in cost_fn(ev["config"], **args).items()}
    if spec["of"] == "flops":
        return 100.0 * total["flops"] / ev["peaks"]["flops_per_s"] / seconds
    roof = costs.roofline(total, seconds, ev["peaks"])
    ev.setdefault("notes", {})[spec["cost"] + "_bound"] = roof["bound"]
    return roof["share_pct"]
