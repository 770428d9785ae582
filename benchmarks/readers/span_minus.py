"""Median of a program span less other spans of the same trace: per
trace that has ``span``, ``span`` minus the sum of the spans under
``minus`` that it has (none need be there; ``minus`` may be empty, and
then this is the median of ``span`` per trace), times ``scale``. With
``value``, what is left of a number the kind took itself once that
median is taken off: ``values[value]`` minus it. A program that does
not record ``span`` has nothing to read."""

import numpy as np


def read(spec, ev):
    span, minus = spec["span"], spec.get("minus", ())
    rest = [r[span] - sum(r.get(m, 0.0) for m in minus)
            for r in ev.get("requests", ()) if span in r]
    if not rest:
        return None
    median = float(np.median(rest)) * spec.get("scale", 1.0)
    if "value" not in spec:
        return median
    value = ev.get("values", {}).get(spec["value"])
    return None if value is None else float(value) - median
