"""Median of a program span, or of the sum of several per request."""

import numpy as np


def read(spec, ev):
    names = spec["spans"]
    if spec.get("per_request"):
        sums = [sum(r.get(n, 0.0) for n in names) for r in ev.get("requests", ())
                if any(n in r for n in names)]
    else:
        sums = [d for n in names for d in ev.get("spans", {}).get(n, ())]
    if not sums:
        return None
    return float(np.median(sums)) * spec.get("scale", 1.0)
