"""Median self time of a program span: per request, the span minus the
child spans the spec names (the children do not overlap). A program
that records none of the children has no split to read."""

import numpy as np


def read(spec, ev):
    span, children = spec["span"], spec["children"]
    own = [r[span] - sum(r.get(c, 0.0) for c in children)
           for r in ev.get("requests", ())
           if span in r and any(c in r for c in children)]
    if not own:
        return None
    return float(np.median(own)) * spec.get("scale", 1.0)
