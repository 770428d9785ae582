"""A counter's change over the window, or the ratio of two."""


def read(spec, ev):
    counters = ev.get("counters", {})
    if spec["counter"] not in counters:
        return None
    value = float(counters[spec["counter"]])
    if "over" in spec:
        below = float(counters.get(spec["over"], 0))
        if below <= 0:
            return None
        value /= below
    return value
