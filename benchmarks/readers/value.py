"""A number the cell's kind took itself (its own clock or count)."""


def read(spec, ev):
    value = ev.get("values", {}).get(spec["value"])
    return None if value is None else float(value) * spec.get("scale", 1.0)
