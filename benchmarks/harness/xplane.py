"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX.

A trace is reduced to, per device, the events of its "XLA Ops" line
(what ran) and of its "XLA Modules" line (which jitted program it ran
in). Everything after :func:`load` works on plain tuples
``(name, start_ns, duration_ns)``, so it can be checked without a trace.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_dir(trace_dir: str) -> list[dict]:
    """:func:`load` of the trace the profiler left under ``trace_dir``."""
    path = find_xplane(trace_dir)
    return load(path) if path else []


#: a program the run starts right after the profiler: its end on the
#: device, set beside the host's clock when its result came back, puts
#: the program's spans on the trace's clock (which starts with the trace)
MARKER = "benchmark_clock_marker"


def short(name: str) -> str:
    """``%fusion.9 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.9 bf16[8,128]``."""
    head, _, rest = name.partition(" = ")
    shape = "" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return (head + " " + shape).strip()[:100]


def load(path: str) -> list[dict]:
    """One dict per device plane: {"device", "ops", "modules"}, event
    lists sorted by start, op names shortened. A host-only trace gives []."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = sorted(
                    ((short(e.name), int(e.start_ns), int(e.duration_ns))
                     for e in line.events), key=lambda e: e[1])
        if lines.get(OPS_LINE):
            planes.append({"device": plane.name,
                           "ops": lines[OPS_LINE],
                           "modules": lines.get(MODULES_LINE, [])})
    return planes


def union(events) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of ``events``."""
    out: list[list[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in union(events)) / 1e9


def extent(events) -> tuple[int, int]:
    """The first start and the last end, ns on the trace's clock."""
    return (min(s for _, s, _ in events),
            max(s + d for _, s, d in events))


def extent_seconds(events) -> float:
    """First start to last end."""
    if not events:
        return 0.0
    first, last = extent(events)
    return (last - first) / 1e9


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def whole_runs(plane: dict, pattern: str) -> list:
    """The runs of the modules whose name matches that the profile holds
    whole. The profiler keeps what ran while it recorded: of a program in
    flight when it started or stopped it keeps a part, as a run of its
    own, shorter than a run. Such a run's interval touches the plane's
    traced extent (its first event's start or its last event's end), so
    a run that does is left out (a whole one that happens to be the
    plane's first or last event goes with it: the others read the same
    per run)."""
    first, last = extent(plane["ops"] + plane["modules"])
    return [m for m in matching(plane["modules"], pattern)
            if first < m[1] and m[1] + m[2] < last]


def within(events, modules, pattern: str) -> list:
    """The events that ran inside a module whose name matches."""
    spans = union(matching(modules, pattern))
    out, k = [], 0
    for e in events:
        while k < len(spans) and spans[k][1] <= e[1]:
            k += 1
        if k < len(spans) and spans[k][0] <= e[1] < spans[k][1]:
            out.append(e)
    return out


def self_times(events) -> dict[str, float]:
    """Seconds per op name, each event counted without the events nested
    inside it (a ``while`` does not swallow its body)."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def top_ops(events, n: int = 10) -> list[list]:
    ranked = sorted(self_times(events).items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:n]]


def without_marker(plane: dict) -> dict:
    """The plane without the clock marker's own events."""
    marks = matching(plane["modules"], MARKER)
    ops = [e for e in plane["ops"]
           if not any(s <= e[1] < s + d for _, s, d in marks)]
    return {**plane, "ops": ops,
            "modules": [m for m in plane["modules"] if m not in marks]}


def host_offset_ns(plane: dict, marker_done_perf: float) -> float | None:
    """perf_counter * 1e9 minus this is a time on the trace's clock."""
    marks = matching(plane["modules"], MARKER)
    if not marks:
        return None
    _, start, dur = marks[0]
    return marker_done_perf * 1e9 - (start + dur)


def idle_gaps(events, host_spans=(), n: int = 10, look_at: int = 300) -> list[list]:
    """Where the device waited: the ``look_at`` longest gaps between its
    operations, each named by the host span (name, start_ns, end_ns on
    the trace's clock) that covers most of it (the shortest such span,
    when several cover it whole) or, where none covers half of it, by
    the operation it followed; summed by name, the ``n`` largest."""
    merged = union(events)
    ends = {}
    for name, start, dur in events:
        ends[start + dur] = name
    pairs = sorted(zip(merged, merged[1:]),
                   key=lambda ab: ab[0][1] - ab[1][0])[:look_at]
    names = [h[0] for h in host_spans]
    h_start = np.array([h[1] for h in host_spans], dtype=np.float64)
    h_end = np.array([h[2] for h in host_spans], dtype=np.float64)
    gaps = []
    for (_, a_end), (b_start, _) in pairs:
        label = f"after:{ends.get(a_end, '?')}"
        if names:
            cover = np.minimum(h_end, b_start) - np.maximum(h_start, a_end)
            most = cover.max()
            if most * 2 >= b_start - a_end:
                ties = np.flatnonzero(cover == most)
                pick = ties[np.argmin((h_end - h_start)[ties])]
                label = f"host:{names[pick]}"
        gaps.append((label, (b_start - a_end) / 1e9))
    # the same label many times over is one line: its longest gap and
    # how much it adds up to would both matter; report the sum
    total: dict[str, float] = {}
    for label, secs in gaps:
        total[label] = total.get(label, 0.0) + secs
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[label, secs] for label, secs in ranked[:n]]
