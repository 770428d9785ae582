"""Share of a thread's recorded time that some of its spans took:
100 x the summed durations of the spans under ``part`` over those under
``whole`` (``part`` lies inside ``whole``; the spans of one thread that
meet end to start, as the dispatcher's four do, make the denominator
that thread's time from its first span's start to its last one's end).

The ring a run reads holds the warm-up's requests too, so the one
stretch between the warm-up's last request and the window's first is in
the share when a span covers it (``dispatcher.idle`` of the window's
first cycle does): the kinds' counter snapshots, the clock marker of a
traced run, the generators' READY and the 0.5 s of ``GO_LEAD``. PERF.md
(section 6, PR 36) has what it read on the chip, of a 51 s window: the
share reads high by that much over the window, and nothing in the
evidence tells that cycle from any other. A program that records none
of the spans under ``whole`` has nothing to read."""


def read(spec, ev):
    spans = ev.get("spans", {})
    whole = sum(sum(spans.get(n, ())) for n in spec["whole"])
    if not whole:
        return None
    return 100.0 * sum(sum(spans.get(n, ())) for n in spec["part"]) / whole
