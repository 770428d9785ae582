"""One traced run of one cell, as ``benchmarks/run.py`` makes it, that
leaves the server's trace ring behind and says what is in it: by hand

    python3 benchmarks/tools/ring_report.py <DIR> --workload <cell> --seed <n> --seconds <s> --trace 1

writes ``DIR/<cell>.<seed>.ring.json`` (every trace of the ring in ring
order: name, id, tags, origin on the perf_counter clock, spans as
``[name, start, duration]`` in seconds from the origin; the profiler's
start and stop and the window's on the same clock; every collector
pause the process made, whichever thread it ran on) and
``DIR/<cell>.<seed>.ring.txt``, the report ``report()`` makes of it,
which also goes to standard error. The result line is the run's own,
printed last. A program without the ``dispatch`` records (the parent of
PR 36) and a run with ``--trace 0`` (no ring) get the collector's part
of the report alone.
"""

import contextlib
import gc
import io
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402

FOUR = ("dispatcher.idle", "dispatcher.collect", "dispatcher.dispatch",
        "dispatcher.handoff")
PHASES = ("dispatch.prepare", "dispatch.gather", "dispatch.enqueue",
          "dispatch.device_wait", "dispatch.fetch", "dispatch.results")
TOP_LEVEL = ("request.read", "parse", "bind", "codec_key",
             "batcher.queue_wait", "batcher.device_dispatch", "batcher.wake",
             "encode", "respond", "request.flush")


def ring_doc(server) -> list:
    with server.service.trace_log._lock:
        traces = list(server.service.trace_log._ring)
    return [{"name": t.name, "id": t.trace_id, "tags": dict(t.tags),
             "origin": t.start_perf,
             "spans": [[name, start, dur]
                       for name, _, _, start, dur in t.spans()]}
            for t in traces]


def sums(trace: dict) -> dict:
    out: dict = {}
    for name, _, dur in trace["spans"]:
        out[name] = out.get(name, 0.0) + dur
    return out


def evidence(traces: list) -> tuple[dict, list]:
    """``spans`` and ``requests`` as ``kinds/serve_open.request_spans``
    folds the ring."""
    by_name: dict = {}
    for t in traces:
        for name, _, dur in t["spans"]:
            by_name.setdefault(name, []).append(dur)
    return by_name, [sums(t) for t in traces]


def _ms(seconds) -> str:
    return f"{seconds * 1e3:.3f}"


def pauses_report(doc: dict) -> list:
    """Collector pauses inside the window (``serve.run_window``'s call:
    GO to the last generator's exit), by generation."""
    lo, hi = doc["window"]
    pauses = [p for p in doc["gc"] if lo <= p[1] <= hi]
    out = [f"window {hi - lo:.3f} s on the perf_counter clock; collector "
           f"pauses inside it:"]
    for gen in (0, 1, 2):
        mine = sorted(p[2] for p in pauses if p[0] == gen)
        if mine:
            out.append(
                f"  generation {gen}: {len(mine)} collections, "
                f"{sum(mine):.4f} s in all, median "
                f"{_ms(statistics.median(mine))} ms, longest "
                f"{_ms(mine[-1])} ms")
    out.append("  every generation 2 pause, ms at s of the window: "
               + (", ".join(f"{_ms(d)} at {at - lo:.3f}"
                            for g, at, d in pauses if g == 2) or "none")
               + f"; before it {sum(1 for p in doc['gc'] if p[0] == 2 and p[1] < lo)}"
               f", after it {sum(1 for p in doc['gc'] if p[0] == 2 and p[1] > hi)}")
    return out


def partition_report(records: list, own: list) -> list:
    """The four spans against the extent they should partition."""
    first, last = records[0], records[-1]
    extent = (last["origin"] + max(s + d for _, s, d in last["spans"])
              - first["origin"])
    total = sum(sum(o.values()) for o in own)
    by_state = {n: sum(o.get(n, 0.0) for o in own) for n in FOUR}
    idles = sorted((o.get("dispatcher.idle", 0.0) for o in own), reverse=True)
    return [
        f"dispatch records {len(records)}; the four spans sum to "
        f"{total:.6f} s, first cycle's start to last cycle's end "
        f"{extent:.6f} s, apart {abs(total - extent) / extent:.2e}",
        "whole run, s: " + ", ".join(
            f"{n} {by_state[n]:.4f} ({100 * by_state[n] / total:.2f}%)"
            for n in FOUR),
        "longest dispatcher.idle (the stretch from the warm-up to the "
        "window's first query is one of these): "
        f"{', '.join(f'{x:.4f}' for x in idles[:4])} s"]


def profile_report(records: list, requests: dict, lo: float, hi: float,
                   busy: float, window: float) -> list:
    """The device's idle seconds in the profiled window by the
    dispatcher's state: every span clipped to the profiler's start and
    stop, so both sides are sums of durations and no clock is joined."""
    def inside(trace, names) -> dict:
        got = {n: 0.0 for n in names}
        for name, start, dur in trace["spans"]:
            if name in got:
                a = max(lo, trace["origin"] + start)
                b = min(hi, trace["origin"] + start + dur)
                got[name] += max(0.0, b - a)
        return got

    state = {n: 0.0 for n in FOUR}
    phases = {n: 0.0 for n in PHASES}
    for r in records:
        for n, secs in inside(r, FOUR).items():
            state[n] += secs
        rider = next((requests[i] for i in r["tags"].get("requests", ())
                      if i in requests), None)
        if rider:
            for n, secs in inside(rider, PHASES).items():
                phases[n] += secs
    in_dispatch = state["dispatcher.dispatch"] - busy
    host = (state["dispatcher.idle"] + state["dispatcher.collect"]
            + state["dispatcher.handoff"] + in_dispatch)
    rest = state["dispatcher.dispatch"] - sum(phases.values())
    return [
        f"profiled window {window:.4f} s (perf_counter start to stop "
        f"{hi - lo:.4f}), device busy {busy:.4f}, idle {window - busy:.4f}; "
        f"the dispatcher's spans cover {sum(state.values()):.4f} s of it",
        "  the device's idle seconds by the dispatcher's state: idle "
        f"{state['dispatcher.idle']:.4f} + collect "
        f"{state['dispatcher.collect']:.4f} + handoff "
        f"{state['dispatcher.handoff']:.4f} + dispatch minus device time "
        f"{in_dispatch:.4f} = {host:.4f}; the device's own "
        f"{window - busy:.4f}; apart {host - (window - busy):+.4f}",
        "  dispatch minus device time, by phase (one rider a cycle): "
        + ", ".join(f"{n[9:]} {phases[n]:.4f}" for n in PHASES
                    if n != "dispatch.device_wait")
        + f", device_wait {phases['dispatch.device_wait']:.4f} minus busy "
        f"{busy:.4f} = {phases['dispatch.device_wait'] - busy:.4f}, "
        f"query_batch outside the phases {rest:.4f}"]


def requests_report(requests: dict, p50: float) -> list:
    """A query inside the server whole: the root span, its top-level
    spans and what no span names, medians per traced query."""
    roots = [r for r in map(sums, requests.values()) if "request" in r]
    if not roots:
        return []
    med = {n: statistics.median(r.get(n, 0.0) for r in roots)
           for n in ("request",) + TOP_LEVEL}
    unspanned = statistics.median(
        r["request"] - sum(r.get(n, 0.0) for n in TOP_LEVEL) for r in roots)
    return [
        f"requests with a root {len(roots)}: median request "
        f"{_ms(med['request'])} ms + outside the server "
        f"{p50 - med['request'] * 1e3:.3f} = the traced query_p50_ms "
        f"{p50:.3f}; medians, ms: "
        + ", ".join(f"{n} {_ms(med[n])}" for n in TOP_LEVEL)
        + f", unspanned {_ms(unspanned)}"]


def longest_report(records: list, own: list, requests: dict) -> list:
    """What the ten longest cycles were made of."""
    out = ["the ten longest cycles (idle left out), ms: collect / dispatch "
           "/ handoff | batch | the first rider's phases | gc.pause on any "
           "rider | at s from the first cycle"]
    ranked = sorted(zip(records, own), reverse=True, key=lambda ro: sum(
        ro[1].get(n, 0.0) for n in FOUR[1:]))
    for r, o in ranked[:10]:
        riders = [requests[i] for i in r["tags"].get("requests", ())
                  if i in requests]
        first_rider = sums(riders[0]) if riders else {}
        gcs = sorted({(name, round(dur * 1e3, 3)) for t in riders
                      for name, _, dur in t["spans"]
                      if name.startswith("gc.pause")})
        out.append(
            "  " + " / ".join(_ms(o.get(n, 0.0)) for n in FOUR[1:])
            + f" | {r['tags'].get('batch')} | "
            + ", ".join(f"{n[9:]} {_ms(first_rider.get(n, 0.0))}"
                        for n in PHASES)
            + f" | {gcs or 'none'} | "
            f"{r['origin'] - records[0]['origin']:.3f}")
    return out


def report(doc: dict) -> str:
    traces, line = doc.get("traces", []), doc["line"]
    out = [f"{doc['cell']} seed {doc['seed']}: {len(traces)} traces in the "
           f"ring"]
    records = [t for t in traces if t["name"] == "dispatch"]
    requests = {t["id"]: t for t in traces if t["name"] != "dispatch"}
    if not records:
        return "\n".join(out + ["no dispatch record: tracing is off, or the "
                                "program does not keep the dispatcher's "
                                "trace"] + pauses_report(doc))
    own = [sums(r) for r in records]
    out += partition_report(records, own)
    prof, device = doc.get("profile"), line.get("device", {})
    if prof and "busy_s" in device:
        out += profile_report(records, requests, prof["start"], prof["stop"],
                              device["busy_s"], device["window_s"])
    out += requests_report(requests, line["notes"]["percentiles_ms"]["50"])
    out += pauses_report(doc)
    as_spans = [dur for t in requests.values()
                for name, _, dur in t["spans"] if name.startswith("gc.pause")]
    out.append(f"gc.pause spans on request traces: {len(as_spans)} (a pause "
               f"under query_batch is copied to every rider), longest "
               f"{_ms(max(as_spans, default=0.0))} ms")
    return "\n".join(out + longest_report(records, own, requests))


def main(out: str, argv: list) -> int:
    from benchmarks.harness import device, serve
    from benchmarks.kinds import serve_open, serve_seq_open, serve_seq_ref_open

    def words(flag):
        return argv[argv.index(flag) + 1]

    doc = {"cell": words("--workload"), "seed": int(words("--seed")),
           "gc": []}
    fold = serve_open.request_spans

    def keeping(server):
        doc["traces"] = ring_doc(server)
        return fold(server)

    for kind in (serve_open, serve_seq_open, serve_seq_ref_open):
        kind.request_spans = keeping
    start_trace = device.start_trace

    def start(trace_dir):
        # JAX is up by now (the kind claimed the chip): its stop is
        # wrapped here, for this one profile
        import jax

        stop_trace = jax.profiler.stop_trace

        def stop():
            doc["profile"]["stop"] = time.perf_counter()
            jax.profiler.stop_trace = stop_trace
            stop_trace()

        jax.profiler.stop_trace = stop
        start_trace(trace_dir)
        doc["profile"] = {"start": time.perf_counter()}

    device.start_trace = start
    run_window = serve.run_window

    def window(*args, **kwargs):
        doc["window"] = [time.perf_counter()]
        try:
            return run_window(*args, **kwargs)
        finally:
            doc["window"].append(time.perf_counter())

    serve.run_window = window
    started = [0.0]

    def pause(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            doc["gc"].append([info["generation"], started[0],
                              time.perf_counter() - started[0]])

    gc.callbacks.append(pause)
    stdout = io.StringIO()
    try:
        with contextlib.redirect_stdout(stdout):
            rc = run.main(argv)
    finally:
        gc.callbacks.remove(pause)
        device.start_trace, serve.run_window = start_trace, run_window
    line = stdout.getvalue().strip().splitlines()[-1]
    doc["line"] = json.loads(line)
    os.makedirs(out, exist_ok=True)
    stem = os.path.join(out, f"{doc['cell']}.{doc['seed']}.ring")
    with open(stem + ".json", "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    text = report(doc)
    with open(stem + ".txt", "w") as f:
        f.write(text + "\n")
    print(text, file=sys.stderr)
    print(line, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
