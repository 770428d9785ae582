"""A serve cell: the engine server in this process under an open-loop
(``serve_open``) or closed-loop (``serve_closed``) load, as the traffic
file says."""

from __future__ import annotations

import json
import shutil
import time

import numpy as np

from benchmarks.harness import data, device, serve, traffic as tr, xplane
from benchmarks.harness.output import end_to_end_line, per_layer_line
from benchmarks.reference import als_numpy

SAMPLE = 256


def check_answers(rec: dict, model, seen: dict, pool, num: int,
                  seed: int) -> tuple[bool, list]:
    """Recompute a seeded sample of answered queries with the NumPy
    reference. Every unknown user's answer is checked too."""
    ok = np.flatnonzero(rec["status"] == 200)
    users = pool[rec["ix"][ok]]
    problems = []
    for k in ok[users < 0]:
        if json.loads(rec["body"][k]).get("itemScores") != []:
            problems.append(f"unknown user answered {rec['body'][k][:80]!r}")
    known = ok[users >= 0]
    rng = np.random.default_rng([seed, tr.SAMPLE])
    sample = rng.choice(known, size=min(SAMPLE, len(known)), replace=False)
    item_f = np.asarray(model.item_factors)
    item_norm = als_numpy.item_norms(item_f)
    for lo in range(0, len(sample), 64):
        part = sample[lo:lo + 64]
        uix = pool[rec["ix"][part]]
        rows = np.asarray(model.user_factors[np.asarray(uix)])
        scores = als_numpy.reference_scores(item_f, rows)
        for j, k in enumerate(part):
            try:
                answer = [(int(s["item"][1:]), float(s["score"]))
                          for s in json.loads(rec["body"][k])["itemScores"]]
            except (ValueError, KeyError, TypeError) as exc:
                problems.append(f"malformed answer: {exc}")
                continue
            why = als_numpy.check_answer(
                scores[j], float(np.linalg.norm(rows[j])), item_norm,
                seen.get(int(uix[j]), np.empty(0, np.int32)), answer, num)
            if why:
                problems.append(f"u{int(uix[j])}: {why}")
    return not problems, problems[:5]


def request_spans(server) -> tuple[dict, list, list]:
    """(durations by span name, per request sums, host spans on the
    perf_counter clock) from the in-process TraceLog."""
    by_name: dict[str, list] = {}
    requests, host = [], []
    with server.service.trace_log._lock:
        traces = list(server.service.trace_log._ring)
    for t in traces:
        sums: dict[str, float] = {}
        for name, _, _, start, dur in t.spans():
            by_name.setdefault(name, []).append(dur)
            sums[name] = sums.get(name, 0.0) + dur
            host.append((name, t.start_perf + start, t.start_perf + start + dur))
        requests.append(sums)
    return by_name, requests, host


def run(cell, args, t_start: float) -> str:
    seconds, trace = float(args.seconds), bool(args.trace)
    work = serve.workdir()
    children = serve.spawn_generators(cell, args.seed, seconds, work)
    try:
        parts_s = {"spawn_generators": time.monotonic() - t_start}
        dev = device.claim(cell)
        parts_s["jax_start"] = time.monotonic() - t_start
        model, seen, pool = data.build_model(cell.config, cell.traffic,
                                             args.seed)
        deployed = data.deployed_engine(cell.config, model)
        server = serve.start_server(deployed, tracing=trace)
        parts_s["model_from_seed"] = time.monotonic() - t_start
        try:
            num = int(cell.traffic["num"])
            n_sigs = serve.warm_up(deployed, server, seen, pool, num,
                                   server.config.batch_max)
            parts_s["warm_up"] = time.monotonic() - t_start
            mark = device.clock_marker() if trace else None
            before = serve.batch_counters(server)
            compiles0 = serve.compile_count()
            trace_dir = f"{work}/trace" if trace else None
            setup_s = time.monotonic() + serve.GO_LEAD - t_start
            parts, clock = serve.run_window(children, server, seconds,
                                            trace_dir, mark)
            after = serve.batch_counters(server)
            window_compiles = serve.compile_count() - compiles0
            spans, requests, host = (request_spans(server) if trace
                                     else ({}, [], []))
        finally:
            server.stop()
        rec = serve.merge(parts)
        m = serve.latency_metrics(rec, seconds)
        m["setup_s"] = setup_s
        correct, problems = check_answers(rec, model, seen, pool, num,
                                          args.seed)
        correct = correct and window_compiles == 0 and m["failed"] == 0
        dev["memory_peak_bytes"] = device.memory_peak_bytes()
        counters = {k: after[k] - before[k] for k in after}
        counters["window_compiles"] = window_compiles
        notes = {"n": m["attempted"], "tail_percentile": m["tail_percentile"],
                 "query_tail_ms": m["query_tail_ms"],
                 "percentiles_ms": {p: m[f"query_p{p}_ms"]
                                    for p in (50, 90, 95, 99)},
                 "served_qps": m["served_qps"], "slices": m["slices"],
                 **counters,
                 "warmed_signatures": n_sigs, "setup_reached_s": parts_s,
                 "problems": problems}
        facts = dict(correct=correct, attempted=m["attempted"],
                     failed=m["failed"], device=dev, notes=notes)
        if not trace:
            return end_to_end_line(cell, m, **facts)
        ev = {"spans": spans, "requests": requests, "counters": counters,
              "values": {**m, "hbm_peak_bytes": dev["memory_peak_bytes"],
                         "batch_size_mean": counters["dispatched_queries"]
                         / max(counters["dispatches"], 1)},
              "notes": notes}
        return per_layer_line(
            cell, ev, xplane.load_dir(trace_dir),
            clock["trace_stop"] - clock["trace_start"],
            clock["marker_perf"], host, **facts)
    finally:
        serve.stop_children(children)
        shutil.rmtree(work, ignore_errors=True)
