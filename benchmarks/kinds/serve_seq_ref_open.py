"""A session-engine serve cell whose configuration file names its own
reference, cost module, counters and ``AlgorithmParams``
(``harness/seq_ref_data``): ``kinds/serve_seq_open.py``'s run with those
in the place of the names it has built in. The counters the file lists
are read beside the dispatch counters, and what the cost module's
``window_values`` makes of them (none where it has no such function)
joins the run's ``notes`` and the readers' ``values``. The window, the
generators, the latency arithmetic and the result line are
``kinds/serve_open.py``'s.
"""

from __future__ import annotations

import shutil
import time

from benchmarks.harness import (
    device, seq_data, seq_ref_check, seq_ref_data, serve, xplane)
from benchmarks.harness.output import end_to_end_line, per_layer_line
from benchmarks.kinds.serve_open import request_spans
from benchmarks.kinds.serve_seq_open import failed_statuses, traced_seconds


def run(cell, args, t_start: float) -> str:
    seconds, trace = float(args.seconds), bool(args.trace)
    work = serve.workdir()
    children = serve.spawn_generators(cell, args.seed, seconds, work)
    try:
        parts_s = {"spawn_generators": time.monotonic() - t_start}
        dev = device.claim(cell)
        parts_s["jax_start"] = time.monotonic() - t_start
        model, histories, pool = seq_ref_data.build_model(
            cell.config, cell.traffic, args.seed)
        deployed = seq_ref_data.deployed_engine(cell.config, model)
        server = serve.start_server(deployed, tracing=trace)
        parts_s["model_from_seed"] = time.monotonic() - t_start
        try:
            num = int(cell.traffic["num"])
            n_sigs = seq_data.warm_up(deployed, server, model, pool, num)
            parts_s["warm_up"] = time.monotonic() - t_start
            mark = device.clock_marker() if trace else None
            before = {**serve.batch_counters(server),
                      **seq_ref_data.seq_counters(server, cell.config)}
            compiles0 = serve.compile_count()
            trace_dir = f"{work}/trace" if trace else None
            setup_s = time.monotonic() + serve.GO_LEAD - t_start
            with traced_seconds(float(cell.traffic.get(
                    "trace_seconds", serve.TRACE_SECONDS))):
                parts, clock = serve.run_window(children, server, seconds,
                                                trace_dir, mark)
            after = {**serve.batch_counters(server),
                     **seq_ref_data.seq_counters(server, cell.config)}
            window_compiles = serve.compile_count() - compiles0
            spans, requests, host = (request_spans(server) if trace
                                     else ({}, [], []))
        finally:
            server.stop()
        rec = serve.merge(parts)
        m = serve.latency_metrics(rec, seconds)
        m["setup_s"] = setup_s
        dev["memory_peak_bytes"] = device.memory_peak_bytes()
        correct, problems, checked = seq_ref_check.check_answers(
            rec, model, histories, pool, cell.config, num, args.seed)
        correct = correct and window_compiles == 0 and m["failed"] == 0
        counters = {k: after[k] - before[k] for k in after}
        counters["window_compiles"] = window_compiles
        derived = seq_ref_data.window_values(counters, cell.config)
        notes = {"n": m["attempted"], "tail_percentile": m["tail_percentile"],
                 "query_tail_ms": m["query_tail_ms"],
                 "percentiles_ms": {p: m[f"query_p{p}_ms"]
                                    for p in (50, 90, 95, 99)},
                 "served_qps": m["served_qps"], "slices": m["slices"],
                 **counters, **derived, "reference": checked,
                 "failed_statuses": failed_statuses(rec),
                 "warmed_signatures": n_sigs, "setup_reached_s": parts_s,
                 "problems": problems}
        facts = dict(correct=correct, attempted=m["attempted"],
                     failed=m["failed"], device=dev, notes=notes)
        if not trace:
            return end_to_end_line(cell, m, **facts)
        ev = {"spans": spans, "requests": requests, "counters": counters,
              "values": {**m, "hbm_peak_bytes": dev["memory_peak_bytes"],
                         "batch_size_mean": counters["dispatched_queries"]
                         / max(counters["dispatches"], 1),
                         "seq_tokens_per_program":
                         counters["seq_padded_tokens"]
                         / max(counters["seq_programs"], 1), **derived},
              "notes": notes}
        return per_layer_line(
            cell, ev, xplane.load_dir(trace_dir),
            clock["trace_stop"] - clock["trace_start"],
            clock["marker_perf"], host, **facts)
    finally:
        serve.stop_children(children)
        shutil.rmtree(work, ignore_errors=True)
