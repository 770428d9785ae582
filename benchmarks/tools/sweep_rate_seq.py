"""Find the knee of a session-engine serve cell once, on the chip.

    python3 benchmarks/tools/sweep_rate_seq.py --workload <cell> --rates 1,1.5,2 [--seconds 40]

``tools/sweep_rate.py`` for the ``serve_seq_open`` kind: one process,
one model, one server; for each rate a fresh set of generator children
runs the cell's traffic at that rate. A rate is sustained when the
server completed what was offered (the last quarter of the window is no
slower than twice the first) and nothing failed. The knee is the highest
sustained rate. Prints one JSON line per rate.
"""

import argparse
import dataclasses
import json
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from benchmarks.harness import device, seq_data, serve
    from benchmarks.harness.manifest import load_cell

    cell = load_cell(args.workload, args.manifest)
    dev = device.claim(cell)
    model, _, pool = seq_data.build_model(cell.config, cell.traffic, args.seed)
    deployed = seq_data.deployed_engine(cell.config, model)
    server = serve.start_server(deployed, tracing=False)
    try:
        seq_data.warm_up(deployed, server, model, pool,
                         int(cell.traffic["num"]))
        for rate in [float(r) for r in args.rates.split(",")]:
            at = dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_qps=rate))
            work = serve.workdir()
            children = serve.spawn_generators(at, args.seed, args.seconds, work)
            try:
                before = {**serve.batch_counters(server),
                          **seq_data.seq_counters(server)}
                parts, _ = serve.run_window(children, server, args.seconds,
                                            None)
                after = {**serve.batch_counters(server),
                         **seq_data.seq_counters(server)}
                rec = serve.merge(parts)
            finally:
                serve.stop_children(children)
                shutil.rmtree(work, ignore_errors=True)
            m = serve.latency_metrics(rec, args.seconds)
            ok = rec["status"] == 200
            lat = (rec["done"] - rec["due"])[ok] * 1e3
            due = rec["due"][ok]
            first = lat[due < args.seconds / 4]
            last = lat[due >= args.seconds * 3 / 4]
            growth = (float(np.median(last) / np.median(first))
                      if len(first) and len(last) else float("inf"))
            print(json.dumps({
                "rate_qps": rate, "offered": m["attempted"],
                "failed": m["failed"], "served_qps": m["served_qps"],
                "p50_ms": m["query_p50_ms"], "p95_ms": m["query_p95_ms"],
                "late_p99_ms": m["gen_late_p99_ms"],
                "last_over_first_quarter": growth,
                "sustained": bool(m["failed"] == 0 and growth < 2.0
                                  and m["served_qps"] * args.seconds
                                  >= 0.9 * m["attempted"]),
                **{k: after[k] - before[k] for k in after},
                "device": dev["kind"]}), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
