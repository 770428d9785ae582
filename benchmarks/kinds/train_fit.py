"""A fit cell: jobs of ``ALSAlgorithm.train`` on ``PreparedData`` made
from the seed, back to back until the window is over; the job in flight
finishes and counts. It bypasses the event store, the preparator and
persist."""

from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.harness import device, serve, traffic as tr, xplane
from benchmarks.harness.output import end_to_end_line, per_layer_line
from benchmarks.reference import als_numpy

SAMPLE_ROWS = 256
RMSE_SAMPLE = 1 << 20

#: ||A x - b|| / ||b|| allowed for a row of the last half-step. The
#: program builds A and b from bfloat16 operands (2^-9 each) with
#: float32 sums and runs 16 steps of CG whose matrix-vector products
#: are bfloat16 from rank 64 up (``ops/als._CG_BF16_RANK``); the old
#: records put that at 2.5e-3 of the solution. 3e-2 holds that with
#: room for short rows; an int8 table or a dropped lambda term would
#: miss it by an order of magnitude.
RESIDUAL_TOL = 3e-2


def check_fit(config: dict, model, coo, seed: int) -> tuple[bool, dict]:
    import jax.numpy as jnp

    rng = np.random.default_rng([seed, tr.SAMPLE])
    # items are the half-step solved last: each sampled item's row must
    # solve its normal equation against the user table it was given
    items = rng.choice(config["items"], size=SAMPLE_ROWS, replace=False)
    keep = np.flatnonzero(np.isin(coo.cols, items))
    order = keep[np.argsort(coo.cols[keep], kind="stable")]
    cols = coo.cols[order]
    user_rows = np.asarray(model.user_factors[jnp.asarray(coo.rows[order])])
    item_rows = np.asarray(model.item_factors[jnp.asarray(items)])
    worst = 0.0
    for j, i in enumerate(items):
        lo, hi = np.searchsorted(cols, [i, i + 1])
        worst = max(worst, als_numpy.normal_equation_residual(
            item_rows[j], user_rows[lo:hi], coo.vals[order[lo:hi]],
            config["lambda"]))
    pick = rng.choice(coo.nnz, size=min(RMSE_SAMPLE, coo.nnz), replace=False)
    pred = np.asarray(jnp.sum(
        model.user_factors[jnp.asarray(coo.rows[pick])]
        * model.item_factors[jnp.asarray(coo.cols[pick])], axis=-1))
    rmse = float(np.sqrt(np.mean((pred - coo.vals[pick]) ** 2)))
    rmse0 = float(np.sqrt(np.mean(coo.vals[pick] ** 2)))   # factors at 0
    finite = bool(np.isfinite(pred).all())
    facts = {"worst_residual": worst, "rmse": rmse, "rmse_initial": rmse0}
    return finite and worst <= RESIDUAL_TOL and rmse < rmse0, facts


def run(cell, args, t_start: float) -> str:
    seconds, trace = float(args.seconds), bool(args.trace)
    dev = device.claim(cell)
    import jax

    from predictionio_tpu.ops.als import RatingsCOO
    from predictionio_tpu.templates import recommendation as rec
    from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.workflow.context import EngineContext

    cfg = cell.config
    u, i, v = tr.make_ratings(cfg, args.seed)
    coo = RatingsCOO(rows=u, cols=i, vals=v, num_rows=cfg["users"],
                     num_cols=cfg["items"])
    # the fit carries the id maps and seen lists into the model without
    # reading them; the preparator that builds them is not in this cell
    empty = EntityIdIxMap(BiMap({}))
    pd = rec.PreparedData(coo=coo, user_ids=empty, item_ids=empty,
                          seen_by_user={})
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(
        rank=cfg["rank"], num_iterations=cfg["num_iterations"],
        lambda_=cfg["lambda"], seed=args.seed))
    ctx = EngineContext()

    def job():
        t = time.perf_counter()
        model = algo.train(ctx, pd)
        jax.block_until_ready((model.user_factors, model.item_factors))
        return model, time.perf_counter() - t

    model, warm_s = job()               # compiles, or loads from the cache
    del model
    compiles0 = serve.compile_count()
    work = serve.workdir() if trace else None
    mark = device.clock_marker() if trace else None
    try:
        t0 = time.monotonic()
        setup_s = t0 - t_start
        times, clock = [], {}
        while time.monotonic() - t0 < seconds:
            tracing = trace and not times   # the traced run records job 1
            if tracing:
                device.start_trace(f"{work}/trace")
                clock["start"] = time.monotonic()
                clock["marker_perf"] = mark()
                clock["fit_start_perf"] = time.perf_counter()
            model, secs = job()
            if tracing:
                clock["fit_end_perf"] = time.perf_counter()
                clock["fit_end"] = time.monotonic()
                jax.profiler.stop_trace()
            times.append(secs)
        wall = time.monotonic() - t0
        window_compiles = serve.compile_count() - compiles0
        correct, facts = check_fit(cfg, model, coo, args.seed)
        correct = correct and window_compiles == 0
        dev["memory_peak_bytes"] = device.memory_peak_bytes()
        m = {"train_ratings_per_s": coo.nnz * len(times) / wall,
             "setup_s": setup_s}
        notes = {"jobs": len(times), "job_s": times[:8], "warm_up_job_s": warm_s,
                 "window_compiles": window_compiles, **facts}
        facts = dict(correct=correct, attempted=len(times), failed=0,
                     device=dev, notes=notes)
        if not trace:
            return end_to_end_line(cell, m, **facts)
        planes = xplane.load_dir(f"{work}/trace")
        # the profile was started before job 1 and stopped after its
        # results were ready: no program is cut, the last one counts
        ev = {"trace_edges": "idle",
              "counters": {"window_compiles": window_compiles},
              "values": {"fit_clock_s": times[0],
                         "iterations": cfg["num_iterations"],
                         "hbm_peak_bytes": dev["memory_peak_bytes"]},
              "notes": notes}
        if planes:
            # is block_until_ready honest here? the host's clock when the
            # wait returned, against the end of the last device operation
            plane = max(planes, key=lambda p: len(p["ops"]))
            offset = xplane.host_offset_ns(plane, clock["marker_perf"])
            ops = xplane.without_marker(plane)["ops"]
            notes["fit_clock_s"] = times[0]
            notes["device_first_to_last_s"] = xplane.extent_seconds(ops)
            if offset is not None:
                last = max(s_ + d for _, s_, d in ops)
                notes["wait_returned_after_last_op_ms"] = (
                    clock["fit_end_perf"] * 1e9 - offset - last) / 1e6
        host = [("fit: packing and staging, then the wait",
                 clock["fit_start_perf"], clock["fit_end_perf"])]
        return per_layer_line(
            cell, ev, planes, clock["fit_end"] - clock["start"],
            clock["marker_perf"], host, **facts)
    finally:
        if work:
            shutil.rmtree(work, ignore_errors=True)
