"""Benchmark entry: prints ONE JSON line with the north-star metrics.

Primary contract (driver): {"metric", "value", "unit", "vs_baseline"}.
The line also carries the rest of the BASELINE.md north star so every
round is comparable on all axes:

- ``value``/``stdev_pct``/``iter_ms`` — ALS train throughput at
  MovieLens-20M shape (138,493 x 26,744, 20M ratings, power-law skew),
  rank 32, full alternating iterations on the library-default path
  (fused MXU-width ladder, bf16 normal equations with f32 accumulation,
  one device program for the whole run — ops/als layout="fused").
  Min-of-N over ``REPS`` timed repeats, relative spread reported.
- ``phase_*_ms`` — per-phase decomposition of one iteration (VERDICT
  r2 weak #1): gather-only and gather+einsum chain variants isolate
  the factor row-gather (row-count-bound: measured invariant to row
  width 32->128 lanes, dtype, and index locality — ~2.8ns/row) and
  the normal-equation einsums; solve+write-back is the remainder.
- ``als_f32_rate`` — the f32-HIGHEST opt-in path
  (matmul_dtype="float32"), tracked so the precision trade stays
  visible round-over-round.
- ``rank200_*`` — the BASELINE.md rank-200 configuration on the same
  ML-20M shape (fused layout; CG step cap active). Its quality
  validation lives in ``rank200_rmse_tpu``/``rank200_rmse_ref``:
  device rank-200 ALS vs an exact per-row NumPy solver on the
  ML-100k-statistics dataset.
- ``mfu_pct``/``useful_tflops``/``padding_x`` — useful-FLOP model
  utilisation (ops/als.half_step_flops): "useful" counts real rating
  entries and algorithmic-minimum (Cholesky-priced) solves; executed
  prices the solve at the CG steps actually run, so padding_x carries
  both layout padding and solver overhead. MFU is quoted against the
  chip's headline dense bf16 peak — conservative by construction.
- ``p50_ms``/``p99_ms``/``serve_inproc_p50_ms`` — end-to-end serving
  latency over HTTP loopback (reference counter:
  CreateServer.scala:583-590) AND the in-process serve path (same
  query flow minus HTTP), so the HTTP share is measured, not
  asserted. ``serve_rtt_floor_ms`` — the minimal dispatch+fetch p50
  of the device link. ``serve_batched_qps_32c`` — 32-concurrent-client HTTP
  throughput through the query micro-batcher
  (ServerConfig.batching; r5). ``batch_predict_qps_2m`` — batched
  top-k scoring rate against a 2M-item catalog (the eval hot path).
  ``calibration_matmul_ms`` — fixed bf16 matmul anchor; quote
  ``rank200_iter_per_calib`` for regime-adjusted comparison.
  ``serving_qps_*``/``serving_speedup_x``/``serving_cached_qps`` —
  the serving-path section (bench_serving.py): adaptive micro-batcher
  vs strict per-query dispatch under concurrent clients, and the
  result-cache regime (full harness artifacts: BENCH_serving_rNN.json).
  ``sections_failed`` — ALWAYS present; [] means complete.
- ``flash_s4096_ms``/``xla_s4096_ms`` — pallas flash (force=True) vs
  XLA attention forward at S=4096. Tracking this pair is what caught
  the round-2 envelope claim being wrong (XLA wins at every measured
  serving shape; auto-dispatch retired — ops/pallas_attention).
- ``map10_*``/``rmse_*`` — quality on the ML-100k-statistics dataset.
  map10_tpu/map10_ref vs an independent NumPy ALS-WR are PARITY keys;
  map10_implicit vs map10_popularity is the ranking-WINS key (explicit
  ALS models rating values and sits below the popularity baseline on
  top-N — MLlib's does too; the implicit path must beat it).
- ``seqrec_*`` — sessionrec transformer training at S=256 (dense
  attention), S=4096 (blockwise long-context path), and serving p50 at
  S=2048.
- ``ingest_events_per_sec`` — batched REST ingest through the real
  event server into file-backed sqlite.

Baseline: Spark/MLlib cannot run here (no JVM), so the comparable is a
measured proxy — a single-core NumPy ALS-WR iteration (segment
reductions, pure useful work), scaled two ways: ``vs_baseline``
against this host's core count as a Spark local[N] perfect-scaling
bound, and ``vs_baseline_64core`` against a 64-core cluster width
(a realistic production Spark allocation) — both generous to Spark by
construction. The BASELINE.md gate is >=10x and is evaluated against
the 64-core figure in README.

MEASUREMENT PROTOCOL: every timing forces real execution by fetching
a scalar reduction of the full result (float(jnp.sum(...))), and
per-iteration time comes from the difference of a long and a short
chain, which cancels the fetch's latency. Chain inputs vary per step
(factors feed back). The protocol has not been re-validated on a
directly attached chip; ROADMAP speed item 1 replaces this harness.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import time

import numpy as np

USERS = 138_493
ITEMS = 26_744
NNZ = 20_000_000
RANK = 32
LAM = 0.08
REPS = 5
SUB_NNZ = 500_000   # numpy-baseline subsample (rate is size-normalised)
SERVE_QUERIES = 500
SERVE_WARMUP = 20

N_SHORT, N_LONG = 2, 10

# headline dense bf16 peak per chip (MFU denominator)
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def make_ratings(nnz: int, seed: int = 0):
    """Power-law-skewed synthetic (user, item, rating) triples."""
    rng = np.random.default_rng(seed)
    users = (USERS * rng.random(nnz) ** 1.8).astype(np.int32)
    items = (ITEMS * rng.random(nnz) ** 1.8).astype(np.int32)
    vals = rng.integers(1, 11, size=nnz).astype(np.float32) / 2.0
    return users, items, vals


def _device_peak():
    import jax

    kind = jax.devices()[0].device_kind
    return kind, _PEAK_BF16.get(kind)


def _chain_time_many(runs: dict, n_short=None, n_long=None, reps=REPS):
    """Differential chains for one or more run variants, INTERLEAVED.

    Each rep times every variant's short chain, then every variant's
    long chain, so variants whose numbers will be SUBTRACTED sample the
    same load conditions (back-to-back variant measurement lets a
    host-load shift between them turn the difference negative). The
    per-variant estimate differences the MIN short and MIN long
    endpoint across reps — immune to one-sided multi-second stalls.
    Returns {name: (robust, per_rep)}."""
    n_short = N_SHORT if n_short is None else n_short
    n_long = N_LONG if n_long is None else n_long
    times = {name: {"s": [], "l": []} for name in runs}
    for _ in range(reps):
        for n_calls, key in ((n_short, "s"), (n_long, "l")):
            for name, run in runs.items():
                t0 = time.perf_counter()
                run(n_calls)
                times[name][key].append(time.perf_counter() - t0)
    dn = n_long - n_short
    out = {}
    for name, t in times.items():
        robust = (min(t["l"]) - min(t["s"])) / dn
        per_rep = [(tl - ts) / dn for ts, tl in zip(t["s"], t["l"])]
        out[name] = (robust, per_rep)
    return out


def _chain_time(run, n_short=None, n_long=None, reps=REPS):
    """Single-variant differential chain (see :func:`_chain_time_many`)."""
    return _chain_time_many({"_": run}, n_short, n_long, reps)["_"]


def bench_calibration(n: int = 2048, rounds: int = 16):
    """Fixed reference-matmul timing: one bf16 ``n x n x n`` matmul's
    per-call ms, measured with the same differential-chain protocol as
    everything else. The chip/session regime drifts session to session
    (rank-200 iter spans 330-497 ms across sessions — VERDICT r4 weak
    #6); this constant-workload anchor makes a future drift in any
    other number attributable: if calibration moved too, it is the
    session, not the code."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    a0 = jax.device_put(jnp.asarray(
        rng.standard_normal((n, n)).astype(np.float32))).astype(jnp.bfloat16)
    b = jax.device_put(jnp.asarray(
        rng.standard_normal((n, n)).astype(np.float32))).astype(jnp.bfloat16)

    @jax.jit
    def step(a):
        c = jnp.dot(a, b, preferred_element_type=jnp.float32)
        # feed back so chained dispatches differ (protocol)
        return (c * (1.0 / float(n))).astype(jnp.bfloat16)

    def run(k):
        a = a0
        for _ in range(k):
            a = step(a)
        return float(jnp.sum(a.astype(jnp.float32)))

    run(1)
    per_call, _ = _chain_time(run, n_short=1, n_long=1 + rounds, reps=3)
    return {"calibration_matmul_ms": round(per_call * 1e3, 3)}


# ---------------------------------------------------------------------------
# ALS train throughput (fused ladder, the library default) + f32 + rank 200
# ---------------------------------------------------------------------------


_LADDER_CACHE: dict = {}


def _staged_ladder(users, items, vals, rank):
    """One ladder layout + HBM staging per rank, memoized — bench_als,
    bench_phases, and bench_rank200 share it (the 20M-entry packing and
    both orientations' device upload are seconds each)."""
    # fingerprint the FULL index arrays (CRC over the raw bytes): a
    # prefix-sum key can alias two datasets that agree on their first
    # entries and silently hand back stale staged buffers (ADVICE r3)
    import zlib

    key = (rank, len(users),
           zlib.crc32(np.ascontiguousarray(users)),
           zlib.crc32(np.ascontiguousarray(items)),
           zlib.crc32(np.ascontiguousarray(vals)))
    if key in _LADDER_CACHE:
        return _LADDER_CACHE[key]
    from predictionio_tpu.ops import als as A

    coo = A.RatingsCOO(users, items, vals, USERS, ITEMS)
    by_u = A.ladder_rows(coo)
    by_i = A.ladder_rows(coo.transpose())
    dev_u = A.stage_buckets(by_u, rank)
    dev_i = A.stage_buckets(by_i, rank)
    out = (by_u, by_i, A._fused_bucket_args(dev_u),
           A._fused_bucket_args(dev_i))
    _LADDER_CACHE[key] = out
    return out


def _fused_run_fn(bu, bi, rank, bf16, item0_np):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as A

    def run(n):
        # item0 uploads fresh per call (the program donates arg 0)
        u, it = A._als_iterate_fused(
            jax.device_put(item0_np), bu, bi, n, LAM, 40.0, False,
            USERS, ITEMS, bf16=bf16, cg_steps=None)
        return float(jnp.sum(jnp.abs(u))) + float(jnp.sum(jnp.abs(it)))

    return run


def bench_als(users, items, vals, reps=REPS):
    from predictionio_tpu.ops.als import half_step_flops

    by_u, by_i, bu, bi = _staged_ladder(users, items, vals, RANK)
    fl_u = half_step_flops(by_u, RANK)
    fl_i = half_step_flops(by_i, RANK)
    useful = fl_u["useful_flops"] + fl_i["useful_flops"]
    executed = fl_u["executed_flops"] + fl_i["executed_flops"]

    rng = np.random.default_rng(1)
    item0 = (rng.standard_normal((ITEMS, RANK)) / np.sqrt(RANK)).astype(
        np.float32)

    run = _fused_run_fn(bu, bi, RANK, True, item0)
    run(N_SHORT)  # compile warm-up — BOTH chain lengths, so no rep
    run(N_LONG)   # ever times a compile
    best, iter_times = _chain_time(run, reps=reps)
    mean = statistics.fmean(iter_times)
    stdev_pct = (
        100.0 * statistics.stdev(iter_times) / mean if reps > 1 else 0.0
    )

    kind, peak = _device_peak()
    result = {
        "rate": NNZ / best,
        "iter_ms": round(best * 1e3, 3),
        "stdev_pct": round(stdev_pct, 1),
        "reps": reps,
        "useful_tflops": round(useful / best / 1e12, 2),
        "padding_x": round(executed / useful, 2),
        "device": kind,
    }
    if peak:
        result["mfu_pct"] = round(100.0 * useful / best / peak, 2)

    # f32-HIGHEST opt-in rate (the precision trade, tracked)
    run32 = _fused_run_fn(bu, bi, RANK, False, item0)
    run32(N_SHORT)
    run32(N_LONG)
    result["als_f32_rate"] = round(
        NNZ / _chain_time(run32, reps=max(2, reps - 3))[0], 1)

    # final factors for the serving benchmark (one more full train)
    import jax
    import numpy as _np

    from predictionio_tpu.ops import als as A

    u, it = A._als_iterate_fused(
        jax.device_put(item0), bu, bi, 10, LAM, 40.0, False,
        USERS, ITEMS, bf16=True, cg_steps=None)
    return result, _np.asarray(u), _np.asarray(it)


def bench_phases(users, items, vals):
    """Per-phase decomposition via chain variants on the ladder layout:
    G = gather + fused reduce (the lightest full consumer), E = gather
    + mask + normal-equation einsums; the full iteration comes from the
    headline. Feedback keeps chain inputs varying (protocol)."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    _, _, bu, bi = _staged_ladder(users, items, vals, RANK)
    _HI = jax.lax.Precision.HIGHEST

    @partial(jax.jit, static_argnames=("einsum",))
    def half_variant(V, buckets, base, einsum: bool):
        # gather from the bf16 table, like the default fused path since
        # r4 (the cast commutes with the row-gather; phase accounting
        # must walk the same bytes the real kernel walks)
        Vb = V.astype(jnp.bfloat16)
        tot = jnp.float32(0.0)
        for row_ids, cols, vals_, deg in buckets:
            L = cols.shape[-1]

            def body(carry, xs):
                c, v, d = xs
                F = Vb[c]
                if einsum:
                    m = (jnp.arange(L, dtype=jnp.int32)[None, :]
                         < d[:, None]).astype(jnp.float32)
                    Fm = F * m[..., None].astype(jnp.bfloat16)
                    Ap = jnp.einsum("blk,blm->bkm", Fm, F,
                                    preferred_element_type=jnp.float32)
                    bp = jnp.einsum("bl,blk->bk", (v * m).astype(jnp.bfloat16),
                                    F, preferred_element_type=jnp.float32)
                    s = jnp.sum(Ap) + jnp.sum(bp)
                else:
                    # lightest full consumer: a fused reduce with f32
                    # accumulation. (An earlier f32-cast-then-mask
                    # consumer materialized an f32 copy of F that the
                    # einsum variant never pays, making "gather-only"
                    # measure SLOWER than gather+einsum.)
                    s = jnp.sum(F, dtype=jnp.float32) + jnp.sum(v)
                return carry + s, None

            tot, _ = jax.lax.scan(body, tot, (cols, vals_, deg))
        return base * (1.0 + 1e-12 * jnp.tanh(tot))

    rng = np.random.default_rng(1)
    item0 = jax.device_put(jnp.asarray(
        (rng.standard_normal((ITEMS, RANK)) / np.sqrt(RANK)).astype(np.float32)))
    base_u = jax.device_put(jnp.asarray(
        (rng.standard_normal((USERS, RANK)) / np.sqrt(RANK)).astype(np.float32)))
    base_i = jax.device_put(jnp.asarray(
        (rng.standard_normal((ITEMS, RANK)) / np.sqrt(RANK)).astype(np.float32)))

    def make_run(einsum):
        def run(n):
            cur = item0
            for _ in range(n):
                uf = half_variant(cur, bu, base_u, einsum)
                cur = half_variant(uf, bi, base_i, einsum)
            return float(jnp.sum(jnp.abs(cur)))

        return run

    runs = {name: make_run(einsum)
            for name, einsum in (("gather", False), ("einsum", True))}
    # interleaved: the einsum number is a DIFFERENCE of the two
    # variants, so they must sample the same load conditions (observed
    # otherwise under a concurrently loaded host: gather 194.7, einsum
    # delta -53.7 — see _chain_time_many)
    for run in runs.values():
        run(N_SHORT)
        run(N_LONG)
    timed = _chain_time_many(runs, reps=3)
    gather_s = timed["gather"][0]
    delta_s = timed["einsum"][0] - gather_s
    result = {
        "phase_gather_ms": round(gather_s * 1e3, 1),
        "phase_einsum_ms": round(delta_s * 1e3, 1),
    }
    if delta_s < 0:
        # still possible under violent load shifts; flag rather than
        # silently report an impossible negative phase (guard on the
        # RAW difference — round() can hide small negatives as -0.0)
        result["phase_warning"] = "negative einsum delta (noisy session)"
    return result


RANK200 = 200


def bench_rank200(users, items, vals):
    """BASELINE.md's rank-200 ML-20M configuration, in the bench
    contract (VERDICT r2 missing #2). Heavy: the normal-equation build
    is 2K^2 FLOPs/entry = ~4.3 PFLOP/iteration at rank 200, so short
    chains."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.als import half_step_flops

    by_u, by_i, bu, bi = _staged_ladder(users, items, vals, RANK200)
    fl_u = half_step_flops(by_u, RANK200)
    fl_i = half_step_flops(by_i, RANK200)
    useful = fl_u["useful_flops"] + fl_i["useful_flops"]

    rng = np.random.default_rng(1)
    item0 = (rng.standard_normal((ITEMS, RANK200)) /
             np.sqrt(RANK200)).astype(np.float32)

    def run(n):
        # cg_bf16 matches als_train's "auto" policy at rank >= 64
        # (bf16 A-matvec, f32 accumulation — 1.51x measured r4)
        u, it = A._als_iterate_fused(
            jax.device_put(item0), bu, bi, n, LAM, 40.0, False,
            USERS, ITEMS, bf16=True, cg_steps=None, cg_bf16=True)
        return float(jnp.sum(jnp.abs(u))) + float(jnp.sum(jnp.abs(it)))

    run(1)
    run(5)    # warm both chain lengths before timing
    best, _ = _chain_time(run, n_short=1, n_long=5, reps=3)
    _, peak = _device_peak()
    out = {
        "rank200_rate": round(NNZ / best, 1),
        "rank200_iter_ms": round(best * 1e3, 1),
    }
    if peak:
        out["rank200_mfu_pct"] = round(100.0 * useful / best / peak, 2)
    return out


# ---------------------------------------------------------------------------
# NumPy single-process baseline -> Spark-on-CPU proxy
# ---------------------------------------------------------------------------


def bench_numpy_baseline(users, items, vals, reps: int = 2):
    """MEASURED CPU baseline (VERDICT r4 next #3): the reference
    template's estimator (ALSAlgorithm.scala:79-93's ALS.train math) as
    a NumPy ALS-WR iteration, actually executed (a) single-threaded and
    (b) multi-threaded at this host's core count — per-row solves are
    independent, so threads take contiguous row-id stripes and NumPy
    releases the GIL inside the einsum/solve kernels. Spark itself
    cannot run here (no JVM — see BASELINE.md "measured baseline" for
    the attempt transcript); `baseline_64core_rate` remains a LABELED
    linear extrapolation of the measured rate to a 64-core cluster
    width, generous to Spark."""
    from concurrent.futures import ThreadPoolExecutor

    from predictionio_tpu.e2.quality import _segment_half_solve

    s_users, s_items, s_vals = (users[:SUB_NNZ], items[:SUB_NNZ],
                                vals[:SUB_NNZ])
    rng = np.random.default_rng(1)
    V0 = (rng.standard_normal((ITEMS, RANK)) / np.sqrt(RANK)).astype(np.float32)

    def half(V, rows, cols, num_rows, threads):
        if threads == 1:
            return _segment_half_solve(V, rows, cols, s_vals, num_rows, LAM)
        out = np.zeros((num_rows, RANK), dtype=V.dtype)
        bounds = np.linspace(0, num_rows, threads + 1).astype(np.int64)

        def work(t):
            lo, hi = int(bounds[t]), int(bounds[t + 1])
            m = (rows >= lo) & (rows < hi)
            if m.any():
                out[lo:hi] = _segment_half_solve(
                    V, rows[m] - lo, cols[m], s_vals[m], hi - lo, LAM)

        with ThreadPoolExecutor(threads) as ex:
            list(ex.map(work, range(threads)))
        return out

    def one_pass(threads):
        t0 = time.perf_counter()
        uf = half(V0, s_users, s_items, USERS, threads)
        half(uf, s_items, s_users, ITEMS, threads)
        return SUB_NNZ / (time.perf_counter() - t0)

    cores = os.cpu_count() or 1
    one_core_rate = max(one_pass(1) for _ in range(reps))
    measured_rate = (one_core_rate if cores == 1
                     else max(one_pass(cores) for _ in range(reps)))
    return {
        "numpy_1core_rate": round(one_core_rate, 1),
        "baseline_rate": round(measured_rate, 1),
        "baseline_cores": cores,
        "baseline_64core_rate": round(measured_rate * 64 / cores, 1),
        "baseline": (
            f"MEASURED multi-threaded NumPy ALS-WR (segment reductions, "
            f"row-stripe threads) at {cores} core(s), best of {reps}; "
            "Spark/JVM unavailable here (BASELINE.md); "
            "vs_baseline_64core linearly extrapolates the measured rate "
            "to a 64-core cluster width (generous to Spark)"
        ),
    }


# ---------------------------------------------------------------------------
# Serving latency: HTTP + in-process + batched top-k at 2M items
# ---------------------------------------------------------------------------


def bench_serving(user_f, item_f, users, items, n_queries=SERVE_QUERIES):
    import datetime
    import urllib.request

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.templates import recommendation as rec
    from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    # id maps over the full catalog (string ids, as in production)
    user_ids = EntityIdIxMap(BiMap({f"u{i}": i for i in range(USERS)}))
    item_ids = EntityIdIxMap(BiMap({f"i{i}": i for i in range(ITEMS)}))

    # seen-item lists only for the users we will query
    order = np.argsort(users, kind="stable")
    su, si = users[order], items[order]
    rng = np.random.default_rng(7)
    query_uix = rng.choice(np.unique(su), size=n_queries + SERVE_WARMUP,
                           replace=True)
    seen_by_user = {}
    for u in np.unique(query_uix):
        lo, hi = np.searchsorted(su, u), np.searchsorted(su, u, side="right")
        seen_by_user[int(u)] = np.unique(si[lo:hi]).astype(np.int32)

    model = ALSModel(
        rank=RANK,
        # device-resident factors: np arrays would re-upload per query
        user_factors=jax.device_put(jnp.asarray(user_f)),
        item_factors=jax.device_put(jnp.asarray(item_f)),
        user_ids=user_ids,
        item_ids=item_ids,
        seen_by_user=seen_by_user,
    )
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=RANK, use_mesh=False))
    now = datetime.datetime.now(datetime.timezone.utc)
    instance = EngineInstance(
        id="bench", status="COMPLETED", start_time=now, completion_time=now,
        engine_id="bench", engine_version="1", engine_variant="bench",
        engine_factory="bench",
    )
    serving = FirstServing()

    # Compile the predict program IN-PROCESS before any HTTP request is
    # in flight: the first query at ML-20M scale pays a full jit compile
    # of the top-k program, and r4 lost the whole serving section to a
    # 60s socket timeout on exactly that query (VERDICT r4 weak #1). A
    # forced scalar fetch guarantees execution, not just dispatch.
    q0 = rec.Query(user=f"u{int(query_uix[0])}", num=10)
    pre = serving.serve(q0, [algo.predict(model, q0)])
    assert pre is not None

    deployed = DeployedEngine(None, instance, [algo], serving, [model])
    server = EngineServer(deployed, ServerConfig(ip="127.0.0.1", port=0))
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/queries.json"

        def query(uix: int, timeout: float = 60.0) -> float:
            body = json.dumps({"user": f"u{int(uix)}", "num": 10}).encode()
            req = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"},
                method="POST",
            )
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=timeout) as r:
                r.read()
            return time.perf_counter() - t0

        # warmup: generous timeout (residual compiles, cold caches) and
        # one retry — a single slow warmup query must never void the
        # section again
        for uix in query_uix[:SERVE_WARMUP]:
            try:
                query(uix, timeout=300.0)
            except OSError:
                query(uix, timeout=300.0)
        lat = np.asarray([query(u) for u in query_uix[SERVE_WARMUP:]])
    finally:
        server.stop()

    # concurrent-clients HTTP throughput with the micro-batcher
    # (ServerConfig.batching, r5): N clients' queries coalesce into one
    # device dispatch, amortizing the per-dispatch cost
    batched = _bench_batched_serving(deployed, query_uix)

    # in-process p50: the identical serve flow minus HTTP + loopback,
    # so the link's share of p50 is measured rather than asserted
    # (VERDICT r2 weak #5)
    def inproc(uix: int) -> float:
        q = rec.Query(user=f"u{int(uix)}", num=10)
        t0 = time.perf_counter()
        serving.serve(q, [algo.predict(model, q)])
        return time.perf_counter() - t0

    for uix in query_uix[:SERVE_WARMUP]:
        inproc(uix)
    inlat = np.asarray([inproc(u) for u in query_uix[SERVE_WARMUP:]])

    # MEASURED single-process CPU serving baseline (VERDICT r4 next
    # #3): the identical serve computation — score, mask seen, top-10 —
    # in plain NumPy, the stand-in for the reference's local-model JVM
    # predict (CreateServer.scala:583-590's avgServingSec observable).
    # In-process on both sides, so the comparison excludes HTTP.
    def np_serve(uix: int) -> float:
        t0 = time.perf_counter()
        scores = item_f @ user_f[int(uix)]
        seen = seen_by_user.get(int(uix))
        if seen is not None and len(seen):
            scores = scores.copy()
            scores[seen] = -np.inf
        top = np.argpartition(scores, -10)[-10:]
        top = top[np.argsort(scores[top])[::-1]]   # cost matters, not order
        return time.perf_counter() - t0

    for uix in query_uix[:SERVE_WARMUP]:
        np_serve(uix)
    nplat = np.asarray([np_serve(u) for u in query_uix[SERVE_WARMUP:]])

    # the dispatch+fetch floor: a minimal varying device op with a
    # forced scalar fetch. p50 minus this is the framework's own
    # serving cost
    one = jax.device_put(jnp.ones((8, 8), jnp.float32))
    float(jnp.sum(one))                       # compile
    rtts = []
    for j in range(30):
        t0 = time.perf_counter()
        float(jnp.sum(one * (1.0 + j)))
        rtts.append(time.perf_counter() - t0)
    rtt_floor = round(float(np.percentile(rtts, 50)) * 1e3, 2)

    return {
        "serve_rtt_floor_ms": rtt_floor,
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        **batched,
        "serve_inproc_p50_ms": round(float(np.percentile(inlat, 50)) * 1e3, 2),
        "baseline_serve_inproc_p50_ms": round(
            float(np.percentile(nplat, 50)) * 1e3, 3),
        "serve_queries": int(len(lat)),
        **bench_batch_predict(),
    }


def _bench_batched_serving(deployed, query_uix, clients: int = 32,
                           per_client: int = 8):
    """HTTP throughput with ``clients`` concurrent connections against
    a batching engine server (one device dispatch per coalesced batch).
    Sequential HTTP tops out at ~1000/p50 qps; this is the number
    that shows the per-dispatch cost amortizing."""
    import json as _json
    import threading
    import urllib.request

    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.workflow.deploy import ServerConfig

    from predictionio_tpu.templates import recommendation as rec

    uixs = np.asarray(query_uix)
    # pre-compile EVERY padded batch signature the coalescer can
    # produce (batch dims pad to powers of two): a partial batch whose
    # signature first appears inside the timed loop would bill a
    # compile as serving time
    for b in (1, 2, 4, 8, 16, 32):
        if b <= clients:
            deployed.query_batch([
                rec.Query(user=f"u{int(uixs[j % len(uixs)])}", num=10)
                for j in range(b)
            ])

    server = EngineServer(deployed, ServerConfig(
        ip="127.0.0.1", port=0, batching=True,
        # 25ms wait: on this 1-core host 32 client threads need more
        # than the 5ms default to get their requests enqueued past
        # the GIL
        batch_max=clients, batch_wait_ms=25.0))
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/queries.json"

        def client(cid, count):
            for j in range(count):
                body = _json.dumps({
                    "user": f"u{int(uixs[(cid * per_client + j) % len(uixs)])}",
                    "num": 10}).encode()
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    r.read()

        def run(count):
            threads = [threading.Thread(target=client, args=(c, count))
                       for c in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        run(2)                                  # warm the batched path
        dt = run(per_client)
        # key carries the client count so the metric always describes
        # its own measurement
        return {f"serve_batched_qps_{clients}c":
                round(clients * per_client / dt, 1)}
    finally:
        server.stop()


def bench_serving_path():
    """Adaptive micro-batcher vs strict per-query dispatch over HTTP
    loopback, plus the cached regime — the PR 3 serving-path
    trajectory. Standalone harness: bench_serving.py (committed
    artifacts: BENCH_serving_rNN.json); this section runs it at
    reduced volume so every round's line carries the serving numbers."""
    import bench_serving

    return bench_serving.bench_section()


def bench_ann_retrieval(shrunk: bool = False):
    """Brute vs ANN (IVF-flat MIPS + exact rescore) catalog-size sweep
    — the PR 8 sublinear-retrieval trajectory. Standalone harness:
    bench_serving.py --ann-only (committed artifacts:
    BENCH_ann_rNN.json); under --skip-heavy it runs one small-but-
    indexable catalog so the harness contract stays exercised."""
    import bench_serving

    return bench_serving.bench_ann_section(shrunk=shrunk)


def bench_workers_scaling(shrunk: bool = False):
    """Prefork serving-pool core scaling (1 vs 2 SO_REUSEPORT workers)
    — the `pio deploy --workers N` trajectory. Standalone harness:
    bench_serving.py --workers-only (committed artifacts:
    BENCH_workers_rNN.json, which also carry the 1M ANN-under-workers
    re-run — skipped in this section at BOTH sizes: the index build
    runs minutes). Under --skip-heavy the catalog and round count
    shrink so the harness contract stays exercised cheaply. The
    scaling ratio only clears 1 on a multi-core host — the section
    records host_cores alongside."""
    import bench_serving

    return bench_serving.bench_workers_section(shrunk=shrunk)


def bench_shm_cache(shrunk: bool = False):
    """Shared-memory serving plane (private per-worker LRU vs ONE
    seqlock shm segment at 1 and 2 SO_REUSEPORT workers) — the PR 18
    trajectory: paired qps/p99, the pool-wide hit ratio from the
    merged /metrics scrape, and the post-invalidation rewarm probe
    (a shared segment pays each cold key ONCE pool-wide; replicated
    LRUs pay it once per worker the replays land on). Standalone
    harness: bench_serving.py --shm-only (committed artifacts:
    BENCH_shm_rNN.json); under --skip-heavy it runs shrunk (small
    catalog, fewer rounds, smaller probe — same contract)."""
    import bench_serving

    return bench_serving.bench_shm_section(shrunk=shrunk)


def bench_gateway_phase(shrunk: bool = False):
    """Multi-tenant gateway: 1 vs 2 engines behind one router + the
    quota-isolation pin (a tenant driven past its qps quota is 429'd
    while the sibling's p99 holds) — the PR 15 trajectory. Standalone
    harness: bench_serving.py --gateway-only (committed artifacts:
    BENCH_gateway_rNN.json); under --skip-heavy it runs shrunk (fewer
    clients/rounds, same contract)."""
    import bench_serving

    return bench_serving.bench_gateway_section(shrunk=shrunk)


def bench_data_plane():
    """Columnar scan vs row iterator + transactional batch ingest — the
    PR 4 data-plane trajectory. Standalone harness: bench_ingest.py
    (committed artifacts: BENCH_ingest_rNN.json); this section runs it
    at reduced volume so every round's line carries the data-plane
    numbers."""
    import bench_ingest

    return bench_ingest.bench_section()


def bench_elasticity_section(shrunk: bool = False):
    """Per-tenant elasticity plane (bench_elasticity.py; committed
    artifacts: BENCH_elasticity_rNN.json): compliant-tenant p99 ratio
    while an abusive sibling is throttled, burst-credit admission vs a
    credit-less control, and the deterministic ManualClock
    scale-decision timeline under a shared replica budget. Router
    threads + stdlib echo backends, no device — runs (shrunk) under
    --skip-heavy."""
    import bench_elasticity

    return bench_elasticity.bench_section(shrunk=shrunk)


def bench_experiment_section(shrunk: bool = False):
    """Experimentation plane (bench_experiment.py; committed
    artifacts: BENCH_experiment_rNN.json): parallel-grid throughput
    1-vs-N (report-not-pin on a 1-core host — the ratio carries
    host_core_ratio_caveat) plus assign()/record() round-trips per
    second on the routed-query path. Fork children + one controller
    loop, no device — runs (shrunk) under --skip-heavy."""
    import bench_experiment

    return bench_experiment.bench_section(shrunk=shrunk)


def bench_freshness_section(shrunk: bool = False):
    """Real-time freshness plane (bench_freshness.py; committed
    artifacts: BENCH_freshness_rNN.json): event→recommendation lag
    distribution under live HTTP ingest+query load, fold-in throughput
    in events/s, and the `--workers 2` spool-propagation variant. CPU +
    storage bound — runs (shrunk) under --skip-heavy."""
    import bench_freshness

    return bench_freshness.bench_section(shrunk=shrunk)


def bench_train_profile():
    """Tiny `pio train --profile` on the recommendation template — the
    device/compiler observability trajectory (PR 12,
    docs/observability.md "Device and compiler observability"): the
    artifact carries MFU (null where no peak-FLOPs entry exists —
    honest-or-nothing), cumulative XLA compile seconds, and the compile
    count, so a drift in the compile story (a new shape sneaking into
    the menu, a program that stopped caching) shows round-over-round.
    Cheap enough to run under --skip-heavy."""
    import os
    import tempfile

    from predictionio_tpu.core.datamap import DataMap
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.obs.compile import recorder
    from predictionio_tpu.obs.device import TrainProfiler
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.utils.testing import memory_storage
    from predictionio_tpu.workflow.train import run_train

    storage = memory_storage()
    app_id = storage.get_meta_data_apps().insert(App(0, "BenchProfApp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(5)
    for u in range(32):
        for i in range(24):
            if rng.random() < 0.4:
                events.insert(
                    Event(event="rate", entity_type="user",
                          entity_id=f"u{u}", target_entity_type="item",
                          target_entity_id=f"i{i}",
                          properties=DataMap(
                              {"rating": float(rng.integers(1, 6))})),
                    app_id)
    variant = {
        "id": "bench-profile",
        "engineFactory":
            "predictionio_tpu.templates.recommendation.engine_factory",
        "datasource": {"params": {"app_name": "BenchProfApp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 8, "num_iterations": 3,
                                   "lambda_": 0.05, "seed": 4}}],
    }
    recorder().reset()
    with tempfile.TemporaryDirectory() as model_dir:
        old = os.environ.get("PIO_MODEL_DIR")
        os.environ["PIO_MODEL_DIR"] = model_dir
        try:
            outcome = run_train(variant=variant, storage=storage,
                                profiler=TrainProfiler())
        finally:
            if old is None:
                os.environ.pop("PIO_MODEL_DIR", None)
            else:
                os.environ["PIO_MODEL_DIR"] = old
    report = outcome.report
    recorder().reset()
    mfu = report["mfu"]
    return {
        "train_profile_mfu": (round(mfu, 6) if isinstance(mfu, float)
                              else None),
        "train_profile_compile_seconds": round(
            report["compile"]["totalSeconds"], 3),
        "train_profile_compiles": report["compile"]["totalCompiles"],
        "train_profile_wall_seconds": round(report["wallSeconds"], 3),
    }


def bench_train_sharding(shrunk: bool = False):
    """DP×MP factor-table sharding on the fused ALS flagship path —
    the ROADMAP item 1 trajectory (standalone harness:
    bench_sharding.py; committed artifacts: BENCH_sharding_rNN.json).
    Runs in a forced-8-device subprocess child (this process owns a
    1-device jax runtime): replicated-vs-sharded MFU/HBM at matched
    shapes from TRAIN_REPORT.json (honest-or-null on CPU) plus
    computed per-device table bytes, the factor-parity max |Δ|, and
    the rank-512 sharded-only point against the stated per-device
    budget. Under --skip-heavy it runs shrunk (tiny shapes, same
    contract)."""
    import bench_sharding

    return bench_sharding.bench_sharding_section(shrunk=shrunk)


def bench_batch_predict(n_items: int = 2_000_000, batch: int = 256,
                        rounds: int = 8):
    """Batched top-k scoring against a 2M-item catalog — the eval hot
    path (recommend_topk_chunked's envelope; VERDICT r2 weak #5)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.topk import recommend_topk_fused

    rng = np.random.default_rng(3)
    item_f = jax.device_put(jnp.asarray(
        rng.standard_normal((n_items, RANK)).astype(np.float32)))
    uv = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, RANK)).astype(np.float32)))
    seen = np.zeros((batch, 32), dtype=np.int32)
    mask = np.zeros((batch, 32), dtype=np.float32)
    allow = jnp.ones((n_items,), dtype=jnp.float32)

    def run(n):
        cur = uv
        for _ in range(n):
            v, i = recommend_topk_fused(cur, item_f, seen, mask, allow, 10)
            # feed the scores back so chained inputs differ (protocol)
            cur = cur * (1.0 + 1e-9 * jnp.tanh(jnp.sum(v)))
        return float(jnp.sum(jnp.asarray(i)))

    run(1)
    per_call, _ = _chain_time(run, n_short=1, n_long=1 + rounds, reps=3)
    return {"batch_predict_qps_2m": round(batch / per_call, 1)}


# ---------------------------------------------------------------------------
# Attention: pallas flash vs XLA at the envelope midpoint
# ---------------------------------------------------------------------------


def bench_attention(S: int = 4096, B: int = 1, H: int = 4, D: int = 64,
                    rounds: int = 64):
    """Forward serving attention at S=4096: the pallas flash kernel vs
    the XLA formulation (VERDICT r2 weak #4 — the 35x/OOM envelope
    lived only in a docstring)."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from predictionio_tpu.ops.attention import full_attention
    from predictionio_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(2)

    def mk():
        return jax.device_put(jnp.asarray(
            rng.standard_normal((B, H, S, D)).astype(np.float32) * 0.05))

    q, k, v = mk(), mk(), mk()

    @partial(jax.jit, static_argnames=("flash",))
    def step(q, k, v, flash: bool):
        fn = (lambda *a, **kw: flash_attention(*a, force=True, **kw)) \
            if flash else full_attention
        o = fn(q, k, v, causal=True)
        # feed back: next q depends on this output (protocol)
        return q * (1.0 + 1e-9 * jnp.tanh(jnp.sum(o))), o

    out = {}
    for name, flash in (("flash", True), ("xla", False)):
        def run(n):
            cur = q
            o = None
            for _ in range(n):
                cur, o = step(cur, k, v, flash)
            return float(jnp.sum(jnp.abs(o)))

        run(1)
        out[f"{name}_s{S}_ms"] = round(
            _chain_time(run, n_short=1, n_long=1 + rounds, reps=3)[0] * 1e3,
            2)
    return out


# ---------------------------------------------------------------------------
# Event-server ingest throughput (the serving plane's front door)
# ---------------------------------------------------------------------------


def bench_ingest(n_events: int = 2000, batch: int = 50):
    """Batched REST ingest rate over HTTP loopback into TWO event
    stores (reference front door: POST /batch/events.json,
    EventServer.scala:376-460; <=50 events/request): file-backed sqlite
    (the jdbc role) AND the binevents C++ append log (the hbase role,
    native/eventlog.cc — its ingest number is tracked so the backend
    earns its keep in the contract, VERDICT r3 weak #7). CPU + storage
    bound — no device involvement."""
    out = {}
    # per-backend isolation: one backend's failure must not discard the
    # other's already-measured number
    for key, backend in (("ingest_events_per_sec", "sqlite"),
                         ("ingest_binevents_per_sec", "binevents")):
        try:
            rate, stdev_pct, reps = _ingest_one(backend, n_events, batch)
            out[key] = rate
            # regression vs host noise must be decidable from the
            # artifact alone (VERDICT r4 weak #3)
            out[f"{key}_stdev_pct"] = stdev_pct
            out[f"{key}_reps"] = reps
        except Exception as e:
            out[f"error_ingest_{backend}"] = f"{type(e).__name__}: {e}"
    return out


def _ingest_one(backend: str, n_events: int, batch: int):
    import json as _json
    import tempfile
    import urllib.request

    from predictionio_tpu.api.event_server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.storage.base import AccessKey, App
    from predictionio_tpu.storage.registry import Storage

    with tempfile.TemporaryDirectory() as tmp:
        if backend == "sqlite":
            src = {"PIO_STORAGE_SOURCES_S_TYPE": "sqlite",
                   "PIO_STORAGE_SOURCES_S_PATH": f"{tmp}/pio.db"}
        else:
            # metadata stays sqlite (binevents is an event store);
            # events go to the native log
            src = {"PIO_STORAGE_SOURCES_S_TYPE": "sqlite",
                   "PIO_STORAGE_SOURCES_S_PATH": f"{tmp}/pio.db",
                   "PIO_STORAGE_SOURCES_B_TYPE": "binevents",
                   "PIO_STORAGE_SOURCES_B_PATH": f"{tmp}/binevents"}
        storage = Storage({
            **src,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE":
                "B" if backend == "binevents" else "S",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "S",
        })
        app_id = storage.get_meta_data_apps().insert(App(0, "BenchApp"))
        storage.get_meta_data_access_keys().insert(
            AccessKey("bench-key", app_id, []))
        storage.get_events().init(app_id)
        server = EventServer(
            storage, EventServerConfig(ip="127.0.0.1", port=0))
        server.start()
        try:
            url = (f"http://127.0.0.1:{server.port}/batch/events.json"
                   f"?accessKey=bench-key")
            payload = [
                {"event": "rate", "entityType": "user",
                 "entityId": f"u{j % 97}", "targetEntityType": "item",
                 "targetEntityId": f"i{j % 53}",
                 "properties": {"rating": float(j % 5 + 1)}}
                for j in range(batch)
            ]
            body = _json.dumps(payload).encode()

            def post():
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()

            for _ in range(4):  # warm connections/WAL
                post()
            posted = (n_events // batch) * batch
            reps = 3
            rates = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(n_events // batch):
                    post()
                rates.append(posted / (time.perf_counter() - t0))
        finally:
            server.stop()
    mean = statistics.fmean(rates)
    stdev_pct = 100.0 * statistics.stdev(rates) / mean
    return round(max(rates), 1), round(stdev_pct, 1), reps


# ---------------------------------------------------------------------------
# Quality (parity + ranking-wins) and the rank-200 quality validation
# ---------------------------------------------------------------------------


def bench_quality():
    from predictionio_tpu.data.movielens import synthesize_ml100k
    from predictionio_tpu.e2 import quality

    ds = synthesize_ml100k()
    q = quality.compare_quality(ds, rank=10, iterations=10, lam=0.05,
                                k_fold=5)
    out = {
        "map10_tpu": q["map10_tpu"],
        "map10_ref": q["map10_ref"],
        "map10_popularity": q["map10_popularity"],
        # ranking-WINS key (vs the parity keys above): the implicit
        # path must beat the popularity baseline; explicit ALS does not
        # (MLlib's doesn't either — it models rating values, not
        # interaction propensity)
        "map10_implicit": q["map10_implicit"],
        "rmse_tpu": q["rmse_tpu"],
        "rmse_ref": q["rmse_ref"],
    }
    out.update(_real_data_ranking())
    out.update(_rank200_quality(ds))
    return out


def _real_data_ranking():
    """Implicit-vs-popularity on the vendored REAL Spark sample dataset
    (examples/data/sample_movielens.txt — public data, not generated by
    us), mean over all 5 folds (VERDICT r3 weak #1: the ranking gate
    must not rest solely on the synthetic generator). 30x100, ~1.5k
    ratings: error bars are wide by construction and the keys are
    REPORTING, not a gate — the gate's domain of validity is stated in
    README."""
    import os

    from predictionio_tpu.data.movielens import load_ratings_file
    from predictionio_tpu.e2 import quality

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", "data", "sample_movielens.txt")
    r = quality.implicit_vs_popularity_kfold(load_ratings_file(path))
    return {
        "map10_implicit_real": round(r["map10_implicit"], 4),
        "map10_popularity_real": round(r["map10_popularity"], 4),
    }


def _rank200_quality(ds, iterations: int = 5, lam: float = 0.1):
    """Rank-200 RMSE parity: device ALS at the BASELINE rank vs an
    exact per-row NumPy solver on the same fold — validates the CG step
    cap at the rank where it matters (VERDICT r2 missing #2 /
    ADVICE r2 medium)."""
    from predictionio_tpu.e2 import quality
    from predictionio_tpu.ops.als import RatingsCOO, als_train

    train, test_by_user = quality.kfold_split(ds, k_fold=5)
    f = als_train(
        RatingsCOO(train.users, train.items, train.ratings,
                   train.num_users, train.num_items),
        rank=RANK200, iterations=iterations, lam=lam, seed=3)
    rmse_tpu = quality.test_rmse(f.user, f.item, test_by_user)
    U, V = quality.numpy_als_wr_rowloop(
        train, rank=RANK200, iterations=iterations, lam=lam, seed=4)
    rmse_ref = quality.test_rmse(U, V, test_by_user)
    return {
        "rank200_rmse_tpu": round(rmse_tpu, 4),
        "rank200_rmse_ref": round(rmse_ref, 4),
    }


# ---------------------------------------------------------------------------
# sessionrec transformer: dense, long-context training, flash serving
# ---------------------------------------------------------------------------


def bench_seqrec(steps: int = 20, batch: int = 64):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seqrec import (
        SeqRecConfig,
        init_params,
        make_train_step,
    )

    cfg = SeqRecConfig(vocab=50_000, max_len=256, d_model=256, n_heads=4,
                       n_layers=4)
    s, d, v, layers = cfg.max_len, cfg.d_model, cfg.vocab, cfg.n_layers
    rng = np.random.default_rng(5)
    seqs = rng.integers(1, v, size=(batch, s), dtype=np.int64).astype(np.int32)
    targets = rng.integers(1, v, size=(batch, s), dtype=np.int64).astype(np.int32)

    params0 = init_params(jax.random.PRNGKey(0), cfg)
    opt_m0 = jax.tree.map(jnp.zeros_like, params0)
    opt_v0 = jax.tree.map(jnp.zeros_like, params0)
    step_fn = make_train_step(cfg)

    def run(n):
        params, opt_m, opt_v = params0, opt_m0, opt_v0
        for i in range(n):
            params, opt_m, opt_v, loss = step_fn(
                params, opt_m, opt_v, i + 1, seqs, targets, 1e-3)
        return float(loss)

    run(1)  # compile
    t0 = time.perf_counter()
    run(2)
    t_short = time.perf_counter() - t0
    t0 = time.perf_counter()
    loss = run(2 + steps)
    dt = (time.perf_counter() - t0) - t_short

    tokens = batch * s * steps
    # fwd FLOPs/token: per layer qkv 6d^2 + wo 2d^2 + mlp 16d^2 (mult 4)
    # + attention 4Sd; tied-logits 2dV. Training ~= 3x fwd.
    per_token = 3.0 * (layers * (24.0 * d * d + 4.0 * s * d) + 2.0 * d * v)
    _, peak = _device_peak()
    out = {
        "seqrec_tokens_per_sec": round(tokens / dt, 1),
        "seqrec_loss": round(float(loss), 3),
    }
    if peak:
        out["seqrec_mfu_pct"] = round(
            100.0 * tokens * per_token / dt / peak, 2)
    out.update(bench_seqrec_longcontext())
    return out


def bench_seqrec_longcontext(steps: int = 4):
    """The long-context ladder's tracked numbers (VERDICT r2 weak #7):
    training step rate at S=4096 (blockwise attention path) and serving
    p50 at S=2048 (predict_topk end to end)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seqrec import (
        PAD,
        SeqRecConfig,
        init_params,
        make_train_step,
        predict_topk,
    )

    out = {}
    rng = np.random.default_rng(6)

    # --- S=4096 training (forward routes through blockwise_attention)
    cfg = SeqRecConfig(vocab=50_000, max_len=4096, d_model=256, n_heads=4,
                       n_layers=4)
    batch = 4
    seqs = rng.integers(1, cfg.vocab, size=(batch, cfg.max_len),
                        dtype=np.int64).astype(np.int32)
    tgts = rng.integers(1, cfg.vocab, size=(batch, cfg.max_len),
                        dtype=np.int64).astype(np.int32)
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    m0 = jax.tree.map(jnp.zeros_like, params0)
    v0 = jax.tree.map(jnp.zeros_like, params0)
    step_fn = make_train_step(cfg)

    def run(n):
        params, m, v = params0, m0, v0
        for i in range(n):
            params, m, v, loss = step_fn(params, m, v, i + 1, seqs, tgts,
                                         1e-3)
        return float(loss)

    run(1)
    per_step, _ = _chain_time(run, n_short=1, n_long=1 + steps, reps=2)
    out["seqrec_s4096_tokens_per_sec"] = round(
        batch * cfg.max_len / per_step, 1)

    # --- S=2048 serving p50 (predict_topk end to end)
    scfg = SeqRecConfig(vocab=50_000, max_len=2048, d_model=256, n_heads=4,
                        n_layers=4)
    sparams = init_params(jax.random.PRNGKey(1), scfg)
    hist = rng.integers(1, scfg.vocab, size=(1, scfg.max_len),
                        dtype=np.int64).astype(np.int32)
    vocab_mask = jnp.zeros((scfg.vocab,), dtype=jnp.float32)

    lats = []
    predict_topk(sparams, jnp.asarray(hist), 10, scfg, vocab_mask)  # compile
    for j in range(40):
        h = jnp.asarray(
            np.where(hist == 0, 0, (hist + j) % (scfg.vocab - 1) + 1)
            .astype(np.int32))
        t0 = time.perf_counter()
        v_, i_ = predict_topk(sparams, h, 10, scfg, vocab_mask)
        float(jnp.sum(v_)) + float(jnp.sum(i_))   # forcing fetch
        lats.append(time.perf_counter() - t0)
    out["seqrec_serve_s2048_p50_ms"] = round(
        float(np.percentile(lats, 50)) * 1e3, 2)
    return out


# ---------------------------------------------------------------------------


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--skip-heavy", action="store_true",
                        help="headline + quality + ingest only")
    args = parser.parse_args()

    users, items, vals = make_ratings(NNZ)
    calib = bench_calibration()
    als, user_f, item_f = bench_als(users, items, vals)
    line = {
        "metric": "als_train_throughput_ml20m_rank32",
        "value": round(als.pop("rate"), 1),
        "unit": "ratings/sec",
        **als,
    }

    line.update(calib)

    base = bench_numpy_baseline(users, items, vals)
    line["vs_baseline"] = round(line["value"] / base["baseline_rate"], 2)
    line["vs_baseline_64core"] = round(
        line["value"] / base["baseline_64core_rate"], 2)
    line.update(base)

    sections = [
        ("phases", lambda: bench_phases(users, items, vals)),
        ("rank200", lambda: bench_rank200(users, items, vals)),
        ("serving", lambda: bench_serving(user_f, item_f, users, items)),
        ("serving_path", bench_serving_path),
        ("attention", bench_attention),
        ("quality", bench_quality),
        ("seqrec", bench_seqrec),
        ("ingest", bench_ingest),
        ("data_plane", bench_data_plane),
        ("ann_retrieval",
         lambda: bench_ann_retrieval(shrunk=args.skip_heavy)),
        ("workers_scaling",
         lambda: bench_workers_scaling(shrunk=args.skip_heavy)),
        ("shm_cache",
         lambda: bench_shm_cache(shrunk=args.skip_heavy)),
        ("gateway",
         lambda: bench_gateway_phase(shrunk=args.skip_heavy)),
        ("freshness",
         lambda: bench_freshness_section(shrunk=args.skip_heavy)),
        ("elasticity",
         lambda: bench_elasticity_section(shrunk=args.skip_heavy)),
        ("experiment",
         lambda: bench_experiment_section(shrunk=args.skip_heavy)),
        ("train_profile", bench_train_profile),
        ("train_sharding",
         lambda: bench_train_sharding(shrunk=args.skip_heavy)),
    ]
    failed = []
    skipped: tuple = ()
    if args.skip_heavy:
        # skipped sections' keys are absent, which IS an incomplete
        # artifact — the completeness marker must say so. data_plane
        # stays: it is CPU+storage bound like ingest, no device needed;
        # ann_retrieval runs SHRUNK (one small indexable catalog), and
        # workers_scaling SHRUNK (small catalog, no 1M ANN re-run);
        # train_profile is a seconds-scale tiny train either way
        # freshness rides along shrunk: CPU + storage bound like
        # data_plane, no device involvement
        # gateway rides along shrunk: CPU + loopback HTTP bound, no
        # device involvement
        # elasticity rides along shrunk: router threads + stdlib echo
        # backends + a ManualClock timeline, no device involvement
        # experiment rides along shrunk: fork eval children + a
        # single-threaded controller loop, no device involvement
        # shm_cache rides along shrunk: subprocess serving pools +
        # loopback HTTP + one POSIX shm segment, no device involvement
        # train_sharding rides along shrunk: a seconds-scale forced-8-
        # device subprocess child (tiny matched-shape parity + a small
        # sharded point — same contract as the full artifact)
        keep = ("quality", "ingest", "data_plane", "ann_retrieval",
                "workers_scaling", "freshness", "train_profile",
                "gateway", "elasticity", "experiment", "shm_cache",
                "train_sharding")
        skipped = tuple(s[0] for s in sections if s[0] not in keep)
        failed.extend(skipped)
        sections = [s for s in sections if s[0] in keep]
    for section, fn in sections:
        try:
            line.update(fn())
        except Exception as e:  # keep the primary metric on partial failure
            line[f"error_{section}"] = f"{type(e).__name__}: {e}"
            failed.append(section)
    # ingest reports per-backend errors without raising (isolation)
    failed.extend(k.removeprefix("error_") for k in line
                  if k.startswith("error_ingest_"))
    # an incomplete artifact must be impossible to mistake for a
    # complete one (VERDICT r4 weak #7) — always present, [] = complete
    line["sections_failed"] = failed

    if {"iter_ms", "phase_gather_ms", "phase_einsum_ms"} <= line.keys():
        # the CG-solve + factor-write-back remainder of the headline
        # iteration (VERDICT r3 weak #5: without it a solver regression
        # is invisible round-over-round)
        line["phase_solve_ms"] = round(
            line["iter_ms"] - line["phase_gather_ms"]
            - line["phase_einsum_ms"], 1)
    if {"rank200_iter_ms", "calibration_matmul_ms"} <= line.keys():
        # session-normalized rank-200 quote (VERDICT r4 weak #6):
        # identical programs measured 330-497 ms/iter across sessions;
        # dividing by the constant-workload anchor makes a
        # round-over-round comparison regime-adjusted
        line["rank200_iter_per_calib"] = round(
            line["rank200_iter_ms"] / line["calibration_matmul_ms"], 1)

    print(json.dumps(line))
    if set(failed) - set(skipped):
        # a section that ran and failed fails the run; the line above
        # still carries everything that was measured
        raise SystemExit(1)


if __name__ == "__main__":
    main()
