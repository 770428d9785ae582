"""bench.py is the driver's interface (BENCH_r{N}.json): its ONE-line
JSON contract must not regress. This smoke test runs the real ALS and
ingest sections at tiny scale on the CPU backend and stubs the
device-heavy sections (serving/quality/seqrec run for minutes at real
shapes), asserting the primary keys and the partial-failure guard."""

import json

import pytest


@pytest.fixture
def tiny_bench(monkeypatch):
    import bench

    monkeypatch.setattr(bench, "USERS", 120)
    monkeypatch.setattr(bench, "ITEMS", 60)
    monkeypatch.setattr(bench, "NNZ", 3000)
    monkeypatch.setattr(bench, "SUB_NNZ", 1000)
    monkeypatch.setattr(bench, "N_SHORT", 1)
    monkeypatch.setattr(bench, "N_LONG", 3)
    monkeypatch.setattr(bench, "bench_serving",
                        lambda *a, **kw: {"p50_ms": 1.0, "p99_ms": 2.0})
    # serving_path drives a real HTTP server fleet at 100k-item scale
    # (bench_serving.py) — stubbed like the other device-heavy sections
    monkeypatch.setattr(bench, "bench_serving_path",
                        lambda: {"serving_speedup_x": 2.0})
    monkeypatch.setattr(bench, "bench_quality",
                        lambda: {"map10_tpu": 0.1, "map10_ref": 0.1})
    monkeypatch.setattr(bench, "bench_seqrec",
                        lambda: {"seqrec_tokens_per_sec": 1.0})
    # device-heavy r3 sections (pallas interpret mode on CPU is minutes;
    # rank 200 is PFLOP-scale at real shapes)
    monkeypatch.setattr(bench, "bench_rank200",
                        lambda *a, **kw: {"rank200_rate": 1.0})
    monkeypatch.setattr(bench, "bench_attention",
                        lambda *a, **kw: {"flash_s4096_ms": 1.0})
    # keep ingest real but tiny (default posts 2000+warmup events)
    real_ingest = bench.bench_ingest
    monkeypatch.setattr(bench, "bench_ingest",
                        lambda: real_ingest(n_events=100, batch=25))
    # data_plane spawns client subprocesses and scans 10k+ events
    # (bench_ingest.py) — stubbed here, covered by its own perf test
    monkeypatch.setattr(bench, "bench_data_plane",
                        lambda: {"scan_speedup_x_sqlite": 3.0,
                                 "ingest_tx_speedup_x": 2.0,
                                 "wal_interval_vs_direct_x": 1.0})
    # ann_retrieval builds IVF indexes and drives HTTP server pairs at
    # catalog scale (bench_serving.py) — stubbed here; the shrunk
    # harness itself is covered by the --skip-heavy artifact runs.
    # The stub mirrors the REAL key naming (suffix = items//1000):
    # full runs emit 100k/1000k keys, shrunk runs emit 16k keys.
    monkeypatch.setattr(
        bench, "bench_ann_retrieval",
        lambda shrunk=False: ({"ann_speedup_16k_x": 1.0,
                               "ann_recall_16k": 0.99} if shrunk else
                              {"ann_speedup_100k_x": 1.0,
                               "ann_recall_100k": 0.99}))
    # workers_scaling spawns engine-server process pools over
    # SO_REUSEPORT (bench_serving.py --workers-only) — stubbed here;
    # the real tiny harness is the slow-marked test below
    monkeypatch.setattr(
        bench, "bench_workers_scaling",
        lambda shrunk=False: {"workers_scaling_2w_vs_1w_x": 1.0,
                              "workers_qps_1w": 100.0,
                              "workers_qps_2w": 160.0,
                              "workers_host_cores": 2,
                              "workers_reported_in_merged_metrics": 2.0})
    # shm_cache spawns paired private-vs-shm serving pools over one
    # POSIX segment (bench_serving.py --shm-only) — stubbed here; the
    # real tiny harness is the slow-marked test below
    monkeypatch.setattr(
        bench, "bench_shm_cache",
        lambda shrunk=False: {"shm_qps_1w_private": 100.0,
                              "shm_qps_1w_shm": 98.0,
                              "shm_qps_2w_private": 150.0,
                              "shm_qps_2w_shm": 148.0,
                              "shm_hit_ratio_2w_private": 0.9,
                              "shm_hit_ratio_2w_shm": 0.95,
                              "shm_rewarm_misses_2w_private": 24,
                              "shm_rewarm_misses_2w_shm": 8,
                              "shm_p99_ms_2w_private": 5.0,
                              "shm_p99_ms_2w_shm": 5.0,
                              "shm_host_cores": 2,
                              "shm_host_cores_caveat": None})
    # freshness trains + deploys a live server fleet (bench_freshness.py)
    # — stubbed here; the real tiny harness is the perf test below
    monkeypatch.setattr(
        bench, "bench_freshness_section",
        lambda shrunk=False: {"freshness_lag_p50_ms": 300.0,
                              "freshness_foldin_events_per_sec": 100.0,
                              "freshness_http_5xx": 0})
    # gateway spawns a replica fleet + two router subprocesses
    # (bench_serving.py --gateway-only) — stubbed here; the real tiny
    # harness is the slow-marked test below
    monkeypatch.setattr(
        bench, "bench_gateway_phase",
        lambda shrunk=False: {"gateway_quota_neighbor_p99_ratio_x": 1.0,
                              "gateway_two_engine_overhead_pct": 0.5,
                              "gateway_throttled_429": 100,
                              "gateway_http_5xx": 0,
                              "gateway_host_cores": 2})
    # elasticity drives live router threads + a ManualClock timeline
    # (bench_elasticity.py) — stubbed here; the real tiny harness is
    # the slow-marked test below
    monkeypatch.setattr(
        bench, "bench_elasticity_section",
        lambda shrunk=False: {"elasticity_compliant_p99_ratio_x": 1.0,
                              "elasticity_b_http_5xx": 0,
                              "elasticity_throttled_429": 100,
                              "elasticity_burst_admitted_with_credits": 21,
                              "elasticity_burst_admitted_control": 5,
                              "elasticity_host_cores": 2,
                              "elasticity_host_cores_caveat": None})
    # experiment forks eval worker children for the grid 1-vs-N ratio
    # (bench_experiment.py) — stubbed here; the real tiny harness is
    # the slow-marked test below
    monkeypatch.setattr(
        bench, "bench_experiment_section",
        lambda shrunk=False: {"experiment_grid_speedup_x": 1.0,
                              "experiment_grid_points": 4,
                              "experiment_grid_parallel": 2,
                              "experiment_grid_seq_s": 0.4,
                              "experiment_grid_par_s": 0.4,
                              "experiment_grid_failed_points": 0,
                              "experiment_assign_ops_per_s": 10_000.0,
                              "experiment_host_cores": 2,
                              "experiment_host_cores_caveat": None})
    # train_sharding spawns a forced-8-device jax subprocess child
    # (bench_sharding.py) — stubbed here; the real tiny harness is the
    # slow-marked test below
    monkeypatch.setattr(
        bench, "bench_train_sharding",
        lambda shrunk=False: {
            "train_sharding_devices": 8,
            "train_sharding_model_axis": 2,
            "train_sharding_replicated_mfu": None,
            "train_sharding_sharded_mfu": None,
            "train_sharding_replicated_hbm_peak_bytes": None,
            "train_sharding_sharded_hbm_peak_bytes": None,
            "train_sharding_replicated_table_bytes_per_device": 5120,
            "train_sharding_sharded_table_bytes_per_device": 2560,
            "train_sharding_parity_max_abs_diff": 0.0,
            "train_sharding_r512_completed": True,
            "train_sharding_r512_fits_replicated": False,
            "train_sharding_r512_fits_sharded": True})
    # keep calibration real but tiny (2048^3 bf16 chains are for the chip)
    real_calib = bench.bench_calibration
    monkeypatch.setattr(bench, "bench_calibration",
                        lambda: real_calib(n=128, rounds=2))
    return bench


def test_single_json_line_with_primary_contract(tiny_bench, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["bench.py"])
    tiny_bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, "bench must print exactly ONE line"
    line = json.loads(out[0])
    # the driver's primary contract
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in line, key
    assert line["unit"] == "ratings/sec"
    assert line["value"] > 0 and line["vs_baseline"] > 0
    # round-over-round comparison keys
    for key in ("stdev_pct", "iter_ms", "padding_x", "p50_ms",
                "map10_tpu", "seqrec_tokens_per_sec",
                "ingest_events_per_sec", "ingest_events_per_sec_stdev_pct",
                "calibration_matmul_ms", "scan_speedup_x_sqlite",
                "ingest_tx_speedup_x", "ann_speedup_100k_x",
                "workers_scaling_2w_vs_1w_x", "workers_host_cores",
                "freshness_lag_p50_ms",
                "freshness_foldin_events_per_sec",
                # the multi-tenant gateway trajectory keys (PR 15)
                "gateway_quota_neighbor_p99_ratio_x",
                "gateway_two_engine_overhead_pct",
                "gateway_throttled_429", "gateway_http_5xx",
                # the per-tenant elasticity trajectory keys (PR 16)
                "elasticity_compliant_p99_ratio_x",
                "elasticity_b_http_5xx", "elasticity_throttled_429",
                "elasticity_burst_admitted_with_credits",
                "elasticity_host_cores_caveat",
                # the experimentation-platform trajectory keys (PR 20)
                "experiment_grid_speedup_x",
                "experiment_grid_failed_points",
                "experiment_assign_ops_per_s",
                "experiment_host_cores_caveat",
                # the shared-memory serving-plane trajectory keys (PR 18)
                "shm_qps_2w_private", "shm_qps_2w_shm",
                "shm_hit_ratio_2w_shm", "shm_rewarm_misses_2w_private",
                "shm_rewarm_misses_2w_shm", "shm_host_cores_caveat",
                # train_profile runs REAL (tiny train, seconds): the
                # device/compiler observability trajectory keys
                "train_profile_mfu", "train_profile_compile_seconds",
                "train_profile_compiles", "train_profile_wall_seconds",
                # the DP×MP factor-sharding trajectory keys (PR 19)
                "train_sharding_devices", "train_sharding_model_axis",
                "train_sharding_parity_max_abs_diff",
                "train_sharding_replicated_table_bytes_per_device",
                "train_sharding_sharded_table_bytes_per_device",
                "train_sharding_r512_completed",
                "train_sharding_r512_fits_sharded"):
        assert key in line, key
    # MFU is honest-or-nothing: a float when a peak is known, else
    # null — never absent, never fabricated
    assert line["train_profile_mfu"] is None \
        or isinstance(line["train_profile_mfu"], float)
    assert line["train_profile_compiles"] >= 1
    # a complete artifact says so explicitly (VERDICT r4 weak #7)
    assert line["sections_failed"] == []


def test_section_failure_keeps_primary_metric(tiny_bench, capsys, monkeypatch):
    """A crashing section must surface as error_<name>, never lose the
    headline metric (the driver records whatever line is printed) —
    and fail the run: only --skip-heavy's skips leave the exit code 0."""
    monkeypatch.setattr("sys.argv", ["bench.py"])
    monkeypatch.setattr(
        tiny_bench, "bench_quality",
        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(SystemExit) as exit_info:
        tiny_bench.main()
    assert exit_info.value.code == 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] > 0
    assert "error_quality" in line and "boom" in line["error_quality"]
    assert "map10_tpu" not in line
    # the hole in the contract is marked at the artifact top level
    assert line["sections_failed"] == ["quality"]


def test_skip_heavy_lists_skipped_sections(tiny_bench, capsys, monkeypatch):
    """--skip-heavy artifacts are INCOMPLETE and must say so: the
    skipped sections land in sections_failed (README contract)."""
    monkeypatch.setattr("sys.argv", ["bench.py", "--skip-heavy"])
    tiny_bench.main()
    line = json.loads(capsys.readouterr().out.strip())
    assert set(line["sections_failed"]) == {
        "phases", "rank200", "serving", "serving_path", "attention",
        "seqrec"}
    assert "ingest_events_per_sec" in line and "map10_tpu" in line
    assert "scan_speedup_x_sqlite" in line   # data_plane runs skip-heavy
    assert "wal_interval_vs_direct_x" in line  # WAL phase rides data_plane
    assert "ann_speedup_16k_x" in line       # ann_retrieval runs SHRUNK
    # workers_scaling runs SHRUNK under --skip-heavy too
    assert "workers_scaling_2w_vs_1w_x" in line
    # freshness runs SHRUNK under --skip-heavy too
    assert "freshness_lag_p50_ms" in line
    # gateway runs SHRUNK under --skip-heavy too
    assert "gateway_quota_neighbor_p99_ratio_x" in line
    # elasticity runs SHRUNK under --skip-heavy too
    assert "elasticity_compliant_p99_ratio_x" in line
    # experiment runs SHRUNK under --skip-heavy too
    assert "experiment_grid_speedup_x" in line
    # shm_cache runs SHRUNK under --skip-heavy too
    assert "shm_rewarm_misses_2w_shm" in line


@pytest.mark.perf
def test_data_plane_harness_contract_tiny():
    """bench_ingest.py's real phases at tiny scale: the scan harness
    must verify row/columnar output equivalence before timing (it
    asserts internally), and the DAO ingest section must report both
    rates plus the ratio. The HTTP section spawns subprocesses and is
    exercised by the full artifact runs, not here."""
    import bench_ingest

    scan = bench_ingest.bench_scan(n_events=1200, rounds=1)
    for kind in ("memory", "sqlite"):
        assert scan[f"scan_row_events_per_sec_{kind}"] > 0
        assert scan[f"scan_columnar_events_per_sec_{kind}"] > 0
    dao = bench_ingest.bench_ingest_dao(n_events=300, batch=50, rounds=1)
    assert dao["ingest_per_event_events_per_sec"] > 0
    assert dao["ingest_batch_tx_events_per_sec"] > 0
    assert dao["ingest_tx_speedup_x"] > 0
    # the WAL phase (PR 13) reports every fsync policy plus the ratio
    # against direct insert — the keys BENCH_wal_rNN.json records
    wal = bench_ingest.bench_wal(n_events=300, batch=50, rounds=1)
    for policy in ("off", "interval", "always"):
        assert wal[f"wal_append_{policy}_events_per_sec"] > 0
        assert wal[f"wal_{policy}_vs_direct_x"] > 0
    assert wal["wal_direct_batch_events_per_sec"] > 0


@pytest.mark.perf
@pytest.mark.slow
@pytest.mark.online
def test_freshness_harness_contract_tiny():
    """bench_freshness.py's real harness at tiny scale: trains, deploys
    --online single + 2-worker-spool fleets in process, probes the
    event→serve lag, and must report the lag distribution, fold-in
    throughput, the workers-variant lag, and ZERO 5xx (the keys
    BENCH_freshness_rNN.json records). Slow-marked: one tiny train +
    three live servers."""
    import bench_freshness

    r = bench_freshness.bench_freshness(
        n_users=12, n_items=10, probe_rounds=2, foldin_events=60,
        workers_rounds=1)
    assert r["freshness_lag_p50_ms"] > 0
    assert r["freshness_foldin_events_per_sec"] > 0
    assert r["freshness_workers_lag_p50_ms"] > 0
    assert r["freshness_http_5xx"] == 0
    assert r["freshness_http_requests"] > 0


@pytest.mark.perf
@pytest.mark.slow
@pytest.mark.fleet
def test_gateway_harness_contract_tiny():
    """bench_serving.py's real gateway phase at tiny scale: spawns the
    2-replica fleet plus the one-engine and two-engine router
    subprocesses, drives both tenants concurrently, throttles tenant
    ``rec`` at runtime, and must report the neighbor-p99 ratio, the
    table-cost delta, a non-zero 429 count for the throttled tenant,
    and ZERO 5xx (the keys BENCH_gateway_rNN.json records).
    Slow-marked: three jax-importing child processes."""
    import bench_serving

    r = bench_serving.bench_gateway(
        items=4096, clients=4, per_client=8, rounds=2,
        quota_qps=5.0)
    assert r["value"] is not None and r["value"] > 0
    assert r["single_engine_qps"] > 0 and r["two_engine_qps"] > 0
    assert r["throttled_429"] > 0
    assert r["rec_quota_throttled_total"] > 0
    assert r["ecom_quota_throttled_total"] == 0
    assert r["http_5xx"] == 0
    assert r["host_cores"] >= 1


@pytest.mark.perf
@pytest.mark.slow
@pytest.mark.elasticity
def test_elasticity_harness_contract_tiny():
    """bench_elasticity.py's real harness at tiny scale: live router +
    echo backends for the isolation and burst-credit phases, a
    ManualClock EngineScaleSet for the timeline phase. Must report the
    compliant-tenant ratio with ZERO 5xx, a throttled abusive tenant,
    more burst admissions with credits than without, a non-empty
    per-engine decision timeline, and the 1-core caveat contract (the
    keys BENCH_elasticity_rNN.json records). Slow-marked: live HTTP
    rounds plus a deliberate credit-accrual idle."""
    import os

    import bench_elasticity

    r = bench_elasticity.bench_elasticity(
        rounds=1, b_requests=20, idle_s=1.0, ticks=12)
    assert r["value"] > 0
    assert r["b_http_5xx"] == 0
    assert r["a_throttled_429"] > 0
    assert r["burst_admitted_with_credits"] > r["burst_admitted_control"]
    assert r["burst_credit_spends"] > 0
    assert r["scale_timeline"], "timeline must record scale decisions"
    assert set(r["scale_decisions"]) == {"diurnal", "spiky", "abusive"}
    # honest 1-core caveat: present exactly when the host is too small
    # for multi-process ratios to be pins
    cores = os.cpu_count() or 1
    if cores < 2:
        assert r["host_cores_caveat"] and "NOT a pin" in r["host_cores_caveat"]
    else:
        assert r["host_cores_caveat"] is None


@pytest.mark.perf
@pytest.mark.slow
@pytest.mark.experiment
def test_experiment_harness_contract_tiny():
    """bench_experiment.py's real harness at tiny scale: the same grid
    through run_parallel_grid at width 1 and width 2 (zero failed
    points on a healthy grid), plus the assign()/record() loop, with
    the honest 1-core caveat contract (the keys
    BENCH_experiment_rNN.json records). Slow-marked: deliberate
    per-point CPU burn times the grid twice."""
    import os

    import bench_experiment

    r = bench_experiment.bench_experiment(points=3, parallel=2,
                                          work_ms=10.0, ops=2_000)
    assert r["grid"]["value"] > 0
    assert r["grid"]["failed_points"] == 0
    assert r["grid"]["seq_s"] > 0 and r["grid"]["par_s"] > 0
    assert r["assign"]["value"] > 0
    cores = os.cpu_count() or 1
    if cores < 2:
        assert r["host_cores_caveat"] and "NOT a pin" in r["host_cores_caveat"]
    else:
        assert r["host_cores_caveat"] is None


@pytest.mark.perf
@pytest.mark.slow
@pytest.mark.shm
def test_shm_harness_contract_tiny():
    """bench_serving.py's real shm phase at tiny scale: spawns the
    paired private-LRU and shared-segment pools at 1 and 2 workers,
    drives the cached workload, scrapes the pool-wide hit ratio, and
    runs the post-invalidation rewarm probe. The shared arm must pay
    each probed key AT MOST what the private arm pays — sharing one
    physical cache can only reduce pool-wide cold misses (the keys
    BENCH_shm_rNN.json records). Slow-marked: four jax-importing
    child processes."""
    import bench_serving

    r = bench_serving.bench_shm(
        items=4096, clients=4, per_client=4, rounds=2, procs=1,
        rewarm_keys=6)
    assert r["value"] is not None and r["value"] > 0
    assert r["host_cores"] >= 1
    by_workers = {e["workers"]: e for e in r["per_workers"]}
    for n in (1, 2):
        e = by_workers[n]
        assert e["private_qps"] > 0 and e["shm_qps"] > 0
        assert e["private_errors"] == 0 and e["shm_errors"] == 0
        assert e["shm_hit_ratio"] is not None and e["shm_hit_ratio"] > 0
        # every probed key misses at least once (the invalidation took)
        # and the shared segment never pays MORE than replicas do
        assert e["shm_rewarm_misses"] >= r["rewarm_keys"]
        assert e["shm_rewarm_misses"] <= e["private_rewarm_misses"]
    # 1 worker: private and shm are the same topology — both pay each
    # probed key exactly once
    assert by_workers[1]["shm_rewarm_misses"] == r["rewarm_keys"]


@pytest.mark.perf
@pytest.mark.slow
def test_workers_harness_contract_tiny():
    """bench_serving.py's real workers phase at tiny scale: spawns the
    1-worker and 2-worker SO_REUSEPORT pools as subprocesses, drives a
    handful of queries, and must report the scaling ratio, per-pool
    qps, host cores, and the merged-scrape worker count (the harness
    sanity the full artifact runs depend on). Slow-marked: three
    jax-importing child processes."""
    import bench_serving

    r = bench_serving.bench_workers(
        items=4096, clients=4, per_client=4, rounds=2, procs=1,
        ann_items=None)
    assert r["value"] > 0
    assert r["qps_1w"] > 0 and r["qps_2w"] > 0
    assert r["host_cores"] >= 1
    assert r["workers_reported_in_merged_metrics"] == 2.0
    assert r["errors"] == 0


@pytest.mark.mesh
@pytest.mark.slow
def test_sharding_harness_contract_tiny():
    """bench_sharding.py's real child at tiny (shrunk) scale: one
    forced-8-device subprocess running replicated-vs-sharded matched
    shapes through `pio train --profile` plus the sharded-only point —
    the keys and invariants BENCH_sharding_rNN.json records.
    Slow-marked: a jax-importing child training four models."""
    import bench_sharding

    r = bench_sharding.bench_sharding_section(shrunk=True)
    assert r["train_sharding_devices"] == 8
    assert r["train_sharding_model_axis"] >= 2
    # the parity number IS the numerics claim: sharded == replicated
    assert r["train_sharding_parity_max_abs_diff"] <= 2e-4
    # per-device table bytes shrink by exactly the model axis
    assert (r["train_sharding_sharded_table_bytes_per_device"]
            == r["train_sharding_replicated_table_bytes_per_device"]
            // r["train_sharding_model_axis"])
    # MFU/HBM are honest-or-null (CPU backend: null)
    for key in ("train_sharding_replicated_mfu",
                "train_sharding_sharded_mfu"):
        assert r[key] is None or isinstance(r[key], float)
    assert r["train_sharding_r512_completed"] is True
    assert r["train_sharding_r512_fits_sharded"] is True
    assert (r["train_sharding_r512_sharded_table_bytes_per_device"]
            == r["train_sharding_r512_replicated_table_bytes"]
            // r["train_sharding_devices"])
