"""Device & compiler observability (PR 12; docs/observability.md
"Device and compiler observability"): the recompile sentinel
(obs/compile.py), the device/MFU accounting (obs/device.py), the
`pio train --profile` TRAIN_REPORT, and the e2e serving-recompile pin
through the recommendation template's real padB path."""

import json
import logging
import os

import numpy as np
import pytest

from predictionio_tpu.core.datamap import DataMap
from predictionio_tpu.core.event import Event
from predictionio_tpu.obs.compile import (
    CompileRecorder,
    compile_metrics_collector,
    describe_abstract_signature,
    instrumented_jit,
    recorder,
)
from predictionio_tpu.obs.device import (
    TrainProfiler,
    resolve_peak_flops,
    summarize_train_report,
    train_report_collector,
)
from predictionio_tpu.obs.exporter import render_metrics
from predictionio_tpu.obs.trace import Trace, use_trace
from predictionio_tpu.storage.base import App
from predictionio_tpu.utils.resilience import ManualClock
from predictionio_tpu.utils.testing import memory_storage
from predictionio_tpu.workflow.train import run_train

pytestmark = [pytest.mark.obs, pytest.mark.profile]


# ---------------------------------------------------------------------------
# CompileRecorder units (no jax)
# ---------------------------------------------------------------------------


class TestCompileRecorder:
    def test_counts_per_fn_and_signature(self):
        clock = ManualClock(100.0)
        rec = CompileRecorder(clock=clock)
        rec.record_compile("f", "(f32[4])", 0.5)
        rec.record_compile("f", "(f32[8])", 0.25)
        rec.record_compile("g", "(f32[4])", 1.0)
        compiles, seconds, recompiles = rec.totals()
        assert compiles == 3
        assert seconds == pytest.approx(1.75)
        assert recompiles == 0
        assert rec.compiles_by_fn() == {"f": 2, "g": 1}
        table = {(row["fn"], row["signature"]): row["compiles"]
                 for row in rec.recompile_table()}
        assert table == {("f", "(f32[4])"): 1, ("f", "(f32[8])"): 1,
                         ("g", "(f32[4])"): 1}

    def test_post_warmup_compiles_count_as_serving_recompiles(self):
        rec = CompileRecorder(clock=ManualClock(0.0))
        assert rec.record_compile("f", "a", 0.1) is False
        rec.mark_warmup_complete()
        assert rec.record_compile("f", "b", 0.1) is True
        assert rec.totals()[2] == 1
        # the SAME signature compiling twice post-warmup counts twice:
        # each fire is a live request paying a compile
        assert rec.record_compile("f", "b", 0.1) is True
        assert rec.totals()[2] == 2

    def test_compile_seconds_between_bins_by_midpoint(self):
        clock = ManualClock(10.0)
        rec = CompileRecorder(clock=clock)
        rec.record_compile("f", "a", 2.0, start=10.0, end=12.0)  # mid 11
        rec.record_compile("f", "b", 2.0, start=20.0, end=22.0)  # mid 21
        assert rec.compile_seconds_between(10.0, 15.0) == pytest.approx(2.0)
        assert rec.compile_seconds_between(15.0, 30.0) == pytest.approx(2.0)
        assert rec.compile_seconds_between(0.0, 5.0) == 0.0

    def test_executed_flops_needs_pricing_and_calls(self):
        rec = CompileRecorder()
        rec.capture_cost = True
        assert rec.executed_flops() is None
        rec.ensure_priced("f", "a", lambda: 100.0)
        rec.record_call("f", "a")
        rec.record_call("f", "a")
        assert rec.executed_flops() == pytest.approx(200.0)
        # a backend answering None is remembered, not re-asked
        asked = []
        rec.ensure_priced("f", "b", lambda: asked.append(1))
        rec.ensure_priced("f", "b", lambda: asked.append(1))
        assert asked == [1]

    def test_reset_restores_cold_state(self):
        rec = CompileRecorder()
        rec.record_compile("f", "a", 0.1)
        rec.mark_warmup_complete()
        rec.capture_cost = True
        rec.reset()
        assert rec.totals() == (0, 0.0, 0)
        assert rec.warmup_complete is False
        assert rec.capture_cost is False

    def test_collector_families_always_present(self):
        rec = CompileRecorder()
        text = render_metrics(list(compile_metrics_collector(rec)()))
        # the aggregate families exist at zero so dashboards/worker
        # merge see them before the first compile
        assert "pio_jit_compile_seconds_total 0" in text
        assert "pio_serving_recompile_total 0" in text
        assert "pio_jit_compiles_total" not in text  # per-fn: first sample
        rec.record_compile("my_fn", "sig", 0.5)
        text = render_metrics(list(compile_metrics_collector(rec)()))
        assert 'pio_jit_compiles_total{fn="my_fn"} 1' in text

    def test_signature_description_bounded_and_stable(self):
        sig = describe_abstract_signature(
            (np.zeros((3, 4), np.float32), 7), {"k": 10})
        assert sig == "(float32[3,4], 7, k=10)"
        huge = describe_abstract_signature(
            tuple(np.zeros((5,)) for _ in range(100)), {})
        assert len(huge) <= 200
        assert huge != describe_abstract_signature(
            tuple(np.zeros((6,)) for _ in range(100)), {})


# ---------------------------------------------------------------------------
# instrumented_jit against real jax
# ---------------------------------------------------------------------------


class TestInstrumentedJit:
    def test_counts_compiles_not_cache_hits(self):
        import jax.numpy as jnp

        rec = CompileRecorder()
        fn = instrumented_jit(lambda x: x * 2, jit_name="unit_fn",
                              recorder=rec)
        out = fn(jnp.ones((3,)))
        assert float(out[0]) == 2.0
        assert rec.compiles_by_fn() == {"unit_fn": 1}
        assert rec.totals()[1] > 0  # attributed compile seconds
        fn(jnp.ones((3,)))
        assert rec.compiles_by_fn() == {"unit_fn": 1}
        fn(jnp.ones((4,)))
        assert rec.compiles_by_fn() == {"unit_fn": 2}

    def test_post_warmup_compile_warns_and_records_trace_span(self, caplog):
        import jax.numpy as jnp

        rec = CompileRecorder()
        fn = instrumented_jit(lambda x: x + 1, jit_name="warm_fn",
                              recorder=rec)
        fn(jnp.ones((2,)))
        rec.mark_warmup_complete()
        trace = Trace("query")
        with use_trace(trace), \
                caplog.at_level(logging.WARNING,
                                logger="predictionio_tpu.obs.compile"):
            fn(jnp.ones((5,)))
        assert rec.totals()[2] == 1
        assert any("serving recompile" in r.message for r in caplog.records)
        assert any(name == "xla_compile" for name, *_ in trace.spans())

    def test_static_args_are_part_of_the_signature(self):
        import jax.numpy as jnp

        rec = CompileRecorder()
        fn = instrumented_jit(lambda x, k: x * k, jit_name="static_fn",
                              recorder=rec, static_argnames=("k",))
        fn(jnp.ones((2,)), k=3)
        fn(jnp.ones((2,)), k=4)   # new static value -> new program
        assert rec.compiles_by_fn() == {"static_fn": 2}

    def test_aot_lower_still_exposed(self):
        import jax.numpy as jnp

        fn = instrumented_jit(lambda x: x * 2, jit_name="aot_fn",
                              recorder=CompileRecorder())
        compiled = fn.lower(jnp.ones((4,))).compile()
        assert compiled.cost_analysis() is not None


# ---------------------------------------------------------------------------
# the e2e pin: template padB path through the sentinel
# ---------------------------------------------------------------------------

#: enough users that an eval-scale batch (> BATCH_WIDTHS[-1] = 256)
#: passes through serving_batch un-snapped — the off-menu width
N_USERS = 300
N_ITEMS = 37


def _train_rec_model(storage, tmp_path, monkeypatch):
    from tests.rec_engine import train_rec

    return train_rec(storage, tmp_path, monkeypatch, n_users=N_USERS,
                     n_items=N_ITEMS, app_name="RecompileApp")


class TestServingRecompilePin:
    def test_on_menu_zero_off_menu_exactly_one(self, storage, tmp_path,
                                               monkeypatch, caplog):
        """The acceptance pin: post-warmup, serving batch widths ON the
        power-of-two menu record ZERO recompiles (padB snapping keeps
        every dispatch on already-compiled programs) while ONE off-menu
        width (an eval-scale batch past the menu cap, which
        serving_batch passes through) records EXACTLY one."""
        from predictionio_tpu.templates.recommendation import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            Query,
        )
        from predictionio_tpu.workflow.persistence import load_models
        from predictionio_tpu.workflow.context import EngineContext

        outcome = _train_rec_model(storage, tmp_path, monkeypatch)
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=5, num_iterations=2,
                                               lambda_=0.05, seed=3))
        manifest = load_models(storage, outcome.instance_id)[0]
        model = algo.load_model(EngineContext(storage=storage), manifest)

        rec = recorder()
        rec.reset()

        def batch(n):
            queries = [(j, Query(user=f"u{j}", num=4)) for j in range(n)]
            return algo.batch_predict(model, queries)

        # warmup traffic: width 5 -> padB 8 (on-menu), compiles once
        assert len(batch(5)) == 5
        rec.mark_warmup_complete()

        # on-menu traffic after warmup: width 6 -> padB 8, SAME program
        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.obs.compile"):
            assert len(batch(6)) == 6
        assert rec.totals()[2] == 0, rec.recompile_table()

        # off-menu width: 300 > BATCH_WIDTHS[-1] passes through
        # serving_batch un-snapped -> exactly ONE live compile
        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.obs.compile"):
            assert len(batch(N_USERS)) == N_USERS
        assert rec.totals()[2] == 1, rec.recompile_table()
        assert any("serving recompile" in r.message for r in caplog.records)

        # ... and the family is live on a rendered registry scrape
        text = render_metrics(list(compile_metrics_collector()()))
        assert "pio_serving_recompile_total 1" in text
        assert 'fn="recommend_topk_rows"' in text

        # the NON-batched single-query path is instrumented too: one
        # predict routes through models/als._serve_recommend (the
        # packed-transfer wrapper), whose compile the sentinel sees
        rec.reset()
        from predictionio_tpu.templates.recommendation import Query as Q

        result = algo.predict(model, Q(user="u1", num=4))
        assert result.item_scores
        assert "_serve_recommend" in rec.compiles_by_fn(), \
            rec.compiles_by_fn()
        rec.reset()


class TestOneLaunchPerDispatch:
    def test_new_batch_width_compiles_exactly_the_topk_program(
            self, storage, tmp_path, monkeypatch):
        """A batched dispatch is ONE device program: through
        ``DeployedEngine.query_batch`` the first dispatch at a batch
        width not yet seen causes exactly one backend compile, the
        row-taking ``recommend_topk*`` program, and a second at that
        width none. The recorder sees only ``instrumented_jit``
        functions, so the count is of jax's own backend-compile event:
        an eager user-row gather ahead of the program shows there as
        further compiles (``gather``, ``less``, ``select_n``, ...) at
        each new shape, and fails this."""
        import jax.monitoring
        from jax._src import monitoring as jax_monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        from predictionio_tpu.templates.recommendation import Query
        from predictionio_tpu.workflow.deploy import load_deployed_engine
        from tests.rec_engine import train_rec

        # table shapes no other test of this process serves: the jit
        # caches are the process's
        train_rec(storage, tmp_path, monkeypatch, n_users=31, n_items=23,
                  app_name="OneLaunchApp")
        deployed = load_deployed_engine(storage=storage)
        backend_compiles = []

        def on_duration(name, seconds, **kwargs):
            if name == BACKEND_COMPILE_EVENT:
                backend_compiles.append(seconds)

        def dispatch(n):
            del backend_compiles[:]
            answers = deployed.query_batch(
                [Query(user=f"u{j}", num=4) for j in range(n)])
            assert len(answers) == n
            assert all(a.item_scores for a in answers)
            return len(backend_compiles)

        rec = recorder()
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            # width 1: whatever is built once a model (the cached allow
            # vector) is built here
            dispatch(1)
            rec.reset()
            assert dispatch(3) == 1, rec.recompile_table()      # padB 4
            assert rec.compiles_by_fn() == {"recommend_topk_rows": 1}
            assert dispatch(4) == 0
            assert dispatch(3) == 0
            assert rec.compiles_by_fn() == {"recommend_topk_rows": 1}
        finally:
            jax_monitoring.unregister_event_duration_listener(on_duration)
            rec.reset()


class TestBatchedRecompileSpan:
    def test_off_menu_width_shows_xla_compile_inside_the_dispatch(
            self, storage, tmp_path, monkeypatch):
        """The batcher binds one ambient trace around ``query_batch``,
        so the sentinel's ``xla_compile`` span — recorded on whatever
        trace is active where the compile happens — reaches every
        request that rode the compiling dispatch (it was silently lost
        on the batched path before: the dispatcher thread had no
        ambient trace)."""
        import time
        from concurrent.futures import Future

        from predictionio_tpu.serving.batcher import _Pending
        from predictionio_tpu.templates.recommendation import Query
        from tests.rec_engine import post_query, start_rec_server

        _train_rec_model(storage, tmp_path, monkeypatch)
        rec = recorder()
        rec.reset()
        server = start_rec_server(storage, tracing=True)
        try:
            # the first answered query ends serving warm-up
            assert post_query(server.port, {"user": "u1", "num": 4})[0] == 200
            # an eval-scale batch (> BATCH_WIDTHS[-1]) handed to the
            # dispatcher as the queue would: off the compiled menu, and
            # not the width the pin test above left in the jit cache
            now = time.perf_counter()
            entries = [_Pending(Query(user=f"u{j}", num=4), Future(), None,
                                None, None, now, Trace("queries.json"))
                       for j in range(N_USERS - 7)]
            server.service.batcher._finish(entries, now)
        finally:
            server.stop()
        assert rec.totals()[2] == 1, rec.recompile_table()
        for e in entries:
            assert e.fut.result(timeout=0).item_scores
            spans = {name: (parent, sid, start, start + dur)
                     for name, parent, sid, start, dur in e.trace.spans()}
            _, dispatch_id, d_start, d_end = spans["batcher.device_dispatch"]
            parent, _, c_start, c_end = spans["xla_compile"]
            assert parent == dispatch_id
            assert d_start <= c_start and c_end <= d_end
            # ... and names the phase that paid it: the launch
            _, _, e_start, e_end = spans["dispatch.enqueue"]
            assert e_start <= c_start and c_end <= e_end
        rec.reset()


# ---------------------------------------------------------------------------
# TRAIN_REPORT (pio train --profile)
# ---------------------------------------------------------------------------


class TestTrainProfile:
    def test_report_schema_cpu_safe(self, storage, tmp_path, monkeypatch):
        """Schema round-trip on the CPU backend: stages carry the
        wall/compile/execute split, MFU and HBM are present-but-null
        with an explicit reason (no fabricated numbers)."""
        monkeypatch.delenv("PIO_DEVICE_PEAK_FLOPS", raising=False)
        recorder().reset()
        monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
        outcome = _run_profiled_train(storage)
        report = outcome.report
        assert report is not None
        # the document is JSON-serializable as written by the CLI
        doc = json.loads(json.dumps(report))
        assert doc["schema"] == "pio.train_report.v1"
        assert doc["status"] == "COMPLETED"
        assert doc["instanceId"] == outcome.instance_id
        for stage in ("read", "prepare", "train", "persist"):
            split = doc["stages"][stage]
            assert set(split) == {"wallSeconds", "compileSeconds",
                                  "executeSeconds"}
            assert split["wallSeconds"] >= split["compileSeconds"]
        # training compiled at least the fused ALS program, and its
        # compile seconds were binned into the train stage
        assert doc["compile"]["totalCompiles"] >= 1
        assert doc["stages"]["train"]["compileSeconds"] > 0
        assert any(row["fn"] == "_als_iterate_fused"
                   for row in doc["compile"]["table"])
        # CPU: no memory_stats, no peak-FLOPs entry -> nulls + reasons
        assert doc["hbm"]["peakBytes"] is None
        assert doc["mfu"] is None
        assert "peak-FLOPs" in doc["mfuReason"] \
            or "cost analysis" in doc["mfuReason"]
        # the human summary renders either way
        assert "MFU n/a" in summarize_train_report(doc)

    def test_mfu_numeric_with_peak_override(self, storage, tmp_path,
                                            monkeypatch):
        """PIO_DEVICE_PEAK_FLOPS gives CPU an honest local peak: the
        executed-FLOPs accounting (cost_analysis × calls) then yields a
        real MFU — the measurement ROADMAP item 1 quotes."""
        monkeypatch.setenv("PIO_DEVICE_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
        recorder().reset()
        outcome = _run_profiled_train(storage)
        report = outcome.report
        assert report["flops"]["executed"] is not None
        assert report["flops"]["executed"] > 0
        assert report["flops"]["peakSource"] == "env"
        assert isinstance(report["mfu"], float) and report["mfu"] > 0
        assert report["mfuReason"] == "ok"
        # the gauge plane picked it up for /metrics
        text = render_metrics(list(train_report_collector()()))
        assert "pio_train_mfu" in text
        assert "pio_train_compile_seconds" in text
        recorder().reset()

    def test_peak_flops_resolution_order(self, monkeypatch):
        monkeypatch.delenv("PIO_DEVICE_PEAK_FLOPS", raising=False)
        assert resolve_peak_flops("TPU v4")[0] == pytest.approx(275e12)
        value, source = resolve_peak_flops("cpu")
        assert value is None and "PIO_DEVICE_PEAK_FLOPS" in source
        monkeypatch.setenv("PIO_DEVICE_PEAK_FLOPS", "not-a-number")
        value, source = resolve_peak_flops("cpu")
        assert value is None  # malformed override degrades, not dies
        monkeypatch.setenv("PIO_DEVICE_PEAK_FLOPS", "2.5e13")
        assert resolve_peak_flops("TPU v4") == (2.5e13, "env")


class TestTrainProfileCli:
    def test_pio_train_profile_writes_report(self, tmp_path, monkeypatch,
                                             capsys):
        """`pio train --profile` end to end: TRAIN_REPORT.json on disk,
        the human summary line printed. Runs the no-jax sample engine —
        zero compiles is a VALID profile (all-null device fields, zero
        compile seconds), which is exactly the CPU-safe contract."""
        from predictionio_tpu.cli.pio import main
        from predictionio_tpu.storage.registry import Storage

        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("PIO_DEVICE_PEAK_FLOPS", raising=False)
        Storage.reset_default()
        try:
            (tmp_path / "engine.json").write_text(json.dumps({
                "id": "prof-cli",
                "engineFactory": "tests.sample_engine.engine_factory",
                "datasource": {"params": {"id": 3, "n_train": 5,
                                          "n_folds": 2}},
                "algorithms": [{"name": "sample",
                                "params": {"id": 0, "mult": 4}}],
            }))
            recorder().reset()
            assert main(["train", "--profile",
                         "--profile-dir", str(tmp_path / "jaxtrace")]) == 0
        finally:
            Storage.reset_default()
        out = capsys.readouterr().out
        assert "Train profile:" in out
        assert "TRAIN_REPORT.json" in out
        # --profile-dir captured a jax.profiler trace (a trace that
        # cannot start fails the command: obs/device.TrainProfiler)
        assert (tmp_path / "jaxtrace").is_dir()
        report = json.loads((tmp_path / "TRAIN_REPORT.json").read_text())
        assert report["schema"] == "pio.train_report.v1"
        assert report["status"] == "COMPLETED"
        assert set(report["stages"]) >= {"read", "prepare", "train",
                                         "persist"}
        assert report["compile"]["totalCompiles"] == 0
        assert report["mfu"] is None and report["mfuReason"]


def _run_profiled_train(storage):
    app_id = storage.get_meta_data_apps().insert(App(0, "ProfApp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(11)
    for u in range(20):
        for i in range(12):
            if rng.random() < 0.5:
                events.insert(
                    Event(event="rate", entity_type="user",
                          entity_id=f"u{u}", target_entity_type="item",
                          target_entity_id=f"i{i}",
                          properties=DataMap({"rating": 4.0})), app_id)
    variant = {
        "id": "prof",
        "engineFactory":
            "predictionio_tpu.templates.recommendation.engine_factory",
        "datasource": {"params": {"app_name": "ProfApp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 3, "num_iterations": 2,
                                   "lambda_": 0.05, "seed": 2}}],
    }
    return run_train(variant=variant, storage=storage,
                     profiler=TrainProfiler())
