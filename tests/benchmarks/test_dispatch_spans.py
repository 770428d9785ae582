"""The dispatch-split metrics (PR 23): the ``span_self`` reader on plain
dicts, and every span a ``layer_metrics`` file names against what one
traced query through the real recommendation template records."""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

from benchmarks.readers import read_metric, span_self

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAYER_METRICS = os.path.join(ROOT, "benchmarks", "layer_metrics")

NEW_METRICS = ("dispatch_prepare_ms", "dispatch_gather_ms",
               "dispatch_enqueue_ms", "dispatch_device_wait_ms",
               "dispatch_fetch_ms", "dispatch_results_ms", "dispatch_self_ms",
               "batch_hold_ms", "result_wake_ms", "server_spans_ms")

SPEC = {"span": "parent", "children": ["a", "b"], "scale": 1000.0}


@pytest.mark.parametrize("requests,want", [
    # per request: parent minus its named children; the median of those
    ([{"parent": 0.010, "a": 0.004, "b": 0.003},
      {"parent": 0.020, "a": 0.010, "b": 0.009},
      {"parent": 0.030, "a": 0.001}], 3.0),
    # a span that is no child (nested in one, or a sibling) is not taken off
    ([{"parent": 0.010, "a": 0.004, "other": 0.005}], 6.0),
    # requests without the parent (a cache hit) or without any child
    # (another template's batch_predict) are not in the sample
    ([{"a": 0.004}, {"parent": 0.010}, {"parent": 0.010, "b": 0.008}], 2.0),
    # a program that records no child (the parent commit): nothing to read
    ([{"parent": 0.010}, {"parent": 0.012}], None),
    ([], None),
])
def test_span_self_on_plain_dicts(requests, want):
    got = span_self.read(SPEC, {"requests": requests})
    assert got == (want if want is None else pytest.approx(want))


def test_span_self_reads_nothing_from_empty_evidence():
    assert span_self.read(SPEC, {}) is None


@pytest.fixture(scope="module")
def traced_request():
    """Span name -> seconds of ONE traced /queries.json through the real
    template, as ``kinds/serve_open.request_spans`` would sum it."""
    from predictionio_tpu.utils.testing import memory_storage
    from tests.rec_engine import post_query, start_rec_server, train_rec

    storage = memory_storage()
    with pytest.MonkeyPatch.context() as mp, \
            tempfile.TemporaryDirectory() as model_dir:
        train_rec(storage, model_dir, mp)
        server = start_rec_server(storage, tracing=True)
        try:
            assert post_query(server.port, {"user": "u1", "num": 3})[0] == 200
            with server.service.trace_log._lock:
                trace = list(server.service.trace_log._ring)[-1]
        finally:
            server.stop()
    sums: dict[str, float] = {}
    for name, _, _, _, dur in trace.spans():
        sums[name] = sums.get(name, 0.0) + dur
    return sums


def _span_specs() -> dict:
    """metric -> spec of every ``layer_metrics`` file a span reader reads."""
    specs = {}
    for path in sorted(glob.glob(os.path.join(LAYER_METRICS, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] in ("span", "span_self"):
            specs[os.path.basename(path)[:-5]] = spec
    return specs


SPAN_SPECS = _span_specs()


@pytest.mark.parametrize("metric", sorted(SPAN_SPECS))
def test_layer_metric_names_spans_the_program_records(
        traced_request, metric):
    spec = SPAN_SPECS[metric]
    names = spec.get("spans") or [spec["span"], *spec["children"]]
    assert set(names) <= set(traced_request), (metric, sorted(traced_request))


def test_the_ten_new_metrics_are_span_metrics():
    assert set(NEW_METRICS) <= set(SPAN_SPECS)


def test_new_metrics_read_one_traced_request(traced_request):
    """Each new metric has a value on a traced request and they
    reconcile: children + self = the dispatch span; hold inside the
    wait; the request's spans add up to ``server_spans_ms``."""
    ev = {"requests": [traced_request],
          "spans": {k: [v] for k, v in traced_request.items()}}
    got = {name: read_metric(name, ev) for name in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    dispatch_ms = read_metric("dispatch_ms", ev)
    phases = sum(v for k, v in got.items()
                 if k.startswith("dispatch_") and k != "dispatch_self_ms")
    assert phases + got["dispatch_self_ms"] == pytest.approx(dispatch_ms)
    assert got["batch_hold_ms"] <= read_metric("queue_wait_ms", ev) + 1e-6
    assert got["server_spans_ms"] >= dispatch_ms + got["result_wake_ms"]


def test_the_parent_program_reports_no_split():
    """Laid over a program without the new spans (the parent commit in
    the driver's check), the new readers find nothing and do not raise;
    ``server_spans_ms`` sums what is there."""
    old = {"parse": 1e-5, "bind": 1e-5, "codec_key": 1e-5, "encode": 1e-5,
           "batcher.queue_wait": 0.005, "batcher.device_dispatch": 0.012}
    ev = {"requests": [old], "spans": {k: [v] for k, v in old.items()}}
    got = {name: read_metric(name, ev) for name in NEW_METRICS}
    assert got.pop("server_spans_ms") == pytest.approx(17.04)
    assert set(got.values()) == {None}


def test_the_traced_rehearsal_prints_the_new_metrics(tmp_path):
    """The cell runner end to end on the CPU toy, on the rehearsal as
    committed: its manifest lists the ten entries for its serve cells."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copytree(os.path.join(HERE, "rehearsal"), tmp_path,
                    dirs_exist_ok=True)
    with open(tmp_path / "BENCHMARK.json") as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert all("rehearsal_tiny.tiny_closed" in listed[name]["workloads"]
               for name in NEW_METRICS)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "rehearsal_tiny.tiny_closed", "--seed", "3000000019", "--seconds",
         "2", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["notes"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS) <= set(got), sorted(got)
    assert all(got[name] >= 0 for name in NEW_METRICS), got
    assert got["dispatch_self_ms"] < got["dispatch_ms"]
    assert got["server_spans_ms"] > got["dispatch_ms"]
