"""The seven metrics of PR 36: the ``span_share`` and ``span_minus``
readers on plain dicts, and a traced rehearsal that prints the seven and
leaves every per-layer value it printed before as the same evidence
gives it with the ``dispatch`` traces taken out."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness.manifest import load_json
from benchmarks.readers import read_metric, span_minus, span_share

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAYER_METRICS = os.path.join(ROOT, "benchmarks", "layer_metrics")

SEVEN = ("dispatcher_idle_share", "batch_handoff_ms", "request_read_ms",
         "request_flush_ms", "server_request_ms", "server_unspanned_ms",
         "outside_server_ms")
FOUR = ("dispatcher.idle", "dispatcher.collect", "dispatcher.dispatch",
        "dispatcher.handoff")

CYCLES = {"dispatcher.idle": [0.030, 0.010], "dispatcher.collect": [0.002] * 3,
          "dispatcher.dispatch": [0.010, 0.020, 0.020],
          "dispatcher.handoff": [0.002, 0.001, 0.001]}


@pytest.mark.parametrize("part,spans,want", [
    # the four partition the thread's time: their shares sum to 100
    (["dispatcher.idle"], CYCLES, 40.0),
    (["dispatcher.collect"], CYCLES, 6.0),
    (["dispatcher.dispatch"], CYCLES, 50.0),
    (["dispatcher.handoff"], CYCLES, 4.0),
    (list(FOUR), CYCLES, 100.0),
    # a dispatcher that never waited (a first cycle only): 0, not nothing
    (["dispatcher.idle"], {"dispatcher.collect": [0.002],
                           "dispatcher.dispatch": [0.010]}, 0.0),
    # a program that records none of the four (the parent commit)
    (["dispatcher.idle"], {"batcher.queue_wait": [0.005]}, None),
    (["dispatcher.idle"], {}, None),
])
def test_span_share_on_plain_dicts(part, spans, want):
    spec = {"part": part, "whole": list(FOUR)}
    got = span_share.read(spec, {"spans": spans})
    assert got == (want if want is None else pytest.approx(want))


def test_span_share_reads_nothing_from_empty_evidence():
    assert span_share.read({"part": ["a"], "whole": ["a", "b"]}, {}) is None


REQUESTS = [{"request": 0.010, "a": 0.004, "b": 0.003},
            {"request": 0.020, "a": 0.010, "b": 0.009, "other": 0.0005},
            {"request": 0.030, "a": 0.001},
            # a dispatch record: no ``request``, not in the sample
            {"dispatcher.idle": 0.5, "dispatcher.handoff": 0.001}]


@pytest.mark.parametrize("spec,ev,want", [
    # minus empty: the median of the span per trace that has it
    ({"span": "request", "minus": []}, {"requests": REQUESTS}, 20.0),
    ({"span": "request"}, {"requests": REQUESTS}, 20.0),
    ({"span": "dispatcher.handoff", "minus": []}, {"requests": REQUESTS},
     1.0),
    # per trace the span less the named spans it has: 3, 1, 29
    ({"span": "request", "minus": ["a", "b"]}, {"requests": REQUESTS}, 3.0),
    # minus naming spans no trace has takes nothing off
    ({"span": "request", "minus": ["cache_lookup", "predict"]},
     {"requests": REQUESTS}, 20.0),
    # with value: what is left of the kind's own number
    ({"span": "request", "minus": [], "value": "query_p50_ms"},
     {"requests": REQUESTS, "values": {"query_p50_ms": 21.5}}, 1.5),
    ({"span": "request", "minus": [], "value": "query_p50_ms"},
     {"requests": REQUESTS, "values": {}}, None),
    # evidence without the span (the parent commit's): nothing to read
    ({"span": "request", "minus": ["a"]},
     {"requests": [{"a": 0.004, "batcher.queue_wait": 0.005}]}, None),
    ({"span": "request", "minus": [], "value": "query_p50_ms"},
     {"requests": [{"a": 0.004}], "values": {"query_p50_ms": 21.5}}, None),
    ({"span": "request", "minus": []}, {}, None),
])
def test_span_minus_on_plain_dicts(spec, ev, want):
    got = span_minus.read({"scale": 1000.0, **spec}, ev)
    assert got == (want if want is None else pytest.approx(want))


def test_the_seven_metric_files_name_the_seven_spans():
    named = set()
    for name in SEVEN:
        spec = load_json(os.path.join(LAYER_METRICS, name + ".json"))
        assert spec["reader"] in ("span", "span_share", "span_minus"), name
        named |= set(spec.get("spans", ())) | set(spec.get("part", ())) \
            | set(spec.get("whole", ())) | {spec.get("span")} - {None}
    assert named == set(FOUR) | {"request", "request.read", "request.flush"}
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in SEVEN:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["moves"] == "query_p50_ms"
        assert "workloads" not in listed[name]


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    """(result line, ring, report) of one traced run of the rehearsal cell,
    made through ``tools/ring_report.py`` in a copy of the checkout."""
    root = tmp_path_factory.mktemp("ring")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copytree(os.path.join(HERE, "rehearsal"), root, dirs_exist_ok=True)
    # the rehearsal's manifest is the benchmark's and is not edited: the
    # copy gains the seven entries as the root manifest has them
    manifest = load_json(root / "BENCHMARK.json")
    manifest["per_layer"] += [
        m for m in load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
        if m["name"] in SEVEN]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f, indent=1)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    cell, seed = "rehearsal_tiny.tiny_open", "3600000011"
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/ring_report.py", "out",
         "--workload", cell, "--seed", seed, "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["notes"]
    with open(root / "out" / f"{cell}.{seed}.ring.json") as f:
        doc = json.load(f)
    assert doc["line"] == line
    with open(root / "out" / f"{cell}.{seed}.ring.txt") as f:
        text = f.read()
    return line, doc["traces"], text


def test_the_traced_rehearsal_prints_the_seven(traced_rehearsal):
    line, traces, _ = traced_rehearsal
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SEVEN) <= set(got), sorted(got)
    assert 0 < got["dispatcher_idle_share"] < 100
    assert all(got[name] >= 0 for name in SEVEN if name != "outside_server_ms")
    # the root is the whole of a query inside the server: at least the
    # eight spans server_spans_ms sums, and the two edges besides
    assert got["server_request_ms"] > got["server_spans_ms"]
    assert got["server_request_ms"] + got["outside_server_ms"] == \
        pytest.approx(line["notes"]["percentiles_ms"]["50"])
    assert {t["name"] for t in traces} == {"dispatch", "queries.json"}


def test_what_it_printed_before_is_unmoved_by_the_dispatch_traces(
        traced_rehearsal):
    """Every span metric the line carries, read again from the ring's
    own evidence: with every trace it is what the line says; with the
    ``dispatch`` traces taken out the metrics that were there before
    PR 36 read the same, to the last bit."""
    from benchmarks.tools.ring_report import evidence

    line, traces, _ = traced_rehearsal
    printed = {k: v["value"] for k, v in line["metrics"].items()}
    values = {"query_p50_ms": line["notes"]["percentiles_ms"]["50"]}

    def read_all(kept):
        spans, requests = evidence(kept)
        ev = {"spans": spans, "requests": requests, "values": values}
        out = {}
        for name in printed:
            spec = load_json(os.path.join(LAYER_METRICS, name + ".json"))
            if spec["reader"] in ("span", "span_self", "span_share",
                                  "span_minus"):
                out[name] = read_metric(name, ev)
        return out

    whole = read_all(traces)
    assert len(whole) >= 17 and set(SEVEN) <= set(whole)
    assert whole == {name: printed[name] for name in whole}
    without = read_all([t for t in traces if t["name"] != "dispatch"])
    before = set(whole) - set(SEVEN)
    assert {"server_spans_ms", "dispatch_ms", "dispatch_self_ms",
            "queue_wait_ms", "result_wake_ms"} <= before
    assert {name: without[name] for name in before} == \
        {name: printed[name] for name in before}
    # and the dispatcher's two read nothing without its records
    assert without["dispatcher_idle_share"] is None
    assert without["batch_handoff_ms"] is None


def test_the_report_accounts_for_the_dispatchers_time(traced_rehearsal):
    _, traces, text = traced_rehearsal
    records = [t for t in traces if t["name"] == "dispatch"]
    assert f"dispatch records {len(records)}; the four spans sum to" in text
    apart = float(text.split("apart ")[1].split()[0])
    assert apart < 1e-3
    assert "requests with a root" in text and "the ten longest cycles" in text
