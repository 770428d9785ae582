"""Run the cell runner end to end on the CPU, on a toy configuration
that lives here and is marked as a rehearsal. A later PR adds a cell the
way this test does: new files and a manifest entry in a copy of
``benchmarks/``, no file edited."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

RUNS = {"open": ("rehearsal_tiny.tiny_open", 0),
        "closed": ("rehearsal_tiny.tiny_closed", 1),
        "fit": ("rehearsal_tiny.tiny_fit", 1)}


def _env():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return env


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}
    shutil.copytree(os.path.join(HERE, "rehearsal"), root, dirs_exist_ok=True)
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    procs = {
        key: subprocess.Popen(
            [sys.executable, "benchmarks/run.py", "--workload", cell,
             "--seed", "5", "--seconds", "2", "--trace", str(trace)],
            cwd=root, env=_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        for key, (cell, trace) in RUNS.items()}
    # the real cell, on a machine without a TPU: it must refuse
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    procs["refused"] = subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         real["workloads"][-1]["name"], "--seed", "5", "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    out = {}
    for key, p in procs.items():
        stdout, stderr = p.communicate(timeout=240)
        out[key] = (p.returncode, stdout, stderr)
    return out


@pytest.mark.parametrize("key", sorted(RUNS))
def test_last_line_is_the_result(results, key):
    rc, stdout, stderr = results[key]
    assert rc == 0, stderr[-2000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "notes"}
    assert line["correct"] is True and line["failed"] == 0, line["notes"]
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
    manifest = json.load(open(os.path.join(HERE, "rehearsal",
                                           "BENCHMARK.json")))
    cell, trace = RUNS[key]
    group = manifest["per_layer" if trace else "end_to_end"]
    allowed = {m["name"] for m in group
               if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    if not trace:
        assert set(line["metrics"]) == allowed
    else:
        # no device plane in a CPU trace: the readers of the device
        # trace found nothing and their metrics are left out
        assert not any(m["source"] == "device_trace"
                       and m["name"] in line["metrics"] for m in group)
        assert any(name.endswith("window_compiles")
                   for name in line["metrics"])


def test_a_real_cell_refuses_to_run_without_a_tpu(results):
    rc, stdout, stderr = results["refused"]
    assert rc != 0
    assert not stdout.strip(), "no result line may be printed"
    assert "needs a TPU" in stderr
