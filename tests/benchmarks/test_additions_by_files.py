"""A later PR adds a cell, a configuration and per-layer metrics with
files and manifest entries alone: it may not edit a file that is there,
so no test under the benchmark's paths may pin what it adds. Proven on a
copy of the checkout's ``benchmarks/`` and ``BENCHMARK.json`` with a
fifth configuration and cell laid over it (the DeepSeek-V2 files under
another name), a per-layer metric that lists the new cell, one that
lists no cell, and the new cell joined to a list that is there: the
manifest lint and what ``test_seq_kind.py`` and ``test_seq_ref_kind.py``
hold of their own cells hold of the copy, by the same code."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import test_benchmark_harness as harness_rules
import test_seq_kind as brumby_cell
import test_seq_ref_kind as deepseek_cell
from benchmarks.harness.manifest import load_cell, load_json

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

FIFTH = "fifth_l5_seqrec.serve_fifth"
LISTED, UNLISTED, JOINED = ("fifth_blocks_per_token", "fifth_everywhere",
                            "seq_tokens_per_program")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("fifth")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench = root / "benchmarks"
    source = next(c for c in manifest["configs"]
                  if c["name"] == "deepseekv2_l5_seqrec")
    config = load_json(os.path.join(ROOT, source["file"]))
    config["name"] = "fifth_l5_seqrec"
    (bench / "configs" / "fifth_l5_seqrec.json").write_text(
        json.dumps(config))
    shutil.copy(bench / "traffic" / "serve_history8k.json",
                bench / "traffic" / "serve_fifth.json")
    manifest["configs"].append({
        **source, "name": "fifth_l5_seqrec",
        "file": "benchmarks/configs/fifth_l5_seqrec.json"})
    manifest["workloads"].append({
        "name": FIFTH, "config": "fifth_l5_seqrec", "traffic": "serve_fifth",
        "chips": 1, "why": "what the next model_config PR adds"})
    for name in (LISTED, UNLISTED):
        shutil.copy(bench / "layer_metrics" / "routed_assignments_per_token"
                    ".json", bench / "layer_metrics" / f"{name}.json")
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "model step",
            "moves": "query_p50_ms"})
    manifest["per_layer"][-2]["workloads"] = [FIFTH]
    joined = next(m for m in manifest["per_layer"] if m["name"] == JOINED)
    joined["workloads"].append(FIFTH)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    return str(root)


@pytest.mark.parametrize("rule", [
    lambda root: harness_rules.lint_keys_and_names(
        harness_rules.load_manifest(root)),
    lambda root: harness_rules.lint_every_cell_finds_its_files(
        harness_rules.load_manifest(root), root),
    brumby_cell.als_only_metrics_list_the_als_cells,
    brumby_cell.configuration_keeps_the_published_widths,
    deepseek_cell.configuration_keeps_the_published_keys,
    deepseek_cell.manifest_has_the_cell_and_its_metrics,
], ids=["lint_names", "lint_files", "brumby_manifest", "brumby_configuration",
        "deepseekv2_configuration", "deepseekv2_manifest"])
def test_a_fifth_cell_breaks_no_rule_of_the_cells_that_are_there(copy, rule):
    rule(copy)
    rule(ROOT)


def test_the_fifth_cell_reads_what_it_lists_and_the_others_only_the_unlisted(
        copy):
    names = {m["name"] for m in load_cell(FIFTH, root=copy).per_layer}
    assert {LISTED, UNLISTED, JOINED, "dispatch_ms"} <= names
    assert not {"mla_moe_forward_mfu", "seq_forward_mfu",
                "topk_device_ms"} & names
    assert load_cell(FIFTH, root=copy).traffic["kind"] == "serve_seq_ref_open"
    for w in load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]:
        was = {m["name"] for m in load_cell(w["name"]).per_layer}
        now = {m["name"] for m in load_cell(w["name"], root=copy).per_layer}
        assert now - was == {UNLISTED} and was <= now, w["name"]


def test_the_runner_finds_the_fifth_cell_in_the_copy(copy):
    """``benchmarks/run.py`` in the copy loads the cell's files and its
    kind, and refuses only for want of a TPU."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", FIFTH, "--seed",
         "3300000001", "--seconds", "2", "--trace", "0"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert f"cell {FIFTH} needs a TPU" in proc.stderr
