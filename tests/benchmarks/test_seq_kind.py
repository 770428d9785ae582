"""The session-engine serve kind, checked on the CPU: its cost
arithmetic by hand, its per-layer metrics from a synthetic trace, its
answer check, and the kind run end to end on a toy configuration from a
rehearsal directory of its own (``rehearsal_seq/``; the accepted
``rehearsal/`` is not edited). Nothing here is a measurement."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

from benchmarks.harness import costs_seq, seq_check, seq_data  # noqa: E402
from benchmarks.harness.manifest import load_cell, load_json  # noqa: E402
from benchmarks.readers import read_metric  # noqa: E402

CELL = "brumby14b_l4_seqrec.serve_history16k"
NEW = ("seq_forward_device_ms", "seq_forward_mfu",
       "power_retention_device_ms", "power_retention_roofline",
       "seq_tokens_per_program")


# -- costs, by hand at one small shape ---------------------------------------

SMALL = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
         "vocab_size": 50, "history_len": 10}


def test_costs_seq_by_hand():
    # q 8x16, k and v 8x8 each, o 16x8, gate 8x2, SwiGLU 3 x 8x12
    assert costs_seq.layer_matrix_params(SMALL) == 128 + 64 + 64 + 128 + 16 + 288
    # 10 monomials of 4 coordinates: 2*10*4*(4+2) + 2*10*4 flops a token and
    # layer; q, k, v, y: 2 bytes * 4 * (4 + 2 + 2 + 4)
    ret = costs_seq.power_retention(SMALL, tokens=20)
    assert ret == {"flops": (480 + 80) * 20 * 3.0, "bytes": 96 * 20 * 3.0}
    fwd = costs_seq.seq_forward(SMALL, tokens=20)
    assert fwd["flops"] == 2 * 688 * 20 * 3 + ret["flops"] + 2 * 50 * 8 * 2
    assert fwd["bytes"] == 2 * (3 * 688 + 50 * 8) + 2 * 20 * 8


def test_costs_at_the_published_widths():
    config = load_cell(CELL).config
    assert costs_seq.layer_matrix_params(config) == 330_342_400
    per_token_layer = costs_seq.power_retention(config, 1)["flops"] / 4
    assert per_token_layer == 2 * 8256 * 128 * 48 + 2 * 8256 * 40
    # one query of 16,384 events: ~50 TFLOP
    assert 49e12 < costs_seq.seq_forward(config, 16384)["flops"] < 51e12


# -- the new per-layer metrics from a synthetic trace ------------------------

def _evidence(config):
    ms = 1_000_000
    ops, modules = [], []
    for run in range(2):                     # two programs, 400 ms each
        t0 = run * 1000 * ms
        modules.append(("jit_predict_topk_batch(123)", t0, 400 * ms))
        ops.append(("%fusion.1 bf16[16384,5120]", t0, 100 * ms))
        for layer in range(4):               # a 30 ms scan per layer ...
            start = t0 + (100 + 70 * layer) * ms
            ops.append((f"%while.{layer + 4}", start, 30 * ms))
            ops.append(("%fusion.9 f32[1,8,5,256,128]", start, 10 * ms))
            ops.append(("%fusion.2 bf16[16384,17408]", start + 30 * ms, 40 * ms))
    ops.append(("%fusion.7 f32[8,128]", 5000 * ms, 1 * ms))   # another module
    ops.sort(key=lambda e: e[1])
    return {"planes": [{"device": "/device:TPU:0", "ops": ops,
                        "modules": modules}],
            "window_s": 6.0, "config": config,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "counters": {"seq_padded_tokens": 5 * 16384, "seq_programs": 4},
            "values": {"seq_tokens_per_program": 5 * 16384 / 4}}


def test_new_metrics_read_a_synthetic_trace():
    config = load_cell(CELL).config
    ev = _evidence(config)
    got = {name: read_metric(name, ev) for name in NEW}
    # busy inside the module: 100 + 4 * (30 + 40) ms a run
    assert got["seq_forward_device_ms"] == pytest.approx(380.0)
    assert got["power_retention_device_ms"] == pytest.approx(120.0)
    assert got["seq_tokens_per_program"] == pytest.approx(20480.0)
    flops = costs_seq.seq_forward(config, 20480.0)["flops"]
    assert got["seq_forward_mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.380)
    ret = costs_seq.power_retention(config, 20480.0)["flops"]
    assert got["power_retention_roofline"] == pytest.approx(
        100 * ret / 197e12 / 0.120)
    assert ev["notes"]["power_retention_bound"] == "flops"
    assert 0 < got["power_retention_roofline"] < 100
    assert 0 < got["seq_forward_mfu"] < 100


def test_new_metrics_find_nothing_without_the_program():
    """On the parent commit (no counters, no such module) every reader
    returns None and the line leaves the metric out."""
    config = load_cell(CELL).config
    ev = _evidence(config)
    ev["planes"][0]["modules"] = [("jit_recommend_topk_rows(1)", 0, 10)]
    ev["counters"], ev["values"] = {}, {}
    assert [read_metric(name, ev) for name in NEW] == [None] * 5


# What has to hold of this cell in a checkout at ``root``, whatever other
# cells, configurations and metrics the manifest there has:
# ``test_additions_by_files.py`` holds a copy with a fifth cell to the same.

def als_only_metrics_list_the_als_cells(root: str = ROOT) -> None:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("topk_device_ms", "recommend_topk_roofline"):
        assert CELL not in by_name[name]["workloads"]
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
    cell = load_cell(CELL, root=root)
    listless = [m for m in manifest["per_layer"] if "workloads" not in m]
    own = [m for m in manifest["per_layer"] if CELL in m.get("workloads", ())]
    assert len(cell.per_layer) == len(listless) + len(own)
    assert set(NEW) <= {m["name"] for m in own}
    assert cell.traffic["kind"] == "serve_seq_open"
    assert cell.config["reduced"] == ["num_hidden_layers"]


def test_als_only_metrics_list_the_als_cells():
    als_only_metrics_list_the_als_cells()


def test_configuration_keeps_the_published_widths():
    configuration_keeps_the_published_widths()


def configuration_keeps_the_published_widths(root: str = ROOT) -> None:
    config = load_cell(CELL, root=root).config
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 5120,
                 "intermediate_size": 17408, "max_position_embeddings": 32768,
                 "max_window_layers": 40, "model_type": "brumby",
                 "num_attention_heads": 40, "num_hidden_layers": 40,
                 "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000,
                 "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 40}
    assert config["items"] + 1 == config["vocab_size"]
    params = seq_data.algorithm_params(config)
    assert (params.backbone, params.d_model, params.n_kv_heads,
            params.d_ff, params.max_len) == ("brumby", 5120, 8, 17408, 16384)


# -- the answer check --------------------------------------------------------

def test_check_one_holds_an_answer_to_the_reference():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal(300).astype(np.float32)
    history = np.array([5, 6, 7, 7, 9], np.int32)
    allowed = logits.copy()
    allowed[[0, 5, 6, 7, 9]] = -np.inf
    top = np.argsort(-allowed)[:10]
    good = [(int(i), float(logits[i]) + 0.01) for i in top]
    assert seq_check.check_one(logits, history, good, 10)[0] is None
    why, worst = seq_check.check_one(
        logits, history, [(i, s + 0.2) for i, s in good], 10)
    assert "score off" in why and worst["score_diff"] > 0.2
    assert "history" in seq_check.check_one(
        logits, history, [(7, good[0][1])] + good[1:], 10)[0]
    assert "items for num" in seq_check.check_one(
        logits, history, good[:9], 10)[0]
    # an item ranked far below the tenth, carrying the tenth's score
    far = int(np.argsort(-allowed)[150])
    swapped = good[:9] + [(far, float(logits[far]))]
    assert "differs" in seq_check.check_one(logits, history, swapped, 10)[0]


# -- the kind, end to end on the CPU -----------------------------------------

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
    shutil.copytree(os.path.join(HERE, "rehearsal_seq"), root,
                    dirs_exist_ok=True)
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    procs = {trace: subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         "rehearsal_brumby_tiny.tiny_history", "--seed", "2500000007",
         "--seconds", "3", "--trace", str(trace)],
        cwd=root, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for trace in (0, 1)}
    out = {}
    for trace, p in procs.items():
        stdout, stderr = p.communicate(timeout=400)
        out[trace] = (p.returncode, stdout, stderr)
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_the_kind_runs_on_the_cpu(results, trace):
    rc, stdout, stderr = results[trace]
    assert rc == 0, stderr[-3000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["notes"]
    assert line["attempted"] > 50
    assert line["device"]["platform"] == "cpu"
    notes = line["notes"]
    assert notes["window_compiles"] == 0 and notes["warmed_signatures"] >= 2
    assert notes["reference"]["checked"] == 3
    assert notes["reference"]["score_diff_max"] < notes["reference"]["score_tol"]
    assert notes["seq_programs"] >= 1
    # every known user's history is max_len long: no token is padding
    assert notes["seq_padded_tokens"] == notes["seq_tokens"] > 0
    assert notes["seq_padded_tokens"] <= 64 * notes["dispatched_queries"]
    if not trace:
        assert set(line["metrics"]) == {"query_p50_ms", "setup_s"}
        return
    # the spans and counters the ALS cells' readers read are found here
    # too; the device trace has no plane on the CPU
    assert {"dispatch_ms", "dispatch_prepare_ms", "dispatch_enqueue_ms",
            "dispatch_gather_ms", "dispatch_device_wait_ms",
            "dispatch_fetch_ms",
            "dispatch_results_ms", "dispatch_self_ms", "queue_wait_ms",
            "batch_hold_ms", "result_wake_ms", "server_spans_ms",
            "http_codec_ms", "batch_size_mean", "serve_window_compiles",
            "serve_hbm_peak_GB", "query_p95_ms", "query_p99_ms",
            "gen_late_p99_ms", "seq_tokens_per_program"} <= set(line["metrics"])
    assert line["metrics"]["seq_tokens_per_program"]["value"] >= 64
    assert "seq_forward_device_ms" not in line["metrics"]
