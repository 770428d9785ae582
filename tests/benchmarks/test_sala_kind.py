"""The MiniCPM-SALA cell (``minicpm_sala_l8_seqrec.serve_history32k``, a
cell of the ``serve_seq_ref_open`` kind), checked on the CPU: the cell's
files, its cost arithmetic by hand and at the published keys, what its
cost module makes of a window's counters, its per-layer metrics from a
synthetic trace, and the kind run end to end on a toy configuration from
a rehearsal directory of its own (``rehearsal_sala/``). Nothing here is a
measurement."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

from benchmarks.harness import costs_sala, seq_ref_data  # noqa: E402
from benchmarks.harness.manifest import load_cell, load_json  # noqa: E402
from benchmarks.readers import read_metric  # noqa: E402
from benchmarks.reference import minicpm_sala_jnp  # noqa: E402

import test_benchmark_harness as harness_rules  # noqa: E402

CELL = "minicpm_sala_l8_seqrec.serve_history32k"
NEW = ("sala_forward_device_ms", "sala_forward_mfu",
       "sparse_attention_device_ms", "sparse_attention_roofline",
       "lightning_attention_device_ms", "lightning_attention_roofline",
       "sparse_blocks_selected_per_token", "sparse_keys_scored_per_selected")
SPARSE_LAYERS = (0, 9, 16, 17, 22, 29, 30, 31)
#: the catalog row's ``config`` (model-configs guide, MiniCPM-SALA)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": ["minicpm4" if i in SPARSE_LAYERS else "lightning-attn"
                    for i in range(32)],
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}


# -- the cell's files --------------------------------------------------------

def test_configuration_keeps_the_published_keys():
    config = load_cell(CELL).config
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) == \
        {"num_hidden_layers", "mixer_types"}
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    # published layers 9-16: a contiguous run in the published ratio
    first, last = config["layers_held"]
    assert config["mixer_types"] == PUBLISHED["mixer_types"][first:last + 1] \
        == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert config["num_hidden_layers"] == len(config["mixer_types"]) == 8
    assert config["items"] + 1 == config["vocab_size"]
    assert config["history_len"] == 4 * config["sparse_config"]["dense_len"]
    for key in ("assumed", "deployment", "guarantees"):
        assert config[key]
    for size in ("sparse_config", "lightning decays", "output gates",
                 "qk_norm_init", "not served"):
        assert config["assumed"][size]
    params = seq_ref_data.algorithm_params(config)
    w = params.sala
    assert (params.backbone, params.d_model, params.n_heads,
            params.n_kv_heads, params.head_dim, params.d_ff, params.n_layers,
            params.max_len, params.rope_theta, params.tie_embeddings) == \
        ("minicpm_sala", 4096, 32, 2, 128, 16384, 8, 32768, 10000, False)
    assert w.mixer_types == tuple(config["mixer_types"])
    assert (w.lightning_nh, w.lightning_nkv, w.lightning_head_dim) == \
        (32, 32, 128)
    assert (w.kernel_size, w.kernel_stride, w.block_size, w.topk,
            w.init_blocks, w.window_size, w.dense_len) == \
        (32, 16, 64, 64, 1, 2048, 8192)
    # c uses the published depth, whatever is held here
    assert (w.scale_emb, w.scale_depth, w.dim_model_base,
            w.published_layers) == (12, 1.4, 256, 32)
    assert w.qk_norm_init == config["qk_norm_init"] > 1
    assert seq_ref_data.reference(config) is minicpm_sala_jnp
    assert seq_ref_data.costs(config) is costs_sala
    assert set(config["counters"].values()) == {
        "seqSparseRows", "seqSparseBlocksSelected", "seqSparseKeysScored"}
    cfg = minicpm_sala_jnp.widths(config)
    assert cfg["c"] == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg["head_scale"] == 1 / 16 and cfg["mixers"] == w.mixer_types


def test_the_manifest_gained_one_cell_and_its_metrics():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness_rules.lint_keys_and_names(manifest)
    harness_rules.lint_every_cell_finds_its_files(manifest, ROOT)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "query_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json"))
    # the cell joins no list that was there
    assert [m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())] == list(NEW)
    cell = load_cell(CELL)
    listless = [m for m in manifest["per_layer"] if "workloads" not in m]
    assert len(cell.per_layer) == len(listless) + len(NEW)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_seq_ref_open"
    assert {e["name"] for e in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    assert cell.traffic["trace_seconds"] == 30 and \
        cell.traffic["timeout_s"] == 30
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "minicpm_sala_l8_seqrec")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert manifest["workloads"][-1]["name"] == CELL
    assert by_name["sala_forward_mfu"]["unit"] == \
        by_name["sparse_attention_roofline"]["unit"] == "%"


def test_the_limits_live_in_the_reference_module():
    assert 0 < minicpm_sala_jnp.SCORE_TOL < 0.06      # logits ~N(0, 0.06)
    assert minicpm_sala_jnp.RANK_TOL == 2 * minicpm_sala_jnp.SCORE_TOL
    assert minicpm_sala_jnp.NEAR_TIE > 0 and minicpm_sala_jnp.MAX_STEPS > 8
    for lower in ("operands", "softmax", "router", None):
        minicpm_sala_jnp.set_lower(lower)       # the names the tool uses


# -- costs -------------------------------------------------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 2, "lightning_nh": 2, "lightning_nkv": 2,
         "lightning_head_dim": 4, "intermediate_size": 12, "vocab_size": 50,
         "history_len": 40,
         "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"],
         "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                           "block_size": 4, "topk": 3, "init_blocks": 1,
                           "window_size": 4, "dense_len": 16}}


def test_costs_by_hand():
    # q, gate, o 8x8 each; k, v 8x4 each; SwiGLU 3 x 8x12
    assert costs_sala.sparse_layer_params(SMALL) == 3 * 64 + 2 * 32 + 288
    assert costs_sala.lightning_layer_params(SMALL) == 5 * 64 + 288
    assert costs_sala.held_params(SMALL) == 544 + 2 * 608 + 2 * 50 * 8
    compressed = keys = 0
    for t in range(40):
        compressed += len([j for j in range(19) if 2 * j + 3 <= t])
        kept = min(t // 4 + 1, 3)
        keys += 4 * (kept - 1) + t % 4 + 1
    assert costs_sala.needed_per_history(SMALL) == \
        {"compressed": compressed, "keys": keys}
    sparse = costs_sala.sparse_attention(SMALL, tokens=80)      # two queries
    assert sparse == {"flops": 2 * 4 * 2 * (compressed + 2 * keys) * 2,
                      "bytes": 2 * 2 * (8 + 4) * 80}
    lightning = costs_sala.lightning_attention(SMALL, tokens=80)
    assert lightning == {"flops": 4 * 16 * 2 * 80 * 2,
                         "bytes": 2 * 4 * 8 * 80 * 2}
    fwd = costs_sala.forward(SMALL, tokens=80)
    assert fwd["flops"] == 2 * (544 + 2 * 608) * 80 + sparse["flops"] \
        + lightning["flops"] + 2 * 50 * 8 * 2
    assert fwd["bytes"] == 2 * costs_sala.held_params(SMALL) + 2 * 80 * 8
    # at or under dense_len a sparse layer is plain causal attention
    dense = {**SMALL, "history_len": 16}
    assert costs_sala.needed_per_history(dense) == \
        {"compressed": 0, "keys": 16 * 17 // 2}


def test_costs_at_the_published_keys_give_the_issues_figures():
    config = load_cell(CELL).config
    assert costs_sala.sparse_layer_params(config) == 253_755_392    # 253.7M
    assert costs_sala.lightning_layer_params(config) == 285_212_672  # 285.2M
    # 2,820M parameters: 5.64 GB at 2 bytes each, 35% of 16 GB
    held = costs_sala.held_params(config)
    assert held == 2_820_472_832 and round(2 * held / 1e9, 2) == 5.64
    S = 32768
    need = costs_sala.needed_per_history(config)
    # a position keeps 59.6 blocks' worth of keys on average
    assert round(need["keys"] / S / 64, 1) == 59.6
    sparse = costs_sala.sparse_attention(config, S)["flops"] / 2
    # stage 2 2.05 TFLOP a layer (4.3 x under the causal 8.8) + stage 1 0.27
    assert round(2 * 2 * 32 * 128 * need["keys"] / 1e12, 2) == 2.05
    assert round(sparse / 1e12, 2) == 2.32
    assert round(costs_sala.lightning_attention(config, S)["flops"] / 6
                 / 1e12, 3) == 0.069
    # one query of 32,768 events: 150.5 TFLOP
    assert round(costs_sala.forward(config, S)["flops"] / 1e12, 1) == 150.5


def test_window_values_come_from_the_cost_module_the_file_names():
    config = {"costs": "costs_sala", "sparse_config": {"block_size": 64}}
    counters = {"seq_programs": 2, "seq_sparse_rows": 1000,
                "seq_sparse_blocks_selected": 60_000,
                "seq_sparse_keys_scored": 60_000 * 64 * 4}
    want = {"sparse_blocks_selected_per_token": 60.0,
            "sparse_keys_scored_per_selected": 4.0}
    assert costs_sala.window_values(counters, config) == want
    assert seq_ref_data.window_values(counters, config) == want
    # the dense path ran, or the program has no such counters (the parent)
    assert costs_sala.window_values(
        {"seq_programs": 2, "seq_sparse_rows": 0,
         "seq_sparse_blocks_selected": 0, "seq_sparse_keys_scored": 0},
        config) == {}
    assert costs_sala.window_values({"seq_programs": 2}, config) == {}


# -- the new per-layer metrics from a synthetic trace ------------------------

def _evidence(config):
    ms = 1_000_000
    ops, modules = [], []
    for run in range(2):                     # two programs, 1,200 ms each
        t0 = 1000 * ms + run * 3000 * ms
        modules.append(("jit_predict_topk_batch(123)", t0, 1200 * ms))
        for layer, mixer in enumerate(config["mixer_types"]):
            start = t0 + 150 * layer * ms    # 150 ms a layer
            ops.append(("%fusion.1 bf16[32768,16384]", start, 100 * ms))
            if mixer == "minicpm4":
                ops.append((f"%sparse_block_selection.{layer} f32[1,2,32768,"
                            "512]", start + 100 * ms, 9 * ms))
                ops.append(("%custom-call.5 f32[1,2,32768,64]",
                            start + 109 * ms, 10 * ms))
                ops.append((f"%sparse_block_attention.{layer} bf16[1,32768,"
                            "4096]", start + 119 * ms, 25 * ms))
            else:
                ops.append((f"%while.{layer}", start + 100 * ms, 7 * ms))
                # the scan's body runs inside the while's interval
                ops.append(("%fusion.8 f32[1,32,1,256,128]",
                            start + 101 * ms, 2 * ms))
    ops.append(("%fusion.7 f32[8,128]", 100 * ms, 1 * ms))     # the marker's
    ops.append(("%fusion.7 f32[8,128]", 9000 * ms, 1 * ms))
    ops.sort(key=lambda e: e[1])
    counters = {"seq_programs": 4, "seq_padded_tokens": 4 * 32768,
                "seq_sparse_rows": 4 * 131072,
                "seq_sparse_blocks_selected": 4 * 7_872_512,
                "seq_sparse_keys_scored": 4 * 2_214_592_512}
    return {"planes": [{"device": "/device:TPU:0", "ops": ops,
                        "modules": modules}],
            "window_s": 10.0, "config": config,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "counters": counters,
            "values": {"seq_tokens_per_program": 32768.0,
                       **costs_sala.window_values(counters, config)}}


def test_new_metrics_read_a_synthetic_trace():
    config = load_cell(CELL).config
    ev = _evidence(config)
    got = {name: read_metric(name, ev) for name in NEW}
    assert got["sala_forward_device_ms"] == pytest.approx(
        8 * 100 + 2 * 44 + 6 * 7)
    assert got["sparse_attention_device_ms"] == pytest.approx(2 * (9 + 25))
    assert got["lightning_attention_device_ms"] == pytest.approx(6 * 7)
    assert got["sparse_blocks_selected_per_token"] == pytest.approx(
        7_872_512 / 131072)
    assert got["sparse_keys_scored_per_selected"] == pytest.approx(
        2_214_592_512 / (7_872_512 * 64))
    flops = costs_sala.forward(config, 32768.0)["flops"]
    assert got["sala_forward_mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.930)
    sparse = costs_sala.sparse_attention(config, 32768.0)
    assert got["sparse_attention_roofline"] == pytest.approx(
        100 * sparse["flops"] / 197e12 / 0.068)
    assert ev["notes"]["sparse_attention_bound"] == "flops"
    lightning = costs_sala.lightning_attention(config, 32768.0)
    assert got["lightning_attention_roofline"] == pytest.approx(
        100 * lightning["bytes"] / 819e9 / 0.042)
    assert ev["notes"]["lightning_attention_bound"] == "bytes"
    for name in ("sala_forward_mfu", "sparse_attention_roofline",
                 "lightning_attention_roofline"):
        assert 0 < got[name] < 100


def test_new_metrics_find_nothing_without_the_program():
    """On a program without the selection's counters or the kernels (the
    parent commit) every reader returns None and the line leaves the
    metric out; none raises."""
    config = load_cell(CELL).config
    ev = _evidence(config)
    ev["planes"][0]["modules"] = [("jit_recommend_topk_rows(1)", 0, 10)]
    ev["counters"] = {"seq_programs": 4, "seq_padded_tokens": 4 * 32768}
    ev["values"] = {"seq_tokens_per_program": 32768.0,
                    **costs_sala.window_values(ev["counters"], config)}
    assert [read_metric(name, ev) for name in NEW] == [None] * 8
    ev = _evidence({k: v for k, v in config.items() if k != "costs"})
    assert read_metric("sala_forward_mfu", ev) is None
    assert read_metric("sparse_attention_device_ms", ev) == pytest.approx(68.0)


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
    shutil.copytree(os.path.join(HERE, "rehearsal_sala"), root,
                    dirs_exist_ok=True)
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    procs = {trace: subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         "rehearsal_sala_tiny.tiny_history192", "--seed", "3400000007",
         "--seconds", "3", "--trace", str(trace)],
        cwd=root, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for trace in (0, 1)}
    out = {}
    for trace, p in procs.items():
        stdout, stderr = p.communicate(timeout=400)
        out[trace] = (p.returncode, stdout, stderr)
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_the_kind_runs_the_backbone_on_the_cpu(results, trace):
    rc, stdout, stderr = results[trace]
    assert rc == 0, stderr[-3000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["notes"]
    assert line["attempted"] > 20
    assert line["device"]["platform"] == "cpu"
    notes = line["notes"]
    assert notes["window_compiles"] == 0 and notes["warmed_signatures"] >= 1
    ref = notes["reference"]
    assert ref["checked"] == 3 and ref["score_diff_max"] < ref["score_tol"]
    assert ref["score_tol"] == minicpm_sala_jnp.SCORE_TOL
    assert notes["seq_programs"] >= 1
    # every history is 192 events: positions x 2 sparse layers x 2 groups,
    # a position keeps min(visible, 6) of its 12 blocks: 4.75 on average
    assert notes["seq_sparse_rows"] == 4 * notes["seq_tokens"] > 0
    assert notes["sparse_blocks_selected_per_token"] == pytest.approx(4.75)
    # the plain form scores every visible block
    assert notes["sparse_keys_scored_per_selected"] == pytest.approx(
        6.5 / 4.75)
    if not trace:
        assert set(line["metrics"]) == {"query_p50_ms", "setup_s"}
        return
    assert {"dispatch_ms", "dispatch_prepare_ms", "dispatch_enqueue_ms",
            "dispatch_gather_ms", "dispatch_device_wait_ms",
            "dispatch_fetch_ms", "dispatch_results_ms", "dispatch_self_ms",
            "queue_wait_ms", "batch_hold_ms", "result_wake_ms",
            "server_spans_ms", "http_codec_ms", "batch_size_mean",
            "serve_window_compiles", "serve_hbm_peak_GB", "query_p95_ms",
            "query_p99_ms", "gen_late_p99_ms",
            "sparse_blocks_selected_per_token",
            "sparse_keys_scored_per_selected"} <= set(line["metrics"])
    assert "sparse_attention_device_ms" not in line["metrics"]
