"""The Laguna cell (``laguna_s21_l5_seqrec.serve_history16k_swa``, a
cell of the ``serve_seq_ref_open`` kind), checked on the CPU: the cell's
files and the published widths, the cut's arithmetic, its cost module by
hand and at the published keys, what it makes of a window's counters,
its per-layer metrics from a synthetic trace (the five it brings and the
five lists it joins), and the kind run end to end on a toy configuration
from a rehearsal directory of its own (``rehearsal_laguna/``). Nothing
here is a measurement."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

from benchmarks.harness import costs_laguna, seq_ref_data  # noqa: E402
from benchmarks.harness.manifest import load_cell, load_json  # noqa: E402
from benchmarks.readers import read_metric  # noqa: E402
from benchmarks.reference import laguna_jnp  # noqa: E402

import test_benchmark_harness as harness_rules  # noqa: E402
import test_nemotron_h_kind as nemotron  # noqa: E402
import test_seq_ref_kind as deepseek  # noqa: E402

CELL = "laguna_s21_l5_seqrec.serve_history16k_swa"
NEW = ("laguna_forward_device_ms", "laguna_forward_mfu",
       "window_attention_device_ms", "window_attention_roofline",
       "window_keys_scored_per_position")
JOINED = ("routed_experts_device_ms", "routed_experts_roofline",
          "routed_assignments_per_token", "expert_load_max_over_mean",
          "moe_rows_device_ms")
REDUCED = ["num_hidden_layers", "layer_types",
           "num_attention_heads_per_layer", "mlp_layer_types",
           "gating_types", "num_experts", "vocab_size"]
#: Laguna-S-2.1's published ``config.json`` (the configuration's
#: ``source``): the numbers and groups, the 48-entry lists by their rule
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention" if i % 4 == 0 else "sliding_attention"
                    for i in range(48)],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48 if i % 4 == 0 else 72
                                      for i in range(48)],
    "moe_router_logit_softcapping": 0}


# -- the cell's files --------------------------------------------------------

def test_configuration_keeps_the_published_keys():
    config = load_cell(CELL).config
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) == set(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    # published layers 0-4: the leading dense full layer and one period,
    # sliding x 3 then full, of expert layers
    first, last = config["layers_held"]
    assert (first, last) == (0, 4) and config["num_hidden_layers"] == 5
    for key in ("layer_types", "num_attention_heads_per_layer",
                "mlp_layer_types", "gating_types"):
        assert config[key] == PUBLISHED[key][first:last + 1], key
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert PUBLISHED["layer_types"][5:9] == config["layer_types"][1:5]
    assert config["experts_held"] == [0, 64] and config["num_experts"] == 64
    assert config["vocab_size"] == 25088 == 100352 // 4 == config["items"] + 1
    assert config["history_len"] == 16384 and config["users"] == 4096
    for key in ("assumed", "deployment", "guarantees"):
        assert config[key]
    for what in ("per-head gate", "qk-norm", "positions", "window",
                 "router", "experts", "weights", "precision"):
        assert config["assumed"][what], what
    assert "four chips" in config["deployment"]
    params = seq_ref_data.algorithm_params(config)
    w = params.laguna
    assert (params.backbone, params.d_model, params.n_heads,
            params.n_kv_heads, params.head_dim, params.d_ff, params.n_layers,
            params.max_len, params.rms_eps, params.tie_embeddings) == \
        ("laguna", 3072, 48, 8, 128, 12288, 5, 16384, 1e-6, False)
    assert w.layer_types == tuple(config["layer_types"])
    assert w.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
    assert (w.sliding_window, w.moe_intermediate_size,
            w.shared_expert_intermediate_size, w.num_experts,
            w.experts_held, w.num_experts_per_tok,
            w.moe_routed_scaling_factor) == \
        (512, 1024, 1024, 256, (0, 64), 10, 2.5)
    full = PUBLISHED["rope_parameters"]["full_attention"]
    assert (w.full_rope_theta, w.full_rope_scaling.factor,
            w.full_rope_scaling.original_max_position_embeddings,
            w.full_rope_scaling.beta_fast, w.full_rope_scaling.beta_slow,
            w.full_attention_factor, w.full_partial_rotary_factor) == \
        (500000, 128, 8192, 32, 1, full["attention_factor"], 0.5)
    assert (w.sliding_rope_theta, w.sliding_partial_rotary_factor) == \
        (10000, 1)
    assert seq_ref_data.reference(config) is laguna_jnp
    assert seq_ref_data.costs(config) is costs_laguna
    assert set(config["counters"].values()) == {
        "seqMoeAssignments", "seqMoeTokens", "seqMoeMaxExpertLoad",
        "seqWindowRows", "seqWindowKeysScored"}
    cfg = laguna_jnp.widths(config)
    assert (cfg["first"], cfg["held"], cfg["top_k"], cfg["scaling"],
            cfg["window"], cfg["kv"], cfg["d"], cfg["eps"]) == \
        (0, 64, 10, 2.5, 512, 8, 128, 1e-6)
    assert not any(cfg[k] for k in ("no_window", "no_gate", "full_rotary"))


def test_the_cut_is_six_gigabytes_of_weights():
    """The cut's table (PERF.md section 4): every width as published,
    layer 0 and four expert layers with 64 of 256 experts, both tables
    at 25,088 rows."""
    config = load_cell(CELL).config
    c = costs_laguna
    assert c.attention_params(config, 48) == 44_187_648             # 44.19M
    assert c.attention_params(config, 72) == 63_135_744             # 63.14M
    assert c.dense_params(config) == 113_246_208                    # 113.25M
    assert c.expert_params(config) == 9_437_184                     # 9.44M
    assert round((c.attention_params(config, 48) + c.dense_params(config))
                 / 1e6, 2) == 157.43
    sliding = c.attention_params(config, 72) + 64 * c.expert_params(
        config) + c.expert_layer_dense_params(config)
    assert round(sliding / 1e6, 2) == 677.34
    held = c.held_params(config)
    assert round(held / 1e6, 2) == 3001.98
    assert round(2 * held / 1e9, 2) == 6.00 and 2 * held / 16e9 > 0.25
    # the whole model at 48 layers and 256 experts: the family's 118B
    whole = {**config, **config["published"], "published": {}}
    assert round(c.held_params(whole) / 1e9, 1) == 117.6


def test_the_manifest_gained_one_cell_its_metrics_and_five_lists():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness_rules.lint_keys_and_names(manifest)
    harness_rules.lint_every_cell_finds_its_files(manifest, ROOT)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "query_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json"))
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
        assert deepseek.CELL in by_name[name]["workloads"]
    assert by_name["moe_rows_device_ms"]["workloads"] == [
        nemotron.CELL, deepseek.CELL, CELL]
    assert [m["name"] for m in manifest["per_layer"][-len(NEW):]] == \
        list(NEW)
    assert {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())} == set(NEW) | set(JOINED)
    cell = load_cell(CELL)
    listless = [m for m in manifest["per_layer"] if "workloads" not in m]
    assert len(cell.per_layer) == len(listless) + len(NEW) + len(JOINED)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_seq_ref_open"
    assert {e["name"] for e in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    assert (cell.traffic["trace_seconds"], cell.traffic["timeout_s"],
            cell.traffic["pool"], cell.traffic["generators"],
            cell.traffic["connections"], cell.traffic["num"],
            cell.traffic["unknown_share"]) == (20, 30, 1024, 2, 16, 10, 0.02)
    entry = manifest["configs"][-1]
    assert entry["name"] == "laguna_s21_l5_seqrec"
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cell.config["source"]
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert len(manifest["workloads"]) == 7
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]
    for share in ("laguna_forward_mfu", "window_attention_roofline"):
        assert by_name[share]["unit"] == "%"
        assert by_name[share]["source"] == "device_trace"


def test_what_the_stale_line_shadows():
    """``tests/conftest.py``: the accepted row-kernel test stops at its
    line "the metric lists the two MoE cells"; what it asserts behind
    that line is asserted here, with the third."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "moe_rows_device_ms")
    assert entry == {"name": "moe_rows_device_ms", "unit": "ms",
                     "better": "lower", "source": "device_trace",
                     "layer": "kernels", "moves": "query_p50_ms",
                     "workloads": [nemotron.CELL, deepseek.CELL, CELL]}
    assert load_json(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "moe_rows_device_ms.json")) == {
            "reader": "xplane_time", "module": "predict_topk_batch",
            "op": "moe_rows", "per": "module_runs", "scale": 1000.0}
    for cell in (nemotron.CELL, deepseek.CELL, CELL):
        assert "moe_rows_device_ms" in {m["name"]
                                        for m in load_cell(cell).per_layer}
    for cell in ("brumby14b_l4_seqrec.serve_history16k",
                 "minicpm_sala_l8_seqrec.serve_history32k",
                 "als_amazon23_books_r128.serve_steady"):
        assert "moe_rows_device_ms" not in {
            m["name"] for m in load_cell(cell).per_layer}


def test_the_limits_live_in_the_reference_module():
    assert 0 < laguna_jnp.SCORE_TOL < 0.5                # logits ~N(0, 1)
    assert laguna_jnp.RANK_TOL == 2 * laguna_jnp.SCORE_TOL
    assert laguna_jnp.NEAR_TIE > 0 and laguna_jnp.MAX_STEPS == 1
    for lower in ("operands", "softmax", "router", None):
        laguna_jnp.set_lower(lower)


# -- costs -------------------------------------------------------------------

SMALL = {"hidden_size": 8, "num_key_value_heads": 2, "head_dim": 2,
         "layer_types": ["full_attention", "sliding_attention"],
         "num_attention_heads_per_layer": [4, 6],
         "mlp_layer_types": ["dense", "sparse"], "intermediate_size": 16,
         "moe_intermediate_size": 3, "shared_expert_intermediate_size": 5,
         "num_experts": 4, "published": {"num_experts": 16},
         "sliding_window": 10, "vocab_size": 50, "history_len": 40}


def test_costs_by_hand():
    c = costs_laguna
    # q and o 8 x 4 x 2 each, k and v 8 x 2 x 2 each, the gate 8 x 4
    assert c.attention_params(SMALL, 4) == 2 * 64 + 2 * 32 + 32
    assert c.attention_params(SMALL, 6) == 2 * 96 + 2 * 32 + 48
    assert c.dense_params(SMALL) == 3 * 8 * 16
    assert c.expert_params(SMALL) == 3 * 8 * 3
    assert c.expert_layer_dense_params(SMALL) == 3 * 8 * 5 + 8 * 16
    assert c.held_params(SMALL) == 224 + 304 + 384 + (4 * 72 + 248) \
        + 2 * 400
    # the window's pairs: 1 + 2 + ... + 10, then 10 a position
    assert c.window_pairs(40, 10) == 55 + 30 * 10
    assert c.window_pairs(6, 10) == 21
    win = c.window_attention(SMALL, tokens=80)             # two queries
    assert win == {"flops": 4 * 2 * 355 * 2 * 6,
                   "bytes": 2 * 80 * 2 * (2 * 6 + 2 * 2)}
    core = c.attention_core(SMALL, tokens=80)
    assert core == {"flops": 4 * 2 * 80 * 41 / 2 * 4}
    routed = c.routed_experts(SMALL, tokens=80, assignments=100)
    assert routed == {"flops": 2 * 72 * 100,
                      "bytes": 2 * 4 * 72 + 2 * 100 * (16 + 12)}
    fwd = c.forward(SMALL, tokens=80, assignments=100)
    assert fwd["flops"] == 2 * (224 + 304 + 384 + 248) * 80 + core["flops"] \
        + win["flops"] + routed["flops"] + 2 * 50 * 8 * 2
    assert fwd["bytes"] == 2 * c.held_params(SMALL) + 2 * 80 * 8


def test_costs_at_the_published_keys():
    config = load_cell(CELL).config
    c = costs_laguna
    S = 16384
    # the window: 8.26M pairs a layer, 0.304 TFLOP and 0.67 GB, so bound
    # by its flops: 1.55 ms at a v5e's peak
    assert c.window_pairs(S, 512) == 8_257_792
    win = c.window_attention(config, S)
    assert round(win["flops"] / 3 / 1e12, 3) == 0.304
    assert round(win["bytes"] / 3 / 1e9, 2) == 0.67
    assert win["flops"] / 197e12 > win["bytes"] / 819e9
    assert round(win["flops"] / 3 / 197e12 * 1e3, 2) == 1.55
    # the full layers' causal cores 6.60 TFLOP
    assert round(c.attention_core(config, S)["flops"] / 1e12, 2) == 6.60
    # one query at 2.5 held assignments a token and layer: 24.76 TFLOP,
    # two thirds of it attention
    assignments = 2.5 * 4 * S
    fwd = c.forward(config, S, assignments)
    assert round(fwd["flops"] / 1e12, 2) == 24.76
    assert round(c.routed_experts(config, S, assignments)["flops"] / 1e12,
                 2) == 3.09
    projections = 2 * S * (2 * c.attention_params(config, 48)
                           + 3 * c.attention_params(config, 72))
    attention = projections + c.attention_core(config, S)["flops"] \
        + win["flops"]
    assert round(attention / fwd["flops"], 2) == 0.67
    # the sliding layers run as plain causal attention: 4.95 TFLOP a
    # layer, 14.8 in all, which would add 56% to the program
    causal = 3 * 4 * 128 * 72 * S * (S + 1) / 2
    assert round(causal / 3 / 1e12, 2) == 4.95
    assert round(causal / 1e12, 1) == 14.8
    assert round((causal - win["flops"]) / fwd["flops"], 2) == 0.56


def test_window_values_come_from_the_cost_module_the_file_names():
    config = {**SMALL, "costs": "costs_laguna"}
    counters = {"seq_programs": 2, "seq_moe_tokens": 80,
                "seq_moe_assignments": 120, "seq_moe_max_expert_load": 40}
    want = {"moe_assignments_per_program": 60.0,
            "routed_assignments_per_token": 1.5,         # one expert layer
            "expert_load_max_over_mean": 40 * 4 / 120}
    assert costs_laguna.window_values(counters, config) == want
    assert seq_ref_data.window_values(counters, config) == want
    assert costs_laguna.window_values(
        {"seq_programs": 2, "seq_moe_tokens": 0, "seq_moe_assignments": 0},
        config) == {}
    assert costs_laguna.window_values({"seq_programs": 2}, config) == {}


# -- the metrics from a synthetic trace --------------------------------------

def _evidence(config):
    ms = 1_000_000
    ops, modules = [], []
    for run in range(2):                     # two programs, 200 ms each
        t0 = 1000 * ms + run * 3000 * ms
        modules.append(("jit_predict_topk_batch(123)", t0, 200 * ms))
        for layer, kind in enumerate(config["layer_types"]):
            start = t0 + 40 * layer * ms     # 40 ms a layer
            ops.append(("%fusion.1 bf16[16384,9216]", start, 20 * ms))
            if kind == "sliding_attention":
                ops.append((f"%window_attention.{layer} bf16[1,16384,9216]",
                            start + 20 * ms, 4 * ms))
            else:
                ops.append((f"%sparse_block_attention.{layer} bf16[1,16384,"
                            "6144]", start + 20 * ms, 12 * ms))
            if layer:
                for k in range(3):           # gate, up and down
                    ops.append((f"%gmm.{3 * layer + k} bf16[163840,3072]",
                                start + (32 + 2 * k) * ms, 2 * ms))
                ops.append((f"%moe_rows.{layer} f32[163840,1,3072]",
                             start + 38 * ms, 1 * ms))
    ops.append(("%fusion.7 f32[8,128]", 100 * ms, 1 * ms))     # the marker's
    ops.append(("%fusion.7 f32[8,128]", 9000 * ms, 1 * ms))
    ops.sort(key=lambda e: e[1])
    counters = {"seq_programs": 4, "seq_padded_tokens": 4 * 16384,
                "seq_moe_tokens": 4 * 16384,
                "seq_moe_assignments": 4 * 4 * 2.5 * 16384,
                "seq_moe_max_expert_load": 4 * 1000,
                "seq_window_rows": 4 * 3 * 8 * 16384,
                "seq_window_keys_scored": 4 * 3 * 8 * 16384 * 640}
    return {"planes": [{"device": "/device:TPU:0", "ops": ops,
                        "modules": modules}],
            "window_s": 10.0, "config": config,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "counters": counters,
            "values": {"seq_tokens_per_program": 16384.0,
                       **costs_laguna.window_values(counters, config)}}


def test_the_metrics_read_a_synthetic_trace():
    config = load_cell(CELL).config
    ev = _evidence(config)
    got = {name: read_metric(name, ev) for name in NEW + JOINED}
    # 5 x 20 of plain products, 3 window kernels of 4, 2 full of 12, 4 x 3
    # grouped products of 2 and 4 row moves of 1
    assert got["laguna_forward_device_ms"] == pytest.approx(
        100 + 12 + 24 + 24 + 4)
    assert got["window_attention_device_ms"] == pytest.approx(12)
    assert got["routed_experts_device_ms"] == pytest.approx(24)
    assert got["moe_rows_device_ms"] == pytest.approx(4)
    assert got["routed_assignments_per_token"] == pytest.approx(2.5)
    assert got["expert_load_max_over_mean"] == pytest.approx(
        1000 * 4 * 64 / (4 * 2.5 * 16384))
    assert got["window_keys_scored_per_position"] == pytest.approx(640)
    c = costs_laguna
    flops = c.forward(config, 16384.0, 4 * 2.5 * 16384.0)["flops"]
    assert got["laguna_forward_mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.164)
    win = c.window_attention(config, 16384.0)
    assert got["window_attention_roofline"] == pytest.approx(
        100 * win["flops"] / 197e12 / 0.012)
    assert ev["notes"]["window_attention_bound"] == "flops"
    routed = c.routed_experts(config, 16384.0, 4 * 2.5 * 16384.0)
    assert got["routed_experts_roofline"] == pytest.approx(
        100 * routed["flops"] / 197e12 / 0.024)
    for name in ("laguna_forward_mfu", "window_attention_roofline",
                 "routed_experts_roofline"):
        assert 0 < got[name] < 100


def test_the_metrics_find_nothing_without_the_program():
    """On a program without the counters or the ops (the parent commit,
    another cell's program) every reader returns None and the line
    leaves the metric out; none raises."""
    config = load_cell(CELL).config
    ev = _evidence(config)
    ev["planes"][0]["modules"] = [("jit_recommend_topk_rows(1)", 0, 10)]
    ev["counters"] = {"seq_programs": 4, "seq_padded_tokens": 4 * 16384}
    ev["values"] = {"seq_tokens_per_program": 16384.0,
                    **costs_laguna.window_values(ev["counters"], config)}
    assert [read_metric(name, ev) for name in NEW + JOINED] == \
        [None] * (len(NEW) + len(JOINED))


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
    shutil.copytree(os.path.join(HERE, "rehearsal_laguna"), root,
                    dirs_exist_ok=True)
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    procs = {trace: subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         "rehearsal_laguna_tiny.tiny_history160_swa", "--seed", "4000000007",
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
    assert ref["score_tol"] == laguna_jnp.SCORE_TOL
    assert notes["seq_programs"] >= 1
    # every history is 160 events; two expert layers, 8 of 16 experts
    # held, 4 a token: ~2 assignments a token and layer land here
    assert notes["seq_moe_tokens"] == notes["seq_tokens"] > 0
    assert 1.6 < notes["routed_assignments_per_token"] < 2.4
    assert notes["expert_load_max_over_mean"] >= 1
    # one sliding layer over two groups; the plain form's mask lets
    # 15.25 keys a position through a window of 16 over 160 events
    assert notes["seq_window_rows"] == 2 * notes["seq_tokens"]
    assert notes["seq_window_keys_scored"] == \
        notes["seq_window_rows"] * 15.25
    if not trace:
        assert set(line["metrics"]) == {"query_p50_ms", "setup_s"}
        return
    assert {"dispatch_ms", "queue_wait_ms", "serve_window_compiles",
            "serve_hbm_peak_GB", "routed_assignments_per_token",
            "expert_load_max_over_mean",
            "window_keys_scored_per_position"} <= set(line["metrics"])
    assert line["metrics"]["window_keys_scored_per_position"]["value"] == \
        pytest.approx(15.25)
    # no device plane in a CPU trace
    assert not {"window_attention_device_ms", "laguna_forward_mfu",
                "routed_experts_device_ms"} & set(line["metrics"])
