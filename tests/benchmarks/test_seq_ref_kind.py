"""The ``serve_seq_ref_open`` kind (a session-engine serve cell whose
configuration names its reference, cost module and ``AlgorithmParams``),
checked on the CPU: the new cell's files, its cost arithmetic by hand
and at the published keys, its per-layer metrics from a synthetic trace,
its answer check, and the kind run end to end on a toy configuration
from a rehearsal directory of its own (``rehearsal_seq_ref/``). Nothing
here is a measurement."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

from benchmarks.harness import (  # noqa: E402
    costs_mla_moe, seq_ref_check, seq_ref_data)
from benchmarks.harness.manifest import load_cell, load_json  # noqa: E402
from benchmarks.readers import read_metric  # noqa: E402
from benchmarks.reference import deepseek_v2_jnp  # noqa: E402

CELL = "deepseekv2_l5_seqrec.serve_history8k"
NEW = ("mla_moe_forward_device_ms", "mla_moe_forward_mfu",
       "mla_attention_device_ms", "mla_attention_roofline",
       "routed_experts_device_ms", "routed_experts_roofline",
       "routed_assignments_per_token", "expert_load_max_over_mean")
#: the catalog row's ``config`` (model-configs guide, DeepSeek-V2)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400}


# -- the cell's files --------------------------------------------------------
# What has to hold of this cell in a checkout at ``root``, whatever other
# cells, configurations and metrics the manifest there has:
# ``test_additions_by_files.py`` holds a copy with a fifth cell to the same.

def configuration_keeps_the_published_keys(root: str = ROOT) -> None:
    config = load_cell(CELL, root=root).config
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) == \
        {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 40, 25600)
    assert config["experts_held"] == [0, 40]
    assert config["items"] + 1 == config["vocab_size"]
    for key in ("assumed", "deployment", "guarantees"):
        assert config[key]
    params = seq_ref_data.algorithm_params(config)
    w = params.mla_moe
    assert (params.backbone, params.d_model, params.n_heads, params.d_ff,
            params.n_layers, params.max_len, params.rope_theta) == \
        ("deepseek_v2", 5120, 128, 12288, 5, 8192, 10000)
    # the router keeps its published width, its groups and its top 6
    assert (w.n_routed_experts, w.experts_held, w.num_experts_per_tok,
            w.n_group, w.topk_group) == (160, (0, 40), 6, 8, 3)
    assert (w.q_lora_rank, w.kv_lora_rank, w.qk_nope_head_dim,
            w.qk_rope_head_dim, w.v_head_dim, w.moe_intermediate_size) == \
        (1536, 512, 128, 64, 128, 1536)
    assert w.rope_scaling.factor == 40 and \
        w.rope_scaling.original_max_position_embeddings == 4096
    assert seq_ref_data.reference(config) is deepseek_v2_jnp
    assert seq_ref_data.costs(config) is costs_mla_moe
    assert set(config["counters"].values()) == {
        "seqMoeAssignments", "seqMoeTokens", "seqMoeMaxExpertLoad"}


def manifest_has_the_cell_and_its_metrics(root: str = ROOT) -> None:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "query_p50_ms"
        assert os.path.exists(os.path.join(
            root, "benchmarks", "layer_metrics", name + ".json"))
    cell = load_cell(CELL, root=root)
    listless = [m for m in manifest["per_layer"] if "workloads" not in m]
    own = [m for m in manifest["per_layer"] if CELL in m.get("workloads", ())]
    assert len(cell.per_layer) == len(listless) + len(own)
    assert set(NEW) <= {m["name"] for m in own}
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_seq_ref_open"
    assert {e["name"] for e in cell.end_to_end} == {"query_p50_ms", "setup_s"}


def test_configuration_keeps_the_published_keys():
    configuration_keeps_the_published_keys()


def test_the_manifest_gained_one_cell_and_its_metrics():
    manifest_has_the_cell_and_its_metrics()


# -- the configuration's counters, window values and limits ------------------

def _server(snapshot: dict):
    stats = types.SimpleNamespace(snapshot=lambda: snapshot)
    return types.SimpleNamespace(
        service=types.SimpleNamespace(serving_stats=stats))


@pytest.mark.parametrize("named, extra", [
    ({}, {}),                                       # a file without the key
    ({"seq_moe_tokens": "seqMoeTokens"}, {"seq_moe_tokens": 7}),
    # a name the program's /stats.json lacks (the parent commit) is left out
    ({"seq_moe_tokens": "seqMoeTokens", "blocks": "seqBlocksChosen"},
     {"seq_moe_tokens": 7})])
def test_seq_counters_reads_the_names_the_configuration_lists(named, extra):
    server = _server({"seqPrograms": 3, "seqTokens": 30, "seqPaddedTokens": 40,
                      "seqSplitDispatches": 1, "seqMoeTokens": 7,
                      "seqMoeAssignments": 9})
    dispatch = {"seq_programs": 3, "seq_tokens": 30, "seq_padded_tokens": 40,
                "seq_split_dispatches": 1}
    config = {"counters": named} if named else {}
    assert seq_ref_data.seq_counters(server, config) == {**dispatch, **extra}


def test_window_values_come_from_the_cost_module_the_file_names():
    counters = {"seq_programs": 2, "seq_moe_tokens": 100,
                "seq_moe_assignments": 300, "seq_moe_max_expert_load": 20}
    config = {"costs": "costs_mla_moe", "num_hidden_layers": 3,
              "first_k_dense_replace": 1, "experts_held": [0, 4]}
    want = {"moe_assignments_per_program": 150.0,
            "routed_assignments_per_token": 1.5,
            "expert_load_max_over_mean": 20 * 2 * 4 / 300}
    assert costs_mla_moe.window_values(counters, config) == want
    assert seq_ref_data.window_values(counters, config) == want
    # a cost module without the function, or no module, adds nothing
    assert seq_ref_data.window_values(counters, {"costs": "costs_seq"}) == {}
    assert seq_ref_data.window_values(counters, {}) == {}


def test_a_reference_without_near_ties_is_its_one_resolution():
    plain = types.SimpleNamespace(
        SCORE_TOL=0.1, RANK_TOL=0.2,
        last_logits=lambda weights, history, config: "logits")
    assert list(seq_ref_check.resolutions(plain, None, None, {})) == \
        [("logits", 0.0)]
    asked = {}

    def resolutions(weights, history, config, **limits):
        asked.update(limits)
        return iter(())

    ties = types.SimpleNamespace(NEAR_TIE=0.07, MAX_STEPS=9,
                                 resolutions=resolutions)
    assert list(seq_ref_check.resolutions(ties, None, None, {})) == []
    assert asked == {"near_tie": 0.07, "max_steps": 9}
    # the cell's reference carries the limits PR 31 fitted, and nothing
    # in the check module stands in for them
    assert (deepseek_v2_jnp.SCORE_TOL, deepseek_v2_jnp.RANK_TOL,
            deepseek_v2_jnp.NEAR_TIE, deepseek_v2_jnp.MAX_STEPS) == \
        (0.4, 0.8, 0.1, 400)
    assert not {"SCORE_TOL", "RANK_TOL", "NEAR_TIE", "MAX_STEPS"} \
        & set(vars(seq_ref_check))


# -- costs -------------------------------------------------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
         "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 6,
         "kv_lora_rank": 3, "intermediate_size": 12,
         "moe_intermediate_size": 5, "n_routed_experts": 4,
         "published": {"n_routed_experts": 16}, "n_shared_experts": 2,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "vocab_size": 50, "history_len": 10}


def test_costs_by_hand():
    # q_a 8x6, q_b 6x12, kv_a 8x5, kv_b 3x16, o 8x8
    assert costs_mla_moe.mla_params(SMALL) == 48 + 72 + 40 + 48 + 64
    assert costs_mla_moe.expert_params(SMALL) == 3 * 8 * 5
    # 3 attentions, 1 dense SwiGLU, 2 x (4 held + 2 shared experts, a
    # router of all 16), two tables of 50 rows
    assert costs_mla_moe.held_params(SMALL) == \
        3 * 272 + 3 * 8 * 12 + 2 * (6 * 120 + 8 * 16) + 2 * 50 * 8
    att = costs_mla_moe.mla_attention(SMALL, tokens=20)
    # a query meets 5.5 keys on average; 2 heads x (6 + 4) wide
    assert att == {"flops": 2 * 2 * 10 * 20 * 5.5 * 3,
                   "bytes": 2 * 2 * (12 + 8) * 20 * 3}
    routed = costs_mla_moe.routed_experts(SMALL, tokens=20, assignments=30)
    assert routed == {"flops": 2 * 120 * 30,
                      "bytes": 2 * 2 * 4 * 120 + 2 * 30 * (16 + 20)}
    fwd = costs_mla_moe.forward(SMALL, tokens=20, assignments=30)
    per_token = 3 * 272 + 288 + 2 * (2 * 120 + 128)
    assert fwd["flops"] == 2 * per_token * 20 + att["flops"] \
        + routed["flops"] + 2 * 50 * 8 * 2
    assert fwd["bytes"] == 2 * costs_mla_moe.held_params(SMALL) + 2 * 20 * 8


def test_costs_at_the_published_keys_give_the_issues_figures():
    config = load_cell(CELL).config
    assert costs_mla_moe.mla_params(config) == 149_225_472       # 149.23M
    assert costs_mla_moe.expert_params(config) == 23_592_960     # 23.59M
    # 5,163.9M parameters: 10.33 GB at 2 bytes each, 64.5% of 16 GB
    held = costs_mla_moe.held_params(config)
    assert held == 5_163_909_120 and round(2 * held / 1e9, 2) == 10.33
    # one query of 8,192 events at the expected 1.5 assignments a token
    # and expert layer: 34.5 TFLOP, the core 2.75 a layer
    S = 8192
    fwd = costs_mla_moe.forward(config, S, 1.5 * S * 4)
    assert round(fwd["flops"] / 1e12, 1) == 34.5
    core = costs_mla_moe.mla_attention(config, S)["flops"] / 5
    assert round(core / 1e12, 2) == 2.75
    routed = costs_mla_moe.routed_experts(config, S, 1.5 * S * 4)
    assert round(routed["flops"] / 4 / 1e12, 2) == 0.58


# -- the new per-layer metrics from a synthetic trace ------------------------

ATTENTION_OP = "%mla_flash_attention.{} bf16[1,8192,16384]"
GROUPED_OP = "%gmm.{} bf16[49152,1536]"


def _evidence(config):
    ms = 1_000_000
    ops, modules = [], []
    for run in range(2):                     # two programs, 300 ms each
        t0 = run * 1000 * ms
        modules.append(("jit_predict_topk_batch(123)", t0, 300 * ms))
        for layer in range(5):               # 60 ms a layer
            start = t0 + 60 * layer * ms
            ops.append(("%fusion.1 bf16[8192,24576]", start, 20 * ms))
            ops.append((ATTENTION_OP.format(layer), start + 20 * ms, 25 * ms))
            if layer:
                for n in range(3):           # three grouped products
                    ops.append((GROUPED_OP.format(3 * layer + n),
                                start + (45 + 3 * n) * ms, 2 * ms))
            ops.append(("%fusion.2 bf16[8192,5120]", start + 55 * ms, 5 * ms))
    ops.append(("%fusion.7 f32[8,128]", 5000 * ms, 1 * ms))   # another module
    ops.sort(key=lambda e: e[1])
    counters = {"seq_programs": 4, "seq_padded_tokens": 4 * 8192,
                "seq_moe_tokens": 4 * 8192,
                "seq_moe_assignments": 4 * 49_000,
                "seq_moe_max_expert_load": 4 * 500}
    return {"planes": [{"device": "/device:TPU:0", "ops": ops,
                        "modules": modules}],
            "window_s": 6.0, "config": config,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "counters": counters,
            "values": {"seq_tokens_per_program": 8192.0,
                       **costs_mla_moe.window_values(counters, config)}}


def test_new_metrics_read_a_synthetic_trace():
    config = load_cell(CELL).config
    ev = _evidence(config)
    got = {name: read_metric(name, ev) for name in NEW}
    assert got["mla_moe_forward_device_ms"] == pytest.approx(
        5 * (20 + 25 + 5) + 4 * 6)
    assert got["mla_attention_device_ms"] == pytest.approx(125.0)
    assert got["routed_experts_device_ms"] == pytest.approx(24.0)
    assert got["routed_assignments_per_token"] == pytest.approx(
        49_000 / (8192 * 4))
    assert got["expert_load_max_over_mean"] == pytest.approx(
        500 * 4 * 40 / 49_000)
    flops = costs_mla_moe.forward(config, 8192.0, 49_000.0)["flops"]
    assert got["mla_moe_forward_mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.274)
    core = costs_mla_moe.mla_attention(config, 8192.0)["flops"]
    assert got["mla_attention_roofline"] == pytest.approx(
        100 * core / 197e12 / 0.125)
    assert ev["notes"]["mla_attention_bound"] == "flops"
    routed = costs_mla_moe.routed_experts(config, 8192.0, 49_000.0)
    least = max(routed["flops"] / 197e12, routed["bytes"] / 819e9)
    assert got["routed_experts_roofline"] == pytest.approx(
        100 * least / 0.024)
    for name in ("mla_moe_forward_mfu", "mla_attention_roofline",
                 "routed_experts_roofline"):
        assert 0 < got[name] < 100


def test_new_metrics_find_nothing_without_the_program():
    """On a program without the routed counters or the kernels (the
    parent commit) every reader returns None and the line leaves the
    metric out; none raises."""
    config = load_cell(CELL).config
    ev = _evidence(config)
    ev["planes"][0]["modules"] = [("jit_recommend_topk_rows(1)", 0, 10)]
    ev["counters"] = {"seq_programs": 4, "seq_padded_tokens": 4 * 8192}
    ev["values"] = {"seq_tokens_per_program": 8192.0,
                    **costs_mla_moe.window_values(ev["counters"], config)}
    assert [read_metric(name, ev) for name in NEW] == [None] * 8
    # a configuration that names no cost module reads no share either
    ev = _evidence({k: v for k, v in config.items() if k != "costs"})
    assert read_metric("mla_moe_forward_mfu", ev) is None
    assert read_metric("mla_attention_device_ms", ev) == pytest.approx(125.0)


# -- the answer check --------------------------------------------------------

def test_an_answer_has_to_agree_with_one_resolution_whole():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal(300).astype(np.float32)
    other = logits + rng.standard_normal(300).astype(np.float32) * 1.5
    history = np.array([5, 6, 7, 7, 9], np.int32)

    def top(lg):
        allowed = lg.copy()
        allowed[[0, 5, 6, 7, 9]] = -np.inf
        return [(int(i), float(lg[i]) + 0.01)
                for i in np.argsort(-allowed)[:10]]

    found = [(logits, 0.0), (other, 0.02)]
    why, worst, tried, cost = seq_ref_check.hold_to_resolutions(
        iter(found), history, top(logits), 10, deepseek_v2_jnp)
    assert (why, tried, cost) == (None, 1, 0.0) and worst["score_diff"] < 0.02
    why, worst, tried, cost = seq_ref_check.hold_to_resolutions(
        iter(found), history, top(other), 10, deepseek_v2_jnp)
    assert (why, tried, cost) == (None, 2, 0.02)
    # half of one and half of the other agrees with neither
    mixed = sorted(top(logits)[:5] + [p for p in top(other)
                                      if p[0] not in dict(top(logits)[:5])][:5],
                   key=lambda p: -p[1])
    why, _, tried, cost = seq_ref_check.hold_to_resolutions(
        iter(found), history, mixed, 10, deepseek_v2_jnp)
    assert why is not None and (tried, cost) == (2, -1.0)
    assert seq_ref_check.hold_to_resolutions(
        iter(found[:1]), history, top(other), 10, deepseek_v2_jnp)[0] \
        is not None


@pytest.mark.parametrize("shift, swap, verdict", [
    (0.01, None, None), (0.3, None, None),           # inside 0.4 (0.1 is not)
    (0.5, None, "score off"), (0.0, 150, "differs"),
    (0.0, "history", "history"), (0.0, "short", "items for num")])
def test_check_one_holds_an_answer_to_this_kinds_limits(shift, swap, verdict):
    rng = np.random.default_rng(1)
    logits = rng.standard_normal(300).astype(np.float32)
    history = np.array([5, 6, 7, 7, 9], np.int32)
    allowed = logits.copy()
    allowed[[0, 5, 6, 7, 9]] = -np.inf
    order = np.argsort(-allowed)
    answer = [(int(i), float(logits[i]) + shift) for i in order[:10]]
    if swap == "history":
        answer[0] = (7, answer[0][1])
    elif swap == "short":
        answer = answer[:9]
    elif swap:      # an item ranked far below the tenth, with its own score
        answer[9] = (int(order[swap]), float(logits[order[swap]]))
    def held_to(reference):
        return seq_ref_check.hold_to_resolutions(
            iter([(logits, 0.0)]), history, answer, 10, reference)

    why, worst, tried, _ = held_to(deepseek_v2_jnp)
    assert tried == 1
    assert (why is None) if verdict is None else (verdict in why)
    # the limits are the reference module's: under a tighter module the
    # same answer is held to that one's
    if shift == 0.3:
        assert "score off" in held_to(types.SimpleNamespace(
            SCORE_TOL=0.05, RANK_TOL=0.1))[0]
    if verdict is None:
        assert worst["score_diff"] == pytest.approx(shift, abs=1e-6)


def test_routed_values_are_empty_without_the_counters():
    config = load_cell(CELL).config
    assert costs_mla_moe.window_values({"seq_programs": 3}, config) == {}
    assert costs_mla_moe.window_values(
        {"seq_programs": 3, "seq_moe_tokens": 0, "seq_moe_assignments": 0,
         "seq_moe_max_expert_load": 0}, config) == {}


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
    shutil.copytree(os.path.join(HERE, "rehearsal_seq_ref"), root,
                    dirs_exist_ok=True)
    for rel, content in before.items():
        assert (root / rel).read_bytes() == content, f"{rel} was edited"
    procs = {trace: subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         "rehearsal_deepseekv2_tiny.tiny_history8", "--seed", "2500000007",
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
    ref = notes["reference"]
    assert ref["checked"] == 3 and ref["score_diff_max"] < ref["score_tol"]
    assert ref["resolutions_tried_most"] >= 1
    assert notes["seq_programs"] >= 1
    # every position of every program is routed once; 3 of 16 experts a
    # token and 4 held: 0.75 expected per token and expert layer
    assert notes["seq_moe_tokens"] == notes["seq_padded_tokens"] > 0
    assert 0.4 < notes["routed_assignments_per_token"] < 1.2
    assert notes["expert_load_max_over_mean"] >= 1.0
    if not trace:
        assert set(line["metrics"]) == {"query_p50_ms", "setup_s"}
        return
    # every list-less metric the program can feed on the CPU, and the two
    # counter metrics of the new cell; the device trace has no plane here
    assert {"dispatch_ms", "dispatch_prepare_ms", "dispatch_enqueue_ms",
            "dispatch_gather_ms", "dispatch_device_wait_ms",
            "dispatch_fetch_ms", "dispatch_results_ms", "dispatch_self_ms",
            "queue_wait_ms", "batch_hold_ms", "result_wake_ms",
            "server_spans_ms", "http_codec_ms", "batch_size_mean",
            "serve_window_compiles", "serve_hbm_peak_GB", "query_p95_ms",
            "query_p99_ms", "gen_late_p99_ms", "routed_assignments_per_token",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    assert "mla_attention_device_ms" not in line["metrics"]
