"""The benchmark's own arithmetic, checked on the CPU at toy sizes.

Nothing here is a measurement: times from these runs are never read.
"""

from __future__ import annotations

import importlib
import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.harness import costs, serve, stats, traffic as tr, xplane  # noqa: E402
from benchmarks.reference import als_numpy  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


# -- manifest lint -----------------------------------------------------------
# Rules of a checkout at ``root``: ``test_additions_by_files.py`` holds a
# copy with a fifth cell laid over it to the same rules.

def lint_keys_and_names(manifest: dict) -> None:
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def lint_every_cell_finds_its_files(manifest: dict, root: str) -> None:
    bench = os.path.join(root, "benchmarks")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert 2 <= len(cells) <= 24 and len(set(cells)) == len(cells)
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        cfg_file = os.path.join(root, configs[w["config"]]["file"])
        with open(cfg_file) as f:
            config = json.load(f)
        assert not config.get("rehearsal"), "a toy cannot be a cell"
        assert config["source"] == configs[w["config"]]["source"]
        assert config["reduced"] == configs[w["config"]]["reduced"]
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        importlib.import_module(f"benchmarks.kinds.{kind}")

        def here(m):
            return "workloads" not in m or w["name"] in m["workloads"]

        mine = {m["name"] for m in manifest["end_to_end"] if here(m)}
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in manifest["per_layer"] if here(m)]
        assert layer
        for m in layer:
            assert m["moves"] in mine, (w["name"], m["name"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        with open(os.path.join(bench, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        importlib.import_module(f"benchmarks.readers.{spec['reader']}")


def test_manifest_keys_and_names(manifest):
    lint_keys_and_names(manifest)


def test_every_cell_finds_its_files(manifest):
    lint_every_cell_finds_its_files(manifest, ROOT)


def test_one_name_per_layer(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert "\n" not in layer and f"**{layer}**" in perf, layer


# -- metric arithmetic -------------------------------------------------------

@pytest.mark.parametrize("n,want", [(50, 50.0), (100, 90.0), (200, 95.0),
                                    (999, 95.0), (1000, 99.0),
                                    (10000, 99.9)])
def test_tail_percentile_rule(n, want):
    assert stats.tail_percentile(n) == want


def test_percentile_is_nearest_rank():
    a = np.arange(1, 101)
    assert stats.percentile(a, 50) == 50 and stats.percentile(a, 99) == 99
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.spread([9, 10, 11, 10, 10]) == pytest.approx(0.0)
    assert stats.spread([8, 10, 12, 9, 11]) == pytest.approx(0.2)


def test_latency_counts_from_the_due_time():
    rec = {"due": np.array([0.0, 0.1, 0.2, 0.3]),
           "sent": np.array([0.0, 0.15, 0.2, 0.3]),
           "done": np.array([0.01, 0.16, 0.23, 2.3]),
           "status": np.array([200, 200, 200, 0])}
    m = serve.latency_metrics(rec, seconds=1.0)
    assert m["attempted"] == 4 and m["failed"] == 1
    # the request sent 50 ms late is charged its lateness
    assert m["query_p50_ms"] == pytest.approx(30.0)
    # the failed one counts as missing: it sits at the top of the sample
    assert m["query_p99_ms"] == float("inf")
    assert m["served_qps"] == pytest.approx(3.0)
    assert m["gen_late_p99_ms"] == pytest.approx(50.0)
    # the note of each second's percentiles: one slice here, its tail lost
    assert m["slices"] == {"width_s": 1.0, "n": [4], "p50_ms": [30.0],
                           "p95_ms": [None]}


# -- traffic -----------------------------------------------------------------

CFG = {"users": 3000, "items": 700, "ratings": 40000, "power": 1.8}
TRAFFIC = {"rate_qps": 400.0, "pool": 512, "unknown_share": 0.05}


def test_traffic_is_a_function_of_the_seed():
    a, b = tr.make_ratings(CFG, 7), tr.make_ratings(CFG, 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], tr.make_ratings(CFG, 8)[0])
    # full width: every user and every item is rated
    assert len(np.unique(a[0])) == CFG["users"]
    assert len(np.unique(a[1])) == CFG["items"]
    assert np.array_equal(tr.query_pool(CFG, TRAFFIC, 7),
                          tr.query_pool(CFG, TRAFFIC, 7))
    pool = tr.query_pool(CFG, TRAFFIC, 7)
    assert (pool == -1).any() and pool.max() < CFG["users"]
    # heavy raters ask most: the lower half of the ids dominates
    assert (pool[pool >= 0] < CFG["users"] // 2).mean() > 0.6


def test_open_loop_schedule():
    one = tr.arrivals(TRAFFIC, 7, 0, 0.5, 10.0)
    assert np.array_equal(one, tr.arrivals(TRAFFIC, 7, 0, 0.5, 10.0))
    other = tr.arrivals(TRAFFIC, 7, 1, 0.5, 10.0)
    assert not np.array_equal(one[:10], other[:10])
    assert np.all(np.diff(one) > 0) and one[-1] < 10.0
    assert len(one) + len(other) == pytest.approx(4000, rel=0.1)
    burst = dict(TRAFFIC, burst={"on_s": 0.2, "period_s": 1.0})
    due = tr.arrivals(burst, 7, 0, 1.0, 10.0)
    assert len(due) == pytest.approx(4000, rel=0.15)
    assert np.all(due % 1.0 < 0.2 + 1e-9)


# -- trace reduction ---------------------------------------------------------

def test_xplane_reduction_on_plain_tuples():
    ops = [("while", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 50, 40),
           ("copy", 200, 50), ("fusion.1", 400, 100)]
    modules = [("jit_a(1)", 0, 100), ("jit_b(2)", 200, 300)]
    assert xplane.busy_seconds(ops) == pytest.approx(250e-9)
    assert xplane.extent_seconds(ops) == pytest.approx(500e-9)
    own = xplane.self_times(ops)
    assert own["while"] == pytest.approx(30e-9)
    assert own["fusion.1"] == pytest.approx(130e-9)
    assert [e[0] for e in xplane.within(ops, modules, "jit_b")] == \
        ["copy", "fusion.1"]
    assert xplane.top_ops(ops, 1) == [["fusion.1", pytest.approx(130e-9)]]
    gaps = dict(map(tuple, xplane.idle_gaps(
        ops, host_spans=[("batcher.queue_wait", 240, 400)])))
    assert gaps["host:batcher.queue_wait"] == pytest.approx(150e-9)
    assert gaps["after:while"] == pytest.approx(100e-9)


def _plane(runs, idle_ends=True):
    """A plane whose programs run ``runs`` [(start, length) in ms]; each
    is busy for its whole length in two ops. With ``idle_ends`` the
    clock marker comes first and another program's op last, as in a
    window that started and stopped between two programs."""
    ms = 1_000_000
    modules = [("jit_predict_topk_batch(7)", s * ms, d * ms) for s, d in runs]
    ops = [op for s, d in runs for op in (
        ("%fusion.1", s * ms, d * ms // 2),
        ("%fusion.2", s * ms + d * ms // 2, d * ms - d * ms // 2))]
    if idle_ends:
        modules.insert(0, ("jit_benchmark_clock_marker(1)", 0, 1000))
        ops.insert(0, ("%add.1", 0, 1000))
        end = max(s + d for s, d in runs) + 50
        modules.append(("jit_other(2)", end * ms, ms))
        ops.append(("%fusion.7", end * ms, ms))
    return {"device": "/device:TPU:0", "ops": ops, "modules": modules}


WHOLE = [(100, 300), (500, 300), (1200, 300)]


@pytest.mark.parametrize("plane, runs, per_run_ms", [
    # the window started and stopped between programs: every run is whole
    (_plane(WHOLE), 3, 300.0),
    # a program in flight when the profile started: its last 120 ms are
    # the plane's first event, the marker waits behind it
    (_plane([(0, 120)] + WHOLE + [(1600, 1)]), 4, 225.25),
    # a program in flight when it stopped: its first 110 ms come last
    (_plane([(40, 5)] + WHOLE + [(1600, 110)], idle_ends=False), 3, 300.0),
    # both at once, and a window that holds no whole run at all
    (_plane([(0, 120)] + WHOLE + [(1600, 110)], idle_ends=False), 3, 300.0),
    (_plane([(0, 120), (500, 110)], idle_ends=False), 0, None)],
    ids=["cut_nowhere", "cut_at_the_start", "cut_at_the_end", "cut_at_both",
         "no_whole_run"])
def test_device_readers_count_whole_program_runs_only(plane, runs, per_run_ms):
    """A run the profile's edge cut is no run: it goes, with its ops,
    before runs and device time are counted. (In the second case the
    marker is not first, so the cut run touches the extent and goes;
    the 1 ms program after the three is whole and counts.)"""
    from benchmarks.readers import device_events, xplane_time

    spec = {"module": "predict_topk_batch", "per": "module_runs",
            "scale": 1000.0}
    ev = {"planes": [plane]}
    ops, counted = device_events(ev, spec)
    assert counted == runs
    got = xplane_time.read(spec, ev)
    assert got == (None if per_run_ms is None else pytest.approx(per_run_ms))
    if runs:
        assert xplane.busy_seconds(ops) * 1e3 == pytest.approx(
            per_run_ms * runs)
    # a kind whose profile starts and stops around its own work counts
    # every run, the plane's first and last among them
    every = len(xplane.matching(plane["modules"], spec["module"]))
    assert device_events({**ev, "trace_edges": "idle"}, spec)[1] == every


def test_xplane_reduction_on_the_recorded_trace():
    """``data/small_loop.xplane.pb``: three runs of an 8-step jitted
    scan on one TPU v5e (``tools/dump_xplane.py --record``)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small_loop.xplane.pb")
    planes = xplane.load(path)
    assert len(planes) == 1 and planes[0]["device"].startswith("/device:TPU")
    ops, modules = planes[0]["ops"], planes[0]["modules"]
    runs = xplane.matching(modules, "small_loop")
    assert len(runs) == 3
    inside = xplane.within(ops, modules, "small_loop")
    assert inside and len(inside) <= len(ops)
    busy = xplane.busy_seconds(inside)
    assert 0 < busy <= xplane.busy_seconds(modules) * 1.001
    assert busy < xplane.extent_seconds(ops)
    assert sum(xplane.self_times(inside).values()) == pytest.approx(busy)
    assert xplane.idle_gaps(ops)
    # the whole reduction of a traced window, as the kinds call it
    from benchmarks.harness import device
    facts, breakdown = device.traced(planes, 0.05, 0.0)
    assert facts["busy_s"] == pytest.approx(xplane.busy_seconds(ops))
    assert 0 < facts["busy_s"] < facts["window_s"]
    assert 1 <= len(breakdown["device_ops"]) <= 10
    assert all(len(name) <= 100 and " = " not in name
               for name, _ in breakdown["device_ops"])
    assert breakdown["idle_gaps"][0][0].startswith("after:")


def test_per_layer_line_from_the_recorded_trace(manifest):
    """The traced line as a kind builds it, device planes included: the
    readers of the device trace find the recorded program."""
    from benchmarks.harness.manifest import Cell
    from benchmarks.harness.output import per_layer_line

    planes = xplane.load(os.path.join(os.path.dirname(__file__), "data",
                                      "small_loop.xplane.pb"))
    metric = {"name": "topk_device_ms", "unit": "ms"}
    cell = Cell(name="x", chips=1, config={"rank": 8, "items": 256},
                traffic={}, end_to_end=(), per_layer=(
                    metric, {"name": "recommend_topk_roofline", "unit": "%"},
                    {"name": "serve_device_idle", "unit": "%"}))
    # the recorded program stands in for the top-k program
    for p in planes:
        p["modules"] = [("jit_recommend_topk(1)", s, d)
                        for _, s, d in p["modules"]]
    line = json.loads(per_layer_line(
        cell, {"values": {"batch_size_mean": 4.0}, "notes": {}}, planes,
        0.05, 0.0, [], correct=True, attempted=1, failed=0,
        device={"kind": "TPU v5 lite"}, notes={}))
    assert set(line["metrics"]) == {"topk_device_ms", "recommend_topk_roofline",
                                    "serve_device_idle"}
    assert 0 < line["metrics"]["topk_device_ms"]["value"] < 1.0
    assert 99 < line["metrics"]["serve_device_idle"]["value"] < 100
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] == 0.05
    assert len(line["breakdown"]["device_ops"]) >= 1


def test_costs_and_roofline():
    cfg = {"rank": 64, "users": 1000, "items": 500, "ratings": 10000}
    c = costs.als_iteration(cfg)
    assert c["flops"] == 2 * 10000 * (2 * 64 * 64 + 128) + 1500 * 16 * 2 * 4096
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    r = costs.roofline({"flops": 1e12, "bytes": 1e10}, 2.0, peaks)
    assert r["bound"] == "flops" and r["share_pct"] == pytest.approx(50.0)
    r = costs.roofline({"flops": 1e9, "bytes": 1e11}, 4.0, peaks)
    assert r["bound"] == "bytes" and r["share_pct"] == pytest.approx(25.0)


@pytest.mark.parametrize("config, width", [
    ({"rank": 64, "items": 500}, 4),          # a file that does not say
    ({"rank": 64, "items": 500, "score_operand_bytes": 4}, 4),
    ({"rank": 64, "items": 500, "score_operand_bytes": 2}, 2)])
def test_recommend_topk_prices_the_guaranteed_operand_width(config, width):
    """By hand at batch 3: 2 B I K flops; the table once at the width
    the configuration guarantees, a float32 score written and read."""
    c = costs.recommend_topk(config, batch=3.0)
    assert c["flops"] == 2 * 3 * 500 * 64
    assert c["bytes"] == 500 * 64 * width + 2 * 3 * 500 * 4


@pytest.mark.parametrize("path, stated", [
    ("benchmarks/configs/als_amazon23_books_r128.json", 2),
    ("benchmarks/configs/als_ml20m_r32.json", None),
    ("benchmarks/configs/als_ml20m_r32_500k.json", None),
    ("benchmarks/configs/als_ml20m_r200.json", None),
    ("tests/benchmarks/rehearsal/benchmarks/configs/rehearsal_tiny.json",
     None)])
def test_configurations_price_the_width_they_state(path, stated):
    """Books guarantees scores from bfloat16 operands and is priced at
    2 bytes a table entry: 1.2566 GB a dispatch at the mean batch of
    3.7 where the float32 price was 2.383. The ML-20M and rehearsal
    configurations say nothing and price 4."""
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    assert config.get("score_operand_bytes") == stated
    c = costs.recommend_topk(config, batch=3.7)
    assert c["bytes"] == pytest.approx(config["items"] * (
        config["rank"] * (stated or 4) + 2 * 3.7 * 4), rel=1e-12)
    if stated:
        assert "bfloat16 operands" in config["guarantees"]
        assert round(c["bytes"] / 1e9, 4) == 1.2566
        assert round((c["bytes"] + 2 * config["items"] * config["rank"])
                     / 1e9, 3) == 2.383


# -- references against the program ------------------------------------------

def test_serving_reference_against_the_program():
    from predictionio_tpu.ops.topk import recommend_topk

    rng = np.random.default_rng(0)
    user_f = rng.standard_normal((16, 8)).astype(np.float32)
    item_f = rng.standard_normal((300, 8)).astype(np.float32)
    seen = [rng.choice(300, size=5, replace=False) for _ in range(16)]
    cols = np.stack(seen).astype(np.int32)
    vals, idxs = recommend_topk(user_f, item_f, cols,
                                np.ones_like(cols, np.float32),
                                np.ones(300, np.float32), 10)
    scores = als_numpy.reference_scores(item_f, user_f)
    item_norm = als_numpy.item_norms(item_f)
    for j in range(16):
        answer = list(zip(np.asarray(idxs[j]).tolist(),
                          np.asarray(vals[j]).tolist()))

        def check(a):
            return als_numpy.check_answer(
                scores[j], float(np.linalg.norm(user_f[j])), item_norm,
                seen[j], a, 10)

        assert check(answer) is None
        # the worst item in place of the best; a seen item; one too few;
        # the best left out and the rest moved up
        worst = int(np.argmin(scores[j]))
        assert check([(worst, answer[0][1])] + answer[1:]) is not None
        assert "seen" in check([(int(seen[j][0]), answer[0][1])] + answer[1:])
        assert check(answer[:9]) is not None
        masked = scores[j].copy()
        masked[seen[j]] = -np.inf
        eleventh = int(np.argsort(-masked)[10])
        assert "left out" in check(
            answer[1:] + [(eleventh, float(masked[eleventh]))])


def test_training_reference_against_the_program():
    from predictionio_tpu.ops.als import RatingsCOO, als_train

    u, i, v = tr.make_ratings(CFG, 3)
    coo = RatingsCOO(u, i, v, CFG["users"], CFG["items"])
    f = als_train(coo, rank=8, iterations=3, lam=0.01, seed=3)
    user, item = np.asarray(f.user), np.asarray(f.item)
    for row in (0, 5, 699):
        mine = i == row
        res = als_numpy.normal_equation_residual(
            item[row], user[u[mine]], v[mine], 0.01)
        assert res < 3e-2
        # the wrong lambda scaling (plain lambda, not lambda * n) and a
        # perturbed row both miss
        assert als_numpy.normal_equation_residual(
            item[row] * 1.1, user[u[mine]], v[mine], 0.01) > 3e-2
