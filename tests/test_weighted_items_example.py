"""Scenario test for examples/ecommerce-weighted-items — the reference's
weighted-items ecommerce variant (examples/
scala-parallel-ecommercerecommendation/weighted-items/): per-item score
weights published live as a $set on the constraint entity
``weightedItems``, re-read per query. Driven through the real train
workflow, the real EVENT server (weights arrive over HTTP like any
event), and the real engine server."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core.datamap import DataMap
from predictionio_tpu.core.event import Event
from predictionio_tpu.storage.base import AccessKey, App
from predictionio_tpu.workflow.context import EngineContext
from predictionio_tpu.workflow.persistence import load_models
from predictionio_tpu.workflow.train import run_train

EXAMPLE_DIR = os.path.join(
    os.path.dirname(__file__), "..", "examples", "ecommerce-weighted-items"
)


@pytest.fixture
def example_engine():
    sys.path.insert(0, EXAMPLE_DIR)
    sys.modules.pop("engine", None)
    try:
        import engine

        yield engine
    finally:
        sys.path.remove(EXAMPLE_DIR)
        sys.modules.pop("engine", None)


@pytest.fixture
def seeded_storage(storage):
    app_id = storage.get_meta_data_apps().insert(App(0, "WeightedEcommApp"))
    storage.get_meta_data_access_keys().insert(
        AccessKey("weighted-key", app_id, []))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(7)
    for u in range(20):
        for i in range(16):
            if i % 2 == u % 2 and rng.random() < 0.85:
                events.insert(
                    Event(event="view", entity_type="user",
                          entity_id=f"u{u}", target_entity_type="item",
                          target_entity_id=f"i{i}", properties=DataMap({})),
                    app_id,
                )
    return storage


def test_unknown_user_cosine_path_is_weighted(example_engine, seeded_storage):
    """The unknown-user fallback ranks by cosine similarity, which
    normalizes a factor-table scaling away — the variant must weight
    the similarity scores instead (reference ALSAlgorithm.scala applies
    weights on BOTH predictKnownUser and predictSimilar)."""
    from predictionio_tpu.core.datamap import DataMap
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.templates.ecommerce import Query

    with open(os.path.join(EXAMPLE_DIR, "engine.json")) as f:
        variant = json.load(f)
    variant["algorithms"][0]["params"]["use_mesh"] = False
    outcome = run_train(variant=variant, storage=seeded_storage)
    eng = example_engine.engine_factory()
    ep = eng.params_from_variant_json(variant)
    ctx = EngineContext(storage=seeded_storage)
    _, _, algos, _ = eng.make_components(ep)
    models = eng.prepare_deploy(
        ctx, ep, load_models(seeded_storage, outcome.instance_id),
        algorithms=algos)
    algo, model = algos[0], models[0]

    app = seeded_storage.get_meta_data_apps().get_by_name("WeightedEcommApp")
    # an unknown user with recent views (the predictSimilar path)
    for i in (2, 4):
        seeded_storage.get_events().insert(
            Event(event="view", entity_type="user", entity_id="ghost",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap({})), app.id)

    base = algo.predict(model, Query(user="ghost", num=4))
    assert base.item_scores, "unknown-user fallback returned nothing"
    target = base.item_scores[-1].item
    seeded_storage.get_events().insert(
        Event(event="$set", entity_type="constraint",
              entity_id="weightedItems",
              properties=DataMap({"weights": [
                  {"items": [target], "weight": 50.0}]})), app.id)
    boosted = algo.predict(model, Query(user="ghost", num=4))
    assert boosted.item_scores[0].item == target, (
        target, [(s.item, s.score) for s in boosted.item_scores])


def test_shipped_engine_json_binds(example_engine):
    with open(os.path.join(EXAMPLE_DIR, "engine.json")) as f:
        variant = json.load(f)
    eng = example_engine.engine_factory()
    ep = eng.params_from_variant_json(variant)
    params = ep.algorithm_params_list[0][1]
    assert params.num_iterations == 12
    assert params.weight_constraint_id == "weightedItems"
    assert params.unseen_only is False


def test_live_weights_shift_ranking(example_engine, seeded_storage):
    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.api.event_server import (
        EventServer,
        EventServerConfig,
    )
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    with open(os.path.join(EXAMPLE_DIR, "engine.json")) as f:
        variant = json.load(f)
    variant["algorithms"][0]["params"]["use_mesh"] = False
    outcome = run_train(variant=variant, storage=seeded_storage)
    assert outcome.status == "COMPLETED"

    eng = example_engine.engine_factory()
    ep = eng.params_from_variant_json(variant)
    ctx = EngineContext(storage=seeded_storage)
    # the real deploy wiring: ONE set of algorithm instances for both
    # load_model (which stashes the live-read context) and serving —
    # the round-3 CLI drive caught the split-instance variant dropping
    # the context and silently disabling live constraints
    _, _, algos, serving = eng.make_components(ep)
    models = eng.prepare_deploy(
        ctx, ep, load_models(seeded_storage, outcome.instance_id),
        algorithms=algos)
    algo = algos[0]
    assert isinstance(algo, example_engine.WeightedECommAlgorithm)
    assert algo._ctx is not None, "load_model must receive the serving instances"

    instance = seeded_storage.get_meta_data_engine_instances().get(
        outcome.instance_id)
    engine_srv = EngineServer(
        DeployedEngine(None, instance, algos, serving, models),
        ServerConfig(ip="127.0.0.1", port=0),
    )
    event_srv = EventServer(
        seeded_storage, EventServerConfig(ip="127.0.0.1", port=0))
    engine_srv.start()
    event_srv.start()
    try:
        def query(user="u1", num=6):
            req = urllib.request.Request(
                f"http://127.0.0.1:{engine_srv.port}/queries.json",
                data=json.dumps({"user": user, "num": num}).encode(),
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())["itemScores"]

        base = query()
        assert len(base) >= 3
        # pick a mid-ranked item to promote and remember the scores
        target = base[2]["item"]
        base_scores = {s["item"]: s["score"] for s in base}

        # publish a weights $set THROUGH THE REAL EVENT SERVER (the
        # operator's live control path), promoting the target 5x and
        # demoting the current leader
        leader = base[0]["item"]
        body = json.dumps({
            "event": "$set", "entityType": "constraint",
            "entityId": "weightedItems",
            "properties": {"weights": [
                {"items": [target], "weight": 5.0},
                {"items": [leader], "weight": 0.1},
            ]},
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{event_srv.port}/events.json"
            "?accessKey=weighted-key",
            data=body, headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 201

        # same deployed model, no retrain: the ranking must move
        weighted = query()
        w_scores = {s["item"]: s["score"] for s in weighted}
        assert weighted[0]["item"] == target
        # brute-force recommend scores from a bfloat16 copy of the
        # (scaled) item table: 5 v and v round apart, each by up to 2^-8
        # of an entry, so the fold is exact to about a percent
        assert w_scores[target] == pytest.approx(
            5.0 * base_scores[target], rel=1e-2)
        assert w_scores.get(leader, 0.0) <= 0.101 * base_scores[leader] + 1e-6

        # weights replace (not merge): publishing a neutral set restores
        body = json.dumps({
            "event": "$set", "entityType": "constraint",
            "entityId": "weightedItems",
            "properties": {"weights": []},
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{event_srv.port}/events.json"
            "?accessKey=weighted-key",
            data=body, headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30):
            pass
        restored = query()
        assert {s["item"]: pytest.approx(s["score"], rel=1e-4)
                for s in restored} == base_scores
    finally:
        engine_srv.stop()
        event_srv.stop()


def test_malformed_weight_group_is_skipped_not_fatal(
        example_engine, seeded_storage):
    """A negative or non-numeric weight in one group must not poison the
    serving path (ADVICE r3): the bad group is logged and skipped, valid
    groups in the same event still apply."""
    from predictionio_tpu.templates.ecommerce import Query

    with open(os.path.join(EXAMPLE_DIR, "engine.json")) as f:
        variant = json.load(f)
    variant["algorithms"][0]["params"]["use_mesh"] = False
    outcome = run_train(variant=variant, storage=seeded_storage)
    eng = example_engine.engine_factory()
    ep = eng.params_from_variant_json(variant)
    ctx = EngineContext(storage=seeded_storage)
    _, _, algos, _ = eng.make_components(ep)
    models = eng.prepare_deploy(
        ctx, ep, load_models(seeded_storage, outcome.instance_id),
        algorithms=algos)
    algo, model = algos[0], models[0]

    base = algo.predict(model, Query(user="u1", num=4))
    assert base.item_scores
    target = base.item_scores[-1].item
    app = seeded_storage.get_meta_data_apps().get_by_name("WeightedEcommApp")
    seeded_storage.get_events().insert(
        Event(event="$set", entity_type="constraint",
              entity_id="weightedItems",
              properties=DataMap({"weights": [
                  {"items": ["i0"], "weight": -3.0},       # invalid: skipped
                  {"items": ["i1"], "weight": "heavy"},    # invalid: skipped
                  {"items": ["i2"], "weight": "nan"},      # invalid: skipped
                  "oops",                                  # non-dict: skipped
                  {"items": [target], "weight": 50.0},     # valid: applies
              ]})), app.id)
    boosted = algo.predict(model, Query(user="u1", num=4))
    assert boosted.item_scores, "serving must survive malformed weights"
    assert boosted.item_scores[0].item == target
