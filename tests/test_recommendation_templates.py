"""ALS template families end-to-end: events -> train -> deploy -> query.

Covers recommendation, similarproduct, and ecommerce templates — the
template-level analogue of the reference's quickstart integration test
(tests/pio_tests/scenarios/quickstart_test.py) run against the in-memory
backend."""

import numpy as np
import pytest

from predictionio_tpu.core.datamap import DataMap
from predictionio_tpu.core.event import Event
from predictionio_tpu.storage.base import App
from predictionio_tpu.workflow.context import EngineContext
from predictionio_tpu.workflow.persistence import load_models
from predictionio_tpu.workflow.train import run_train

N_USERS = 24
N_ITEMS = 16


def _event(event, user, item, props=None):
    return Event(
        event=event,
        entity_type="user",
        entity_id=user,
        target_entity_type="item",
        target_entity_id=item,
        properties=DataMap(props or {}),
    )


@pytest.fixture
def storage(storage):
    """Two taste clusters: even users like even items, odd users odd items."""
    app_id = storage.get_meta_data_apps().insert(App(0, "RecApp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(0)
    for u in range(N_USERS):
        for i in range(N_ITEMS):
            if i % 2 == u % 2 and rng.random() < 0.8:
                events.insert(
                    _event("rate", f"u{u}", f"i{i}", {"rating": 5.0}), app_id
                )
            elif rng.random() < 0.1:
                events.insert(
                    _event("rate", f"u{u}", f"i{i}", {"rating": 1.0}), app_id
                )
        if u % 3 == 0:
            events.insert(_event("buy", f"u{u}", f"i{(u % 2) + 2}"), app_id)
        # view events for similarproduct/ecommerce
        for i in range(N_ITEMS):
            if i % 2 == u % 2 and rng.random() < 0.7:
                events.insert(_event("view", f"u{u}", f"i{i}"), app_id)
    # item categories: low items "alpha", high items "beta"
    for i in range(N_ITEMS):
        events.insert(
            Event(
                event="$set",
                entity_type="item",
                entity_id=f"i{i}",
                properties=DataMap(
                    {"categories": ["alpha" if i < N_ITEMS // 2 else "beta"]}
                ),
            ),
            app_id,
        )
    return storage


REC_VARIANT = {
    "id": "rec",
    "engineFactory": "predictionio_tpu.templates.recommendation.engine_factory",
    "datasource": {"params": {"app_name": "RecApp"}},
    "algorithms": [
        {"name": "als",
         "params": {"rank": 8, "num_iterations": 8, "lambda_": 0.05, "seed": 1}}
    ],
}


class TestRecommendation:
    def test_train_deploy_query(self, storage, monkeypatch, tmp_path):
        from predictionio_tpu.templates.recommendation import Query, engine_factory

        monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
        outcome = run_train(variant=REC_VARIANT, storage=storage)
        assert outcome.status == "COMPLETED"

        engine = engine_factory()
        inst = storage.get_meta_data_engine_instances().get(outcome.instance_id)
        ep = engine.params_from_instance_json(
            inst.data_source_params, inst.preparator_params,
            inst.algorithms_params, inst.serving_params,
        )
        ctx = EngineContext(storage=storage)
        models = engine.prepare_deploy(
            ctx, ep, load_models(storage, outcome.instance_id)
        )
        _, _, algos, serving = engine.make_components(ep)

        q = Query(user="u0", num=5)
        result = serving.serve(q, [a.predict(m, q) for a, m in zip(algos, models)])
        assert 0 < len(result.item_scores) <= 5
        # u0 likes even items: the top recommendation should be even
        top = result.item_scores[0].item
        assert int(top[1:]) % 2 == 0
        # unknown user -> empty result (reference behavior)
        q2 = Query(user="stranger", num=5)
        r2 = serving.serve(q2, [a.predict(m, q2) for a, m in zip(algos, models)])
        assert r2.item_scores == ()

    def test_eval_precision(self, storage):
        from predictionio_tpu.templates.recommendation import engine_factory

        engine = engine_factory()
        variant = {
            **REC_VARIANT,
            "datasource": {"params": {"app_name": "RecApp", "eval_k": 2}},
        }
        ep = engine.params_from_variant_json(variant)
        results = engine.eval(EngineContext(storage=storage), ep)
        assert len(results) == 2
        for ei, fold in results:
            assert len(fold) > 0
            for q, p, a in fold:
                assert isinstance(a, tuple)

    def test_batch_predict_matches_predict(self, storage):
        from predictionio_tpu.templates.recommendation import (
            ALSAlgorithm, ALSPreparator, Query, RecommendationDataSource,
        )

        ctx = EngineContext(storage=storage)
        ds = RecommendationDataSource.__new__(RecommendationDataSource)
        from predictionio_tpu.templates.recommendation import DataSourceParams

        ds.params = DataSourceParams(app_name="RecApp")
        td = ds.read_training(ctx)
        pd = ALSPreparator().prepare(ctx, td)
        algo = ALSAlgorithm.__new__(ALSAlgorithm)
        from predictionio_tpu.templates.recommendation import ALSAlgorithmParams

        algo.params = ALSAlgorithmParams(rank=6, num_iterations=6, seed=2)
        model = algo.train(ctx, pd)
        queries = [(0, Query(user="u1", num=4)), (1, Query(user="nope", num=4)),
                   (2, Query(user="u2", num=4))]
        batch = dict(algo.batch_predict(model, queries))
        assert batch[1].item_scores == ()
        single = algo.predict(model, Query(user="u1", num=4))
        assert [s.item for s in batch[0].item_scores] == [
            s.item for s in single.item_scores
        ]

        # heterogeneous per-query num: the batch computes one
        # menu-ized top_k width (k/num are serving-client-controlled
        # and static jit args — r5 micro-batcher hardening) but each
        # query still gets exactly its own count back
        mixed = dict(algo.batch_predict(model, [
            (0, Query(user="u1", num=2)), (1, Query(user="u2", num=5))]))
        assert len(mixed[0].item_scores) == 2
        assert len(mixed[1].item_scores) == 5
        assert [s.item for s in mixed[0].item_scores] == [
            s.item for s in single.item_scores[:2]]


class TestSimilarProduct:
    VARIANT = {
        "id": "sim",
        "engineFactory": "predictionio_tpu.templates.similarproduct.engine_factory",
        "datasource": {"params": {"app_name": "RecApp"}},
        "algorithms": [
            {"name": "als",
             "params": {"rank": 8, "num_iterations": 10, "alpha": 5.0, "seed": 1}}
        ],
    }

    def test_train_and_query(self, storage, monkeypatch, tmp_path):
        from predictionio_tpu.templates.similarproduct import Query, engine_factory

        monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
        outcome = run_train(variant=self.VARIANT, storage=storage)
        assert outcome.status == "COMPLETED"

        engine = engine_factory()
        inst = storage.get_meta_data_engine_instances().get(outcome.instance_id)
        ep = engine.params_from_instance_json(
            inst.data_source_params, inst.preparator_params,
            inst.algorithms_params, inst.serving_params,
        )
        ctx = EngineContext(storage=storage)
        models = engine.prepare_deploy(
            ctx, ep, load_models(storage, outcome.instance_id)
        )
        _, _, algos, _ = engine.make_components(ep)
        algo, model = algos[0], models[0]

        # items co-viewed by the same user group should rank as similar:
        # i0 (even group) -> top similars should be even items
        result = algo.predict(model, Query(items=("i0",), num=4))
        assert len(result.item_scores) == 4
        evens = [s for s in result.item_scores if int(s.item[1:]) % 2 == 0]
        assert len(evens) >= 3
        assert all(s.item != "i0" for s in result.item_scores)

    def test_category_and_list_filters(self, storage):
        from predictionio_tpu.templates.similarproduct import (
            Query, engine_factory,
        )

        engine = engine_factory()
        ep = engine.params_from_variant_json(self.VARIANT)
        ctx = EngineContext(storage=storage)
        tr = engine.train(ctx, ep)
        _, _, algos, _ = engine.make_components(ep)
        algo, model = algos[0], tr.models[0]
        from predictionio_tpu.templates.similarproduct import Query

        r = algo.predict(model, Query(items=("i0",), num=6, categories=("alpha",)))
        assert all(int(s.item[1:]) < N_ITEMS // 2 for s in r.item_scores)
        r2 = algo.predict(
            model, Query(items=("i0",), num=6, white_list=("i2", "i4"))
        )
        assert {s.item for s in r2.item_scores} <= {"i2", "i4"}
        r3 = algo.predict(
            model, Query(items=("i0",), num=6, black_list=("i2",))
        )
        assert all(s.item != "i2" for s in r3.item_scores)


class TestECommerce:
    VARIANT = {
        "id": "ecomm",
        "engineFactory": "predictionio_tpu.templates.ecommerce.engine_factory",
        "datasource": {"params": {"app_name": "RecApp"}},
        "algorithms": [
            {"name": "ecomm",
             "params": {"app_name": "RecApp", "rank": 8, "num_iterations": 10,
                         "alpha": 5.0, "seed": 1}}
        ],
    }

    def _trained(self, storage):
        from predictionio_tpu.templates.ecommerce import engine_factory

        engine = engine_factory()
        ep = engine.params_from_variant_json(self.VARIANT)
        ctx = EngineContext(storage=storage)
        tr = engine.train(ctx, ep)
        _, _, algos, _ = engine.make_components(ep)
        # algo used for predict must be the same instance that trained
        # (it caches ctx for live event reads); re-train on fresh algo
        algo = algos[0]
        model = algo.train(ctx, engine.make_components(ep)[1].prepare(
            ctx, engine.make_components(ep)[0].read_training(ctx)))
        return algo, model

    def test_known_user_filters(self, storage):
        from predictionio_tpu.templates.ecommerce import Query

        algo, model = self._trained(storage)
        r = algo.predict(model, Query(user="u0", num=5))
        assert 0 < len(r.item_scores) <= 5
        # category filter
        r2 = algo.predict(model, Query(user="u0", num=5, categories=("beta",)))
        assert all(int(s.item[1:]) >= N_ITEMS // 2 for s in r2.item_scores)

    def test_unavailable_items_filtered_live(self, storage):
        from predictionio_tpu.templates.ecommerce import Query

        algo, model = self._trained(storage)
        r1 = algo.predict(model, Query(user="u0", num=3))
        top = r1.item_scores[0].item
        # mark the top item unavailable via a live constraint $set
        app = storage.get_meta_data_apps().get_by_name("RecApp")
        storage.get_events().insert(
            Event(
                event="$set",
                entity_type="constraint",
                entity_id="unavailableItems",
                properties=DataMap({"items": [top]}),
            ),
            app.id,
        )
        r2 = algo.predict(model, Query(user="u0", num=3))
        assert all(s.item != top for s in r2.item_scores)

    def test_unknown_user_recent_views_fallback(self, storage):
        from predictionio_tpu.templates.ecommerce import Query

        algo, model = self._trained(storage)
        app = storage.get_meta_data_apps().get_by_name("RecApp")
        # a brand-new user views two even items -> similar-items fallback
        for item in ("i0", "i2"):
            storage.get_events().insert(_event("view", "newbie", item), app.id)
        r = algo.predict(model, Query(user="newbie", num=4))
        assert len(r.item_scores) > 0
        evens = [s for s in r.item_scores if int(s.item[1:]) % 2 == 0]
        assert len(evens) >= len(r.item_scores) - 1
        # no history at all -> empty
        r2 = algo.predict(model, Query(user="ghost", num=4))
        assert r2.item_scores == ()


class TestTemplateEvaluations:
    """The per-template Evaluation classes (role of the reference
    templates' Evaluation.scala) run through the real eval workflow."""

    def test_recommendation_precision_eval(self, storage, tmp_path):
        from predictionio_tpu.controller import EngineParams, EngineParamsGenerator
        from predictionio_tpu.templates.recommendation import (
            ALSAlgorithmParams,
            DataSourceParams,
            RecommendationEvaluation,
        )
        from predictionio_tpu.workflow.evaluation import run_evaluation

        generator = EngineParamsGenerator([
            EngineParams.of(
                data_source=DataSourceParams(app_name="RecApp", eval_k=2),
                algorithms=[("als", ALSAlgorithmParams(
                    rank=rank, num_iterations=6, lambda_=0.05, seed=3))],
            )
            for rank in (4, 8)
        ])
        outcome = run_evaluation(
            RecommendationEvaluation(k=4, output_path=str(tmp_path / "best.json")),
            generator, storage=storage)
        result = outcome.result
        # even/odd taste clusters are trivially learnable: the best grid
        # point must beat random (8 of 16 items relevant -> ~0.5)
        assert result.best_score.score > 0.5
        assert "Precision@4" in result.metric_header
        assert len(result.engine_params_scores) == 2


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
def test_columnar_rating_rule_equals_row_rule(backend, tmp_path):
    """``ratings_from_columns`` over ``find_columnar`` batches yields
    the triples a per-event loop over ``find`` yields: ``rate`` takes
    its ``rating``, a missing or malformed one drops the row, every
    other event is worth ``buy_rating``, no target no row."""
    from predictionio_tpu.storage.base import EventFilter, StorageClientConfig
    from predictionio_tpu.storage.memory import MemoryStorageClient
    from predictionio_tpu.storage.sqlite import SQLiteStorageClient
    from predictionio_tpu.templates.recommendation import ratings_from_columns

    client = MemoryStorageClient() if backend == "memory" else \
        SQLiteStorageClient(StorageClientConfig(
            properties={"PATH": str(tmp_path / "scan.sqlite")}))
    dao = client.events()
    dao.init(1)
    rng = np.random.default_rng(5)
    events = []
    for j in range(300):
        user, item = f"u{rng.integers(20)}", f"i{rng.integers(30)}"
        kind = rng.integers(6)
        if kind == 0:
            events.append(Event(event="$set", entity_type="user",
                                entity_id=user,
                                properties=DataMap({"segment": j % 7})))
        elif kind == 1:
            events.append(_event("rate", user, item))            # no rating
        elif kind == 2:
            events.append(_event("rate", user, item, {"rating": "n/a"}))
        elif kind == 3:
            events.append(_event("rate", user, item,
                                 {"rating": float(rng.integers(1, 11)) / 2}))
        else:
            events.append(_event(("buy", "view")[kind - 4], user, item))
    try:
        for at in range(0, len(events), 50):
            dao.insert_batch(events[at:at + 50], 1)
        flt = EventFilter(entity_type="user")
        want = []
        for e in dao.find(1, None, flt):
            if e.target_entity_id is None:
                continue
            rating = 4.0
            if e.event == "rate":
                try:
                    rating = float(e.properties.get("rating"))
                except (KeyError, TypeError, ValueError):
                    continue
            want.append((e.entity_id, e.target_entity_id, rating))
        got = []
        for cols in dao.find_columnar(1, None, flt, batch_size=64):
            part = ratings_from_columns(cols, 4.0)
            if part is not None:
                got.extend(zip(part[0], part[1], part[2].tolist()))
    finally:
        client.close()
    assert 100 < len(want) < 300
    assert got == want


def test_map_at_k_metric():
    """MAP@K math on hand-checked cases."""
    from predictionio_tpu.templates.recommendation import (
        ItemScore, MAPAtK, PredictedResult,
    )

    m = MAPAtK(k=3)
    pr = lambda *items: PredictedResult(
        item_scores=tuple(ItemScore(item=i, score=1.0) for i in items))
    # perfect ranking of 2 relevant in top-3: (1/1 + 2/2) / 2 = 1.0
    assert m.calculate_qpa(None, pr("a", "b", "x"), ("a", "b")) == 1.0
    # relevant at ranks 1 and 3: (1/1 + 2/3) / 2 = 0.8333...
    v = m.calculate_qpa(None, pr("a", "x", "b"), ("a", "b"))
    assert abs(v - (1 + 2 / 3) / 2) < 1e-9
    # nothing relevant retrieved -> 0; no ground truth -> None (skip)
    assert m.calculate_qpa(None, pr("x", "y", "z"), ("a",)) == 0.0
    assert m.calculate_qpa(None, pr("a"), ()) is None
    # more relevant than k: denominator is k
    v = m.calculate_qpa(None, pr("a", "b", "c"), ("a", "b", "c", "d", "e"))
    assert v == 1.0


def test_custom_query_white_black_lists(storage, monkeypatch, tmp_path):
    """Reference custom-query variant parity: whiteList restricts the
    candidate set, blackList excludes from it."""
    from predictionio_tpu.templates.recommendation import Query, engine_factory

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    outcome = run_train(variant=REC_VARIANT, storage=storage)
    engine = engine_factory()
    inst = storage.get_meta_data_engine_instances().get(outcome.instance_id)
    ep = engine.params_from_instance_json(
        inst.data_source_params, inst.preparator_params,
        inst.algorithms_params, inst.serving_params,
    )
    ctx = EngineContext(storage=storage)
    models = engine.prepare_deploy(
        ctx, ep, load_models(storage, outcome.instance_id))
    _, _, algos, serving = engine.make_components(ep)
    algo, model = algos[0], models[0]

    q = Query(user="u0", num=4, white_list=("i3", "i7"))
    r = serving.serve(q, [algo.predict(model, q)])
    assert {s.item for s in r.item_scores} <= {"i3", "i7"}
    assert r.item_scores  # at least one candidate survives

    full = serving.serve(
        Query(user="u0", num=4),
        [algo.predict(model, Query(user="u0", num=4))])
    top = full.item_scores[0].item
    qb = Query(user="u0", num=4, black_list=(top,))
    rb = serving.serve(qb, [algo.predict(model, qb)])
    assert all(s.item != top for s in rb.item_scores)
    assert rb.item_scores


def test_similarproduct_and_ecommerce_batch_predict(storage):
    """ShardedAlgorithm contract: every template algorithm must serve
    batch_predict (the eval path) — heterogeneous queries included."""
    from predictionio_tpu.templates import ecommerce, similarproduct
    from predictionio_tpu.workflow.train import run_train
    from predictionio_tpu.workflow.persistence import load_models

    for module, variant, queries in (
        (similarproduct,
         {"id": "sim", "engineFactory":
              "predictionio_tpu.templates.similarproduct.engine_factory",
          "datasource": {"params": {"app_name": "RecApp"}},
          "algorithms": [{"name": "als", "params": {"rank": 8,
                                                    "num_iterations": 5}}]},
         [similarproduct.Query(items=("i1",), num=3),
          similarproduct.Query(items=("i2", "i4"), num=2)]),
        (ecommerce,
         {"id": "ec", "engineFactory":
              "predictionio_tpu.templates.ecommerce.engine_factory",
          "datasource": {"params": {"app_name": "RecApp"}},
          "algorithms": [{"name": "ecomm", "params": {"rank": 8,
                                                      "num_iterations": 5}}]},
         [ecommerce.Query(user="u0", num=3),
          ecommerce.Query(user="u1", num=2, categories=("alpha",))]),
    ):
        outcome = run_train(variant=variant, storage=storage)
        assert outcome.status == "COMPLETED"
        engine = module.engine_factory()
        inst = storage.get_meta_data_engine_instances().get(outcome.instance_id)
        ep = engine.params_from_instance_json(
            inst.data_source_params, inst.preparator_params,
            inst.algorithms_params, inst.serving_params)
        ctx = EngineContext(storage=storage)
        models = engine.prepare_deploy(
            ctx, ep, load_models(storage, outcome.instance_id))
        _, _, algos, _ = engine.make_components(ep)
        results = dict(algos[0].batch_predict(models[0],
                                              list(enumerate(queries))))
        assert set(results) == set(range(len(queries)))
        for qi, q in enumerate(queries):
            single = algos[0].predict(models[0], q)
            assert [s.item for s in results[qi].item_scores] == \
                [s.item for s in single.item_scores]
