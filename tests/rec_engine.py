"""The real recommendation template, trained small and served from a
batching engine server — what the dispatch-span tests drive (the sample
engine's ``batch_predict`` records no ``dispatch.*`` phase)."""

from __future__ import annotations

import json
import urllib.request

import numpy as np

from predictionio_tpu.core.datamap import DataMap
from predictionio_tpu.core.event import Event
from predictionio_tpu.storage.base import App
from predictionio_tpu.workflow.train import run_train

FACTORY = "predictionio_tpu.templates.recommendation.engine_factory"

#: the phases ``batch_predict`` / ``ALSModel.batch_topk`` record, in the
#: order they run
DISPATCH_PHASES = ("dispatch.prepare", "dispatch.gather", "dispatch.enqueue",
                   "dispatch.device_wait", "dispatch.fetch",
                   "dispatch.results")


def train_rec(storage, model_dir, monkeypatch, n_users: int = 24,
              n_items: int = 17, app_name: str = "SpanApp"):
    """Seed ``n_users`` x 4 ratings and train rank-5 ALS; users are
    ``u0..``, items ``i0..``."""
    monkeypatch.setenv("PIO_MODEL_DIR", str(model_dir))
    app_id = storage.get_meta_data_apps().insert(App(0, app_name))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(7)
    for u in range(n_users):
        for i in rng.choice(n_items, size=4, replace=False):
            events.insert(
                Event(event="rate", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item", target_entity_id=f"i{i}",
                      properties=DataMap({"rating": 5.0})), app_id)
    outcome = run_train(storage=storage, variant={
        "id": app_name, "engineFactory": FACTORY,
        "datasource": {"params": {"app_name": app_name}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 5, "num_iterations": 2,
                                   "lambda_": 0.05, "seed": 3}}]})
    assert outcome.status == "COMPLETED"
    return outcome


def start_rec_server(storage, **config):
    """A started batching engine server over the latest trained
    instance; the caller stops it."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.workflow.deploy import ServerConfig

    server = create_engine_server(storage=storage, config=ServerConfig(
        ip="127.0.0.1", port=0, batching=True, **config))
    server.start()
    return server


def post_query(port: int, payload: dict):
    """(status, body, headers) of one ``POST /queries.json``."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def trace_of(port: int, trace_id: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/traces.json", timeout=10) as r:
        doc = json.loads(r.read())
    return next(t for t in doc["traces"] if t["traceId"] == trace_id)
