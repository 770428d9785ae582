"""The session engine's model for a serve cell whose configuration file
says how it is read: ``algorithm_params`` maps the template's
``AlgorithmParams`` fields to the file's keys, ``reference`` names the
module under ``benchmarks/reference/`` that decides ``correct`` and
``costs`` the module under ``benchmarks/harness/`` that prices the
forward pass and says what the window's counters mean
(``window_values``), ``counters`` the program's own counters beside the
dispatch counters. The next backbone brings those four and its files,
and no copy of this module. Histories, warm-up and the dispatch counters
are ``harness/seq_data``'s.
"""

from __future__ import annotations

import functools
import importlib

from benchmarks.harness import seq_data, traffic as tr


def _bind(spec, config: dict):
    """A string names a key of the file (``a.b``: key ``b`` of its
    object ``a``); an object binds its values the same way; anything
    else stands for itself."""
    if isinstance(spec, str):
        return functools.reduce(lambda obj, key: obj[key], spec.split("."),
                                config)
    if isinstance(spec, dict):
        return {k: _bind(v, config) for k, v in spec.items()}
    return spec


def algorithm_params(config: dict):
    """What ``engine.json`` would carry for this configuration."""
    from predictionio_tpu.templates import sessionrec

    return sessionrec.AlgorithmParams(
        **_bind(config["algorithm_params"], config))


def reference(config: dict):
    return importlib.import_module(
        f"benchmarks.reference.{config['reference']}")


def costs(config: dict):
    return importlib.import_module(f"benchmarks.harness.{config['costs']}")


def window_values(counters: dict, config: dict) -> dict:
    """What the configuration's cost module makes of a window's counters
    (its ``window_values``: values for the run's notes and the readers);
    nothing where the file names no module or the module has no such
    function."""
    derive = getattr(costs(config), "window_values", None) \
        if config.get("costs") else None
    return derive(counters, config) if derive else {}


def build_model(config: dict, traffic: dict, seed: int):
    """A ``SeqRecEngineModel`` whose weights are already on the device in
    the served type, drawn from the seed a layer at a time. Returns
    (model, histories array, pool)."""
    import jax

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.templates import sessionrec
    from predictionio_tpu.utils.bimap import BiMap

    cfg = algorithm_params(config).seqrec_config(vocab=config["vocab_size"])
    if config["items"] + 1 != cfg.vocab:
        raise ValueError("items + PAD must fill the vocabulary")
    weights = jax.block_until_ready(jax.jit(
        lambda key: seqrec.init_params(key, cfg, dtype=cfg.param_dtype))(
            jax.random.PRNGKey(seed)))
    histories = seq_data.seeded_histories(config, seed)
    pool = tr.query_pool(config, traffic, seed)
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(config["items"])}),
        histories={f"u{u}": histories[u] for u in range(config["users"])})
    return model, histories, pool


def deployed_engine(config: dict, model):
    """``seq_data.deployed_engine`` with this file's ``AlgorithmParams``."""
    import datetime

    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.templates import sessionrec
    from predictionio_tpu.workflow.deploy import DeployedEngine

    algo = sessionrec.SeqRecAlgorithm(algorithm_params(config))
    now = datetime.datetime.now(datetime.timezone.utc)
    instance = EngineInstance(
        id="benchmark", status="COMPLETED", start_time=now,
        completion_time=now, engine_id="benchmark", engine_version="1",
        engine_variant="benchmark", engine_factory="benchmark")
    return DeployedEngine(None, instance, [algo], FirstServing(), [model])


def seq_counters(server, config: dict) -> dict:
    """``seq_data.seq_counters`` and the counters the configuration's
    file lists under ``counters`` (the name a window's evidence gives
    it -> its name in ``/stats.json`` ``serving``; one that is absent
    from the program is left out)."""
    snap = server.service.serving_stats.snapshot()
    return {**seq_data.seq_counters(server),
            **{k: int(snap[v]) for k, v in config.get("counters", {}).items()
               if v in snap}}
