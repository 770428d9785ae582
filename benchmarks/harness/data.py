"""The model and the training set a cell starts from, made from the seed.

Nothing here trains: a serve cell gets factor tables drawn on the device
in one jitted call, in the type they are served in.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import traffic as tr


def seeded_tables(config: dict, seed: int):
    """(user_factors, item_factors), float32, on the device: the scale
    ``als_train`` starts its item table at (normal / sqrt(rank))."""
    import jax
    import jax.numpy as jnp

    users, items, rank = config["users"], config["items"], config["rank"]

    @jax.jit
    def make(key):
        ku, ki = jax.random.split(key)
        scale = 1.0 / np.sqrt(rank)
        return (jax.random.normal(ku, (users, rank), jnp.float32) * scale,
                jax.random.normal(ki, (items, rank), jnp.float32) * scale)

    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def seen_lists(users: np.ndarray, items: np.ndarray,
               wanted: np.ndarray) -> dict[int, np.ndarray]:
    """user -> sorted distinct items rated, for the ``wanted`` users only
    (the ones this run's traffic asks about: no request reads another's)."""
    keep = np.isin(users, wanted)
    u, i = users[keep], items[keep]
    order = np.lexsort((i, u))
    u, i = u[order], i[order]
    first = np.ones(len(u), dtype=bool)
    first[1:] = (u[1:] != u[:-1]) | (i[1:] != i[:-1])
    u, i = u[first], i[first]
    starts = np.flatnonzero(np.r_[True, u[1:] != u[:-1]])
    ends = np.r_[starts[1:], len(u)]
    return {int(u[s]): i[s:e].astype(np.int32) for s, e in zip(starts, ends)}


def build_model(config: dict, traffic: dict, seed: int):
    """An ``ALSModel`` over seeded tables with the id maps and seen lists
    of the configuration's full rating draw. Returns (model, seen, pool)."""
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap

    user_f, item_f = seeded_tables(config, seed)
    pool = tr.query_pool(config, traffic, seed)
    u, i, _ = tr.make_ratings(config, seed)
    seen = seen_lists(u, i, np.unique(pool[pool >= 0]))
    del u, i
    model = ALSModel(
        rank=config["rank"], user_factors=user_f, item_factors=item_f,
        user_ids=EntityIdIxMap(BiMap(
            {f"u{k}": k for k in range(config["users"])})),
        item_ids=EntityIdIxMap(BiMap(
            {f"i{k}": k for k in range(config["items"])})),
        seen_by_user=seen)
    return model, seen, pool


def deployed_engine(config: dict, model):
    """What ``pio deploy`` holds after restoring a model: a
    ``DeployedEngine`` over the recommendation template's algorithm
    (``bench_serving.build_deployed``, copied)."""
    import datetime

    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.templates import recommendation as rec
    from predictionio_tpu.workflow.deploy import DeployedEngine

    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(
        rank=config["rank"], exclude_seen=config["exclude_seen"],
        use_mesh=False))
    now = datetime.datetime.now(datetime.timezone.utc)
    instance = EngineInstance(
        id="benchmark", status="COMPLETED", start_time=now,
        completion_time=now, engine_id="benchmark", engine_version="1",
        engine_variant="benchmark", engine_factory="benchmark")
    return DeployedEngine(None, instance, [algo], FirstServing(), [model])
