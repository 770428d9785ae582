"""The session engine's model and histories a serve cell starts from,
made from the seed. Nothing here trains: the stack's weights are drawn
on the device in one jitted call, in the type they are served in.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import traffic as tr

#: stream of the seed for the histories (traffic.py has 1-4)
HISTORIES = 5


def algorithm_params(config: dict):
    """The configuration file's widths as the template's
    ``AlgorithmParams`` (what ``engine.json`` would carry)."""
    from predictionio_tpu.templates import sessionrec

    return sessionrec.AlgorithmParams(
        backbone=config["model_type"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        n_layers=config["num_hidden_layers"], max_len=config["history_len"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        retention_degree=config["retention_degree"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["param_dtype"], use_mesh=False)


def seeded_histories(config: dict, seed: int) -> np.ndarray:
    """(users, history_len) int32 dense item indices in [1, items]: the
    catalog's power law, so a few items fill most of every history."""
    rng = np.random.default_rng([seed, HISTORIES])
    n = config["users"] * config["history_len"]
    ids = tr.power_law_ids(rng, config["items"], n, config["power"])
    ids += 1                                    # 0 is PAD
    return ids.reshape(config["users"], config["history_len"])


def build_model(config: dict, traffic: dict, seed: int):
    """A ``SeqRecEngineModel`` whose weights are already on the device in
    the served type. Returns (model, histories array, pool)."""
    import jax

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.templates import sessionrec
    from predictionio_tpu.utils.bimap import BiMap

    params = algorithm_params(config)
    cfg = params.seqrec_config(vocab=config["vocab_size"])
    if config["items"] + 1 != cfg.vocab:
        raise ValueError("items + PAD must fill the vocabulary")
    if cfg.gate_init_logit != config["gate_init_logit"]:
        raise ValueError("the program's gate offset is not the file's")
    weights = jax.block_until_ready(jax.jit(
        lambda key: seqrec.init_params(key, cfg, dtype=cfg.param_dtype))(
            jax.random.PRNGKey(seed)))
    histories = seeded_histories(config, seed)
    pool = tr.query_pool(config, traffic, seed)
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(config["items"])}),
        histories={f"u{u}": histories[u] for u in range(config["users"])})
    return model, histories, pool


def deployed_engine(config: dict, model):
    """What ``pio deploy`` holds after restoring the model: a
    ``DeployedEngine`` over the session template's algorithm."""
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


def warm_up(deployed, server, model, pool, num: int) -> int:
    """Every (B, S) signature the token budget allows, once through
    ``query_batch`` (compile or cache load), then a few requests over
    the socket, which is also what marks the program's own warm-up
    complete. Returns the number of signatures."""
    import socket

    from benchmarks.harness.loadgen import read_response
    from predictionio_tpu.templates import sessionrec

    known = [int(u) for u in dict.fromkeys(pool.tolist()) if u >= 0]
    widest = max(1, sessionrec.token_budget(model) // model.cfg.max_len)
    widths = [b for b in (1 << n for n in range(9))
              if b <= min(widest, server.config.batch_max, len(known))]
    for b in widths:
        deployed.query_batch([sessionrec.Query(user=f"u{u}", num=num)
                              for u in known[:b]])
    with socket.create_connection(("127.0.0.1", server.port), timeout=60) as s:
        buf = bytearray()
        for u in [int(x) for x in pool[:2]] + [-1]:
            body = tr.request_body(u, num)
            s.sendall(b"POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      b"Content-Type: application/json\r\nContent-Length: "
                      + str(len(body)).encode() + b"\r\n\r\n" + body)
            status, _ = read_response(s, buf)
            if status != 200:
                raise RuntimeError(f"warm-up request answered {status}")
    return len(widths)


def seq_counters(server) -> dict:
    """The session engine's dispatch counters (``/stats.json``
    ``serving``), cumulative."""
    snap = server.service.serving_stats.snapshot()
    return {"seq_programs": int(snap["seqPrograms"]),
            "seq_tokens": int(snap["seqTokens"]),
            "seq_padded_tokens": int(snap["seqPaddedTokens"]),
            "seq_split_dispatches": int(snap["seqSplitDispatches"])}
