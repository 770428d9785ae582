"""Operations and bytes the session engine's forward pass needs,
computed from the configuration's widths (``costs.py`` has the ALS
programs'). The algorithm's requirements, not what a compiler emitted:
recomputation, padding of the feature map and the work inside a chunk
do not count, so a share reads the same whatever implements the layer.
"""

from __future__ import annotations


def _widths(config: dict) -> dict:
    return {"hidden": config["hidden_size"], "ff": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "d": config["head_dim"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"]}


def layer_matrix_params(config: dict) -> int:
    """Weights of one layer's matrix products: q, k, v, the gate, o and
    the three SwiGLU matrices."""
    w = _widths(config)
    attn = w["hidden"] * w["d"] * (2 * w["heads"] + 2 * w["kv"]) \
        + w["hidden"] * w["kv"]
    return attn + 3 * w["hidden"] * w["ff"]


def power_retention(config: dict, tokens: float) -> dict:
    """The recurrence over ``tokens`` tokens in every layer, with the
    d(d+1)/2 degree-2 monomials as features: per token and layer the
    state is read by every query head and written by every key/value
    head (2*F*d each), and the normaliser read by every query head
    (2*F); q, k, v and y cross memory once in bfloat16."""
    w = _widths(config)
    feats = w["d"] * (w["d"] + 1) // 2
    flops = 2 * feats * w["d"] * (w["heads"] + w["kv"]) + 2 * feats * w["heads"]
    bytes_ = 2 * w["d"] * (2 * w["heads"] + 2 * w["kv"])
    return {"flops": float(flops) * tokens * w["layers"],
            "bytes": float(bytes_) * tokens * w["layers"]}


def seq_forward(config: dict, tokens: float) -> dict:
    """One serving program over ``tokens`` tokens (queries x history
    length): every layer's matrix products at 2 flops a weight and
    token, retention's recurrence, and the head over the whole catalog
    for each query's last position. Bytes: every weight once in
    bfloat16 (the tables: the rows gathered and the head)."""
    w = _widths(config)
    queries = tokens / config["history_len"]
    products = 2.0 * layer_matrix_params(config) * tokens * w["layers"]
    head = 2.0 * w["vocab"] * w["hidden"] * queries
    weights = w["layers"] * layer_matrix_params(config) + w["vocab"] * w["hidden"]
    return {"flops": products + power_retention(config, tokens)["flops"] + head,
            "bytes": 2.0 * weights + 2.0 * tokens * w["hidden"]}
