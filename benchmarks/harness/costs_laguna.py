"""Operations and bytes of a forward pass of the Laguna stack (sliding-
window and full grouped-query attention mixed by layer, each head's
output gated, a dense layer, then softmax-routed experts of which this
chip holds a share beside a shared one), from the configuration's
widths: the algorithm's requirements, not what a compiler or a kernel's
tiling emitted. Key tiles above the diagonal, keys outside a window that
a tile covers, padding of the assignment rows and half-empty row tiles
of the grouped product do not count.

``tokens`` is queries x history length; ``assignments`` the tokens'
assignments to **held** experts summed over the expert layers, as the
window's counters report them (an expected 2.5 a token and layer at 64
of 256 experts and 10 a token).
"""

from __future__ import annotations


def _w(config: dict) -> dict:
    types = config["layer_types"]
    return {"d": config["hidden_size"], "G": config["num_key_value_heads"],
            "hd": config["head_dim"], "types": types,
            "heads": config["num_attention_heads_per_layer"],
            "mlp": config["mlp_layer_types"],
            "ff": config["intermediate_size"],
            "ffe": config["moe_intermediate_size"],
            "shared": config["shared_expert_intermediate_size"],
            "held": config["num_experts"],
            "router": config.get("published", {}).get(
                "num_experts", config["num_experts"]),
            "window": config["sliding_window"],
            "vocab": config["vocab_size"], "S": config["history_len"]}


def _of_type(w: dict, layer_type: str) -> list:
    """The query heads of each layer of a type."""
    return [H for t, H in zip(w["types"], w["heads"]) if t == layer_type]


def attention_params(config: dict, heads: int) -> int:
    """One layer's attention of ``heads`` query heads: q, k, v, o and the
    per-head gate (the norms' vectors are not counted)."""
    w = _w(config)
    return (2 * heads * w["hd"] * w["d"] + 2 * w["G"] * w["hd"] * w["d"]
            + w["d"] * heads)


def dense_params(config: dict) -> int:
    w = _w(config)
    return 3 * w["d"] * w["ff"]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    w = _w(config)
    return 3 * w["d"] * w["ffe"]


def expert_layer_dense_params(config: dict) -> int:
    """What every token of an expert layer meets: the shared expert and
    the router over every published expert."""
    w = _w(config)
    return 3 * w["d"] * w["shared"] + w["d"] * w["router"]


def held_params(config: dict) -> int:
    """Every weight this chip holds: the layers and both tables."""
    w = _w(config)
    sparse = w["mlp"].count("sparse")
    return (sum(attention_params(config, H) for H in w["heads"])
            + w["mlp"].count("dense") * dense_params(config)
            + sparse * (w["held"] * expert_params(config)
                        + expert_layer_dense_params(config))
            + 2 * w["vocab"] * w["d"])


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs a sliding layer needs over a history of
    ``seq_len``: ``sum_t min(t + 1, window)``."""
    inside = min(seq_len, window)
    return inside * (inside + 1) // 2 + (seq_len - inside) * window


def window_attention(config: dict, tokens: float) -> dict:
    """The sliding layers' core: 2 flops a product over the head width
    for scores and again for values, every query head, for the pairs the
    window needs; q, k, v and the output once in bfloat16."""
    w = _w(config)
    queries = tokens / w["S"]
    pairs = window_pairs(w["S"], w["window"]) * queries
    heads = _of_type(w, "sliding_attention")
    return {"flops": 4.0 * w["hd"] * pairs * sum(heads),
            "bytes": 2.0 * tokens * w["hd"]
            * sum(2 * H + 2 * w["G"] for H in heads)}


def attention_core(config: dict, tokens: float) -> dict:
    """The full layers' causal core: a query of a history of S events
    meets (S + 1) / 2 keys on average, 2 flops a product over the head
    width for scores and again for values, every query head."""
    w = _w(config)
    return {"flops": 4.0 * w["hd"] * tokens * (w["S"] + 1) / 2
            * sum(_of_type(w, "full_attention"))}


def routed_experts(config: dict, tokens: float, assignments: float) -> dict:
    """The grouped products of the expert layers: 2 flops a weight of
    one expert for each assignment to a held expert. Bytes: every held
    expert's weights once a layer, each assignment's row in and out and
    its gate, up and hidden rows, in bfloat16. (The router and the
    shared expert are priced in :func:`forward`.)"""
    w = _w(config)
    sparse = w["mlp"].count("sparse")
    return {"flops": 2.0 * expert_params(config) * assignments,
            "bytes": 2.0 * sparse * w["held"] * expert_params(config)
            + 2.0 * assignments * (2 * w["d"] + 4 * w["ffe"])}


def forward(config: dict, tokens: float, assignments: float) -> dict:
    """One serving program: 2 flops a weight and token for every dense
    product (attention's projections and gates, the dense layer, the
    shared experts and the router), both attention cores by the pairs
    they need, the routed experts by the assignments counted, and the
    head over the slice for each query's last position. Bytes: every
    held weight once, the residual stream once in bfloat16."""
    w = _w(config)
    queries = tokens / w["S"]
    per_token = (sum(attention_params(config, H) for H in w["heads"])
                 + w["mlp"].count("dense") * dense_params(config)
                 + w["mlp"].count("sparse") * expert_layer_dense_params(config))
    flops = (2.0 * per_token * tokens
             + attention_core(config, tokens)["flops"]
             + window_attention(config, tokens)["flops"]
             + routed_experts(config, tokens, assignments)["flops"]
             + 2.0 * w["vocab"] * w["d"] * queries)
    return {"flops": flops,
            "bytes": 2.0 * held_params(config) + 2.0 * tokens * w["d"]}


def window_values(counters: dict, config: dict) -> dict:
    """What the expert layers' counters say of the window (the kind
    merges it into the run's ``notes`` and the readers' ``values``):
    assignments to held experts per program, per token and expert
    layer, and the fullest (layer, expert) of a program over the mean.
    Empty where the program has no such counters or routed nothing."""
    tokens = counters.get("seq_moe_tokens", 0)
    assignments = counters.get("seq_moe_assignments", 0)
    if not tokens or not assignments:
        return {}
    w = _w(config)
    layers = w["mlp"].count("sparse")
    return {"moe_assignments_per_program":
            assignments / max(counters["seq_programs"], 1),
            "routed_assignments_per_token": assignments / (tokens * layers),
            "expert_load_max_over_mean":
            counters["seq_moe_max_expert_load"] * layers * w["held"]
            / assignments}
