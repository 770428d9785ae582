"""Operations and bytes of a forward pass of latent attention (MLA) and
routed + shared experts, from the configuration's widths: the
algorithm's requirements, not what a compiler emitted. Padding of the
assignment rows, half-empty row tiles of the grouped product, key tiles
above the diagonal and recomputation do not count, so a share reads the
same whatever implements the layer.

``tokens`` is queries x history length; ``assignments`` the tokens'
assignments to **held** experts summed over the expert layers, as the
window's counters report them (an expected 1.5 a token and layer at 40
of 160 experts and 6 a token).
"""

from __future__ import annotations


def _w(config: dict) -> dict:
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "qk": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "nope": config["qk_nope_head_dim"], "v": config["v_head_dim"],
            "ql": config["q_lora_rank"], "kvl": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "ff": config["intermediate_size"],
            "ffe": config["moe_intermediate_size"],
            "held": config["n_routed_experts"],
            "router": config.get("published", {}).get(
                "n_routed_experts", config["n_routed_experts"]),
            "shared": config["n_shared_experts"],
            "layers": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "vocab": config["vocab_size"], "S": config["history_len"]}


def mla_params(config: dict) -> int:
    """Weights of one layer's attention: q_a, q_b, kv_a, kv_b, o."""
    w = _w(config)
    return (w["d"] * w["ql"] + w["ql"] * w["H"] * w["qk"]
            + w["d"] * (w["kvl"] + w["rope"])
            + w["kvl"] * w["H"] * (w["nope"] + w["v"]) + w["H"] * w["v"] * w["d"])


def expert_params(config: dict) -> int:
    w = _w(config)
    return 3 * w["d"] * w["ffe"]


def held_params(config: dict) -> int:
    """Every weight this chip holds: the layers and both tables."""
    w = _w(config)
    moe = w["layers"] - w["dense"]
    return (w["layers"] * mla_params(config) + w["dense"] * 3 * w["d"] * w["ff"]
            + moe * ((w["held"] + w["shared"]) * expert_params(config)
                     + w["d"] * w["router"])
            + 2 * w["vocab"] * w["d"])


def mla_attention(config: dict, tokens: float) -> dict:
    """The causal core in every layer: a query of a history of S events
    meets (S + 1) / 2 keys on average, 2 flops a product, over the
    query/key width and the value width of every head; q, k, v and the
    output cross memory once in bfloat16 (k with its shared rotary
    slice once per head, as the core reads it)."""
    w = _w(config)
    per_pair = 2.0 * w["H"] * (w["qk"] + w["v"])
    flops = per_pair * tokens * (w["S"] + 1) / 2 * w["layers"]
    bytes_ = 2.0 * w["H"] * (2 * w["qk"] + 2 * w["v"]) * tokens * w["layers"]
    return {"flops": flops, "bytes": bytes_}


def routed_experts(config: dict, tokens: float, assignments: float) -> dict:
    """The grouped products of the expert layers: 2 flops a weight of
    one expert for each assignment to a held expert. Bytes: every held
    expert's weights once a layer, each assignment's row in and out and
    its hidden row twice, in bfloat16. (The router and the shared
    experts are priced in :func:`forward`.)"""
    w = _w(config)
    moe = w["layers"] - w["dense"]
    return {"flops": 2.0 * expert_params(config) * assignments,
            "bytes": 2.0 * moe * w["held"] * expert_params(config)
            + 2.0 * assignments * (2 * w["d"] + 4 * w["ffe"])}


def forward(config: dict, tokens: float, assignments: float) -> dict:
    """One serving program: MLA's projections, the causal core, the
    dense and shared SwiGLUs, the router, the routed experts by the
    assignments counted, and the head over the slice for each query's
    last position. Bytes: every held weight once, the residual stream
    once in bfloat16."""
    w = _w(config)
    moe = w["layers"] - w["dense"]
    queries = tokens / w["S"]
    per_token = (w["layers"] * mla_params(config)
                 + w["dense"] * 3 * w["d"] * w["ff"]
                 + moe * (w["shared"] * expert_params(config)
                          + w["d"] * w["router"]))
    flops = (2.0 * per_token * tokens
             + mla_attention(config, tokens)["flops"]
             + routed_experts(config, tokens, assignments)["flops"]
             + 2.0 * w["vocab"] * w["d"] * queries)
    return {"flops": flops,
            "bytes": 2.0 * held_params(config) + 2.0 * tokens * w["d"]}


def window_values(counters: dict, config: dict) -> dict:
    """What the routed layers' counters say of the window (the kind
    merges it into the run's ``notes`` and the readers' ``values``):
    assignments to held experts per program, per token and expert
    layer, and the fullest (layer, expert) of a program over the mean.
    Empty where the program has no such counters or routed nothing."""
    tokens = counters.get("seq_moe_tokens", 0)
    assignments = counters.get("seq_moe_assignments", 0)
    if not tokens or not assignments:
        return {}
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    held = config["experts_held"][1]
    return {"moe_assignments_per_program":
            assignments / max(counters["seq_programs"], 1),
            "routed_assignments_per_token": assignments / (tokens * layers),
            "expert_load_max_over_mean":
            counters["seq_moe_max_expert_load"] * layers * held / assignments}
