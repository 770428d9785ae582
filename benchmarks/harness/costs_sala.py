"""Operations and bytes of a forward pass of the MiniCPM-SALA stack
(block-sparse attention chosen per query position beside lightning
attention), from the configuration's widths and sparse sizes: the
algorithm's requirements, not what a compiler or a kernel's tiling
emitted. A sparse layer is priced by what its positions **need** — the
compressed keys a position can see and the keys of the ``min(visible,
topk)`` blocks it keeps, its own block up to itself — so a kernel that
scores unselected keys reads a lower share and none can read over 100%.
Lightning attention is priced as its recurrence (a ``d x d`` state a
head, read and updated once a token), whatever chunking implements it.

``tokens`` is queries x history length (``history_len`` a query).
"""

from __future__ import annotations

import numpy as np


def _w(config: dict) -> dict:
    sparse = config["sparse_config"]
    mixers = config["mixer_types"]
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "G": config["num_key_value_heads"], "hd": config["head_dim"],
            "Hl": config["lightning_nh"], "Gl": config["lightning_nkv"],
            "dl": config["lightning_head_dim"],
            "ff": config["intermediate_size"], "vocab": config["vocab_size"],
            "S": config["history_len"],
            "sparse_layers": sum(m == "minicpm4" for m in mixers),
            "lightning_layers": sum(m != "minicpm4" for m in mixers),
            **sparse}


def sparse_layer_params(config: dict) -> int:
    """q, the output gate and o at full width, k and v over the
    key/value heads, and SwiGLU's three."""
    w = _w(config)
    return (3 * w["d"] * w["H"] * w["hd"] + 2 * w["d"] * w["G"] * w["hd"]
            + 3 * w["d"] * w["ff"])


def lightning_layer_params(config: dict) -> int:
    """q, the output gate and o, k and v at their head counts, and
    SwiGLU's three (the norms' vectors are not counted)."""
    w = _w(config)
    return (3 * w["d"] * w["Hl"] * w["dl"] + 2 * w["d"] * w["Gl"] * w["dl"]
            + 3 * w["d"] * w["ff"])


def held_params(config: dict) -> int:
    """Every weight this chip holds: the layers and both tables."""
    w = _w(config)
    return (w["sparse_layers"] * sparse_layer_params(config)
            + w["lightning_layers"] * lightning_layer_params(config)
            + 2 * w["vocab"] * w["d"])


def needed_per_history(config: dict) -> dict:
    """Summed over the positions of one history and one sparse layer:
    the compressed keys seen (stage 1) and the keys of the kept blocks
    at or before the position (stage 2); both over every visible key
    where the history is at or under ``dense_len`` (no selection)."""
    w = _w(config)
    S, block = w["S"], w["block_size"]
    if S <= w["dense_len"]:
        return {"compressed": 0, "keys": S * (S + 1) // 2}
    t = np.arange(S)
    compressed = np.maximum(
        (t - w["kernel_size"] + 1) // w["kernel_stride"] + 1, 0)
    kept = np.minimum(t // block + 1, w["topk"])
    keys = (kept - 1) * block + t % block + 1
    return {"compressed": int(compressed.sum()), "keys": int(keys.sum())}


def sparse_attention(config: dict, tokens: float) -> dict:
    """Both stages of the sparse layers: 2 flops a (position, head,
    compressed key seen) product over the head width, and 2 x 2 a
    (position, head, kept key) over query/key and value widths. Bytes:
    q and the output at every head, k and v at the key/value heads, once
    in bfloat16."""
    w = _w(config)
    need = needed_per_history(config)
    per_history = 2.0 * w["H"] * w["hd"] * (need["compressed"]
                                             + 2 * need["keys"])
    return {"flops": per_history * tokens / w["S"] * w["sparse_layers"],
            "bytes": 2.0 * w["hd"] * (2 * w["H"] + 2 * w["G"]) * tokens
            * w["sparse_layers"]}


def lightning_attention(config: dict, tokens: float) -> dict:
    """The recurrence of the lightning layers: a head's ``d x d`` state
    read by the query and updated by ``k v^T`` once a token, 2 flops a
    product each. Bytes: q, k, v and the output once in bfloat16."""
    w = _w(config)
    return {"flops": 4.0 * w["dl"] * w["dl"] * w["Hl"] * tokens
            * w["lightning_layers"],
            "bytes": 2.0 * w["dl"] * (2 * w["Hl"] + 2 * w["Gl"]) * tokens
            * w["lightning_layers"]}


def forward(config: dict, tokens: float) -> dict:
    """One serving program: 2 flops a layer weight and token, both
    stages of the sparse layers by what the shapes need, the lightning
    recurrence, and the head for each query's last position. Bytes:
    every held weight once, the residual stream once in bfloat16."""
    w = _w(config)
    queries = tokens / w["S"]
    per_token = (w["sparse_layers"] * sparse_layer_params(config)
                 + w["lightning_layers"] * lightning_layer_params(config))
    flops = (2.0 * per_token * tokens
             + sparse_attention(config, tokens)["flops"]
             + lightning_attention(config, tokens)["flops"]
             + 2.0 * w["vocab"] * w["d"] * queries)
    return {"flops": flops,
            "bytes": 2.0 * held_params(config) + 2.0 * tokens * w["d"]}


def window_values(counters: dict, config: dict) -> dict:
    """What the sparse layers' counters say of the window (the kind
    merges it into the run's ``notes`` and the readers' ``values``): the
    blocks a (position, sparse layer, key/value head) row kept, and the
    keys whose scores stage 2 computed over the keys of the kept blocks
    (1.0: a kernel that scores only what was selected; the masked-dense
    form scores every visible key). Empty where the program has no such
    counters or nothing selected (histories at or under ``dense_len``)."""
    rows = counters.get("seq_sparse_rows", 0)
    blocks = counters.get("seq_sparse_blocks_selected", 0)
    if not rows or not blocks:
        return {}
    return {"sparse_blocks_selected_per_token": blocks / rows,
            "sparse_keys_scored_per_selected":
            counters.get("seq_sparse_keys_scored", 0)
            / (blocks * config["sparse_config"]["block_size"])}
