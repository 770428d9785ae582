"""Operations and bytes a call needs, computed from its shapes.

These are the algorithm's requirements, not what a compiler emitted:
recomputation and padding do not count.
"""

from __future__ import annotations


def als_iteration(config: dict, nnz: int | None = None) -> dict:
    """One ALS iteration (both half-steps), explicit feedback, CG.

    Build: per rating one rank-1 update of a K x K matrix and one of a
    K vector, 2*K*K + 2*K flops, on each side. Solve: per row
    ``cg_steps`` matrix-vector products of 2*K*K flops. Bytes: per
    rating one gathered row of the other table in bfloat16 (the program
    gathers from a bf16 copy) plus its index and value, on each side;
    each table written once in float32 and read once to be cast."""
    k = config["rank"]
    nnz = config["ratings"] if nnz is None else nnz
    rows = config["users"] + config["items"]
    steps = config.get("cg_steps", 16)
    flops = 2 * nnz * (2 * k * k + 2 * k) + rows * steps * 2 * k * k
    bytes_ = 2 * nnz * (2 * k + 8) + rows * k * (4 + 4 + 2)
    return {"flops": float(flops), "bytes": float(bytes_)}


def recommend_topk(config: dict, batch: float) -> dict:
    """One score-and-top-k dispatch of ``batch`` queries: B x I x K
    multiply-adds; the item table read once at the operand width the
    configuration guarantees its scores are formed from
    (``score_operand_bytes``; 4, float32, where the file does not say),
    one float32 score per (query, item) written and read again by the
    selection. What the guarantee needs, not what one program happens
    to hold: a program that reads a wider table than the guarantee asks
    for moves bytes that need not move, and its share says so."""
    k, items = config["rank"], config["items"]
    width = float(config.get("score_operand_bytes", 4))
    return {"flops": 2.0 * batch * items * k,
            "bytes": items * k * width + 2 * batch * items * 4.0}


def roofline(cost: dict, seconds: float, peaks: dict) -> dict:
    """Share of the roofline reached, and which bound binds."""
    t_flops = cost["flops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"share_pct": 100.0 * least / seconds,
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops_per_s": cost["flops"] / seconds,
            "bytes_per_s": cost["bytes"] / seconds}
