"""Plain reference of the DeepSeek-V2 block stack as the session engine
serves it: the logits of a history's last position.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no batching, every query row against every earlier
key. It shares no code with ``predictionio_tpu.models`` or
``predictionio_tpu.ops``; it takes the weights that are served, whatever
type they are held in, upcasts them a matrix at a time and works a block
of heads, of query rows and of experts at a time, so that S = 8,192 at
the published widths fits beside the served copy.

Per layer, ``x`` (S, hidden), every matrix bias-free, RMSNorm eps 1e-6::

    h = rmsnorm(x, in_norm)
    c_q = rmsnorm(h wq_a, q_a_norm);  [q_nope | q_pe] = c_q wq_b      per head
    [c_kv | k_pe] = h wkv_a          (k_pe: one head shared by all query heads)
    [k_nope | v] = rmsnorm(c_kv, kv_a_norm) wkv_b                     per head
    q_pe, k_pe rotated by position, pairs (2i, 2i+1), YaRN frequencies
    y = causal softmax(([q_nope | q_pe] . [k_nope | k_pe]) scale) v
    x = x + concat(y) wo
    h2 = rmsnorm(x, post_norm)
    x = x + swiglu(h2; ffn)                          the first_k_dense layers
    x = x + sum_k 16 s_k E_{e_k}(h2) [first <= e_k < first + held] + swiglu(h2; shared)

``s = softmax(h2 router)`` over all published experts; the best
``topk_group`` of ``n_group`` groups by their best expert are kept, the
``num_experts_per_tok`` best scores of those groups chosen, not
renormalised (``norm_topk_prob`` false), times ``routed_scaling_factor``.
``(first, held)`` are the experts this chip holds: what the others would
add is left out, as in the program. YaRN: ``scale = qk**-0.5 m**2``,
``m = 0.1 mscale_all_dim ln(factor) + 1``; the factor on cos and sin is
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.

**Near ties in the router.** The served path's activations are bfloat16,
so where scores of the last position lie closer than that rounding the
program may choose another expert, and the weight 16 s makes such a
token differ by far more than rounding (an expert's output is a quarter
of the residual stream). :func:`resolutions` yields the logits under the
resolutions of the last position's near ties, best first: the
reference's own choice, then those that give up the least margin (a
chosen group or expert exchanged for an excluded one whose score is
within ``near_tie`` of it, ``ln`` of the ratio). The positions before
the last are worked once, whole; the last row is then taken through the
stack once per resolution, every later layer routed from that
resolution's own hidden state. Nothing is left out of any of them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# -- the limits ``harness/seq_ref_check`` holds a served answer to -----------
#: |returned score - reference logit of that item|, in logits (~N(0, 1)
#: over the catalog, the top ten between 3.5 and 5). Four times
#: ``harness/seq_check``'s limits: beside the bfloat16 rounding of every
#: activation through five layers, a router near tie at a position
#: *before* the last may fall the other way in the served path, and only
#: the last position's are resolved. The limit lies between two readings
#: on the chip (PERF.md section 6, PR 31), three times above the one and
#: six times under the other: the served path's worst over 77 checked
#: queries of 20 seeds, 0.132, and this reference with both operands of
#: every product rounded to 8 bits (float8 e4m3), whose own top ten are
#: off by 2.61 to 4.11 and so come out as not correct (2.84 to 3.74
#: again in PR 33, which also read served answers at 0.30 to 0.35: the
#: check stops at the first resolution that agrees, so a last-position
#: tie worth less than the limit stays unresolved; PERF.md section 6)
SCORE_TOL = 0.4
#: a returned item may rank below the reference's tenth, and a reference
#: top-ten item may be missing, only if its reference logit is this
#: close to the tenth's: two scores' worth (the worst seen is 0.222)
RANK_TOL = 2 * SCORE_TOL
#: two router scores of the last position are a near tie when the ``ln``
#: of their ratio is under this: the served path's bfloat16 activations
#: have turned ties of margins up to 0.029 (PERF.md section 6, PR 31);
#: a chosen group or expert that close to an excluded one may have been
#: exchanged for it
NEAR_TIE = 0.1
#: search steps (one layer of one row each, ~0.15 s on the chip) the
#: reference may spend on one answer
MAX_STEPS = 400

#: None: float32 everywhere (the reference). tools/seq_ref_precision.py
#: sets one of these for the readings that have to come out as not
#: correct: "operands" rounds both operands of every product to 8 bits
#: (float8 e4m3), "softmax" keeps attention's probabilities and their
#: sums in bfloat16, "router" rounds the router's scores to bfloat16
#: before the selection
_LOWER = None


def set_lower(what) -> None:
    global _LOWER
    assert what in (None, "operands", "softmax", "router")
    _LOWER = what
    jax.clear_caches()


def _op(x):
    x = x.astype(F32)
    return x.astype(jnp.float8_e4m3fn).astype(F32) if _LOWER == "operands" \
        else x


def widths(config: dict) -> dict:
    """The configuration file's keys under the names used here."""
    first, held = config.get("experts_held",
                             (0, config["n_routed_experts"]))
    return {"heads": config["num_attention_heads"],
            "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
            "dv": config["v_head_dim"], "kv_lora": config["kv_lora_rank"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "yarn": config.get("rope_scaling"),
            "first": int(first), "held": int(held),
            "top_k": config["num_experts_per_tok"],
            "n_group": config["n_group"], "topk_group": config["topk_group"],
            "scaling": float(config["routed_scaling_factor"])}


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def yarn_range(dim, base, original, beta_fast, beta_slow):
    def cd(r):
        return dim * math.log(original / (r * 2 * math.pi)) \
            / (2 * math.log(base))
    return max(math.floor(cd(beta_fast)), 0), min(math.ceil(cd(beta_slow)),
                                                  dim - 1)


def inv_freq(dim, base, yarn):
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return f
    low, high = yarn_range(dim, base,
                           yarn["original_max_position_embeddings"],
                           yarn["beta_fast"], yarn["beta_slow"])
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f * (1 - ramp) + f / yarn["factor"] * ramp


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg):
    yarn = cfg["yarn"]
    m = mscale(yarn["factor"], yarn["mscale_all_dim"]) if yarn else 1.0
    return (cfg["dn"] + cfg["dr"]) ** -0.5 * m * m


def rope(x, pos, cfg):
    """x (..., S, dr) rotated at positions ``pos`` (S,): the complex
    product on pairs (2i, 2i+1), left interleaved as published."""
    yarn = cfg["yarn"]
    mag = mscale(yarn["factor"], yarn["mscale"]) \
        / mscale(yarn["factor"], yarn["mscale_all_dim"]) if yarn else 1.0
    ang = jnp.asarray(pos, F32)[:, None] * jnp.asarray(
        inv_freq(cfg["dr"], cfg["theta"], yarn), F32)
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x = x.astype(F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@jax.jit
def _matmul(x, w):
    return _op(x) @ _op(w)


def latents(x, w, cfg, first=0):
    """(normed latent (n, kv_lora), rotated shared key (n, dr)) of the
    rows ``x`` at positions ``first .. first + n - 1``."""
    c = _matmul(rmsnorm(x, w["in_norm"], cfg["eps"]), w["wkv_a"])
    return (rmsnorm(c[:, :cfg["kv_lora"]], w["kv_a_norm"], cfg["eps"]),
            rope(c[:, cfg["kv_lora"]:], first + np.arange(x.shape[0]), cfg))


@jax.jit
def _attend_rows(q, k, v, rows, scale):
    """q (h, R, dq) at positions ``rows`` against k (h, S, dq), v
    (h, S, dv): causal softmax attention, (R, h, dv)."""
    logits = jnp.einsum("hrd,hsd->hrs", _op(q), _op(k)) * scale
    seen = rows[:, None] >= jnp.arange(k.shape[1])[None, :]
    logits = jnp.where(seen[None], logits, -jnp.inf)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    if _LOWER == "softmax":
        p = p.astype(jnp.bfloat16)
        den = jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.bfloat16)
        out = jnp.einsum("hrs,hsd->rhd", p, v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.bfloat16)
        return (out / den.transpose(1, 0, 2)).astype(F32)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("hrs,hsd->rhd", _op(p), _op(v))


def attention(x, rows, c_kv, k_pe, w, cfg, head_block=16, row_block=1024):
    """The layer's attention output (len(rows), hidden) for the query
    rows ``x`` (len(rows), hidden) at positions ``rows`` against every
    position's latent."""
    H, dn, dr, dv = cfg["heads"], cfg["dn"], cfg["dr"], cfg["dv"]
    S, scale = c_kv.shape[0], softmax_scale(cfg)
    h = rmsnorm(x, w["in_norm"], cfg["eps"])
    c_q = rmsnorm(_matmul(h, w["wq_a"]), w["q_a_norm"], cfg["eps"])
    wq_b = w["wq_b"].reshape(-1, H, dn + dr)
    wkv_b = w["wkv_b"].reshape(-1, H, dn + dv)
    wo = w["wo"].reshape(H, dv, -1)
    out = jnp.zeros((len(rows), x.shape[1]), F32)
    for lo in range(0, H, head_block):
        hb = slice(lo, lo + head_block)
        n = wq_b[:, hb].shape[1]
        q = _matmul(c_q, wq_b[:, hb].reshape(c_q.shape[1], -1)
                    ).reshape(len(rows), n, dn + dr).transpose(1, 0, 2)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], rows, cfg)], -1)
        kv = _matmul(c_kv, wkv_b[:, hb].reshape(c_kv.shape[1], -1)
                     ).reshape(S, n, dn + dv).transpose(1, 0, 2)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe[None], (n, S, dr))], -1)
        y = jnp.concatenate(
            [_attend_rows(q[:, r:r + row_block], k, kv[..., dn:],
                          jnp.asarray(rows[r:r + row_block]), scale)
             for r in range(0, len(rows), row_block)], axis=0)
        out = out + _matmul(y.reshape(len(rows), n * dv),
                            wo[hb].reshape(n * dv, -1))
    return out


@jax.jit
def _swiglu(h, ffn):
    return _op(jax.nn.silu(_op(h) @ _op(ffn["w_gate"]))
               * (_op(h) @ _op(ffn["w_up"]))) @ _op(ffn["w_down"])


def swiglu(h, ffn, block=2048):
    return jnp.concatenate([_swiglu(h[lo:lo + block], ffn)
                            for lo in range(0, h.shape[0], block)], axis=0)


def router_scores(h, w):
    s = jax.nn.softmax(_matmul(h, w["router"]), axis=-1)
    return s.astype(jnp.bfloat16).astype(F32) if _LOWER == "router" else s


def _ln(x):
    return float(np.log(max(x, 1e-300)))


def _exchanges(ranked, take, value, near_tie):
    """The first ``take`` of ``ranked`` (cost 0), then that choice with
    one chosen member exchanged for one excluded, where the chosen one's
    ``value`` is within ``near_tie`` (``ln`` of the ratio) of the
    excluded one's: [(margin given up, members)], cheapest first."""
    chosen, rest = ranked[:take], ranked[take:]
    out = [(0.0, chosen)]
    for i in chosen:
        for o in rest:
            cost = _ln(value(i)) - _ln(value(o))
            if cost < near_tie:
                out.append((cost, [m for m in chosen if m != i] + [o]))
    return sorted(out, key=lambda c: c[0])


def selections(scores: np.ndarray, cfg, near_tie: float = 0.0):
    """Group-limited greedy selection of one token, a plain loop over
    its (E,) scores; ties go to the lower index. Returns [(margin given
    up, expert ids)]: first the selection itself (0, the ``top_k`` best
    of the ``topk_group`` best groups), then, cheapest first, every
    selection that differs from it by one near tie at the group level
    and one at the expert level (:func:`_exchanges`)."""
    G, K = cfg["n_group"], cfg["top_k"]
    per = len(scores) // G
    best = [max(scores[g * per:(g + 1) * per]) for g in range(G)]
    order = sorted(range(G), key=lambda g: (-best[g], g))
    out = []
    for group_cost, groups in _exchanges(order, cfg["topk_group"],
                                         lambda g: best[g], near_tie):
        kept = [e for g in sorted(groups)
                for e in range(g * per, (g + 1) * per)]
        ranked = sorted(kept, key=lambda e: (-scores[e], e))
        out += [(group_cost + cost, ids) for cost, ids in _exchanges(
            ranked, K, lambda e: scores[e], near_tie)]
    return sorted(out, key=lambda c: c[0])


def select(scores: np.ndarray, cfg) -> list:
    """The selection itself: expert ids, best first."""
    return selections(scores, cfg)[0][1]


def _token_weights(scores, cfg):
    """(T, held) weight of each held expert for each token (0 where not
    chosen): the selection above, vectorised for whole sequences."""
    T, E = scores.shape
    G, per = cfg["n_group"], scores.shape[1] // cfg["n_group"]
    by_group = scores.reshape(T, G, per)
    _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), cfg["topk_group"])
    keep = jnp.any(best[:, :, None] == jnp.arange(G)[None, None], axis=1)
    kept = jnp.where(keep[:, :, None], by_group, 0.0).reshape(T, E)
    top, ids = jax.lax.top_k(kept, cfg["top_k"])
    w = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], ids].add(
        top * cfg["scaling"])
    return w[:, cfg["first"]:cfg["first"] + cfg["held"]]


@jax.jit
def _expert_add(acc, h, wg, wu, wd, weight):
    y = _op(jax.nn.silu(_op(h) @ _op(wg)) * (_op(h) @ _op(wu))) @ _op(wd)
    return acc + weight[:, None] * y


def experts(h, w, cfg, weights):
    """``weights`` (T, held): the routed part over the held experts plus
    the shared experts, (T, hidden)."""
    acc = swiglu(h, w["shared"])
    ex = w["experts"]
    for e in range(cfg["held"]):
        acc = _expert_add(acc, h, ex["w_gate"][e], ex["w_up"][e],
                          ex["w_down"][e], weights[:, e])
    return acc


def layer(x, c_kv, k_pe, w, cfg):
    """One block over every row of ``x`` (S, hidden) float32."""
    rows = np.arange(x.shape[0])
    x = x + attention(x, rows, c_kv, k_pe, w, cfg)
    h = rmsnorm(x, w["post_norm"], cfg["eps"])
    if "ffn" in w:
        return x + swiglu(h, w["ffn"])
    return x + experts(h, w, cfg,
                       _token_weights(router_scores(h, w), cfg))


def resolutions(weights, history, config: dict, near_tie: float = 0.0,
                max_steps: int = MAX_STEPS):
    """Yields (logits (vocabulary,), margin given up) of the position
    after ``history`` under the resolutions of the last position's
    router near ties, best first (module docstring): a best-first search
    over the last row's way through the stack, a step one layer of one
    row, at most ``max_steps`` steps. The first is the reference's own
    choice everywhere; with ``near_tie`` 0 it is the only one.
    Resolutions that give the held experts the same weights are one."""
    import heapq

    cfg = widths(config)
    layers = weights["layers"]
    head = weights["head"] if "head" in weights else weights["item_emb"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["item_emb"], jnp.asarray(history), axis=0
                     ).astype(F32)
        S = x.shape[0]
        lat = []
        for n, w in enumerate(layers):
            lat.append(latents(x, w, cfg))
            if n < len(layers) - 1:
                x = layer(x, *lat[-1], w, cfg)
        del x
        start = jnp.take(weights["item_emb"], jnp.asarray(history[-1:]),
                         axis=0).astype(F32)
        # (margin given up, tie-break, layer to enter, the row before it,
        # the held experts' weights chosen for the layer just left)
        frontier, pushed = [(0.0, 0, 0, start, None)], 1
        for _ in range(max_steps):
            if not frontier:
                return
            cost, _, n, row, chosen = heapq.heappop(frontier)
            if chosen is not None:              # finish the layer just left
                w = layers[n - 1]
                h = rmsnorm(row, w["post_norm"], cfg["eps"])
                row = row + experts(h, w, cfg, jnp.asarray(chosen))
            if n == len(layers):
                h = rmsnorm(row[0], weights["out_norm"], cfg["eps"])
                yield jnp.concatenate(
                    [_matmul(head[lo:lo + 32768], h)
                     for lo in range(0, head.shape[0], 32768)]), cost
                continue
            w = layers[n]
            c_row, k_row = latents(row, w, cfg, first=S - 1)
            row = row + attention(
                row, np.asarray([S - 1]), lat[n][0].at[S - 1].set(c_row[0]),
                lat[n][1].at[S - 1].set(k_row[0]), w, cfg)
            h = rmsnorm(row, w["post_norm"], cfg["eps"])
            if "ffn" in w:
                heapq.heappush(frontier, (cost, pushed, n + 1,
                                          row + swiglu(h, w["ffn"]), None))
                pushed += 1
                continue
            scores = np.asarray(router_scores(h, w)[0], np.float64)
            seen = set()
            for extra, ids in selections(scores, cfg, near_tie):
                wt = np.zeros((1, cfg["held"]), np.float32)
                for e in ids:
                    if cfg["first"] <= e < cfg["first"] + cfg["held"]:
                        wt[0, e - cfg["first"]] = scores[e] * cfg["scaling"]
                if wt.tobytes() not in seen:
                    seen.add(wt.tobytes())
                    heapq.heappush(frontier,
                                   (cost + extra, pushed, n + 1, row, wt))
                    pushed += 1


def last_logits(weights, history, config: dict):
    """Logits (vocabulary,) of the position after ``history``."""
    return next(resolutions(weights, history, config))[0]


def expert_layer(h, w, config: dict):
    """The expert layer alone over (T, hidden) normed inputs: what the
    tests hold ``ops.moe`` and the shares' sum against."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        h = h.astype(F32)
        return experts(h, w, cfg, _token_weights(router_scores(h, w), cfg))


def mla(x, w, config: dict):
    """The attention sublayer alone over (S, hidden): tests."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        x = x.astype(F32)
        return attention(x, np.arange(x.shape[0]), *latents(x, w, cfg), w,
                         cfg)
