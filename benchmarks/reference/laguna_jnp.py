"""Plain reference of the Laguna block stack as the session engine
serves it: the logits of a history's last position.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no batching. Both attentions are dense masked
scores, a block of query rows at a time (a sliding layer's block against
the band of keys its window reaches, a full layer's against every key);
the experts are a loop over the held ones, each over every row. It
computes nothing with ``predictionio_tpu.models`` or
``predictionio_tpu.ops`` (what it asks of them is under "Near ties"
below): its rotary tables and YaRN frequencies are written out here, in
the published lane order. It takes the weights that are served,
whatever type they are held in, and upcasts them a matrix at a time, so
that S = 16,384 at the published widths fits beside the served copy.

Layer l of type ``layer_types[l]`` (full or sliding) has
``num_attention_heads_per_layer[l]`` = H query heads over G key/value
heads of d, eps ``rms_norm_eps``::

    h = rmsnorm(x, in_norm)
    q = h wq (S, H d);  k = h wk, v = h wv (S, G d);  g = sigmoid(h wg) (S, H)
    q, k = rmsnorm per head (q_norm, k_norm), then rotary:
        sliding: RoPE, theta 10,000, over all d lanes, pairs (i, i + d/2)
        full:    lanes 0 .. r-1 (r = d partial_rotary_factor = 64), pairs
                 (i, i + r/2), YaRN frequencies (theta 500,000, factor 128,
                 original 8,192, beta 32 / 1), cos and sin times
                 attention_factor 1.4852; lanes r .. d-1 pass through
    a_h(t) = sum_{i in N(t)} softmax_i(q_h(t) . k_{h/R}(i) / sqrt(d)) v_{h/R}(i)
        full:    N(t) = {i <= t};   sliding: N(t) = {t - W < i <= t}, W = 512
    x = x + concat_h(g_h(t) a_h(t)) wo
    h = rmsnorm(x, post_norm)
    dense (layer 0):  x = x + swiglu_12288(h)
    sparse:           p = softmax(h router) over all 256 experts
                      E = the top_k (10) highest, ties to the lower index
                      w_e = scaling (2.5) p_e / sum_E p       (all ten, held or not)
                      x = x + sum_{e in E, held} w_e swiglu_1024^e(h) + swiglu_1024^shared(h)
    swiglu(h) = (silu(h w_gate) * (h w_up)) w_down

then a final RMSNorm and the untied head. What experts outside the
share would add is left out, as in the program.

**Near ties in the router.** Softmax scores over 256 experts, ten
chosen: where the tenth and eleventh lie closer than the served path's
bfloat16 rounding, the program may choose the other. As in
``nemotron_h_jnp`` (whose module docstring has the reasoning),
:func:`resolutions` asks the served forward pass for the experts it
chose at every position (:func:`served_choices`: ids, no number, the
last output of the program that answered) and adopts a token's served
choice where the reference's own float32 scores call it a near tie:
where the weakest expert the program chose scores within ``near_tie``
(``ln`` of the ratio of scores) of the reference's own tenth. Anywhere
else the reference keeps its own choice. The weights are the
reference's own scores at the ids used.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# -- the limits ``harness/seq_ref_check`` holds a served answer to -----------
# Each lies between two readings taken on one v5e at the cell's shape
# (tools/laguna_controls.py and the cell's own runs; PERF.md section 6
# has every one): the served path's largest, and the smallest a fault
# the check must refuse reads, each held as the check holds it.
#: |returned score - reference logit of that item|, in logits (~N(0, 1)
#: over the catalog). The served path: 0.021-0.082 over 46 answers of
#: nine seeds, the reference following its choices within ``NEAR_TIE``.
#: Faults: this reference with both operands of every
#: product rounded to 8 bits (float8 e4m3, the nearest precision below
#: the stated one) 3.03, the sliding layers without their window 0.218,
#: no output gate 3.61, full-width rotary in the full layers 5.17. Under
#: it, and so not seen: attention's probabilities in bfloat16 (0.010) and
#: the router's scores rounded to bfloat16 (0.001)
SCORE_TOL = 0.12
#: a returned item may rank below the reference's tenth, and a reference
#: top-ten item may be missing, only if its reference logit is this
#: close to the tenth's: two scores' worth (the served path: 0.036 at
#: most)
RANK_TOL = 2 * SCORE_TOL
#: a token's served choice of experts is adopted where the weakest of
#: them scores within this (``ln`` of the ratio of scores) of the
#: reference's own ``top_k``-th. The served path's choices lie up to
#: 0.149 under the reference's own (46 answers of nine seeds; ~10,900
#: of an answer's 65,536 tokens x layers differ from the reference's own
#: choice). Softmax scores over 256 experts make this the logits' own
#: gap, so the bfloat16 path's flips lie further off than a sigmoid
#: router's: at Nemotron-H's 0.03 the reference kept its own choice on
#: ~1,150 tokens an answer, and where one was a last position's the
#: answer read 0.125 where, followed, it reads 0.026. Faults choose much
#: further off: at this margin 8-bit operands leave 62,857 choices
#: unfollowed and the sliding layers without their window 8,407, and
#: each fault is refused by the scores at 0.03, 0.1 and 0.2 alike
NEAR_TIE = 0.2
#: resolutions :func:`resolutions` yields: the one that follows the
#: served path through its near ties
MAX_STEPS = 1
#: query rows of attention worked at a time
ROWS = 128

#: None: float32 everywhere (the reference). The tools set one of these
#: for the readings that have to come out as not correct: "operands"
#: rounds both operands of every product to 8 bits (float8 e4m3, the
#: nearest precision below the stated bfloat16), "softmax" keeps
#: attention's probabilities and their sums in bfloat16, "router" rounds
#: the router's scores to bfloat16 before the selection
_LOWER = None
LOWER = ("operands", "softmax", "router")


def set_lower(what) -> None:
    """Every compiled piece below is keyed by the precision it was
    traced under, so a change compiles that piece anew."""
    global _LOWER
    assert what is None or what in LOWER
    _LOWER = what


def _op(x, lower):
    x = x.astype(F32)
    return x.astype(jnp.float8_e4m3fn).astype(F32) if lower == "operands" \
        else x


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits, float32 out (not a pair of
    casts, which a compiler may keep in float32)."""
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=8,
                                    mantissa_bits=7)


def widths(config: dict) -> dict:
    """The configuration file's keys under the names used here. The
    ``control_*`` keys take one mechanism away (tools/laguna_controls.py,
    the tests): none is a key of a configuration that is served."""
    held = config.get("experts_held", (0, config["num_experts"]))
    rope = config["rope_parameters"]
    return {"types": tuple(config["layer_types"]),
            "heads": tuple(config["num_attention_heads_per_layer"]),
            "mlp": tuple(config["mlp_layer_types"]),
            "kv": config["num_key_value_heads"], "d": config["head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "window": int(config["sliding_window"]),
            "full": _frozen(rope["full_attention"]),
            "sliding": _frozen(rope["sliding_attention"]),
            "first": int(held[0]), "held": int(held[1]),
            "top_k": config["num_experts_per_tok"],
            "scaling": float(config["moe_routed_scaling_factor"]),
            "no_window": config.get("control_no_window", False),
            "no_gate": config.get("control_no_gate", False),
            "full_rotary": config.get("control_full_rotary", False)}


def _frozen(cfg: dict):
    return tuple(sorted(cfg.items()))


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


@functools.partial(jax.jit, static_argnames=("lower",))
def _matmul_at(x, w, lower):
    return _op(x, lower) @ _op(w, lower)


def _matmul(x, w):
    return _matmul_at(x, w, lower=_LOWER)


# -- positions ------------------------------------------------------------------

def inv_freq(rope: dict, rotary: int) -> np.ndarray:
    """(rotary / 2,) frequencies of a layer type's ``rope_parameters``:
    ``theta**(-2i / rotary)``; with ``rope_type`` "yarn" pair i keeps it
    below ``low``, takes it over ``factor`` above ``high``, and blends
    linearly between, ``low`` and ``high`` the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original length."""
    base = float(rope["rope_theta"])
    freq = base ** (-np.arange(0, rotary, 2) / rotary)
    if rope.get("rope_type", "default") != "yarn":
        return freq

    def pair(turns):
        return rotary * math.log(rope["original_max_position_embeddings"]
                                 / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), rotary - 1)
    ramp = np.clip((np.arange(rotary // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return freq * (1 - ramp) + freq / rope["factor"] * ramp


def rotate(x, positions, rope: dict, d: int, full_rotary: bool = False):
    """x (T, heads, d) at ``positions`` (T,): the rotary lanes turned as
    the layer type says (``partial_rotary_factor``, ``attention_factor``
    on cos and sin), the rest passed through. ``full_rotary``: every
    lane turned (a control)."""
    factor = 1.0 if full_rotary else rope.get("partial_rotary_factor", 1.0)
    r = int(d * factor)
    ang = np.asarray(positions, np.float64)[:, None] \
        * inv_freq(rope, r)[None, :]                   # (T, r / 2)
    mag = rope.get("attention_factor", 1.0)
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1) * mag, F32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1) * mag, F32)
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    return jnp.concatenate([rot * cos[:, None] + half * sin[:, None], rest],
                           -1)


# -- attention ------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lower",))
def _attend(q, k, v, ok, lower):
    """q (G, R, r, d) scaled, k, v (G, n, d), ``ok`` (r, n) -> (r, G, R,
    d): softmax over the keys ``ok`` lets through."""
    logits = jnp.einsum("grtd,gsd->grts", _op(q, lower), _op(k, lower))
    logits = jnp.where(ok[None, None], logits, -jnp.inf)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    if lower == "softmax":
        p = p.astype(jnp.bfloat16)
        den = jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.bfloat16)
        out = jnp.einsum("grts,gsd->tgrd", p, v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.bfloat16)
        return (out / den.transpose(2, 0, 1, 3)).astype(F32)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("grts,gsd->tgrd", _op(p, lower), _op(v, lower))


def attention_rows(u, w, cfg, layer_type: str, H: int):
    """The attention part of every row of the normed ``u`` (S, hidden):
    (S, hidden)."""
    S, G, d = u.shape[0], cfg["kv"], cfg["d"]
    R = H // G
    rope = dict(cfg["full" if layer_type == "full_attention" else "sliding"])
    positions = np.arange(S)
    full_rotary = cfg["full_rotary"] and layer_type == "full_attention"

    def heads(name, n, norm):
        x = _matmul(u, w[name]).reshape(S, n, d)
        x = rmsnorm(x, w[norm], cfg["eps"])
        return rotate(x, positions, rope, d, full_rotary)

    q = heads("wq", H, "q_norm") / math.sqrt(d)
    k, v = heads("wk", G, "k_norm"), _matmul(u, w["wv"]).reshape(S, G, d)
    q = q.reshape(S, G, R, d).transpose(1, 2, 0, 3)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    window = S if cfg["no_window"] or layer_type == "full_attention" \
        else cfg["window"]
    band = min(S, window - 1 + ROWS)
    out = []
    for lo in range(0, S, ROWS):
        rows = np.arange(lo, min(lo + ROWS, S))
        start = max(0, min(lo - (window - 1), S - band))
        keys = np.arange(start, start + band)
        back = rows[:, None] - keys[None, :]
        ok = jnp.asarray((back >= 0) & (back < window))
        out.append(_attend(q[:, :, rows], k[:, start:start + band],
                           v[:, start:start + band], ok, lower=_LOWER))
    a = jnp.concatenate(out, axis=0)                     # (S, G, R, d)
    if not cfg["no_gate"]:
        gate = jax.nn.sigmoid(_matmul(u, w["wg"]))      # (S, H)
        a = a * gate.reshape(S, G, R)[..., None]
    return _matmul(a.reshape(S, H * d), w["wo"])


# -- the feed-forward layers ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lower",))
def _swiglu(h, gate, up, down, lower=None):
    a = jax.nn.silu(_op(h, lower) @ _op(gate, lower)) \
        * (_op(h, lower) @ _op(up, lower))
    return _op(a, lower) @ _op(down, lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def _expert_add(acc, h, gate, up, down, weight, lower=None):
    return acc + weight[:, None] * _swiglu(h, gate, up, down, lower)


def swiglu_rows(u, ffn, block=4096):
    return jnp.concatenate(
        [_swiglu(u[lo:lo + block], ffn["w_gate"], ffn["w_up"], ffn["w_down"],
                 lower=_LOWER) for lo in range(0, u.shape[0], block)], axis=0)


def router_scores(u, w):
    s = jax.nn.softmax(_matmul(u, w["router"]), axis=-1)
    return _bf16(s) if _LOWER == "router" else s


def select(scores: np.ndarray, cfg) -> list:
    """The selection of one token, a plain loop over its (E,) scores:
    the ``top_k`` highest, ties to the lower index; expert ids, best
    first. What :func:`choose` does for whole sequences (the tests hold
    the two together)."""
    return sorted(range(len(scores)), key=lambda e: (-scores[e], e))[
        :cfg["top_k"]]


@functools.partial(jax.jit, static_argnames=("top_k",))
def choose(scores, served, near_tie, *, top_k):
    """The experts each token's part is computed from: ``scores`` (T,
    E), ``served`` (T, top_k) the ids the served path chose. Returns (ids
    (T, top_k), the margin each adopted token gave up (T,), 0 where the
    choice is the reference's own; whether the served choice was a near
    tie (T,))."""
    top, own = jax.lax.top_k(scores, top_k)
    weakest = jnp.min(jnp.take_along_axis(scores, served, axis=1), axis=1)
    margin = jnp.log(jnp.maximum(top[:, -1], 1e-30)) \
        - jnp.log(jnp.maximum(weakest, 1e-30))
    near = margin < near_tie
    return (jnp.where(near[:, None], served, own),
            jnp.where(near, margin, 0.0), near | (margin <= 0.0))


@functools.partial(jax.jit, static_argnames=("first", "held", "scaling"))
def token_weights(scores, ids, *, first, held, scaling):
    """(T, held) weight of each held expert for each token under the
    choice ``ids`` (0 where not chosen), renormalised over all chosen."""
    T, E = scores.shape
    top = jnp.take_along_axis(scores, ids, axis=1)
    top = scaling * top / (jnp.sum(top, axis=1, keepdims=True) + 1e-20)
    w = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], ids].add(top)
    return w[:, first:first + held]


def expert_rows(u, w, cfg, served=None, near_tie: float = 0.0):
    """The expert part over the rows ``u`` (T, hidden), already normed:
    the held experts by their weights plus the shared expert. Returns
    ((T, hidden), the ids used (T, top_k), margins (T,), near (T,)) as
    :func:`choose` gives them."""
    scores = router_scores(u, w)
    if served is None:
        _, served = jax.lax.top_k(scores, cfg["top_k"])
    ids, margin, near = choose(scores, jnp.asarray(served), near_tie,
                               top_k=cfg["top_k"])
    weights = token_weights(scores, ids, first=cfg["first"],
                            held=cfg["held"], scaling=cfg["scaling"])
    acc = swiglu_rows(u, w["shared"])
    ex = w["experts"]
    for e in range(cfg["held"]):
        acc = _expert_add(acc, u, ex["w_gate"][e], ex["w_up"][e],
                          ex["w_down"][e], weights[:, e], lower=_LOWER)
    return acc, ids, margin, near


# -- the stack -------------------------------------------------------------------

def layer(x, w, cfg, n: int, served=None, near_tie: float = 0.0):
    """Layer ``n`` over every row of ``x`` (S, hidden) float32. Returns
    (x after, what an expert layer chose: :func:`expert_rows`' last
    three, else None)."""
    u = rmsnorm(x, w["in_norm"], cfg["eps"])
    x = x + attention_rows(u, w, cfg, cfg["types"][n], cfg["heads"][n])
    u = rmsnorm(x, w["post_norm"], cfg["eps"])
    if cfg["mlp"][n] == "dense":
        return x + swiglu_rows(u, w["ffn"]), None
    y, *chose = expert_rows(u, w, cfg, served, near_tie)
    return x + y, chose


def served_choices(weights, history, config: dict,
                   num: int = 10) -> np.ndarray:
    """The experts the served program chose over ``history``: (expert
    layers, S, top_k) int32, the last output of
    ``models/seqrec.predict_topk_batch`` called as
    ``templates/sessionrec.batch_predict`` calls it for one query of
    ``num`` items under the ``AlgorithmParams`` the configuration's file
    states: the executable that answered, so the choices are its own to
    the bit."""
    from benchmarks.harness import seq_ref_data
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops.topk import serving_k

    cfg = seq_ref_data.algorithm_params(config).seqrec_config(
        vocab=config["vocab_size"])
    history = np.asarray(history, np.int32)[-cfg.max_len:]
    padded = np.zeros((1, cfg.max_len), np.int32)
    padded[0, :len(history)] = history
    return np.asarray(seqrec.predict_topk_batch(
        weights, padded, serving_k(num, cfg.vocab - 1), cfg,
        np.zeros((1, cfg.vocab), np.float32))[-1])[:, 0, :len(history)]


def answer(weights, history, config: dict, choices=None,
           near_tie: float = 0.0):
    """(logits (vocabulary,) of the position after ``history``, what
    the expert layers chose). ``choices`` (expert layers, S, top_k):
    the ids another path chose, adopted where they are near ties; None:
    the reference's own everywhere. The second result: ``ids`` as used,
    ``margin`` the most an adopted token gave up, ``adopted`` the tokens
    whose adopted choice is not the reference's own and ``refused``
    those whose offered choice was no near tie."""
    cfg = widths(config)
    used, margin, adopted, refused, m = [], 0.0, 0, 0, 0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["item_emb"], jnp.asarray(history), axis=0
                     ).astype(F32)
        for n, w in enumerate(weights["layers"]):
            served = None
            if cfg["mlp"][n] == "sparse" and choices is not None:
                served, m = choices[m], m + 1
            x, chose = layer(x, w, cfg, n, served, near_tie)
            if chose:
                ids, gave_up, near = (np.asarray(a) for a in chose)
                used.append(ids)
                margin = max(margin, float(gave_up.max()))
                adopted += int((gave_up > 0).sum())
                refused += int((~near).sum())
        h = rmsnorm(x[-1], weights["out_norm"], cfg["eps"])
        head = weights["head"]
        logits = jnp.concatenate(
            [_matmul(head[lo:lo + 32768], h)
             for lo in range(0, head.shape[0], 32768)])
    return logits, {"ids": np.stack(used) if used else None,
                    "margin": margin, "adopted": adopted,
                    "refused": refused}


def resolutions(weights, history, config: dict, near_tie: float = 0.0,
                max_steps: int = MAX_STEPS, choices=None):
    """Yields (logits (vocabulary,), margin given up) of the position
    after ``history``: the one resolution of the router's near ties that
    follows the path whose answer is held to it. ``choices``: that
    path's, from :func:`served_choices` when not given; with
    ``near_tie`` 0 nothing is adopted."""
    if choices is None and near_tie > 0:
        choices = served_choices(weights, history, config)
    logits, chose = answer(weights, history, config, choices, near_tie)
    yield logits, chose["margin"]


def last_logits(weights, history, config: dict):
    """Logits (vocabulary,) of the position after ``history``, the
    reference's own choice at every near tie."""
    return answer(weights, history, config)[0]


def hidden_states(layers, x, config: dict, first: int = 0):
    """``x`` (S, hidden) through ``layers``, every row, the first being
    layer ``first`` of the configuration's lists: what the tests hold
    the depth cut against."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        x = x.astype(F32)
        for n, w in enumerate(layers):
            x, _ = layer(x, w, cfg, first + n)
        return x


def attention_layer(u, w, config: dict, n: int):
    """Layer ``n``'s attention part alone over (S, hidden) normed
    inputs: tests."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        return attention_rows(u.astype(F32), w, cfg, cfg["types"][n],
                              cfg["heads"][n])


def expert_layer(u, w, config: dict):
    """The expert part alone over (T, hidden) normed inputs: what the
    tests hold ``ops.moe`` and the shares' sum against."""
    with jax.default_matmul_precision("highest"):
        return expert_rows(u.astype(F32), w, widths(config))[0]
