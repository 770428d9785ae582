"""Plain reference of the MiniCPM-SALA block stack as the session engine
serves it: the logits of a history's last position.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no chunk scan, no carried state, no batching. Lightning
attention is the masked quadratic form ``((q k^T) * decay) v``, sparse
attention dense scores under the mask the selection gives, both a block
of query rows at a time against every key, so that S = 32,768 at the
published widths fits beside the served copy. It shares no code with
``predictionio_tpu.models`` or ``predictionio_tpu.ops``; it takes the
weights that are served, whatever type they are held in, and upcasts
them a matrix at a time.

Per layer, ``x`` (S, hidden), no biases, RMSNorm eps 1e-6, ``c =
scale_depth / sqrt(published depth)``::

    x = x + c mixer(rmsnorm(x, in_norm))
    x = x + c swiglu(rmsnorm(x, post_norm))
    x_0 = scale_emb item_emb[ids]
    logits = rmsnorm(x_L, out_norm) / (hidden / dim_model_base) head^T

``lightning-attn`` (h the normed input, H heads of d)::

    q, k = rope(rmsnorm_d(h wq)), rope(rmsnorm_d(h wk));  v = h wv
    o[t, a] = sum_{i <= t} lambda_a^(t - i) (q[t, a] . k[i, a] / sqrt(d)) v[i, a]
    lambda_a = exp(-2^(-8 a / H)), a = 1..H
    y = (rmsnorm(o, o_norm) * sigmoid(h wg)) wo

``minicpm4`` (H query heads over G key/value heads, no positions)::

    q, k = rmsnorm_d(h wq), rmsnorm_d(h wk);  v = h wv
    kc[j, g] = mean(k[stride j : stride j + kernel, g])
    p[t, a, .] = softmax_j(q[t, a] . kc[j, g(a)] / sqrt(d)),  stride j + kernel - 1 <= t
    P[t, g, j] = sum_{a in g} p[t, a, j]
    B[t, g, b] = max_{j = ratio b - 1 .. ratio b + ratio - 1} P[t, g, j]
    forced (+inf): b < init_blocks, and the window / block blocks ending at t's own
    sel[t, g] = the topk highest blocks b <= t's own (every one where fewer are visible)
    o[t, a] = softmax over keys i <= t in blocks of sel[t, g(a)] of (q[t, a] . k[i, g] / sqrt(d)) v[i, g]
    S <= dense_len: plain causal attention, no selection
    y = (o * sigmoid(h wg)) wo

**Near ties in the selection.** The served path's activations are
bfloat16, so where two blocks' scores at the last position lie closer
than that rounding the program may keep the other one. :func:`resolutions`
yields the logits under the resolutions of the last position's near
ties, best first: the reference's own choice, then those that give up
the least margin (a kept block exchanged for a dropped one whose score
is within ``near_tie`` of it, ``ln`` of the ratio). The positions before
the last are worked once, whole; the last row is then taken through the
stack once per resolution. A lightning layer's history enters the last
row as ``sum_{i < t} lambda^(t - i) k_i v_i^T``, one sum over all
positions (not a recurrence). A flip at an *earlier* position is not
resolved and shows as noise.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# -- the limits ``harness/seq_ref_check`` holds a served answer to -----------
#: |returned score - reference logit of that item|, in logits. The head's
#: 1 / (hidden / dim_model_base) = 1/16 makes a seeded model's logits
#: ~N(0, 0.06) over the catalog, the top ten between 0.22 and 0.28. The
#: limit lies between two readings on the chip (PERF.md section 6,
#: PR 34), 1.9 times above the one and 1.9 under the other: the served
#: path's worst over 40 checked queries of 13 seeds, 0.0187 (0.008 to
#: 0.017 more often: not the rounding of the stream, which is 0.0005,
#: but blocks chosen otherwise at positions *before* the last, where the
#: served path's bfloat16 queries and keys turn near ties of the
#: selection and only the last position's are resolved: the reference
#: with its block scores rounded to bfloat16 reads 0.012 to 0.013
#: against itself), and this reference with both operands of every
#: product rounded to 8 bits (float8 e4m3), whose own top ten are off by
#: 0.065 to 0.077 and so come out as not correct
SCORE_TOL = 0.035
#: a returned item may rank below the reference's tenth, and a reference
#: top-ten item may be missing, only if its reference logit is this
#: close to the tenth's: two scores' worth (the worst seen is 0.017; the
#: 8-bit reading is 0.044 to 0.067, so it fails by the limit above)
RANK_TOL = 2 * SCORE_TOL
#: two block scores of the last position are a near tie when the ``ln``
#: of their ratio is under this: a kept block that close to a dropped
#: one may have been exchanged for it by the served path's bfloat16
#: queries and keys (the margin given up when a second resolution was
#: needed read 0.001 to 0.003)
NEAR_TIE = 0.1
#: search steps (one layer of one row each, ~0.1 s on the chip; the
#: whole rows before them take ~11 s once) the reference may spend on
#: one answer
MAX_STEPS = 200
#: query rows worked at a time
ROWS = 128

#: None: float32 everywhere (the reference). tools/seq_ref_precision.py
#: sets one of these for the readings that have to come out as not
#: correct: "operands" rounds both operands of every product to 8 bits
#: (float8 e4m3), "softmax" keeps attention's probabilities and their
#: sums in bfloat16, "router" (the tool's name for what chooses) rounds
#: the selection's block scores to bfloat16 before the top-k
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
    sparse = config["sparse_config"]
    return {"H": config["num_attention_heads"],
            "G": config["num_key_value_heads"], "d": config["head_dim"],
            "Hl": config["lightning_nh"], "dl": config["lightning_head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "c": config["scale_depth"] / math.sqrt(
                config.get("published", {}).get(
                    "num_hidden_layers", config["num_hidden_layers"])),
            "scale_emb": float(config["scale_emb"]),
            "head_scale": config["dim_model_base"] / config["hidden_size"],
            "mixers": tuple(config["mixer_types"]),
            "kernel": sparse["kernel_size"], "stride": sparse["kernel_stride"],
            "block": sparse["block_size"], "topk": sparse["topk"],
            "init": sparse["init_blocks"], "window": sparse["window_size"],
            "dense_len": sparse["dense_len"],
            # a control, never a configuration's: lightning attention cut
            # at every multiple of this many positions (0: not cut), as a
            # chunked scan that dropped its carried state would compute
            "cut": int(config.get("control_lightning_cut", 0))}


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """(T, heads, d) rotated by ``positions`` (T,): pairs (i, i + d/2),
    frequency theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@jax.jit
def _matmul(a, b):
    return _op(a) @ _op(b)


@jax.jit
def _swiglu_rows(h, w_gate, w_up, w_down):
    return _matmul(jax.nn.silu(_matmul(h, w_gate)) * _matmul(h, w_up), w_down)


def swiglu(h, w, rows: int = 4096):
    """A block of rows at a time: the hidden layer of 32,768 rows whole
    would be 2 GB an array."""
    return jnp.concatenate([
        _swiglu_rows(h[lo:lo + rows], w["w_gate"], w["w_up"], w["w_down"])
        for lo in range(0, h.shape[0], rows)])


def log_decay(heads: int):
    return -(2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads))


def _softmax(s):
    """Rows of masked scores -> probabilities; a row with nothing to
    see gives zeros."""
    seen = s > -1e29
    e = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    if _LOWER == "softmax":
        e = e.astype(jnp.bfloat16)
        return (e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True),
                                1e-30).astype(jnp.bfloat16)).astype(F32)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def _by_rows(fn, rows, *per_row):
    """``fn(positions (ROWS,), *blocks of per_row)`` over blocks of
    ``ROWS`` rows, the last padded by repeating the final row."""
    n = len(rows)
    pad = (-n) % ROWS
    take = np.concatenate([np.arange(n), np.full(pad, n - 1)])
    blocks = [jnp.asarray(rows)[take].reshape(-1, ROWS)] + [
        a[take].reshape(-1, ROWS, *a.shape[1:]) for a in per_row]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
    return out.reshape(-1, *out.shape[2:])[:n]


# -- lightning attention ------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("heads", "d", "eps", "theta"))
def _lightning_qkv(h, positions, wq, wk, wv, q_norm, k_norm, *, heads, d,
                   eps, theta):
    T = h.shape[0]
    q = rope(rmsnorm(_matmul(h, wq).reshape(T, heads, d), q_norm, eps),
             positions, theta)
    k = rope(rmsnorm(_matmul(h, wk).reshape(T, heads, d), k_norm, eps),
             positions, theta)
    return q, k, _matmul(h, wv).reshape(T, heads, d)


@functools.partial(jax.jit, static_argnames=("cut",))
def _lightning_rows(q, rows, k, v, cut=0):
    """o[t, a] = sum_{i <= t} lambda_a^(t - i) (q . k_i / sqrt(d)) v_i for
    query rows ``q`` (T, H, d) at positions ``rows`` against every key
    (``cut``: only those of the position's own run of that length)."""
    H, d = q.shape[1], q.shape[2]
    keys = jnp.arange(k.shape[0])

    def block(pos, qb):
        s = jnp.einsum("thd,shd->hts", _op(qb), _op(k)) / math.sqrt(d)
        gap = (pos[:, None] - keys[None, :]).astype(F32)        # (T, S)
        seen = gap >= 0
        if cut:
            seen = seen & (pos[:, None] // cut == keys[None, :] // cut)
        decay = jnp.exp(jnp.where(seen, log_decay(H)[:, None, None]
                                  * gap[None], -jnp.inf))
        return jnp.einsum("hts,shd->thd", _op(s * decay), _op(v))

    return _by_rows(block, rows, q)


def lightning_mixer(h, w, cfg):
    """The lightning-attn mixer over every row of ``h`` (S, hidden)."""
    S = h.shape[0]
    pos = jnp.arange(S)
    q, k, v = _lightning_qkv(h, pos, w["wq"], w["wk"], w["wv"], w["q_norm"],
                             w["k_norm"], heads=cfg["Hl"], d=cfg["dl"],
                             eps=cfg["eps"], theta=cfg["theta"])
    o = _lightning_rows(q, pos, k, v, cut=cfg["cut"]).reshape(S, -1)
    return _gated(rmsnorm(o, w["o_norm"], cfg["eps"]), h, w)


def _gated(o, h, w):
    return _matmul(o * jax.nn.sigmoid(_matmul(h, w["wg"])), w["wo"])


# -- sparse attention ---------------------------------------------------------


def compressed(k, cfg):
    """(S, G, d) -> (Nc, G, d): means of ``kernel`` keys every ``stride``."""
    S = k.shape[0]
    n = max((S - cfg["kernel"]) // cfg["stride"] + 1, 0)
    if n == 0:
        return jnp.zeros((0, *k.shape[1:]), F32)
    idx = (cfg["stride"] * np.arange(n)[:, None]
           + np.arange(cfg["kernel"])[None, :])
    return jnp.mean(k[idx], axis=1)


def block_scores(q, rows, kc, cfg, n_blocks):
    """Steps 2 to 5 for query rows ``q`` (T, H, d) at positions ``rows``:
    (G, T, n_blocks) with the forced blocks at +inf and the blocks after
    a position's own at -inf."""
    H, d = q.shape[1], q.shape[2]
    G, ratio = kc.shape[1], cfg["block"] // cfg["stride"]
    nc = kc.shape[0]
    rows = jnp.asarray(rows)
    blocks = jnp.arange(n_blocks)
    own = (rows // cfg["block"])[:, None]
    if nc:
        kch = jnp.repeat(kc, H // G, axis=1)                    # (Nc, H, d)
        s = jnp.einsum("thd,jhd->htj", _op(q), _op(kch)) / math.sqrt(d)
        ends = cfg["stride"] * jnp.arange(nc) + cfg["kernel"] - 1
        p = _softmax(jnp.where(ends[None, :] <= rows[:, None], s, -1e30))
        P = p.reshape(G, H // G, *p.shape[1:]).sum(axis=1)      # (G, T, Nc)
    else:
        P = jnp.zeros((G, len(rows), 0), F32)
    padded = jnp.pad(P, ((0, 0), (0, 0), (1, n_blocks * ratio - nc)),
                     constant_values=-jnp.inf)
    first = padded[..., :n_blocks * ratio].reshape(*P.shape[:2], n_blocks,
                                                   ratio).max(axis=-1)
    B = jnp.maximum(first, padded[..., ratio::ratio])
    if _LOWER == "router":
        B = B.astype(jnp.bfloat16).astype(F32)
    forced = (blocks < cfg["init"]) | \
        (blocks > own - cfg["window"] // cfg["block"])
    B = jnp.where(forced, jnp.inf, B)
    return jnp.where(blocks <= own, B, -jnp.inf)


def kept_blocks(B, cfg):
    """(..., n_blocks) block scores -> bool: the ``topk`` highest (ties
    to the lower block) that are visible."""
    n = B.shape[-1]
    order = jnp.argsort(-B, axis=-1, stable=True)[..., :min(cfg["topk"], n)]
    kept = jnp.any(order[..., None] == jnp.arange(n), axis=-2)
    return kept & (B > -jnp.inf)


def _attend(q, rows, k, v, kept, cfg):
    """Query rows (T, H, d) over the keys i <= t of the kept blocks
    (``kept`` (G, T, n_blocks) or None: every key i <= t)."""
    H, d = q.shape[1], q.shape[2]
    G, S = k.shape[1], k.shape[0]
    keys = jnp.arange(S)
    ok = keys[None, :] <= jnp.asarray(rows)[:, None]            # (T, S)
    ok = jnp.broadcast_to(ok, (G, *ok.shape))
    if kept is not None:
        ok = ok & jnp.repeat(kept, cfg["block"], axis=-1)[..., :S]
    ok = jnp.repeat(ok, H // G, axis=0)                         # (H, T, S)
    kh, vh = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    s = jnp.einsum("thd,shd->hts", _op(q), _op(kh)) / math.sqrt(d)
    p = _softmax(jnp.where(ok, s, -1e30))
    return jnp.einsum("hts,shd->thd", _op(p), _op(vh))


@functools.partial(jax.jit, static_argnames=("heads", "groups", "d", "eps"))
def _sparse_qkv(h, wq, wk, wv, q_norm, k_norm, *, heads, groups, d, eps):
    T = h.shape[0]
    q = rmsnorm(_matmul(h, wq).reshape(T, heads, d), q_norm, eps)
    k = rmsnorm(_matmul(h, wk).reshape(T, groups, d), k_norm, eps)
    return q, k, _matmul(h, wv).reshape(T, groups, d)


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnames=("frozen", "selects"))
def _sparse_rows(q, rows, k, v, kc, *, frozen, selects):
    cfg = dict(frozen)
    n_blocks = -(-k.shape[0] // cfg["block"])

    def block(pos, qb):
        kept = kept_blocks(block_scores(qb, pos, kc, cfg, n_blocks), cfg) \
            if selects else None
        return _attend(qb, pos, k, v, kept, cfg)

    return _by_rows(block, rows, q)


def sparse_mixer(h, w, cfg):
    """The minicpm4 mixer over every row of ``h`` (S, hidden)."""
    S = h.shape[0]
    q, k, v = _sparse_qkv(h, w["wq"], w["wk"], w["wv"], w["q_norm"],
                          w["k_norm"], heads=cfg["H"], groups=cfg["G"],
                          d=cfg["d"], eps=cfg["eps"])
    o = _sparse_rows(q, jnp.arange(S), k, v, compressed(k, cfg),
                     frozen=_frozen(cfg), selects=S > cfg["dense_len"])
    return _gated(o.reshape(S, -1), h, w)


# -- the stack ----------------------------------------------------------------


def layer(x, w, cfg, mixer: str):
    """One block over every row of ``x`` (S, hidden) float32."""
    h = rmsnorm(x, w["in_norm"], cfg["eps"])
    mix = sparse_mixer if mixer == "minicpm4" else lightning_mixer
    x = x + cfg["c"] * mix(h, w, cfg)
    return x + cfg["c"] * swiglu(rmsnorm(x, w["post_norm"], cfg["eps"]), w)


def hidden_states(layers, x, config: dict, mixers=None):
    """``x`` (S, hidden) through ``layers`` (their mixers ``mixers``,
    the file's list when not given): what the stack hands the final norm
    (tests: the depth cut against the uncut model)."""
    cfg = widths(config)
    with jax.default_matmul_precision("highest"):
        x = x.astype(F32)
        for w, mixer in zip(layers, mixers or cfg["mixers"]):
            x = layer(x, w, cfg, mixer)
        return x


def embed(weights, history, cfg):
    return jnp.take(weights["item_emb"], jnp.asarray(history),
                    axis=0).astype(F32) * cfg["scale_emb"]


def selections(scores: np.ndarray, cfg, near_tie: float, most: int = 4):
    """[(margin given up, kept blocks as a sorted tuple)] for one group's
    block scores of one position, best first: the ``topk`` highest, then
    that with one kept block exchanged for a dropped one whose score is
    within ``near_tie`` of it (``ln`` of the ratio), at most ``most``."""
    visible = np.flatnonzero(scores > -np.inf)
    order = visible[np.argsort(-scores[visible], kind="stable")]
    k = min(cfg["topk"], len(order))
    own = order[:k]
    found = [(0.0, tuple(sorted(own.tolist())))]
    if near_tie <= 0 or k == len(order):
        return found
    swaps = []
    for s in own[::-1]:
        if not np.isfinite(scores[s]) or scores[s] <= 0:
            continue
        for u in order[k:]:
            if scores[u] <= 0:
                break
            margin = float(np.log(scores[s] / scores[u]))
            if margin >= near_tie:
                break
            swaps.append((margin, int(s), int(u)))
    for margin, s, u in sorted(swaps)[:most - 1]:
        found.append((margin, tuple(sorted(set(own.tolist()) - {s} | {u}))))
    return found


def resolutions(weights, history, config: dict, near_tie: float = 0.0,
                max_steps: int = MAX_STEPS):
    """Yields (logits (vocabulary,), margin given up) of the position
    after ``history`` under the resolutions of the last position's
    selection near ties, best first (module docstring): a best-first
    search over the last row's way through the stack, a step one layer
    of one row, at most ``max_steps`` steps. The first is the
    reference's own choice everywhere; with ``near_tie`` 0 it is the
    only one."""
    import heapq
    import itertools

    cfg = widths(config)
    layers, mixers = weights["layers"], cfg["mixers"]
    S = len(history)
    t = S - 1
    selects = S > cfg["dense_len"]
    with jax.default_matmul_precision("highest"):
        x = embed(weights, history, cfg)
        start = x[t:]
        # what the last row needs of the rows before it, layer by layer
        past = []
        for n, (w, mixer) in enumerate(zip(layers, mixers)):
            h = rmsnorm(x, w["in_norm"], cfg["eps"])
            if mixer == "minicpm4":
                _, k, v = _sparse_qkv(
                    h, w["wq"], w["wk"], w["wv"], w["q_norm"], w["k_norm"],
                    heads=cfg["H"], groups=cfg["G"], d=cfg["d"],
                    eps=cfg["eps"])
                past.append((k, v))
            else:
                _, k, v = _lightning_qkv(
                    h, jnp.arange(S), w["wq"], w["wk"], w["wv"], w["q_norm"],
                    w["k_norm"], heads=cfg["Hl"], d=cfg["dl"], eps=cfg["eps"],
                    theta=cfg["theta"])
                weight = jnp.exp(log_decay(cfg["Hl"])[None, :]
                                 * (t - jnp.arange(t, dtype=F32))[:, None])
                if cfg["cut"]:
                    weight = jnp.where(
                        (jnp.arange(t) // cfg["cut"] == t // cfg["cut"])
                        [:, None], weight, 0.0)
                past.append(jnp.einsum("sh,shd,she->hde", weight, _op(k[:t]),
                                       _op(v[:t])))
            del h, k, v
            if n < len(layers) - 1:
                x = layer(x, w, cfg, mixer)
        del x

        def mix_last(n, row, kept):
            """Layer ``n`` over the last row alone."""
            w, mixer = layers[n], mixers[n]
            h = rmsnorm(row, w["in_norm"], cfg["eps"])
            if mixer == "minicpm4":
                q, k1, v1 = _sparse_qkv(
                    h, w["wq"], w["wk"], w["wv"], w["q_norm"], w["k_norm"],
                    heads=cfg["H"], groups=cfg["G"], d=cfg["d"],
                    eps=cfg["eps"])
                k = past[n][0].at[t].set(k1[0])
                v = past[n][1].at[t].set(v1[0])
                o = _attend(q, np.asarray([t]), k, v, kept, cfg)
            else:
                q, k1, v1 = _lightning_qkv(
                    h, jnp.asarray([t]), w["wq"], w["wk"], w["wv"],
                    w["q_norm"], w["k_norm"], heads=cfg["Hl"], d=cfg["dl"],
                    eps=cfg["eps"], theta=cfg["theta"])
                scale = 1.0 / math.sqrt(cfg["dl"])
                o = jnp.einsum("thd,hde->the", _op(q), past[n]) * scale \
                    + jnp.sum(_op(q) * _op(k1), axis=-1,
                              keepdims=True) * scale * v1
                o = rmsnorm(o.reshape(1, -1), w["o_norm"], cfg["eps"])
            row = row + cfg["c"] * _gated(o.reshape(1, -1), h, w)
            return row + cfg["c"] * swiglu(
                rmsnorm(row, w["post_norm"], cfg["eps"]), w)

        def last_scores(n, row):
            """(G, n_blocks) block scores of the last row at layer n."""
            w = layers[n]
            h = rmsnorm(row, w["in_norm"], cfg["eps"])
            q, k1, _ = _sparse_qkv(
                h, w["wq"], w["wk"], w["wv"], w["q_norm"], w["k_norm"],
                heads=cfg["H"], groups=cfg["G"], d=cfg["d"], eps=cfg["eps"])
            kc = compressed(past[n][0].at[t].set(k1[0]), cfg)
            return np.asarray(block_scores(
                q, np.asarray([t]), kc, cfg, -(-S // cfg["block"]))[:, 0],
                np.float64)  # pio: lint-ignore[dtype-discipline]: host-side ordering of 2 x 512 block scores, never on the device

        head = weights["head"] if "head" in weights else weights["item_emb"]
        # (margin given up, tie-break, layer to enter, the row before it,
        # the blocks chosen for that layer or None)
        frontier, pushed = [(0.0, 0, 0, start, None)], 1
        for _ in range(max_steps):
            if not frontier:
                return
            cost, _, n, row, chosen = heapq.heappop(frontier)
            if n == len(layers):
                hid = rmsnorm(row[0], weights["out_norm"], cfg["eps"]) \
                    * cfg["head_scale"]
                yield jnp.concatenate(
                    [_matmul(head[lo:lo + 32768], hid)
                     for lo in range(0, head.shape[0], 32768)]), cost
                continue
            if mixers[n] == "minicpm4" and selects and chosen is None:
                scores = last_scores(n, row)
                per_group = [selections(s, cfg, near_tie) for s in scores]
                for combo in itertools.product(*per_group):
                    kept = np.zeros((len(combo), 1, scores.shape[1]), bool)
                    for g, (_, ids) in enumerate(combo):
                        kept[g, 0, list(ids)] = True
                    heapq.heappush(frontier, (
                        cost + sum(m for m, _ in combo), pushed, n, row,
                        jnp.asarray(kept)))
                    pushed += 1
                continue
            heapq.heappush(frontier, (cost, pushed, n + 1,
                                      mix_last(n, row, chosen), None))
            pushed += 1


def last_logits(weights, history, config: dict):
    """Logits (vocabulary,) of the position after ``history``."""
    return next(resolutions(weights, history, config))[0]


def last_kept(weights, history, config: dict):
    """[(G, n_blocks) float64 block scores of the last position] for each
    sparse layer under the reference's own choice (tests; the served
    path's returned ids are held against these up to near ties)."""
    cfg = widths(config)
    S = len(history)
    out = []
    with jax.default_matmul_precision("highest"):
        x = embed(weights, history, cfg)
        for w, mixer in zip(weights["layers"], cfg["mixers"]):
            if mixer == "minicpm4" and S > cfg["dense_len"]:
                h = rmsnorm(x, w["in_norm"], cfg["eps"])
                q, k, _ = _sparse_qkv(
                    h, w["wq"], w["wk"], w["wv"], w["q_norm"], w["k_norm"],
                    heads=cfg["H"], groups=cfg["G"], d=cfg["d"],
                    eps=cfg["eps"])
                out.append(np.asarray(block_scores(
                    q[S - 1:], np.asarray([S - 1]), compressed(k, cfg), cfg,
                    -(-S // cfg["block"]))[:, 0], np.float64))  # pio: lint-ignore[dtype-discipline]: host-side copy of 2 x 512 scores for a test
            x = layer(x, w, cfg, mixer)
    return out
