"""The ``laguna`` block of the session engine at a tiny size on the CPU:
the windowed attention core (its kernel interpreted, its plain form, the
window mask against a brute-force one, the keys it counts), YaRN on half
of a head's lanes against the formula written out, the routed layer's
four shares adding up to the uncut layer, the depth cut against the
uncut model, the controls that prove the check sees the window, the
gate, the half-lane rotary and 8-bit operands, the other kinds' programs
as they were, and the model through ``batch_predict``, the engine server
and ``pio train`` against ``benchmarks/reference/laguna_jnp.py``."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import urllib.request
from datetime import datetime, timedelta, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.models.blocks import laguna
from predictionio_tpu.ops import mla_attention, moe, qk_norm
from predictionio_tpu.ops import sparse_attention as sa
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests.seq_wiring import wired_stats

ITEMS, S, WINDOW = 300, 96, 16
TYPES = ["full_attention", "sliding_attention", "full_attention"]
HEADS = [4, 6, 4]
MLP = ["dense", "sparse", "sparse"]
#: the published rotary parameters but the original length, cut to the
#: tiny history's scale so that YaRN's blend falls inside 8 pairs
FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
SLIDING = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}
#: the tiny preset: d 64; a dense full layer (4 query heads), then a
#: sliding expert layer (6 query heads, a window of 16) and a full expert
#: layer, over 2 key/value heads of 16; 16 experts of width 32 (4 a
#: token, experts 0-7 held) beside a shared one of 32; a dense width of
#: 128
WIDTHS = dict(layer_types=TYPES, num_attention_heads_per_layer=HEADS,
              mlp_layer_types=MLP, sliding_window=WINDOW,
              full_rope_scaling=dict(factor=128.0,
                                     original_max_position_embeddings=64,
                                     beta_fast=32.0, beta_slow=1.0),
              moe_intermediate_size=32, shared_expert_intermediate_size=32,
              num_experts=16, experts_held=[0, 8], num_experts_per_tok=4)
PARAMS = dict(backbone="laguna", d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, d_ff=128, n_layers=3, max_len=S, rms_eps=1e-6,
              tie_embeddings=False, param_dtype="bfloat16", use_mesh=False,
              laguna=WIDTHS)
#: logits of the tiny model against the reference: bfloat16 activations
#: through three layers whose q.k carry YaRN's attention_factor squared
#: on their rotary half (logits are ~N(0, 1); the worst seen is 0.10,
#: and the model in float32 is 4e-6 from the reference)
LOGIT_TOL = 0.2


def ref_config(**over):
    """The keys the reference reads, as a configuration file has them."""
    return {"layer_types": TYPES, "num_attention_heads_per_layer": HEADS,
            "mlp_layer_types": MLP, "num_key_value_heads": 2, "head_dim": 16,
            "rms_norm_eps": 1e-6, "sliding_window": WINDOW,
            "rope_parameters": {"full_attention": FULL,
                                "sliding_attention": SLIDING},
            "num_experts": 8, "experts_held": [0, 8],
            "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
            **over}


def _weights(seed=3, dtype=jnp.bfloat16, **over):
    params = sessionrec.AlgorithmParams(**{**PARAMS, **over})
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    return cfg, seqrec.init_params(jax.random.PRNGKey(seed), cfg, dtype)


def _history(seed, n=S):
    return np.random.default_rng(seed).integers(1, ITEMS + 1, n) \
        .astype(np.int32)


def _program(weights, cfg, history):
    """The served program's logits of the last position, its
    assignments, its sliding layers' counts and the experts it chose at
    the history's positions (expert layers, len(history), top_k)."""
    padded = np.zeros((1, cfg.max_len), np.int32)
    padded[0, :len(history)] = history
    hidden, assignments, window, chose = seqrec.BLOCKS["laguna"].forward(
        weights, padded, cfg, None, "seq", True)
    logits = jnp.einsum("d,vd->v", hidden[0, len(history) - 1],
                        weights["head"].astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return (np.asarray(logits), np.asarray(assignments), np.asarray(window),
            np.asarray(chose)[:, 0, :len(history)])


def _resolved(weights, cfg, history, config, near_tie=0.05):
    """The reference following the program through its near ties."""
    return np.asarray(next(ref.resolutions(
        weights, history, config, near_tie=near_tie,
        choices=_program(weights, cfg, history)[3]))[0])


def _draw(rng, *shape, scale=1.0):
    """Values bfloat16 holds exactly, as float32."""
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16
                       ).astype(jnp.float32)


# -- the windowed core ---------------------------------------------------------

def _brute(q, k, v, window):
    """Attention written out: every (t, i) scored, ``t - window < i <=
    t`` kept, float64."""
    B, S_, G, R, d = q.shape
    qf, kf, vf = (np.asarray(a, np.float64) for a in (q, k, v))  # pio: lint-ignore[dtype-discipline]: a host-side oracle written out
    s = np.einsum("btgrd,bsgd->bgrts", qf, kf)
    t, i = np.arange(S_)[:, None], np.arange(S_)[None, :]
    s = np.where((t - i >= 0) & (t - i < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bgrts,bsgd->btgrd", p, vf)


def _qkv(seed, n=256, G=2, R=3, d=128):
    rng = np.random.default_rng(seed)
    q = _draw(rng, 1, n, G, R, d, scale=2 / math.sqrt(d)).astype(jnp.bfloat16)
    k, v = (_draw(rng, 1, n, G, d).astype(jnp.bfloat16) for _ in range(2))
    return q, k, v


@pytest.mark.parametrize("window", [1, 16, 37, 300])
def test_the_window_mask_is_the_brute_force_one(window):
    """The plain form with a window (``t - window < i <= t``, its own
    included) is attention written out with that mask; a window past the
    history is plain causal attention."""
    q, k, v = _qkv(window, n=96, d=16)
    got = sa._attend_plain(q, k, v, None, None, window)
    np.testing.assert_allclose(np.asarray(got), _brute(q, k, v, window),
                               atol=2e-2, rtol=2e-2)
    if window >= 96:
        causal = sa._attend_plain(q, k, v, None, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(causal))


@pytest.mark.parametrize("tile_q, tile_k, window, n", [
    # windows start mid-tile, and at 0 for t < 48
    pytest.param(128, 128, 48, 256, id="128-128-48"),
    # a window over two key tiles and a part
    pytest.param(64, 32, 48, 256, id="64-32-48"),
    # a key tile wider than a query tile
    pytest.param(32, 128, 200, 256, id="32-128-200"),
    # the window reaches position 0 from every row
    pytest.param(128, 64, 256, 256, id="128-64-256"),
    # each position alone
    pytest.param(128, 128, 1, 256, id="128-128-1"),
    # the history is one band: every query tile scores all of it
    pytest.param(128, 128, 200, 256, id="one-band"),
    # bands of three tiles where some tiles' windows reach two: the last
    # query tiles' bands are pulled back from past the history's end
    pytest.param(32, 128, 200, 512, id="band-pulled-back"),
    # the cell's tiles: a window of four whole tiles, a band of five
    pytest.param(128, 128, 512, 1024, id="window-of-whole-tiles"),
    # a query tile wider than the window
    pytest.param(128, 32, 48, 512, id="query-tile-wider-than-window")])
def test_the_windowed_kernel_interpreted_is_its_plain_form(
        tile_q, tile_k, window, n):
    q, k, v = _qkv(tile_q + tile_k + window, n=n)
    B, n, G, R, d = q.shape
    got = sa.window_attention(q.reshape(B, n, -1), k.reshape(B, n, -1),
                              v.reshape(B, n, -1), groups=G, window=window,
                              interpret=True, tile_q=tile_q, tile_k=tile_k)
    plain = sa._attend_plain(q, k, v, None, None, window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(plain.reshape(B, n, -1).astype(jnp.bfloat16), np.float32),
        atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _brute(q, k, v, window).reshape(B, n, -1),
                               atol=2e-2, rtol=2e-2)


def test_the_tiles_a_window_reaches_and_the_keys_counted():
    """``window_tiles`` against a search over every key tile; the band
    (``window_band``) against the same search: as wide as the most tiles
    a query tile reaches, and holding every key each one reaches inside
    the history; the keys counted a position (the kernel's band, the
    plain form's mask) against a count of the pairs; at the cell's shape
    640 keys at every position, where every visible key would be
    ~8,200."""
    for tq, tk, window, n in ((128, 128, 512, 2048), (128, 128, 48, 512),
                              (64, 32, 48, 512), (32, 128, 200, 512),
                              (128, 128, 256, 1024), (256, 128, 512, 2048),
                              (128, 32, 48, 512), (128, 128, 200, 256)):
        band = sa.window_band(n, tq, tk, window)
        assert band % tk == 0 and band <= n
        widths = []
        for qi in range(n // tq):
            rows = np.arange(qi * tq, qi * tq + tq)
            back = rows[:, None] - np.arange(n)[None, :]
            seen = (back >= 0) & (back < window)                  # (tq, n)
            reached = [kt for kt in range(n // tk)
                       if seen[:, kt * tk:(kt + 1) * tk].any()]
            first, last = sa.window_tiles(qi, tq, tk, window)
            assert list(range(first, last + 1)) == reached
            widths.append(len(reached) * tk)
            start = min(first * tk, n - band)
            assert start % tk == 0 and start >= 0
            assert not seen[:, :start].any() and \
                not seen[:, start + band:].any()
        assert band == max(widths)
    assert sa.window_band(16384, 128, 128, 512) == 640
    plain = sa.window_scored(96, WINDOW, kernel=False)
    t, i = np.arange(96)[:, None], np.arange(96)[None, :]
    np.testing.assert_array_equal(
        plain, ((t - i >= 0) & (t - i < WINDOW)).sum(axis=1))
    cell = sa.window_scored(16384, 512, kernel=True)
    assert cell.tolist() == [640] * 16384
    assert cell.sum() / 16384 < 1024 < 8192


def test_the_layer_core_counts_rows_and_keys_of_real_positions():
    q, k, v = _qkv(5, n=96, d=16)
    B, n, G, R, d = q.shape
    valid = jnp.arange(n)[None] < 50
    out, counts = sa.attend_window(
        q.reshape(B, n, -1), k.reshape(B, n, -1), v.reshape(B, n, -1),
        groups=G, window=WINDOW, valid=valid)
    assert counts.dtype == jnp.int32
    assert counts.tolist() == [
        50 * G, int(sa.window_scored(n, WINDOW, False)[:50].sum()) * G]
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        _brute(q, k, v, WINDOW).reshape(B, n, -1), atol=2e-2, rtol=2e-2)


# -- positions -------------------------------------------------------------------

def _hf_rotary(x, positions, inv, magnitude, rotary):
    """The published form, written out: rotate-half over the first
    ``rotary`` lanes of each head, cos and sin times ``magnitude``, the
    rest passed through. x (T, heads, d) float64."""
    ang = positions[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None] * magnitude
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None] * magnitude
    rot, rest = x[..., :rotary], x[..., rotary:]
    half = np.concatenate([-rot[..., rotary // 2:], rot[..., :rotary // 2]],
                          -1)
    return np.concatenate([rot * cos + half * sin, rest], -1)


def _yarn_direct(dim, base, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies as its authors publish them, written out."""
    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        extrapolated = base ** (-2 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(extrapolated * (1 - ramp) + extrapolated / factor * ramp)
    return np.asarray(out)


@pytest.mark.parametrize("d, rotary, heads", [(16, 8, 3), (128, 64, 2)])
def test_yarn_on_half_the_lanes_is_the_direct_formula(d, rotary, heads):
    """The program's way (the weights' columns in ``partial_order``,
    full-width tables with passed-through lanes, ``qk_norm.prepare``)
    and the reference's own, each against the formula written out, at
    the published keys (and the tiny preset's original length)."""
    original = 8192 if d == 128 else 64
    inv = _yarn_direct(rotary, 500000.0, 128.0, original, 32.0, 1.0)
    np.testing.assert_allclose(
        mla_attention.yarn_inv_freq(rotary, 500000.0, seqrec.YarnScaling(
            factor=128.0, original_max_position_embeddings=original,
            beta_fast=32.0, beta_slow=1.0)), inv, rtol=1e-6)
    np.testing.assert_allclose(
        ref.inv_freq({**FULL, "original_max_position_embeddings": original},
                     rotary), inv, rtol=1e-12)
    rng = np.random.default_rng(d)
    n, mag = 40, FULL["attention_factor"]
    x = rng.standard_normal((1, n, heads * d))
    weight = rng.random(d) + 0.5
    normed = x.reshape(n, heads, d) / np.sqrt(
        np.mean(x.reshape(n, heads, d) ** 2, -1, keepdims=True) + 1e-6) * weight
    want = _hf_rotary(normed, np.arange(n), inv, mag, rotary)
    order = qk_norm.partial_order(d, rotary)
    cols = (np.arange(heads)[:, None] * d + order).ravel()
    got = qk_norm.prepare(
        jnp.asarray(x[..., cols], jnp.float32),
        jnp.asarray(weight[order], jnp.float32), heads=heads, eps=1e-6,
        rope=qk_norm.partial_rope_tables(n, d, inv, mag))
    np.testing.assert_allclose(np.asarray(got[0])[..., np.argsort(order)],
                               want, atol=1e-4, rtol=1e-4)
    mine = ref.rotate(jnp.asarray(normed, jnp.float32), np.arange(n),
                      {**FULL, "original_max_position_embeddings": original},
                      d)
    np.testing.assert_allclose(np.asarray(mine), want, atol=1e-4, rtol=1e-4)
    # dot products are the published ones: the same order on q and k
    q, k = np.asarray(got[0]), np.asarray(got[0])[::-1]
    np.testing.assert_allclose(np.einsum("thd,shd->hts", q, k),
                               np.einsum("thd,shd->hts", want, want[::-1]),
                               atol=1e-3, rtol=1e-4)


def test_the_sliding_layers_rotate_every_lane_by_plain_rope():
    cfg, _ = _weights()
    order, tables = laguna.rope(cfg, "sliding_attention", 40)
    np.testing.assert_array_equal(order, np.arange(16))
    for got, want in zip(tables, qk_norm.rope_tables(40, 16, 10000.0)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
    order, (cos, sin) = laguna.rope(cfg, "full_attention", 40)
    assert laguna.rotary_lanes(cfg, "full_attention") == 8
    # the passed-through lanes: cos 1, sin 0, wherever they sit
    assert np.all(np.asarray(cos)[:, [4, 5, 6, 7, 12, 13, 14, 15]] == 1)
    assert np.all(np.asarray(sin)[:, [4, 5, 6, 7, 12, 13, 14, 15]] == 0)


# -- the routed layer ------------------------------------------------------------

def _expert_weights(rng, n, d=64, f=32):
    return {"w_gate": _draw(rng, n, d, f, scale=d ** -0.5),
            "w_up": _draw(rng, n, d, f, scale=d ** -0.5),
            "w_down": _draw(rng, n, f, d, scale=f ** -0.5)}


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_the_four_shares_add_up_to_the_uncut_layer(kernel):
    """Experts 0-3, 4-7, 8-11 and 12-15, each share's routed part from
    the program, plus the shared expert counted once, equal the uncut
    layer from the uncut reference (softmax over all 16, top 4,
    renormalised over all four, times 2.5)."""
    rng = np.random.default_rng(6)
    T = 96
    h = _draw(rng, T, 64)
    layer = {"router": _draw(rng, 64, 16, scale=0.125),
             "experts": _expert_weights(rng, 16),
             "shared": {k: v[0] for k, v in _expert_weights(rng, 1).items()}}
    uncut = ref.expert_layer(h, layer, ref_config(experts_held=[0, 16]))
    ids, weights, scores = moe.route(h, layer["router"], n_group=1,
                                     topk_group=1, top_k=4, scaling=2.5,
                                     renormalise=True)
    cfg = ref.widths(ref_config())
    for t in range(0, T, 8):
        assert ids[t].tolist() == ref.select(np.asarray(scores[t]), cfg)
    shared = np.asarray(ref._swiglu(h, *(layer["shared"][k] for k in (
        "w_gate", "w_up", "w_down"))), np.float64)   # pio: lint-ignore[dtype-discipline]: a host-side sum of the shares
    total, counted = shared.copy(), 0
    for first in (0, 4, 8, 12):
        share = {k: v[first:first + 4] for k, v in layer["experts"].items()}
        part, counts = moe.routed_experts(
            h, ids, weights, share["w_gate"], share["w_up"], share["w_down"],
            first, kernel=kernel)
        alone = ref.expert_layer(h, {**layer, "experts": share},
                                 ref_config(experts_held=[first, 4]))
        np.testing.assert_allclose(np.asarray(part) + shared, alone,
                                   atol=3e-5, rtol=3e-5)
        total += np.asarray(part, np.float64)   # pio: lint-ignore[dtype-discipline]: a host-side sum of the shares
        counted += int(counts.sum())
    assert counted == T * 4                       # no assignment lost
    np.testing.assert_allclose(total, uncut, atol=6e-5, rtol=6e-5)


# -- the kinds that share ops/sparse_attention, ops/qk_norm and ops/moe ---------

#: sha256 of the lowered text (no debug information) of the tiny programs
#: of the three kinds whose code this kind shares, at the parent commit
#: (e49c881), on the CPU (the plain paths: serving and training lower
#: alike there)
PARENT_LOWERED = {
    ("deepseek_v2", True):
    "1d83d00091429c4302f598b2a62aafe575babd62476906a8ef0b4fd96713bb17",
    ("deepseek_v2", False):
    "1d83d00091429c4302f598b2a62aafe575babd62476906a8ef0b4fd96713bb17",
    ("minicpm_sala", True):
    "6b2ee11b5e1f7090f1a6cc8831d74fc90f591869999f4dc80b71ad77ef201540",
    ("minicpm_sala", False):
    "6b2ee11b5e1f7090f1a6cc8831d74fc90f591869999f4dc80b71ad77ef201540",
    ("nemotron_h", True):
    "f8465738a7bbb41b7bf5ae9cc779ab0f6d584f3650b5f5994a92fe50a9b990fd",
    ("nemotron_h", False):
    "f8465738a7bbb41b7bf5ae9cc779ab0f6d584f3650b5f5994a92fe50a9b990fd",
}


def _lowered_digest(kind, inference):
    import importlib

    module = importlib.import_module(
        {"deepseek_v2": "tests.test_deepseek_v2_seqrec",
         "minicpm_sala": "tests.test_minicpm_sala_seqrec",
         "nemotron_h": "tests.test_nemotron_h_seqrec"}[kind])
    cfg = sessionrec.AlgorithmParams(**module.PARAMS).seqrec_config(
        vocab=module.ITEMS + 1)
    weights = jax.eval_shape(lambda key: seqrec.init_params(
        key, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    text = jax.jit(lambda w, s: seqrec.BLOCKS[kind].forward(
        w, s, cfg, None, "seq", inference)).lower(
            weights, jax.ShapeDtypeStruct((2, cfg.max_len), jnp.int32)
    ).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("kind, inference", list(PARENT_LOWERED))
def test_the_other_kinds_programs_are_the_parents(kind, inference):
    assert _lowered_digest(kind, inference) == \
        PARENT_LOWERED[(kind, inference)]


# -- the model ---------------------------------------------------------------------

def test_config_and_params_hold_the_widths_in_one_record():
    assert seqrec.SeqRecConfig(vocab=11).laguna is None
    assert sessionrec.AlgorithmParams().laguna is None
    params = sessionrec.AlgorithmParams(**PARAMS)
    w = params.laguna
    assert isinstance(w, seqrec.LagunaWidths)
    assert w.experts_held == (0, 8) and w.layer_types == tuple(TYPES)
    assert isinstance(w.full_rope_scaling, seqrec.YarnScaling)
    assert (w.count("sparse"), w.count("dense"), w.count("full_attention"),
            w.count("sliding_attention")) == (2, 1, 2, 1)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    assert cfg.laguna is w and hash(cfg) == hash(
        sessionrec.AlgorithmParams(**PARAMS).seqrec_config(vocab=ITEMS + 1))
    # the defaults are the published model's
    pub = seqrec.LagunaWidths()
    assert len(pub.layer_types) == len(pub.mlp_layer_types) == 48
    assert pub.num_attention_heads_per_layer[:5] == (48, 72, 72, 72, 48)
    assert (pub.count("full_attention"), pub.count("sliding_attention"),
            pub.count("dense")) == (12, 36, 1)
    assert (pub.sliding_window, pub.num_experts, pub.num_experts_per_tok,
            pub.moe_intermediate_size, pub.full_rope_scaling.factor,
            pub.full_rope_scaling.original_max_position_embeddings) == \
        (512, 256, 10, 1024, 128.0, 8192)
    from predictionio_tpu.controller.params import (
        params_from_json, params_to_json)
    again = params_from_json(sessionrec.AlgorithmParams,
                             json.loads(json.dumps(params_to_json(params))))
    assert again == params
    tree = seqrec.init_params(jax.random.PRNGKey(0), cfg)
    first, sliding, last = tree["layers"]
    assert first["wq"].shape == (64, 4 * 16) and "ffn" in first
    assert sliding["wq"].shape == (64, 6 * 16)
    assert sliding["wg"].shape == (64, 6) and sliding["wo"].shape == (96, 64)
    assert sliding["router"].shape == (64, 16)
    assert sliding["experts"]["w_gate"].shape == (8, 64, 32)
    assert sliding["shared"]["w_down"].shape == (32, 64)
    assert first["ffn"]["w_up"].shape == (64, 128) and "router" not in first
    assert tree["head"].shape == tree["item_emb"].shape == (ITEMS + 1, 64)
    assert float(jnp.std(last["wq"])) == pytest.approx(1.0 / 8, rel=0.1)
    with pytest.raises(ValueError, match="layer_types"):
        seqrec.init_params(jax.random.PRNGKey(0), sessionrec.AlgorithmParams(
            **{**PARAMS, "n_layers": 4}).seqrec_config(vocab=11))
    with pytest.raises(ValueError, match="layer_types"):
        seqrec.init_params(jax.random.PRNGKey(0), sessionrec.AlgorithmParams(
            **{**PARAMS, "laguna": {**WIDTHS, "mlp_layer_types": [
                "dense", "sparse", "moe"]}}).seqrec_config(vocab=11))


PUBLISHED = dict(backbone="laguna", d_model=3072, n_heads=48, n_kv_heads=8,
                 head_dim=128, d_ff=12288, n_layers=5, max_len=16384,
                 laguna={"layer_types": seqrec.LagunaWidths().layer_types[:5],
                         "num_attention_heads_per_layer":
                         [48, 72, 72, 72, 48],
                         "mlp_layer_types": ["dense"] + ["sparse"] * 4,
                         "experts_held": [0, 64]})


def test_kernel_rule_and_bytes_a_token(monkeypatch):
    from predictionio_tpu.ops import pallas_attention

    cfg = sessionrec.AlgorithmParams(**PARAMS).seqrec_config(ITEMS + 1)
    kind = seqrec.BLOCKS["laguna"]
    assert kind.kernels(cfg, 16384) == ()                   # the CPU
    assert "window_attention" not in seqrec.kernels(cfg, 16384)
    published = sessionrec.AlgorithmParams(**PUBLISHED).seqrec_config(
        vocab=25088)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert kind.kernels(published, 16384) == (
        "window_attention", "sparse_block_attention", "qk_norm_rope", "gmm",
        "moe_rows")
    assert {"window_attention", "qk_norm_rope", "moe_rows"} <= set(
        seqrec.kernels(published, 16384))
    # heads of 16 lanes are no kernel's; rows of 64 are not whole lanes
    assert kind.kernels(cfg, 96) == ("gmm",)
    # no sliding layer, no window kernel; no expert layer, no grouped one
    full = dataclasses.replace(published, laguna=dataclasses.replace(
        published.laguna, layer_types=("full_attention",) * 5))
    assert "window_attention" not in kind.kernels(full, 16384)
    dense = dataclasses.replace(published, n_layers=1, laguna=dataclasses.
                                replace(published.laguna,
                                        layer_types=("full_attention",),
                                        num_attention_heads_per_layer=(48,),
                                        mlp_layer_types=("dense",)))
    assert kind.kernels(dense, 16384) == ("sparse_block_attention",
                                          "qk_norm_rope")
    # a token of the program: 10 assignments of (2 x 3,072 + 3 x 1,024)
    # bfloat16 values and the float32 sum, beside the residual twice: 221
    # KB, where the program compiled for a described v5e holds 210
    assert kind.bytes_per_token(published) == \
        2 * 3072 * 2 + 10 * (2 * 3072 + 3 * 1024) * 2 + 2 * 3072 * 4 \
        == 221_184
    # the budget then takes one history of 16,384 at a time
    assert int(0.25 * 16e9 // kind.bytes_per_token(published)) < 2 * 16384


@pytest.mark.parametrize("n", [S, 70, 17, 1])
def test_forward_matches_the_reference(n):
    """A full history, ragged, barely past the window, a single event."""
    cfg, weights = _weights()
    history = _history(10 + n, n)
    got, assignments, window, _ = _program(weights, cfg, history)
    want = _resolved(weights, cfg, history, ref_config())
    assert float(np.max(np.abs(got - want))) < LOGIT_TOL
    assert assignments.shape == (2, 8) and assignments.dtype == np.int32
    # every position is routed, padding too: ~2 of 4 land on held experts
    assert 0.3 < assignments.sum() / (2 * S * 4) < 0.7
    # the real positions of the one sliding layer, two groups
    assert window.tolist() == [
        n * 2, int(sa.window_scored(S, WINDOW, False)[:n].sum()) * 2]


def test_padding_after_the_history_changes_nothing():
    cfg, weights = _weights()
    history = _history(5, 50)
    short = _program(weights, cfg, history)[0]
    cfg2, _ = _weights(max_len=2 * S)
    longer = _program(weights, cfg2, history)[0]
    assert np.max(np.abs(short - longer)) < 2e-2
    padded = np.zeros((2, S), np.int32)
    padded[:, :50] = history
    padded[1, 50:] = 7                                   # junk after the end
    hidden, _, window, chose = seqrec.BLOCKS["laguna"].forward(
        weights, padded, cfg, None, "seq", True)
    assert chose.shape == (2, 2, S, 4)
    # the counts are of real positions: 50 and 96
    assert int(window[0]) == (50 + S) * 2
    np.testing.assert_array_equal(chose[:, 0, :50], chose[:, 1, :50])
    np.testing.assert_array_equal(np.asarray(hidden[0, :50], np.float32),
                                  np.asarray(hidden[1, :50], np.float32))


def test_the_reference_follows_a_choice_only_where_it_is_a_near_tie():
    cfg, weights = _weights()
    history = _history(2)
    config = ref_config()
    own, chose = ref.answer(weights, history, config)
    own = np.asarray(own)
    assert chose["ids"].shape == (2, S, 4)
    assert (chose["margin"], chose["adopted"], chose["refused"]) == (0, 0, 0)
    offered = chose["ids"].copy()
    # the last position's weakest expert of the first expert layer
    # exchanged for one it did not choose: under a margin of nothing
    # refused, and the answer is the reference's own
    offered[0, -1, -1] = int(np.setdiff1d(np.arange(16), offered[0, -1])[0])
    logits, chose = ref.answer(weights, history, config, offered, 1e-6)
    assert chose["adopted"] == 0 and chose["refused"] >= 1
    np.testing.assert_array_equal(np.asarray(logits), own)
    # under a margin wide enough it is followed, and the answer moves
    logits, chose = ref.answer(weights, history, config, offered, 50.0)
    assert chose["adopted"] >= 1 and chose["refused"] == 0
    assert float(np.max(np.abs(np.asarray(logits) - own))) > 0


@pytest.mark.parametrize("seed", [2, 5, 8])
def test_the_check_adopts_every_choice_the_program_made(seed):
    """Under the check's own margin the reference follows every expert
    the program chose, the last position's too: a choice the reference
    kept for itself would be a whole expert of difference that the
    program's rounding did not make (at the cell's size the program's
    choices lie up to ~0.1 under the reference's own, PERF.md section
    6)."""
    cfg, weights = _weights()
    history = _history(seed)
    choices = _program(weights, cfg, history)[3]
    _, chose = ref.answer(weights, history, ref_config(), choices,
                          ref.NEAR_TIE)
    assert chose["refused"] == 0 and chose["margin"] < ref.NEAR_TIE
    np.testing.assert_array_equal(np.sort(chose["ids"], -1),
                                  np.sort(choices, -1))


CONTROLS = {"no_window": {"control_no_window": True},
            "no_gate": {"control_no_gate": True},
            "full_rotary": {"control_full_rotary": True}}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_each_mechanism_moves_the_logits_by_more_than_the_limits(control):
    """(a) the sliding layer without its window, (b) no output gate, (c)
    full-width rotary in the full layers: each moves the last position's
    logits, and its top ten, by more than the check allows."""
    cfg, weights = _weights()
    history = _history(1)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    other = np.asarray(ref.last_logits(
        weights, history, ref_config(**CONTROLS[control])))
    top = np.argsort(-own)[:10]
    assert np.max(np.abs(other - own)) > ref.SCORE_TOL
    assert np.max(np.abs(other[top] - own[top])) > ref.SCORE_TOL


def test_a_served_layer_without_its_window_fails_the_check():
    """The program itself with its sliding layer run as plain causal
    attention against the reference: off by more than the limits, where
    the program as it is sits inside its own tolerance; and it is what
    the reference without the window gives."""
    cfg, weights = _weights()
    history = _history(1)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    real = sa.attend_window

    def unwindowed(q, k, v, *, groups, window, valid=None, inference=False):
        return real(q, k, v, groups=groups, window=q.shape[1], valid=valid,
                    inference=inference)

    try:
        sa.attend_window = unwindowed
        jax.clear_caches()
        cut = _program(weights, cfg, history)[0]
    finally:
        sa.attend_window = real
        jax.clear_caches()
    assert np.max(np.abs(cut - own)) > ref.SCORE_TOL
    want = np.asarray(ref.last_logits(weights, history,
                                      ref_config(control_no_window=True)))
    assert np.max(np.abs(cut - want)) < LOGIT_TOL


@pytest.mark.parametrize("lower", ref.LOWER)
def test_each_lower_precision_is_a_reading_of_its_own(lower):
    """The reference in each lower precision differs from itself, by
    more than the limit for 8-bit operands; the readings at the cell's
    size are PERF.md section 6's."""
    cfg, weights = _weights()
    history = _history(1)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    ref.set_lower(lower)
    try:
        low = np.asarray(ref.last_logits(weights, history, ref_config()))
    finally:
        ref.set_lower(None)
    moved = float(np.max(np.abs(low - own)))
    assert moved > (ref.SCORE_TOL if lower == "operands" else 0)
    np.testing.assert_array_equal(
        np.asarray(ref.last_logits(weights, history, ref_config())), own)


def test_the_cut_stack_is_layers_0_to_4_of_the_uncut_model():
    """Published layers 0-4 at the tiny widths: a model of the published
    depth, cut to its first five layers (the dense full layer, then
    sliding, sliding, sliding, full: one whole period at 3 : 1), gives
    from the same input what those layers of the uncut model give, and
    the program over the cut stack is the reference over it."""
    pub = seqrec.LagunaWidths()
    assert pub.layer_types[:5] == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert pub.layer_types[5:9] == pub.layer_types[1:5]       # the period
    assert pub.mlp_layer_types[:5] == ("dense",) + ("sparse",) * 4
    heads = [4 if t == "full_attention" else 6 for t in pub.layer_types]
    widths = {**WIDTHS, "layer_types": pub.layer_types,
              "num_attention_heads_per_layer": heads,
              "mlp_layer_types": pub.mlp_layer_types}
    cfg = sessionrec.AlgorithmParams(**{
        **PARAMS, "n_layers": 48, "laguna": widths}).seqrec_config(
            vocab=ITEMS + 1)
    uncut = seqrec.init_params(jax.random.PRNGKey(4), cfg, jnp.bfloat16)
    config = ref_config(layer_types=list(pub.layer_types),
                        num_attention_heads_per_layer=heads,
                        mlp_layer_types=list(pub.mlp_layer_types))
    x = _draw(np.random.default_rng(4), 48, 64)
    held = uncut["layers"][:5]
    cut = {**config, "layer_types": config["layer_types"][:5],
           "num_attention_heads_per_layer": heads[:5],
           "mlp_layer_types": config["mlp_layer_types"][:5]}
    want = ref.hidden_states(held, x, cut)
    through = x
    for n in range(5):
        through = ref.hidden_states([uncut["layers"][n]], through, config,
                                    first=n)
    np.testing.assert_allclose(np.asarray(through), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    cut_cfg = sessionrec.AlgorithmParams(**{
        **PARAMS, "n_layers": 5, "max_len": 48, "laguna": {
            **widths, "layer_types": pub.layer_types[:5],
            "num_attention_heads_per_layer": heads[:5],
            "mlp_layer_types": pub.mlp_layer_types[:5]}}
    ).seqrec_config(vocab=ITEMS + 1)
    weights = {**uncut, "layers": held}
    history = _history(6, 48)
    got, assignments, _, _ = _program(weights, cut_cfg, history)
    want = _resolved(weights, cut_cfg, history, cut)
    assert float(np.max(np.abs(got - want))) < 1.5 * LOGIT_TOL
    assert assignments.shape == (4, 8)


@pytest.fixture(scope="module")
def engine_model():
    params = sessionrec.AlgorithmParams(**PARAMS)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(21), cfg))          # float32, as a trained one
    rng = np.random.default_rng(22)
    histories = {f"u{u}": rng.integers(1, ITEMS + 1, size=n).astype(np.int32)
                 for u, n in enumerate([S, S, 40, 7, 130, S, S, S, S, S])}
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(ITEMS)}),
        histories=histories)
    return sessionrec.SeqRecAlgorithm(params), model


def _check_against_reference(model, user, item_scores, num):
    """The answer agrees, whole, with the reference following the
    program through its router's near ties."""
    from predictionio_tpu.ops.topk import serving_k

    history = model.histories[user][-S:]
    tree = sessionrec._as_device_tree(model)
    padded = np.zeros((1, S), np.int32)
    padded[0, :len(history)] = history
    chose = np.asarray(seqrec.predict_topk_batch(
        tree, padded, serving_k(num, ITEMS), model.cfg,
        np.zeros((1, ITEMS + 1), np.float32))[-1])[:, 0, :len(history)]
    logits, _ = next(ref.resolutions(tree, history, ref_config(),
                                     near_tie=0.05, choices=chose))
    logits = np.asarray(logits)
    assert len(item_scores) == num
    ids = [int(s["item"][1:]) + 1 for s in item_scores]
    assert not set(ids) & set(history.tolist()) and 0 not in ids
    allowed = logits.copy()
    allowed[0] = -np.inf
    allowed[history] = -np.inf
    tenth = np.sort(allowed)[-num]
    worst = max(max(abs(s["score"] - logits[ix])
                    for ix, s in zip(ids, item_scores)),
                (tenth - min(allowed[ids])) / 2)
    assert worst < LOGIT_TOL, worst


@pytest.mark.parametrize("user", ["u0", "u2", "u3", "u4"])
def test_batch_predict_matches_the_reference(engine_model, user):
    """Full, short, very short and over-long (newest max_len kept)."""
    algo, model = engine_model
    queries = [(0, sessionrec.Query(user=user, num=10)),
               (1, sessionrec.Query(user="nobody", num=10))]
    got = dict(algo.batch_predict(model, queries))
    assert got[1].item_scores == ()
    scores = [{"item": s.item, "score": s.score} for s in got[0].item_scores]
    _check_against_reference(model, user, scores, 10)


def test_token_budget_splits_a_batch_and_counters_report_it(
        engine_model, monkeypatch):
    algo, model = engine_model
    seen = []
    model.set_dispatch_observer(seen.append)
    users = [f"u{u}" for u in (0, 1, 5, 6, 7, 8, 9, 2)]
    queries = [(i, sessionrec.Query(user=u, num=5))
               for i, u in enumerate(users)]
    monkeypatch.setattr(sessionrec, "_device_memory_bytes", lambda: 16e9)
    model.budget = 0
    whole = dict(algo.batch_predict(model, queries))
    per_token = seqrec.activation_bytes_per_token(model.cfg)
    # the residual twice, and an expert layer's rows: 4 assignments of
    # (2 x 64 + 3 x 32) x 2 in bfloat16 and the float32 sum
    assert per_token == 2 * 64 * 2 + 4 * (2 * 64 + 3 * 32) * 2 + 2 * 64 * 4
    monkeypatch.setattr(sessionrec, "_device_memory_bytes",
                        lambda: 4 * per_token * 2.9 * S)
    model.budget = 0
    assert sessionrec.token_budget(model) == 256        # two histories of 96
    split = dict(algo.batch_predict(model, queries))
    model.set_dispatch_observer(None)
    model.budget = 0
    one, four = seen
    assert (one.programs, four.programs, one.split, four.split) == (1, 4, 0, 1)
    assert one.moe_tokens == four.moe_tokens == 8 * S   # once, not per layer
    assert one.moe_assignments == four.moe_assignments > 0
    # seven full histories and one of 40, one sliding layer, two groups
    real = 7 * S + 40
    assert one.window_rows == four.window_rows == 2 * real
    keys = sa.window_scored(S, WINDOW, False)
    assert one.window_keys_scored == four.window_keys_scored == \
        2 * (7 * int(keys.sum()) + int(keys[:40].sum()))
    assert one.ssm_tokens == one.sparse_rows == 0
    stats = ServingStats()
    for report in seen:
        stats.record_seq_dispatch(report)
    assert stats.count("seq_programs") == 5
    assert stats.count("seq_window_rows") == 4 * real
    assert stats.count("seq_window_keys_scored") == \
        2 * one.window_keys_scored
    for i in whole:
        assert [s.item for s in whole[i].item_scores] == \
            [s.item for s in split[i].item_scores]


def test_the_dispatch_counts_the_programs_that_ran_the_window_kernel(
        engine_model, monkeypatch):
    """Where the rule says the windowed kernel runs, the server's gauge
    lists it for every program of the deployment, beside
    ``seq_programs``; where it does not (the CPU), it does not."""
    algo, model = engine_model
    assert wired_stats(algo, model).snapshot()["seqKernels"] == []
    monkeypatch.setattr(seqrec, "kernels", lambda cfg, s: (
        "window_attention",) if s == cfg.max_len else ())
    stats = wired_stats(algo, model)
    seen = []
    model.set_dispatch_observer(seen.append)
    try:
        algo.batch_predict(model, [(i, sessionrec.Query(user=u, num=3))
                                   for i, u in enumerate(["u0", "u2"])])
    finally:
        model.set_dispatch_observer(None)
    stats.record_seq_dispatch(seen[0])
    snap = stats.snapshot()
    assert snap["seqKernels"] == ["window_attention"]
    assert snap["seqPrograms"] > 0


def test_engine_server_answers_match_the_reference(engine_model):
    import datetime as dt

    from predictionio_tpu.api.engine_server import EngineServer
    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    algo, model = engine_model
    now = dt.datetime.now(dt.timezone.utc)
    instance = EngineInstance(
        id="t", status="COMPLETED", start_time=now, completion_time=now,
        engine_id="t", engine_version="1", engine_variant="t",
        engine_factory="t")
    server = EngineServer(
        DeployedEngine(None, instance, [algo], FirstServing(), [model]),
        ServerConfig(ip="127.0.0.1", port=0, batching=True, tracing=True))
    server.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}{path}") as resp:
                return resp.read()

        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/queries.json",
            data=json.dumps({"user": "u1", "num": 10}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            answer = json.loads(resp.read())
        _check_against_reference(model, "u1", answer["itemScores"], 10)
        serving = json.loads(get("/stats.json"))["serving"]
        assert serving["seqPrograms"] == 1 and serving["seqMoeTokens"] == S
        assert 0 < serving["seqMoeAssignments"] < 2 * S * 4
        keys = int(sa.window_scored(S, WINDOW, False).sum()) * 2
        assert serving["seqWindowRows"] == 2 * S
        assert serving["seqWindowKeysScored"] == keys
        assert serving["seqKernels"] == []                  # the CPU
        metrics = get("/metrics")
        assert b"pio_serving_seq_window_rows_total %d" % (2 * S) in metrics
        assert b"pio_serving_seq_window_keys_scored_total %d" % keys \
            in metrics
        assert b"pio_serving_seq_kernel{" not in metrics
    finally:
        server.stop()
        model.set_dispatch_observer(None)


def test_train_deploy_query_with_the_laguna_backbone(
        storage, monkeypatch, tmp_path):
    """engine.json -> pio train -> model store -> pio deploy --batching
    -> /queries.json, as every engine is reached."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    app_id = storage.get_meta_data_apps().insert(App(0, "LagunaApp"))
    events = storage.get_events()
    events.init(app_id)
    t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
    rng = np.random.default_rng(0)
    for u in range(32):
        start = int(rng.integers(10))
        for t in range(8):
            events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + t) % 10}",
                event_time=t0 + timedelta(minutes=u * 100 + t)), app_id)
    variant = {
        "id": "laguna-sess",
        "engineFactory": "predictionio_tpu.templates.sessionrec.engine_factory",
        "datasource": {"params": {"app_name": "LagunaApp"}},
        "algorithms": [{"name": "seqrec", "params": {
            "backbone": "laguna", "d_model": 32, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 8, "d_ff": 64, "n_layers": 3,
            "max_len": 16, "rms_eps": 1e-6, "tie_embeddings": False,
            "param_dtype": "bfloat16", "epochs": 30, "batch_size": 16,
            "lr": 3e-3, "seed": 0,
            "laguna": {
                "layer_types": TYPES, "num_attention_heads_per_layer":
                [4, 6, 4], "mlp_layer_types": MLP, "sliding_window": 4,
                "full_rope_scaling": WIDTHS["full_rope_scaling"],
                "moe_intermediate_size": 16,
                "shared_expert_intermediate_size": 16, "num_experts": 8,
                "experts_held": [0, 8], "num_experts_per_tok": 2}}}],
    }
    outcome = run_train(variant=variant, storage=storage)
    assert outcome.status == "COMPLETED"
    server = create_engine_server(
        storage=storage,
        config=ServerConfig(ip="127.0.0.1", port=0, batching=True))
    server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/queries.json",
            data=json.dumps({"items": ["i3", "i4", "i5"], "num": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            scores = json.loads(resp.read())["itemScores"]
        # the item cycle is learnable: after i3 i4 i5 comes i6
        assert scores and scores[0]["item"] == "i6"
        model = server.service.deployed.models[0]
        assert model.cfg.block == "laguna"
        assert model.cfg.laguna.experts_held == (0, 8)
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(model.device_tree))
    finally:
        server.stop()
