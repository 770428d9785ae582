"""The ``deepseek_v2`` block of the session engine at a tiny size on the
CPU: latent attention and YaRN against an explicit per-head loop, the
tiled attention kernel in interpret mode, the router against a plain
loop, the routed layer's shares adding up to the uncut layer, and the
model through ``batch_predict``, the engine server and ``pio train``
against ``benchmarks/reference/deepseek_v2_jnp.py``."""

from __future__ import annotations

import dataclasses
import json
import math
import urllib.request
from datetime import datetime, timedelta, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v2_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import mla_attention, moe
from predictionio_tpu.ops.attention import full_attention
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap

ITEMS, S = 500, 64
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
#: the tiny preset: d 64, 4 heads of 16 + 8 / 16, 16 experts of width 32
#: in 4 groups (2 kept, 3 a token), experts 4-7 held, 1 dense + 2 expert
#: layers
WIDTHS = dict(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
              n_routed_experts=16, experts_held=[4, 4], n_shared_experts=2,
              num_experts_per_tok=3, n_group=4, topk_group=2,
              routed_scaling_factor=4.0, first_k_dense_replace=1,
              rope_scaling=YARN)
PARAMS = dict(backbone="deepseek_v2", d_model=64, n_heads=4, d_ff=128,
              n_layers=3, max_len=S, rope_theta=10000.0,
              tie_embeddings=False, param_dtype="bfloat16", use_mesh=False,
              mla_moe=WIDTHS)
#: logits of the tiny model against the reference: bfloat16 activations
#: through three layers (logits are ~N(0, 1); the worst seen is 0.045)
LOGIT_TOL = 0.08


def ref_config(widths=WIDTHS, **over):
    """The keys the reference reads, as a configuration file has them."""
    first, held = widths["experts_held"]
    return {**widths, "num_attention_heads": 4, "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "n_routed_experts": held,
            "published": {"n_routed_experts": widths["n_routed_experts"]},
            **over}


def _draw(rng, *shape, scale=1.0):
    """Values bfloat16 holds exactly, as float32."""
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16
                       ).astype(jnp.float32)


# -- positions and latent attention ------------------------------------------

def test_yarn_at_the_published_keys():
    assert mla_attention.yarn_correction_range(64, 10000, 4096, 32, 1) == \
        ref.yarn_range(64, 10000, 4096, 32, 1) == (10, 23)
    yarn = seqrec.YarnScaling()
    inv = mla_attention.yarn_inv_freq(64, 10000.0, yarn)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < plain[11:23]) and \
        np.all(inv[11:23] > plain[11:23] / 40)
    np.testing.assert_allclose(
        inv, ref.inv_freq(64, 10000.0, dict(
            factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1)), rtol=1e-6)
    # m = 0.1 * 0.707 * ln 40 + 1 = 1.2608; scale = 192^-1/2 * m^2
    assert mla_attention.softmax_scale(192, yarn) == \
        pytest.approx(0.11472, abs=2e-5)
    assert mla_attention.softmax_scale(192, None) == 192 ** -0.5


@pytest.mark.parametrize("yarn", [None, YARN], ids=["rope", "yarn"])
def test_mla_matches_an_explicit_per_head_loop(yarn):
    """The reference's attention sublayer against the equations written
    out head by head and row by row in float64, with the YaRN blend live
    (S = 48 past the original 16) and without it."""
    rng = np.random.default_rng(3)
    n, d, H, dn, dr, dv, ql, kvl = 48, 64, 4, 16, 8, 16, 32, 16
    w = {"in_norm": _draw(rng, d) * 0.1 + 1,
         "q_a_norm": _draw(rng, ql) * 0.1 + 1,
         "kv_a_norm": _draw(rng, kvl) * 0.1 + 1,
         "wq_a": _draw(rng, d, ql, scale=d ** -0.5),
         "wq_b": _draw(rng, ql, H * (dn + dr), scale=ql ** -0.5),
         "wkv_a": _draw(rng, d, kvl + dr, scale=d ** -0.5),
         "wkv_b": _draw(rng, kvl, H * (dn + dv), scale=kvl ** -0.5),
         "wo": _draw(rng, H * dv, d, scale=(H * dv) ** -0.5)}
    x = _draw(rng, n, d)
    config = ref_config(rope_scaling=yarn)
    got = np.asarray(ref.mla(x, w, config), np.float64)

    f = {k: np.asarray(v, np.float64) for k, v in w.items()}
    xs = np.asarray(x, np.float64)

    def rms(a, g):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True) + 1e-6) * g

    freq = 10000.0 ** (-np.arange(0, dr, 2) / dr)
    m_all = 1.0
    if yarn:
        low, high = ref.yarn_range(dr, 10000.0, 16, 32, 1)
        ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0, 1)
        freq = freq * (1 - ramp) + freq / 40 * ramp
        assert 0 < ramp.min() or ramp.max() > 0        # the blend is live
        m_all = 0.1 * 0.707 * math.log(40) + 1
    scale = (dn + dr) ** -0.5 * m_all ** 2

    def rot(vec, t):
        out = vec.copy()
        for i in range(dr // 2):
            c, s_ = math.cos(t * freq[i]), math.sin(t * freq[i])
            out[2 * i] = vec[2 * i] * c - vec[2 * i + 1] * s_
            out[2 * i + 1] = vec[2 * i] * s_ + vec[2 * i + 1] * c
        return out

    h = rms(xs, f["in_norm"])
    q = (rms(h @ f["wq_a"], f["q_a_norm"]) @ f["wq_b"]).reshape(n, H, dn + dr)
    ckv = h @ f["wkv_a"]
    kv = (rms(ckv[:, :kvl], f["kv_a_norm"]) @ f["wkv_b"]
          ).reshape(n, H, dn + dv)
    k_pe = np.stack([rot(ckv[t, kvl:], t) for t in range(n)])
    want = np.zeros((n, H * dv))
    for head in range(H):
        for t in range(n):
            qt = np.concatenate([q[t, head, :dn], rot(q[t, head, dn:], t)])
            keys = np.concatenate([kv[:t + 1, head, :dn], k_pe[:t + 1]], 1)
            logit = keys @ qt * scale
            p = np.exp(logit - logit.max())
            want[t, head * dv:(head + 1) * dv] = \
                (p / p.sum()) @ kv[:t + 1, head, dn:]
    want = want @ f["wo"]
    assert np.max(np.abs(got - want)) < 2e-4 * np.abs(want).max()


@pytest.mark.parametrize("S_, tiles, H", [
    (256, (64, 128), 3), (256, (128, 64), 4), (384, (128, 128), 6),
    (128, (128, 128), 2)])
def test_tiled_kernel_matches_full_attention(S_, tiles, H):
    """Interpret mode, on the projections' layouts: qk width 16 + 8
    beside v width 16, lengths of several tiles (and one), groups of 3,
    4, 3 and 2 heads a grid step."""
    rng = np.random.default_rng(S_)
    dn, dr, dv = 16, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    qn, qp = draw(2, S_, H * dn) * 0.4, draw(2, S_, H * dr) * 0.4
    kv, kp = draw(2, S_, H * (dn + dv)), draw(2, S_, dr)
    assert mla_attention.heads_per_step(H, dr) == {3: 3, 4: 4, 6: 6, 2: 2}[H]
    got = mla_attention.flash(qn, qp, kv, kp, heads=H, dn=dn, dv=dv,
                              interpret=True, tile_q=tiles[0],
                              tile_k=tiles[1])
    assert got.shape == (2, S_, H * dv) and got.dtype == jnp.bfloat16

    def by_head(t, width):
        return t.reshape(2, S_, H, width).transpose(0, 2, 1, 3).astype(
            jnp.float32)

    kvh = by_head(kv, dn + dv)
    q = jnp.concatenate([by_head(qn, dn), by_head(qp, dr)], -1) \
        * math.sqrt(dn + dr)
    k = jnp.concatenate([kvh[..., :dn], jnp.broadcast_to(
        kp[:, None].astype(jnp.float32), (2, H, S_, dr))], -1)
    want = full_attention(q, k, kvh[..., dn:]).transpose(0, 2, 1, 3) \
        .reshape(2, S_, H * dv)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02
    # the dispatcher's plain path is the same attention
    plain = mla_attention.attend(qn, qp, kv, kp, heads=H, dn=dn, dv=dv,
                                 scale=1.0)
    assert float(jnp.max(jnp.abs(plain.astype(jnp.float32) - want))) < 0.03
    with pytest.raises(ValueError, match="whole tiles"):
        mla_attention.flash(qn, qp, kv, kp, heads=H, dn=dn, dv=dv,
                            interpret=True, tile_q=80)


def test_rope_on_the_projections_layout_is_the_published_rotation():
    """Columns reordered on the weights' side, then one lane-dense
    rotation: the same dot products as rotating the published pairs."""
    rng = np.random.default_rng(9)
    H, dn, dr, n = 3, 4, 8, 10
    x = jnp.asarray(rng.standard_normal((1, n, H * (dn + dr))), jnp.float32)
    inv = mla_attention.yarn_inv_freq(dr, 10000.0)
    cols = mla_attention.query_columns(H, dn, dr)
    assert sorted(cols[:H * (dn + dr)].tolist()) == list(range(H * (dn + dr)))
    q = x[..., cols]
    got = mla_attention.rope_apart(
        q[..., H * dn:H * (dn + dr)], q[..., H * (dn + dr):], inv, dr)
    per = np.asarray(x).reshape(n, H, dn + dr)[..., dn:]
    for t in range(n):
        for h in range(H):
            for i in range(dr // 2):
                a, b = per[t, h, 2 * i], per[t, h, 2 * i + 1]
                c, s_ = math.cos(t * inv[i]), math.sin(t * inv[i])
                np.testing.assert_allclose(
                    [got[0, t, h * dr + i], got[0, t, h * dr + dr // 2 + i]],
                    [a * c - b * s_, a * s_ + b * c], atol=1e-5)


def test_kernel_rule_is_shape_and_backend():
    assert not mla_attention.uses_kernel(8192, inference=True)     # the CPU
    assert mla_attention.heads_per_step(128, 64) == 2
    assert not moe.uses_kernel(inference=True)
    cfg = sessionrec.AlgorithmParams(**PARAMS).seqrec_config(ITEMS + 1)
    assert seqrec.BLOCKS["deepseek_v2"].kernels(cfg, 8192) == ()
    assert "retention_state_pass" not in seqrec.kernels(cfg, 8192)
    assert "moe_rows" not in seqrec.kernels(cfg, 8192)


def test_a_compiled_backend_moves_the_routed_rows_by_kernel(monkeypatch):
    from predictionio_tpu.ops import pallas_attention

    cfg = sessionrec.AlgorithmParams(**PARAMS).seqrec_config(ITEMS + 1)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    assert moe.uses_kernel(True)
    assert not moe.uses_kernel(False)               # a gradient may follow
    assert moe.moves_rows_by_dma("compiled", 5120)
    assert moe.moves_rows_by_dma("interpret", 128)
    assert not moe.moves_rows_by_dma(None, 5120)    # no gmm: plain rows
    assert not moe.moves_rows_by_dma("compiled", 64)    # a DMA moves lanes
    # rows of 64: the grouped products as gmm, the rows by plain gathers
    assert seqrec.BLOCKS["deepseek_v2"].kernels(cfg, 8192)[-1:] == ("gmm",)
    assert "moe_rows" not in seqrec.kernels(cfg, 8192)
    wide = dataclasses.replace(cfg, d_model=128)
    # experts 32 wide over rows of 128: the up products read as held
    assert seqrec.BLOCKS["deepseek_v2"].kernels(wide, 8192)[-3:] == (
        "gmm", "gmm_transpose_rhs", "moe_rows")
    assert "moe_rows" in seqrec.kernels(wide, 8192)


# -- the router and the routed layer -----------------------------------------

def _route(x, w_router, **over):
    kw = dict(n_group=4, topk_group=2, top_k=3, scaling=4.0)
    return moe.route(x, w_router, **{**kw, **over})


def test_router_matches_a_plain_loop_ties_included():
    rng = np.random.default_rng(5)
    x, w_router = _draw(rng, 200, 64), _draw(rng, 64, 16, scale=0.125)
    ids, weights, scores = _route(x, w_router)
    cfg = ref.widths(ref_config())
    for t in range(200):
        want = ref.select(np.asarray(scores[t], np.float64), cfg)
        assert ids[t].tolist() == want
        np.testing.assert_allclose(weights[t], 4.0 * scores[t][ids[t]],
                                   rtol=1e-6)
    # ties: equal logits everywhere, so every score is 1/16 and the
    # lower index wins, for groups (0, 1) and for experts (0, 1, 2)
    ids, weights, scores = _route(jnp.zeros((3, 64)), w_router)
    assert ids.tolist() == [[0, 1, 2]] * 3
    assert ref.select(np.full(16, 1 / 16), cfg) == [0, 1, 2]
    # experts tied for the third place in one kept group, and the third
    # group's best within 2.5% of the second's: the lower index is kept,
    # and the other resolutions come after it, cheapest first
    tied = np.array([.3, .02, .02, .02, .02, .2, .02, .02,
                     .195, .01, .01, .01, .1, .01, .01, .01])
    found = ref.selections(tied / tied.sum(), cfg, near_tie=0.05)
    assert found[0] == (0.0, [0, 5, 1])
    resolved = [ids for _, ids in found]
    assert [0, 5, 2] in resolved and [0, 5, 3] in resolved    # expert ties
    assert [0, 8, 1] in resolved and [0, 12, 1] not in resolved  # group 2 for 1
    assert all(cost < 0.05 for cost, _ in found)
    assert [cost for cost, _ in found] == sorted(cost for cost, _ in found)
    assert ref.selections(tied / tied.sum(), cfg) == [(0.0, [0, 5, 1])]


def _expert_weights(rng, n, d=64, f=32):
    return {"w_gate": _draw(rng, n, d, f, scale=d ** -0.5),
            "w_up": _draw(rng, n, d, f, scale=d ** -0.5),
            "w_down": _draw(rng, n, f, d, scale=f ** -0.5)}


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_the_four_shares_add_up_to_the_uncut_layer(kernel):
    """Experts 0-3, 4-7, 8-11 and 12-15, each share's routed part from
    the program, plus the shared experts counted once, equal the uncut
    layer from the uncut reference."""
    rng = np.random.default_rng(6)
    T = 96
    h = _draw(rng, T, 64)
    layer = {"router": _draw(rng, 64, 16, scale=0.125),
             "experts": _expert_weights(rng, 16),
             "shared": {k: v[0] for k, v in
                        _expert_weights(rng, 1, f=64).items()}}
    uncut = ref.expert_layer(h, layer, ref_config(
        {**WIDTHS, "experts_held": [0, 16]}))
    ids, weights, _ = _route(h, layer["router"])
    total = np.asarray(ref.swiglu(h, layer["shared"]), np.float64)
    counted = 0
    for first in (0, 4, 8, 12):
        share = {k: v[first:first + 4] for k, v in layer["experts"].items()}
        part, counts = moe.routed_experts(
            h, ids, weights, share["w_gate"], share["w_up"],
            share["w_down"], first, kernel=kernel)
        # each share alone is what the reference gives for that share
        alone = ref.expert_layer(h, {**layer, "experts": share}, ref_config(
            {**WIDTHS, "experts_held": [first, 4]}))
        np.testing.assert_allclose(
            np.asarray(part) + np.asarray(ref.swiglu(h, layer["shared"])),
            alone, atol=2e-5, rtol=2e-5)
        total += np.asarray(part, np.float64)
        counted += int(counts.sum())
    assert counted == T * 3                       # no assignment lost
    np.testing.assert_allclose(total, uncut, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_no_token_is_dropped_when_all_pick_one_expert(kernel):
    rng = np.random.default_rng(7)
    T = 80
    h = _draw(rng, T, 64)
    ex = _expert_weights(rng, 4)
    ids = jnp.full((T, 3), 6, jnp.int32).at[:, 1].set(1).at[:, 2].set(12)
    weights = jnp.asarray(rng.random((T, 3)), jnp.float32) + 0.5
    out, counts = moe.routed_experts(h, ids, weights, ex["w_gate"],
                                     ex["w_up"], ex["w_down"], 4,
                                     kernel=kernel)
    assert counts.tolist() == [0, 0, T, 0]
    e = {k: v[2] for k, v in ex.items()}
    want = weights[:, :1] * ref.swiglu(h, e)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.min(jnp.max(jnp.abs(out), axis=1))) > 0


def test_a_router_fed_bfloat16_scores_fails_where_it_matters():
    """160 experts as published: float32 scores choose what the plain
    loop chooses for every token; scores rounded to bfloat16 tie where
    float32 tells apart, and the layer's output moves by more than the
    check's tolerance on those tokens."""
    rng = np.random.default_rng(8)
    T = 2048
    h = _draw(rng, T, 64)
    w_router = _draw(rng, 64, 160, scale=0.125)
    kw = dict(n_group=8, topk_group=3, top_k=6, scaling=16.0)
    ids, weights, scores = moe.route(h, w_router, **kw)
    low_ids, low_w, _ = moe.route(h, w_router, score_dtype=jnp.bfloat16, **kw)
    cfg = {**ref.widths(ref_config()), "n_group": 8, "topk_group": 3,
           "top_k": 6}
    for t in range(0, T, 16):
        assert ids[t].tolist() == ref.select(
            np.asarray(scores[t], np.float64), cfg)
    flipped = np.flatnonzero(np.any(np.sort(ids, 1) != np.sort(low_ids, 1),
                                    axis=1))
    assert 0.005 * T < len(flipped) < 0.3 * T
    ex = _expert_weights(rng, 40)
    out, _ = moe.routed_experts(h, ids, weights, ex["w_gate"], ex["w_up"],
                                ex["w_down"], 0, kernel=None)
    low, _ = moe.routed_experts(h, low_ids, low_w, ex["w_gate"], ex["w_up"],
                                ex["w_down"], 0, kernel=None)
    moved = np.max(np.abs(np.asarray(out - low)), axis=1)
    rest = np.setdiff1d(np.arange(T), flipped)
    assert np.max(moved[flipped]) > 0.5 > LOGIT_TOL
    # where the choice is the same only the weights' rounding is left
    assert np.max(moved[rest]) < 0.05


# -- the model ---------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_model():
    params = sessionrec.AlgorithmParams(**PARAMS)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(21), cfg))          # float32, as a trained one
    rng = np.random.default_rng(22)
    histories = {f"u{u}": rng.integers(1, ITEMS + 1, size=n).astype(np.int32)
                 for u, n in enumerate([S, S, 40, 7, 90, S, S, S, S, S])}
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(ITEMS)}),
        histories=histories)
    return sessionrec.SeqRecAlgorithm(params), model


def _check_against_reference(model, user, item_scores, num):
    """The answer agrees, whole, with the reference under one resolution
    of the last position's router near ties."""
    history = model.histories[user][-S:]
    variants = ref.resolutions(
        sessionrec._as_device_tree(model), history, ref_config(),
        near_tie=0.05)
    assert len(item_scores) == num
    ids = [int(s["item"][1:]) + 1 for s in item_scores]
    assert not set(ids) & set(history.tolist()) and 0 not in ids
    worst = []
    for logits, _ in variants:
        logits = np.asarray(logits)
        allowed = logits.copy()
        allowed[0] = -np.inf
        allowed[history] = -np.inf
        tenth = np.sort(allowed)[-num]
        worst.append(max(
            max(abs(s["score"] - logits[ix])
                for ix, s in zip(ids, item_scores)),
            (tenth - min(allowed[ids])) / 2))
    assert min(worst) < LOGIT_TOL, worst


def test_config_and_params_hold_the_widths_in_one_record():
    assert seqrec.SeqRecConfig(vocab=11).mla_moe is None          # today's
    assert sessionrec.AlgorithmParams().mla_moe is None
    params = sessionrec.AlgorithmParams(**PARAMS)
    assert isinstance(params.mla_moe, seqrec.MlaMoeWidths)
    assert params.mla_moe.experts_held == (4, 4)
    assert params.mla_moe.rope_scaling == seqrec.YarnScaling(
        factor=40, original_max_position_embeddings=16)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    assert cfg.mla_moe is params.mla_moe and hash(cfg) == hash(
        sessionrec.AlgorithmParams(**PARAMS).seqrec_config(vocab=ITEMS + 1))
    # what the model store writes and reads back binds to the same record
    from predictionio_tpu.controller.params import (
        params_from_json, params_to_json)
    again = params_from_json(sessionrec.AlgorithmParams,
                             json.loads(json.dumps(params_to_json(params))))
    assert again == params
    tree = seqrec.init_params(jax.random.PRNGKey(0), cfg)
    assert "ffn" in tree["layers"][0] and "router" not in tree["layers"][0]
    assert tree["layers"][1]["router"].shape == (64, 16)
    assert tree["layers"][1]["experts"]["w_gate"].shape == (4, 64, 32)
    assert tree["layers"][2]["shared"]["w_down"].shape == (64, 64)


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


def test_forward_logits_match_the_reference_over_the_catalog(engine_model):
    _, model = engine_model
    tree = sessionrec._as_device_tree(model)
    history = model.histories["u1"]
    scores, ids, assignments = seqrec.predict_topk_batch(
        tree, jnp.asarray(history[None]), ITEMS + 1, model.cfg,
        jnp.zeros((1, ITEMS + 1)))
    got = np.zeros(ITEMS + 1, np.float32)
    got[np.asarray(ids[0])] = np.asarray(scores[0])
    variants = ref.resolutions(tree, history, ref_config(), near_tie=0.05)
    assert min(float(np.max(np.abs(got - np.asarray(v)))) for v, _ in variants
               ) < LOGIT_TOL
    assert assignments.shape == (2, 4) and assignments.dtype == jnp.int32
    assert 0 < int(assignments.sum()) < 2 * S * 3


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
    assert per_token == seqrec.BLOCKS["deepseek_v2"].bytes_per_token(
        model.cfg) == 2 * (
            2 * 64 + 4 * (2 * 24 + 2 * 32 + 2 * 16) + 3 * (3 * 64 + 3 * 32))
    monkeypatch.setattr(sessionrec, "_device_memory_bytes",
                        lambda: 4 * per_token * 2.5 * S)
    model.budget = 0
    assert sessionrec.token_budget(model) == 2 * S
    split = dict(algo.batch_predict(model, queries))
    model.set_dispatch_observer(None)
    model.budget = 0
    one, four = seen
    assert (one.programs, four.programs, one.split, four.split) == (1, 4, 0, 1)
    assert one.padded_tokens == four.padded_tokens == 8 * S
    assert one.moe_tokens == four.moe_tokens == 8 * S   # once, not per layer
    # every position is routed, padding too: the same assignments whether
    # one program ran or four; the fullest expert is summed per program
    assert one.moe_assignments == four.moe_assignments > 0
    assert one.moe_assignments < 2 * 8 * S * 3
    assert one.moe_max_expert_load < four.moe_max_expert_load \
        <= four.moe_assignments
    assert one.sparse_rows == one.ssm_tokens == 0
    stats = ServingStats()
    for report in seen:
        stats.record_seq_dispatch(report)
    assert stats.count("seq_programs") == 5
    assert stats.count("seq_moe_tokens") == 16 * S
    assert stats.count("seq_moe_assignments") == 2 * one.moe_assignments
    assert stats.count("seq_moe_max_expert_load") == \
        one.moe_max_expert_load + four.moe_max_expert_load
    for i in whole:
        assert [s.item for s in whole[i].item_scores] == \
            [s.item for s in split[i].item_scores]


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
        assert 0 < serving["seqMoeAssignments"] < 2 * S * 3
        assert 0 < serving["seqMoeMaxExpertLoad"] <= \
            serving["seqMoeAssignments"]
        assert serving["seqKernels"] == []                      # the CPU
        metrics = get("/metrics")
        assert b"pio_serving_seq_moe_tokens_total %d" % S in metrics
        assert b"pio_serving_seq_moe_assignments_total" in metrics
    finally:
        server.stop()
        model.set_dispatch_observer(None)


def test_train_deploy_query_with_the_deepseek_v2_backbone(
        storage, monkeypatch, tmp_path):
    """engine.json -> pio train -> model store -> pio deploy --batching
    -> /queries.json, as every engine is reached."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    app_id = storage.get_meta_data_apps().insert(App(0, "DeepSeekApp"))
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
        "id": "deepseek-sess",
        "engineFactory": "predictionio_tpu.templates.sessionrec.engine_factory",
        "datasource": {"params": {"app_name": "DeepSeekApp"}},
        "algorithms": [{"name": "seqrec", "params": {
            "backbone": "deepseek_v2", "d_model": 32, "n_heads": 4,
            "d_ff": 64, "n_layers": 2, "max_len": 16, "rope_theta": 10000.0,
            "tie_embeddings": False, "param_dtype": "bfloat16",
            "epochs": 30, "batch_size": 16, "lr": 3e-3, "seed": 0,
            "mla_moe": {
                "q_lora_rank": 16, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
                "qk_rope_head_dim": 4, "v_head_dim": 8,
                "moe_intermediate_size": 16, "n_routed_experts": 8,
                "experts_held": [0, 8], "n_shared_experts": 1,
                "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
                "routed_scaling_factor": 1.0, "first_k_dense_replace": 1,
                "rope_scaling": None}}}],
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
        assert model.cfg.block == "deepseek_v2"
        assert model.cfg.mla_moe.experts_held == (0, 8)
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(model.device_tree))
    finally:
        server.stop()
