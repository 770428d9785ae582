"""The ``nemotron_h`` block of the session engine at a tiny size on the
CPU: the reference's Mamba-2 layer against the recurrence written out in
a Python loop, the chunked state-space scan against that recurrence at
several chunks and ragged lengths, the convolution and the gated norm
against loops, the sigmoid router against a plain loop, the routed
layer's shares adding up to the uncut layer, the depth cut against the
uncut model, the controls that prove the check sees each mechanism and
each precision, ``ops/moe`` and ``ops/retention`` byte for byte what
they were for the kinds that share them, and the model through
``batch_predict``, the engine server and ``pio train`` against
``benchmarks/reference/nemotron_h_jnp.py``."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import urllib.request
from datetime import datetime, timedelta, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.models.blocks import nemotron_h
from predictionio_tpu.ops import moe, retention, ssm
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests import retention_cases as cases
from tests.seq_wiring import wired_stats

ITEMS, S = 300, 96
PATTERN = "EMEM*"
#: the tiny preset: d 64; 8 Mamba heads of 8 in 2 groups, state 16,
#: chunks of 32; 4 query heads over 2 key/value heads of 16; 16 experts
#: of width 32 (3 a token, experts 0-7 held) and a shared one of 64.
#: The tests' own weights (``_weights``) are the family's init with
#: ``A`` at a sixteenth and ``wq``, ``wk`` doubled, which keep the carry
#: between chunks and the attention layer alive at this size (the
#: controls): a choice of weights, as a trained model's are
WIDTHS = dict(hybrid_override_pattern=PATTERN, mamba_num_heads=8,
              mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
              chunk_size=32, moe_intermediate_size=32,
              moe_shared_expert_intermediate_size=64, n_routed_experts=16,
              experts_held=[0, 8], num_experts_per_tok=3,
              routed_scaling_factor=2.5)
PARAMS = dict(backbone="nemotron_h", d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, n_layers=len(PATTERN), max_len=S, rms_eps=1e-5,
              tie_embeddings=False, param_dtype="bfloat16", use_mesh=False,
              nemotron_h=WIDTHS)
#: logits of the tiny model against the reference: bfloat16 activations
#: through five layers (logits are ~N(0, 1); the worst seen is 0.03)
LOGIT_TOL = 0.08


def ref_config(widths=WIDTHS, **over):
    """The keys the reference reads, as a configuration file has them."""
    first, held = widths["experts_held"]
    keep = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel",
            "num_experts_per_tok", "routed_scaling_factor")
    return {**{k: widths[k] for k in keep}, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "layer_norm_epsilon": 1e-5, "n_routed_experts": held,
            "experts_held": [first, held],
            "published": {"n_routed_experts": widths["n_routed_experts"]},
            **over}


def _weights(seed=3, dtype=jnp.bfloat16, **over):
    params = sessionrec.AlgorithmParams(**{**PARAMS, **over})
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = seqrec.init_params(jax.random.PRNGKey(seed), cfg, dtype)
    for layer in weights["layers"]:
        if "A_log" in layer:
            layer["A_log"] = (layer["A_log"].astype(jnp.float32)
                              - np.log(16.0)).astype(dtype)
        if "wq" in layer:
            layer["wq"], layer["wk"] = layer["wq"] * 2, layer["wk"] * 2
    return cfg, weights


def _history(seed, n=S):
    return np.random.default_rng(seed).integers(1, ITEMS + 1, n) \
        .astype(np.int32)


def _program(weights, cfg, history):
    """The served program's logits of the last position, its
    assignments, and the experts it chose at the history's positions
    (expert layers, len(history), top_k)."""
    padded = np.zeros((1, cfg.max_len), np.int32)
    padded[0, :len(history)] = history
    hidden, assignments, _, chose = seqrec.BLOCKS["nemotron_h"].forward(
        weights, padded, cfg, None, "seq", True)
    logits = jnp.einsum("d,vd->v", hidden[0, len(history) - 1],
                        weights["head"].astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return (np.asarray(logits), np.asarray(assignments),
            np.asarray(chose)[:, 0, :len(history)])


def _program_logits(weights, cfg, history):
    return _program(weights, cfg, history)[:2]


def _choices(weights, cfg, history):
    return _program(weights, cfg, history)[2]


def _resolved(weights, cfg, history, config, near_tie=0.05):
    """The reference following the program through its near ties."""
    return np.asarray(next(ref.resolutions(
        weights, history, config, near_tie=near_tie,
        choices=_choices(weights, cfg, history)))[0])


def _draw(rng, *shape, scale=1.0):
    """Values bfloat16 holds exactly, as float32."""
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16
                       ).astype(jnp.float32)


def _np(tree):
    return {k: np.asarray(v.astype(jnp.float32), np.float64)  # pio: lint-ignore[dtype-discipline]: a host-side oracle written out in loops
            for k, v in tree.items()}


# -- the reference against the equations, position by position ---------------

def test_the_references_mamba_layer_is_the_recurrence_in_a_loop():
    """[z | xBC | dt], the convolution over the last four positions,
    softplus steps, one (p x n) state a head updated and read a
    position, the skip, the gated group norm: float64 loops."""
    cfg, weights = _weights()
    w = weights["layers"][1]
    n, H, p, G, N = 40, 8, 8, 2, 16
    inner, conv_dim = H * p, H * p + 2 * G * N
    u = _draw(np.random.default_rng(4), n, 64)
    got = np.asarray(ref.mamba_layer(u, w, ref_config()), np.float64)

    f, us = _np(w), np.asarray(u, np.float64)
    proj = us @ f["w_in"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + conv_dim],
                  proj[:, inner + conv_dim:])

    def silu(a):
        return a / (1 + np.exp(-a))

    conv = np.zeros_like(xbc)
    for t in range(n):
        for c in range(conv_dim):
            acc = f["conv_b"][c]
            for j in range(4):
                if t - 3 + j >= 0:
                    acc += f["conv_w"][c, j] * xbc[t - 3 + j, c]
            conv[t, c] = silu(acc)
    step = np.log1p(np.exp(dt + f["dt_bias"]))
    a = -np.exp(f["A_log"])
    state = np.zeros((H, p, N))
    y = np.zeros((n, H, p))
    for t in range(n):
        x = conv[t, :inner].reshape(H, p)
        b = conv[t, inner:inner + G * N].reshape(G, N)
        c = conv[t, inner + G * N:].reshape(G, N)
        for h in range(H):
            g = h // (H // G)
            state[h] = np.exp(step[t, h] * a[h]) * state[h] \
                + step[t, h] * np.outer(x[h], b[g])
            y[t, h] = state[h] @ c[g] + f["D"][h] * x[h]
    v = y.reshape(n, inner) * silu(z)
    v = v.reshape(n, G, inner // G)
    v = v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + 1e-5)
    want = (v.reshape(n, inner) * f["gated_norm"]) @ f["w_out"]
    assert np.max(np.abs(got - want)) < 2e-4 * np.abs(want).max()
    # the head mapping is live: heads of one group share B and C, the
    # two groups' differ
    assert a[0] != a[1] and not np.allclose(conv[:, inner:inner + N],
                                            conv[:, inner + N:inner + 2 * N])


def test_the_references_attention_is_causal_softmax_over_grouped_heads():
    cfg, weights = _weights()
    w = weights["layers"][4]
    n, H, G, d = 30, 4, 2, 16
    u = _draw(np.random.default_rng(5), n, 64)
    got = np.asarray(ref.attention_layer(u, w, ref_config()), np.float64)
    f, us = _np(w), np.asarray(u, np.float64)
    q = (us @ f["wq"]).reshape(n, H, d)
    k = (us @ f["wk"]).reshape(n, G, d)
    v = (us @ f["wv"]).reshape(n, G, d)
    out = np.zeros((n, H, d))
    for t in range(n):
        for h in range(H):
            g = h // (H // G)
            logit = k[:t + 1, g] @ q[t, h] / np.sqrt(d)       # no positions
            pr = np.exp(logit - logit.max())
            out[t, h] = (pr / pr.sum()) @ v[:t + 1, g]
    want = out.reshape(n, H * d) @ f["wo"]
    assert np.max(np.abs(got - want)) < 2e-4 * np.abs(want).max()


# -- the state-space scan and the two element passes -------------------------

def _ssd_inputs(seed, n, B=2, H=8, p=8, G=2, N=16):
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((B, n, H, p)), bf)
    b, c = (jnp.asarray(rng.standard_normal((B, n, G, N)) * 0.5, bf)
            for _ in range(2))
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((B, n, H)) - 3)),
                     jnp.float32)
    a = -jnp.asarray(np.arange(1, H + 1) / 8, jnp.float32)
    d = jnp.asarray(rng.standard_normal(H), jnp.float32)
    return x, dt, a, b, c, d


def _ssd_loop(x, dt, a, b, c, d, cut=0):
    """The recurrence, one position and head at a time, float64."""
    x, dt, a, b, c, d = (np.asarray(t.astype(jnp.float32), np.float64)
                         for t in (x, dt, a, b, c, d))
    B, n, H, p = x.shape
    R = H // b.shape[2]
    y = np.zeros_like(x)
    for i in range(B):
        state = np.zeros((H, p, b.shape[3]))
        for t in range(n):
            if cut and t % cut == 0:
                state[:] = 0
            for h in range(H):
                state[h] = np.exp(dt[i, t, h] * a[h]) * state[h] \
                    + dt[i, t, h] * np.outer(x[i, t, h], b[i, t, h // R])
                y[i, t, h] = state[h] @ c[i, t, h // R] + d[h] * x[i, t, h]
    return y


@pytest.mark.parametrize("n,chunk", [(96, 16), (128, 64), (256, 128),
                                     (70, 32), (40, 64), (130, 128)])
def test_the_chunked_scan_is_the_recurrence(n, chunk):
    """Whole and ragged lengths, a history shorter than a chunk, chunks
    16 / 32 / 64 / 128: the result does not depend on the chunk."""
    args = _ssd_inputs(n, n)
    want = _ssd_loop(*args)
    got, steps = retention.ssd_scan(*args, chunk=chunk)
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    # the steps the loop itself counted: the chunks that cover n
    assert steps.dtype == jnp.int32 and int(steps) == -(-n // chunk)
    # bfloat16 operands of the in-chunk products, a bfloat16 result
    assert np.max(np.abs(got - want)) < 0.02 * np.abs(want).max()
    other = np.asarray(retention.ssd_scan(*args, chunk=16)[0], np.float64)
    assert np.max(np.abs(got - other)) < 0.02 * np.abs(want).max()


def test_a_dropped_carry_or_a_bfloat16_state_is_caught():
    """What the scan's float32 buys, at the operation: against the
    float64 recurrence the scan is within 0.6%; one that drops its state
    between chunks computes another function, and a state held in
    bfloat16 reads half as far off again as float32 does."""
    n, chunk = 512, 64
    x, dt, a, b, c, d = _ssd_inputs(9, n, B=1)
    a = a / 16                                  # memories of ~500 positions
    f32 = (x.astype(jnp.float32), dt, a, b.astype(jnp.float32),
           c.astype(jnp.float32), d)
    want = _ssd_loop(x, dt, a, b, c, d)
    scale = np.abs(want).max()

    def off(got):
        return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                     ) / scale

    base = off(retention.ssd_scan(*f32, chunk=chunk)[0])
    assert base < 0.006
    # a scan that lost its carry: every chunk scanned from a zero state
    x32, _, _, b32, c32, _ = f32
    lost = jnp.concatenate([retention.ssd_scan(
        x32[:, lo:lo + chunk], dt[:, lo:lo + chunk], a,
        b32[:, lo:lo + chunk], c32[:, lo:lo + chunk], d, chunk=chunk)[0]
        for lo in range(0, n, chunk)], axis=1)
    assert off(lost) > 0.2
    cut = _ssd_loop(x, dt, a, b, c, d, cut=chunk)
    assert np.max(np.abs(np.asarray(lost, np.float64) - cut)) < 0.006 * scale
    assert off(retention.ssd_scan(*f32, chunk=chunk,
                                  _state_dtype=jnp.bfloat16)[0]) > 1.5 * base


def test_the_scan_is_one_while_and_differentiable():
    args = _ssd_inputs(2, 64, B=1)
    text = jax.jit(lambda *a: retention.ssd_scan(*a, chunk=16)).lower(
        *args).as_text()
    assert text.count("stablehlo.while") == 1
    grads = jax.grad(lambda x, dt, b, c: jnp.sum(retention.ssd_scan(
        x, dt, args[2], b, c, args[5], chunk=16)[0].astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3))(*(t.astype(jnp.float32) for t in (
            args[0], args[1], args[3], args[4])))
    assert all(float(jnp.sum(jnp.abs(g))) > 0 for g in grads)
    with pytest.raises(ValueError, match="value heads over"):
        retention.ssd_scan(args[0][:, :, :7], args[1][:, :, :7], args[2][:7],
                           args[3], args[4], args[5][:7])


def test_the_convolution_and_the_gated_norm_are_their_loops():
    rng = np.random.default_rng(6)
    n, ch, groups = 21, 32, 4
    x = jnp.asarray(rng.standard_normal((2, n, ch)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((ch, 4)) * 0.5, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal(ch) * 0.5, jnp.bfloat16)
    got = np.asarray(ssm.causal_conv_silu(x, w, b), np.float64)
    xs, ws, bs = (np.asarray(t.astype(jnp.float32), np.float64)
                  for t in (x, w, b))
    want = np.zeros_like(xs)
    for t in range(n):
        acc = bs.copy()
        for j in range(4):
            if t - 3 + j >= 0:
                acc = acc + ws[:, j] * xs[:, t - 3 + j]
        want[:, t] = acc / (1 + np.exp(-acc))
    assert got.dtype == np.float64 and np.max(np.abs(got - want)) < 0.02
    # padding after a history cannot reach an earlier position
    longer = jnp.concatenate([x, jnp.ones((2, 5, ch), jnp.bfloat16)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(ssm.causal_conv_silu(longer, w, b)[:, :n], np.float32),
        np.asarray(ssm.causal_conv_silu(x, w, b), np.float32))

    y, z = (jnp.asarray(rng.standard_normal((2, n, ch)), jnp.bfloat16)
            for _ in range(2))
    g = jnp.asarray(rng.standard_normal(ch) * 0.1 + 1, jnp.bfloat16)
    out = ssm.gated_group_norm(y, z, g, groups=groups, eps=1e-5)
    assert out.dtype == jnp.bfloat16 and out.shape == y.shape
    ys, zs, gs = (np.asarray(t.astype(jnp.float32), np.float64)
                  for t in (y, z, g))
    v = (ys * zs / (1 + np.exp(-zs))).reshape(2, n, groups, ch // groups)
    v = v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + 1e-5)
    want = v.reshape(2, n, ch) * gs
    assert np.max(np.abs(np.asarray(out, np.float64) - want)) < 0.03
    assert float(jnp.sum(jnp.abs(jax.grad(lambda t: jnp.sum(
        ssm.gated_group_norm(t, z.astype(jnp.float32), g, groups=groups,
                             eps=1e-5) ** 2))(y.astype(jnp.float32))))) > 0


# -- the router and the routed layer -----------------------------------------

def _route(x, w_router, bias=None, **over):
    kw = dict(n_group=1, topk_group=1, top_k=3, scaling=2.5,
              scoring="sigmoid", bias=bias, renormalise=True)
    return moe.route(x, w_router, **{**kw, **over})


def test_the_sigmoid_router_matches_a_plain_loop_bias_and_ties_included():
    rng = np.random.default_rng(5)
    x, w_router = _draw(rng, 200, 64), _draw(rng, 64, 16, scale=0.125)
    bias = _draw(rng, 16, scale=0.05)
    cfg = ref.widths(ref_config())
    for b in (None, bias):
        ids, weights, scores = _route(x, w_router, b)
        bs = np.zeros(16) if b is None else np.asarray(b, np.float64)
        for t in range(200):
            s = np.asarray(scores[t], np.float64)
            assert ids[t].tolist() == ref.select(s, bs, cfg)
            # the weights are the scores, never the bias, over all chosen
            np.testing.assert_allclose(
                weights[t], 2.5 * s[ids[t]] / s[ids[t]].sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(scores), 1 / (1 + np.exp(
        -np.asarray(x @ w_router, np.float64))), rtol=1e-5)
    # the bias chooses: it changes some token's experts
    assert np.any(np.asarray(_route(x, w_router)[0])
                  != np.asarray(_route(x, w_router, bias)[0]))
    # ties: equal logits everywhere, every score 1/2, the lower index wins
    ids, weights, _ = _route(jnp.zeros((3, 64)), w_router)
    assert ids.tolist() == [[0, 1, 2]] * 3
    np.testing.assert_allclose(weights, 2.5 / 3, rtol=1e-6)
    assert ref.select(np.full(16, 0.5), np.zeros(16), cfg) == [0, 1, 2]
    # near ties: another path's choice is followed where its weakest
    # expert scores within the margin of the reference's own third, and
    # nowhere else; what was given up is ln of the ratio
    tied = jnp.asarray([[.9, .8, .5, .495, .49, .2] + [.1] * 10] * 4)
    offered = jnp.asarray([[0, 1, 2], [1, 0, 3], [0, 4, 3], [0, 1, 5]])
    ids, margin, near = ref.choose(tied, jnp.zeros(16), offered, 0.05,
                                   top_k=3)
    assert ids.tolist() == [[0, 1, 2], [1, 0, 3], [0, 4, 3], [0, 1, 2]]
    np.testing.assert_allclose(
        margin, [0, np.log(.5 / .495), np.log(.5 / .49), 0], atol=1e-6)
    assert near.tolist() == [True, True, True, False]
    ids, margin, near = ref.choose(tied, jnp.zeros(16), offered, 0.0, top_k=3)
    assert ids.tolist() == [[0, 1, 2]] * 4 and not np.any(margin)
    assert near.tolist() == [True, False, False, False]
    # softmax scoring, group limits and no renormalising are still there
    ids, weights, scores = moe.route(x, w_router, n_group=4, topk_group=2,
                                     top_k=3, scaling=4.0)
    np.testing.assert_allclose(np.asarray(scores).sum(axis=1), 1, rtol=1e-5)


def _expert_weights(rng, n, d=64, f=32):
    return {"w_up": _draw(rng, n, d, f, scale=d ** -0.5),
            "w_down": _draw(rng, n, f, d, scale=f ** -0.5)}


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_the_two_shares_add_up_to_the_uncut_layer(kernel):
    """Experts 0-7 and 8-15, each share's routed part from the program,
    plus the shared expert counted once, equal the uncut layer from the
    uncut reference."""
    rng = np.random.default_rng(6)
    T = 96
    h = _draw(rng, T, 64)
    layer = {"router": _draw(rng, 64, 16, scale=0.125),
             "router_bias": _draw(rng, 16, scale=0.02),
             "experts": _expert_weights(rng, 16),
             "shared": {k: v[0] for k, v in
                        _expert_weights(rng, 1, f=64).items()}}
    uncut = ref.expert_layer(h, layer, ref_config(
        {**WIDTHS, "experts_held": [0, 16]}))
    ids, weights, _ = _route(h, layer["router"], layer["router_bias"])
    shared = np.asarray(ref._unit(h, layer["shared"]["w_up"],
                                  layer["shared"]["w_down"]), np.float64)
    total, counted = shared.copy(), 0
    for first in (0, 8):
        share = {k: v[first:first + 8] for k, v in layer["experts"].items()}
        part, counts = moe.routed_experts(
            h, ids, weights, None, share["w_up"], share["w_down"], first,
            kernel=kernel, activation=moe.relu2)
        # each share alone is what the reference gives for that share
        alone = ref.expert_layer(h, {**layer, "experts": share}, ref_config(
            {**WIDTHS, "experts_held": [first, 8]}))
        np.testing.assert_allclose(np.asarray(part) + shared, alone,
                                   atol=3e-5, rtol=3e-5)
        total += np.asarray(part, np.float64)
        counted += int(counts.sum())
    assert counted == T * 3                       # no assignment lost
    np.testing.assert_allclose(total, uncut, atol=6e-5, rtol=6e-5)


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_no_token_is_dropped_when_all_pick_one_expert(kernel):
    rng = np.random.default_rng(7)
    T = 80
    h = _draw(rng, T, 64)
    ex = _expert_weights(rng, 4)
    ids = jnp.full((T, 3), 6, jnp.int32).at[:, 1].set(1).at[:, 2].set(12)
    weights = jnp.asarray(rng.random((T, 3)), jnp.float32) + 0.5
    out, counts = moe.routed_experts(h, ids, weights, None, ex["w_up"],
                                     ex["w_down"], 4, kernel=kernel,
                                     activation=moe.relu2)
    assert counts.tolist() == [0, 0, T, 0]
    want = weights[:, :1] * ref._unit(h, ex["w_up"][2], ex["w_down"][2])
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
    assert float(jnp.min(jnp.max(jnp.abs(out), axis=1))) > 0
    # plain relu in the place of relu**2 is another layer
    plain, _ = moe.routed_experts(h, ids, weights, None, ex["w_up"],
                                  ex["w_down"], 4, kernel=kernel,
                                  activation=jax.nn.relu)
    assert float(jnp.max(jnp.abs(plain - out))) > 0.1


def test_a_router_fed_bfloat16_scores_fails_where_it_matters():
    """128 experts as published, 6 a token: float32 scores choose what
    the plain loop chooses; scores rounded to bfloat16 tie where float32
    tells apart, and the layer's output moves by more than the check's
    tolerance on those tokens."""
    rng = np.random.default_rng(8)
    T = 2048
    h = _draw(rng, T, 64)
    w_router = _draw(rng, 64, 128, scale=0.125)
    kw = dict(top_k=6)
    ids, weights, scores = _route(h, w_router, **kw)
    low_ids, low_w, _ = _route(h, w_router, score_dtype=jnp.bfloat16, **kw)
    cfg = {**ref.widths(ref_config()), "top_k": 6}
    for t in range(0, T, 16):
        assert ids[t].tolist() == ref.select(
            np.asarray(scores[t], np.float64), np.zeros(128), cfg)
    flipped = np.flatnonzero(np.any(np.sort(ids, 1) != np.sort(low_ids, 1),
                                    axis=1))
    assert 0.005 * T < len(flipped) < 0.4 * T
    ex = _expert_weights(rng, 64)
    out, _ = moe.routed_experts(h, ids, weights, None, ex["w_up"],
                                ex["w_down"], 0, kernel=None,
                                activation=moe.relu2)
    low, _ = moe.routed_experts(h, low_ids, low_w, None, ex["w_up"],
                                ex["w_down"], 0, kernel=None,
                                activation=moe.relu2)
    moved = np.max(np.abs(np.asarray(out - low)), axis=1)
    rest = np.setdiff1d(np.arange(T), flipped)
    assert np.max(moved[flipped]) > 0.5 > LOGIT_TOL
    # where the choice is the same only the weights' rounding is left
    assert np.max(moved[rest]) < 0.05


# -- the kinds that share ops/moe and ops/retention --------------------------

#: sha256 of the parent commit's (8afa7e5) outputs on the CPU for
#: DeepSeek-V2's arguments: ``route`` (ids, weights, scores) and
#: ``routed_experts`` (sum, counts) at d 64, 16 experts in 4 groups,
#: experts 4-7 held, plain and kernel (interpret) forms; and the tiny
#: ``deepseek_v2`` stack's hidden states and assignments
PARENT_MOE = {
    "route": "55793ea26c30bb4ea2844003f09e211176fdd6bf1b2f98d5bcc2ead1cdc14ce5",
    "routed_experts":
    "f439c74b908b3ddd99adab149360b66648ae75e8a07952ead6c7b1cc1bd4a83b",
    "deepseek_v2":
    "6214baf364327ae71b3946ac2ff3585b96c7dfca26a2575621b887bc1557cef1"}


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_route_and_routed_experts_are_byte_for_byte_the_parents(kernel):
    rng = np.random.default_rng(38)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    x, w_router = draw(96, 64), draw(64, 16, scale=0.125)
    ids, weights, scores = moe.route(x, w_router, n_group=4, topk_group=2,
                                     top_k=3, scaling=4.0)
    assert _digest(ids, weights, scores) == PARENT_MOE["route"]
    ex = [draw(4, 64, 32, scale=0.125), draw(4, 64, 32, scale=0.125),
          draw(4, 32, 64, scale=32 ** -0.5)]
    y, counts = moe.routed_experts(x, ids, weights, *ex, 4, kernel=kernel)
    assert _digest(y, counts) == PARENT_MOE["routed_experts"]
    # the grouped product's tiles at DeepSeek-V2's widths are what they were
    assert [moe._long_tile(n) for n in (5120, 1536, 2688, 1856, 64)] == \
        [512, 512, 384, 1856, 64]


@pytest.mark.parametrize("inference", [True, False])
def test_the_deepseek_v2_stack_is_byte_for_byte_the_parents(inference):
    from tests.test_deepseek_v2_seqrec import ITEMS as N, PARAMS as DS

    cfg = sessionrec.AlgorithmParams(**DS).seqrec_config(vocab=N + 1)
    w = seqrec.init_params(jax.random.PRNGKey(38), cfg, jnp.bfloat16)
    hist = np.random.default_rng(38).integers(
        1, N + 1, (2, cfg.max_len)).astype(np.int32)
    hidden, assignments = seqrec.BLOCKS["deepseek_v2"].forward(
        w, jnp.asarray(hist), cfg, None, "seq", inference)
    assert _digest(hidden, assignments) == PARENT_MOE["deepseek_v2"]


@pytest.mark.parametrize("name", list(cases.CASES))
def test_both_retention_degrees_are_byte_for_byte_the_parents(name):
    """The state-space member shares ``_chunk_major``, the decay helper
    (now at module level) and the way out with degrees 1 and 2: their
    values do not move (the digests are PR 37's parent's, which PR 37
    and this PR's parent both equal)."""
    case = cases.CASES[name]
    q, k, v, log_g = cases.inputs(name, **case)
    out = retention.power_retention(q, k, v, log_g, degree=case["degree"],
                                    chunk=case["chunk"])
    assert cases.digest(out) == cases.PARENT[name][0]


# -- the model ---------------------------------------------------------------

def test_config_and_params_hold_the_widths_in_one_record():
    assert seqrec.SeqRecConfig(vocab=11).nemotron_h is None       # today's
    assert sessionrec.AlgorithmParams().nemotron_h is None
    params = sessionrec.AlgorithmParams(**PARAMS)
    w = params.nemotron_h
    assert isinstance(w, seqrec.NemotronHWidths)
    assert w.experts_held == (0, 8) and (w.inner, w.conv_dim) == (64, 128)
    assert (w.count("M"), w.count("E"), w.count("*")) == (2, 2, 1)
    assert w.scan_chunk(S) == 32 and w.scan_chunk(20) == 16
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    assert cfg.nemotron_h is w and hash(cfg) == hash(
        sessionrec.AlgorithmParams(**PARAMS).seqrec_config(vocab=ITEMS + 1))
    # the defaults are the published model's
    pub = seqrec.NemotronHWidths()
    assert len(pub.hybrid_override_pattern) == 52
    assert (pub.count("M"), pub.count("E"), pub.count("*")) == (23, 23, 6)
    assert (pub.inner, pub.conv_dim, pub.chunk_size) == (4096, 6144, 128)
    # what the model store writes and reads back binds to the same record
    from predictionio_tpu.controller.params import (
        params_from_json, params_to_json)
    again = params_from_json(sessionrec.AlgorithmParams,
                             json.loads(json.dumps(params_to_json(params))))
    assert again == params
    tree = seqrec.init_params(jax.random.PRNGKey(0), cfg)
    assert [sorted(set(layer) - {"norm"})[0] for layer in tree["layers"]] == \
        ["experts", "A_log", "experts", "A_log", "wk"]
    mamba = tree["layers"][1]
    assert mamba["w_in"].shape == (64, 64 + 128 + 8)
    assert mamba["conv_w"].shape == (128, 4) and mamba["conv_b"].shape == (128,)
    np.testing.assert_allclose(np.exp(mamba["A_log"]), np.arange(1, 9),
                               rtol=1e-6)              # as the family's
    assert np.all(np.asarray(mamba["D"]) == 1)
    step = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float64)))
    assert np.all((step > 0.00099) & (step < 0.1001))      # as the family's
    ex = tree["layers"][0]
    assert ex["router"].shape == (64, 16) and not np.any(ex["router_bias"])
    assert ex["experts"]["w_up"].shape == (8, 64, 32)
    assert ex["shared"]["w_down"].shape == (64, 64)
    assert "w_gate" not in ex["experts"]
    assert tree["layers"][4]["wq"].shape == (64, 64)
    # no multiple on any matrix: normal / sqrt(fan-in)
    assert float(jnp.std(tree["layers"][4]["wq"])) == pytest.approx(
        1.0 / 8, rel=0.1)
    with pytest.raises(ValueError, match="pattern"):
        seqrec.init_params(jax.random.PRNGKey(0), sessionrec.AlgorithmParams(
            **{**PARAMS, "n_layers": 4}).seqrec_config(vocab=11))
    with pytest.raises(ValueError, match="pattern"):
        seqrec.init_params(jax.random.PRNGKey(0), sessionrec.AlgorithmParams(
            **{**PARAMS, "nemotron_h": {**WIDTHS, "hybrid_override_pattern":
                                        "EMEM-"}}).seqrec_config(vocab=11))


def test_kernel_rule_is_shape_and_backend(monkeypatch):
    from predictionio_tpu.ops import pallas_attention

    cfg = sessionrec.AlgorithmParams(**PARAMS).seqrec_config(ITEMS + 1)
    kind = seqrec.BLOCKS["nemotron_h"]
    assert kind.kernels(cfg, 16384) == ()                   # the CPU
    assert seqrec.kernels(cfg, 16384) == ()
    published = sessionrec.AlgorithmParams(
        backbone="nemotron_h", d_model=2688, n_heads=32, n_kv_heads=2,
        head_dim=128, n_layers=9, max_len=16384, nemotron_h={
            "hybrid_override_pattern": "EMEMEMEM*", "experts_held": [0, 64]}
    ).seqrec_config(vocab=65536)
    monkeypatch.setattr(pallas_attention, "_mode", lambda: "compiled")
    # no fourth attention kernel: the sparse layers' one, nothing masked
    assert kind.kernels(published, 16384) == (
        "sparse_block_attention", "gmm", "gmm_transpose_rhs", "moe_rows")
    assert kind.kernels(published, 500) == (
        "gmm", "gmm_transpose_rhs", "moe_rows")
    # the routed layers' row moves: the kinds that have them, by the rule
    # the forward pass applies (ops/moe.uses_kernel)
    assert "moe_rows" in seqrec.kernels(published, 16384)
    assert "moe_rows" not in seqrec.kernels(cfg, 16384)  # rows of 64
    no_experts = dataclasses.replace(published, nemotron_h=dataclasses.replace(
        published.nemotron_h, hybrid_override_pattern="M*"), n_layers=2)
    assert "moe_rows" not in seqrec.kernels(no_experts, 16384)
    assert "moe_rows" not in seqrec.kernels(sessionrec.AlgorithmParams(
        backbone="brumby").seqrec_config(vocab=11), 16384)
    # a token of the program holds 272 KB: the budget takes one history
    assert kind.bytes_per_token(published) == 271_872


@pytest.mark.parametrize("n", [S, 70, 33, 1])
def test_forward_matches_the_reference(n):
    """Whole chunks, ragged, barely past a chunk, a single event."""
    cfg, weights = _weights()
    history = _history(10 + n, n)
    got, assignments = _program_logits(weights, cfg, history)
    want = _resolved(weights, cfg, history, ref_config())
    assert float(np.max(np.abs(got - want))) < LOGIT_TOL
    assert assignments.shape == (2, 8) and assignments.dtype == np.int32
    # every position is routed, padding too: ~1.5 of 3 land on held experts
    assert 0.35 < assignments.sum() / (2 * S * 3) < 0.65


def test_padding_after_the_history_changes_nothing():
    cfg, weights = _weights()
    history = _history(5, 50)
    short, _ = _program_logits(weights, cfg, history)
    cfg2, _ = _weights(max_len=2 * S)
    longer, _ = _program_logits(weights, cfg2, history)
    assert np.max(np.abs(short - longer)) < 2e-2        # other chunking
    padded = np.zeros((2, S), np.int32)
    padded[:, :50] = history
    padded[1, 50:] = 7                                   # junk after the end
    hidden, _, steps, chose = seqrec.BLOCKS["nemotron_h"].forward(
        weights, padded, cfg, None, "seq", True)
    # two Mamba layers of S / 32 chunk steps; two expert layers' choices
    assert int(steps) == 2 * 3 and chose.shape == (2, 2, S, 3)
    np.testing.assert_array_equal(chose[:, 0, :50], chose[:, 1, :50])
    np.testing.assert_array_equal(np.asarray(hidden[0, :50], np.float32),
                                  np.asarray(hidden[1, :50], np.float32))


def test_the_reference_follows_a_choice_only_where_it_is_a_near_tie():
    cfg, weights = _weights()
    history = _history(2)
    config = ref_config()
    own, chose = ref.answer(weights, history, config)
    own = np.asarray(own)
    assert chose["ids"].shape == (2, S, 3)
    assert (chose["margin"], chose["adopted"], chose["refused"]) == (0, 0, 0)
    # with no margin nothing is followed: the one resolution is its own
    served = _choices(weights, cfg, history)
    found = list(ref.resolutions(weights, history, config, choices=served))
    assert len(found) == ref.MAX_STEPS == 1 and found[0][1] == 0.0
    np.testing.assert_array_equal(np.asarray(found[0][0]), own)
    # the program's own choices: a few tokens differ, each by a near tie
    logits, chose = ref.answer(weights, history, config, served, 0.05)
    assert chose["refused"] == 0 and chose["margin"] < 0.05
    assert chose["adopted"] == int(np.sum(np.any(
        np.sort(chose["ids"]) != np.sort(ref.answer(
            weights, history, config)[1]["ids"]), axis=-1)))
    # the last position's weakest expert exchanged for one far below:
    # refused, and the answer is the reference's own
    scores = np.asarray(ref.router_scores(ref.rmsnorm(jnp.take(
        weights["item_emb"], history, axis=0), weights["layers"][0]["norm"],
        1e-5), weights["layers"][0], ref.widths(config)))
    offered = ref.answer(weights, history, config)[1]["ids"].copy()
    offered[0, -1, -1] = int(np.argmin(scores[-1]))
    logits, chose = ref.answer(weights, history, config, offered, 0.05)
    assert (chose["adopted"], chose["refused"]) == (0, 1)
    np.testing.assert_array_equal(np.asarray(logits), own)
    # under a margin wide enough it is followed, and the answer moves
    logits, chose = ref.answer(weights, history, config, offered, 50.0)
    assert (chose["adopted"], chose["refused"]) == (1, 0)
    assert chose["margin"] > 0.05
    assert float(np.max(np.abs(np.asarray(logits) - own))) > LOGIT_TOL


CONTROLS = {"ssm_cut": {"control_ssm_cut": 32},
            "no_conv": {"control_no_conv": True},
            "no_skip": {"control_no_skip": True},
            "softmax_router": {"control_softmax_router": True},
            "plain_relu": {"control_plain_relu": True},
            "no_attention": {"control_no_attention": True}}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_each_mechanism_moves_the_logits_by_more_than_the_limits(control):
    """(a) the carry between chunks zeroed, (b) the convolution dropped,
    (c) the D skip dropped, (d) softmax routing, (e) plain relu, (f) the
    attention layer removed: each moves the last position's logits, and
    its top ten, by more than the check allows."""
    cfg, weights = _weights()
    history = _history(1)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    other = np.asarray(ref.last_logits(
        weights, history, ref_config(**CONTROLS[control])))
    top = np.argsort(-own)[:10]
    assert np.max(np.abs(other - own)) > ref.SCORE_TOL
    assert np.max(np.abs(other[top] - own[top])) > LOGIT_TOL


def test_a_served_scan_that_lost_its_carry_fails_the_check():
    """The program itself with the state dropped between chunks against
    the reference: off by more than the limits, where the program as it
    is sits inside them."""
    cfg, weights = _weights()
    history = _history(1)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    got, _ = _program_logits(weights, cfg, history)
    assert np.max(np.abs(got - own)) < LOGIT_TOL
    real = retention.ssd_scan

    def lost(x, dt, a, b, c, d, *, chunk):
        """Every chunk scanned from a zero state."""
        parts = [real(x[:, lo:lo + chunk], dt[:, lo:lo + chunk], a,
                      b[:, lo:lo + chunk], c[:, lo:lo + chunk], d,
                      chunk=chunk) for lo in range(0, x.shape[1], chunk)]
        return (jnp.concatenate([y for y, _ in parts], axis=1),
                sum(n for _, n in parts))

    try:
        nemotron_h.ssd_scan = lost
        jax.clear_caches()
        cut, _ = _program_logits(weights, cfg, history)
    finally:
        nemotron_h.ssd_scan = real
        jax.clear_caches()
    assert np.max(np.abs(cut - own)) > ref.SCORE_TOL
    want = np.asarray(ref.last_logits(weights, history,
                                      ref_config(control_ssm_cut=32)))
    assert np.max(np.abs(cut - want)) < LOGIT_TOL


@pytest.mark.parametrize("lower", ref.LOWER)
def test_each_lower_precision_is_a_reading_of_its_own(lower):
    """The reference in each lower precision differs from itself, by more
    than the limit for 8-bit operands; the readings at the cell's size
    are PERF.md section 6's."""
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


def test_the_cut_stack_is_layers_34_to_42_of_the_uncut_model():
    """The published pattern's one run of nine between two attention
    layers: a model of the published depth at the tiny widths, cut to
    layers 34-42, gives from the same input what those layers of the
    uncut model give."""
    published = seqrec.NemotronHWidths().hybrid_override_pattern
    first, last = 34, 42
    assert published[first:last + 1] == "EMEMEMEM*"
    assert published[first - 1] == "*" and len(published) == 52
    runs = [len(r) for r in published.split("*")]
    assert max(runs[1:-1]) == 8 and runs.count(8) == 1      # the one such run
    widths = {**WIDTHS, "hybrid_override_pattern": published}
    cfg = sessionrec.AlgorithmParams(**{
        **PARAMS, "n_layers": 52, "nemotron_h": widths}).seqrec_config(
            vocab=ITEMS + 1)
    uncut = seqrec.init_params(jax.random.PRNGKey(4), cfg, jnp.bfloat16)
    x = _draw(np.random.default_rng(4), 48, 64)
    held = uncut["layers"][first:last + 1]
    want = ref.hidden_states(held, x, ref_config(
        hybrid_override_pattern=published[first:last + 1]))
    through = x
    for n in range(first, last + 1):
        through = ref.hidden_states(
            [uncut["layers"][n]], through,
            ref_config(hybrid_override_pattern=published[n]))
    np.testing.assert_allclose(np.asarray(through), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and the program over the cut stack is the reference over it
    cut_cfg = sessionrec.AlgorithmParams(**{
        **PARAMS, "n_layers": 9, "max_len": 48, "nemotron_h": {
            **WIDTHS, "hybrid_override_pattern": "EMEMEMEM*"}}
    ).seqrec_config(vocab=ITEMS + 1)
    weights = {**uncut, "layers": held}
    history = _history(6, 48)
    got, assignments = _program_logits(weights, cut_cfg, history)
    want = _resolved(weights, cut_cfg, history, ref_config(
        hybrid_override_pattern="EMEMEMEM*"))
    assert float(np.max(np.abs(got - want))
                 ) < 1.5 * LOGIT_TOL                   # nine layers, not five
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
    # the experts the program that answered chose: its last output
    padded = np.zeros((1, S), np.int32)
    padded[0, :len(history)] = history
    chose = np.asarray(seqrec.predict_topk_batch(
        tree, padded, serving_k(num, ITEMS), model.cfg,
        np.zeros((1, ITEMS + 1), np.float32))[-1])[:, 0, :len(history)]
    variants = ref.resolutions(tree, history, ref_config(), near_tie=0.05,
                               choices=chose)
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
    # the residual twice, and twice an expert layer's rows: 3 assignments
    # of (64 + 32) x 2 in bfloat16 and the float32 sum twice
    assert per_token == 2 * 64 * 2 + 2 * (3 * 2 * (64 + 32) * 2 + 2 * 64 * 4)
    monkeypatch.setattr(sessionrec, "_device_memory_bytes",
                        lambda: 4 * per_token * 2.9 * S)
    model.budget = 0
    assert sessionrec.token_budget(model) == 256        # two histories of 96
    split = dict(algo.batch_predict(model, queries))
    model.set_dispatch_observer(None)
    model.budget = 0
    one, four = seen
    assert (one.programs, four.programs, one.split, four.split) == (1, 4, 0, 1)
    assert one.padded_tokens == four.padded_tokens == 8 * S
    assert one.moe_tokens == four.moe_tokens == 8 * S   # once, not per layer
    assert one.moe_assignments == four.moe_assignments > 0
    assert one.moe_max_expert_load < four.moe_max_expert_load \
        <= four.moe_assignments
    # two Mamba layers scan every token; S / 32 = 3 chunk steps a layer
    # and program, whatever the batch
    assert one.ssm_tokens == four.ssm_tokens == 2 * 8 * S
    assert (one.ssm_scan_steps, four.ssm_scan_steps) == (2 * 3, 4 * 2 * 3)
    assert one.sparse_rows == four.window_rows == 0
    stats = ServingStats()
    for report in seen:
        stats.record_seq_dispatch(report)
    assert stats.count("seq_programs") == 5
    assert stats.count("seq_moe_tokens") == 16 * S
    assert stats.count("seq_ssm_tokens") == 4 * 8 * S
    assert stats.count("seq_ssm_scan_steps") == 30
    for i in whole:
        assert [s.item for s in whole[i].item_scores] == \
            [s.item for s in split[i].item_scores]


def test_the_dispatch_counts_the_programs_that_moved_rows_by_dma(
        engine_model, monkeypatch):
    """Where the rule says the row kernel runs, the server's gauge lists
    it for every program of the deployment, beside ``seq_programs``;
    where it does not (the CPU), it does not."""
    algo, model = engine_model
    assert wired_stats(algo, model).snapshot()["seqKernels"] == []
    monkeypatch.setattr(seqrec, "kernels", lambda cfg, s: (
        "gmm", "moe_rows") if s == cfg.max_len else ())
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
    assert snap["seqKernels"] == ["gmm", "moe_rows"]
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
        assert 0 < serving["seqMoeAssignments"] < 2 * S * 3
        assert 0 < serving["seqMoeMaxExpertLoad"] <= \
            serving["seqMoeAssignments"]
        assert serving["seqSsmTokens"] == 2 * S
        assert serving["seqSsmScanSteps"] == 2 * 3
        # the row kernel runs on a compiled TPU backend only
        assert serving["seqKernels"] == []
        metrics = get("/metrics")
        assert b"pio_serving_seq_kernel{" not in metrics
        assert b"pio_serving_seq_ssm_tokens_total %d" % (2 * S) in metrics
        assert b"pio_serving_seq_ssm_scan_steps_total 6" in metrics
        assert b"pio_serving_seq_moe_assignments_total" in metrics
    finally:
        server.stop()
        model.set_dispatch_observer(None)


def test_train_deploy_query_with_the_nemotron_h_backbone(
        storage, monkeypatch, tmp_path):
    """engine.json -> pio train -> model store -> pio deploy --batching
    -> /queries.json, as every engine is reached."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    app_id = storage.get_meta_data_apps().insert(App(0, "NemotronApp"))
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
        "id": "nemotron-sess",
        "engineFactory": "predictionio_tpu.templates.sessionrec.engine_factory",
        "datasource": {"params": {"app_name": "NemotronApp"}},
        "algorithms": [{"name": "seqrec", "params": {
            "backbone": "nemotron_h", "d_model": 32, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 8, "n_layers": 3, "max_len": 16,
            "rms_eps": 1e-5, "tie_embeddings": False,
            "param_dtype": "bfloat16", "epochs": 30, "batch_size": 16,
            "lr": 3e-3, "seed": 0,
            "nemotron_h": {
                "hybrid_override_pattern": "ME*", "mamba_num_heads": 4,
                "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 8,
                "conv_kernel": 4, "chunk_size": 8,
                "moe_intermediate_size": 16,
                "moe_shared_expert_intermediate_size": 32,
                "n_routed_experts": 8, "experts_held": [0, 8],
                "num_experts_per_tok": 2,
                "routed_scaling_factor": 2.5}}}],
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
        assert model.cfg.block == "nemotron_h"
        assert model.cfg.nemotron_h.experts_held == (0, 8)
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(model.device_tree))
    finally:
        server.stop()
