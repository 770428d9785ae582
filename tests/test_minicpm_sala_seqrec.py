"""The ``minicpm_sala`` block of the session engine at a tiny size on the
CPU: the reference's two mixers against the equations written out in a
loop over positions, the chunked lightning scan against the quadratic
form, both sparse kernels in interpret mode against the ``jax.numpy``
forms, the selection's counters against a count by hand, the controls
that prove the check sees each mechanism, the depth cut against the
uncut model, and the model through ``batch_predict``, the engine server
and ``pio train`` against ``benchmarks/reference/minicpm_sala_jnp.py``."""

from __future__ import annotations

import hashlib
import json
import math
import urllib.request
from datetime import datetime, timedelta, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala_jnp as ref
from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.models import seqrec
from predictionio_tpu.models.blocks import minicpm_sala
from predictionio_tpu.ops import qk_norm, retention, sparse_attention as sa
from predictionio_tpu.templates import sessionrec
from predictionio_tpu.utils.bimap import BiMap
from tests import retention_cases as cases

ITEMS, S = 300, 192
MIXERS = ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
#: the tiny preset: d 64, 4 heads over 2 key/value heads of 16, 4
#: lightning heads of 16; key blocks of 16 of which a position keeps 6
#: (the first, its own two, and three it chooses) once a history passes
#: 64 events; compressed keys are means of 8 every 4
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
              init_blocks=1, window_size=32, dense_len=64)
WIDTHS = dict(mixer_types=list(MIXERS), lightning_nh=4, lightning_nkv=4,
              lightning_head_dim=16, scale_emb=12.0, scale_depth=1.4,
              dim_model_base=16, published_layers=32, qk_norm_init=2.0,
              **SPARSE)
PARAMS = dict(backbone="minicpm_sala", d_model=64, n_heads=4, n_kv_heads=2,
              head_dim=16, d_ff=128, n_layers=4, max_len=S,
              rope_theta=10000.0, tie_embeddings=False,
              param_dtype="bfloat16", use_mesh=False, sala=WIDTHS)
#: logits of the tiny model against the reference: bfloat16 activations
#: through four layers. The head's dim_model_base / d_model = 1/4 makes
#: the logits ~N(0, 0.25), so this is 5% of their spread (the worst seen
#: is 0.005)
LOGIT_TOL = 0.012


def ref_config(sparse=SPARSE, mixers=MIXERS, **over):
    """The keys the reference reads, as a configuration file has them."""
    return {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
            "lightning_nkv": 4, "lightning_head_dim": 16,
            "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "scale_emb": 12,
            "scale_depth": 1.4, "dim_model_base": 16,
            "mixer_types": list(mixers), "num_hidden_layers": len(mixers),
            "published": {"num_hidden_layers": 32},
            "sparse_config": dict(sparse), **over}


def _weights(seed=3, dtype=jnp.bfloat16, **over):
    params = sessionrec.AlgorithmParams(**{**PARAMS, **over})
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    return cfg, seqrec.init_params(jax.random.PRNGKey(seed), cfg, dtype)


def _history(seed, n=S):
    return np.random.default_rng(seed).integers(1, ITEMS + 1, n) \
        .astype(np.int32)


def _program_logits(weights, cfg, history):
    """The served program's logits of the last position, its counts and
    the blocks that position kept."""
    padded = np.zeros((1, cfg.max_len), np.int32)
    padded[0, :len(history)] = history
    hidden, counts, kept = seqrec.BLOCKS["minicpm_sala"].forward(
        weights, padded, cfg, None, "seq", True)
    logits = jnp.einsum("d,vd->v", hidden[0, len(history) - 1],
                        weights["head"].astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return np.asarray(logits), np.asarray(counts), np.asarray(kept[0])


# -- the reference against the equations, position by position ---------------

def _np_rms(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _np(tree):
    return {k: np.asarray(v.astype(jnp.float32), np.float64)  # pio: lint-ignore[dtype-discipline]: a host-side oracle written out in loops
            for k, v in tree.items()}


def test_the_references_lightning_mixer_is_the_equation_in_a_loop():
    _, weights = _weights()
    w, n, H, d = weights["layers"][1], 40, 4, 16
    h = np.random.default_rng(0).standard_normal((n, 64))
    got = np.asarray(ref.lightning_mixer(
        jnp.asarray(h, jnp.float32), w, ref.widths(ref_config())))
    wn = _np(w)

    def rope(x, t):
        inv = 10000.0 ** (-np.arange(0, d, 2) / d)
        cos = np.concatenate([np.cos(t * inv)] * 2)
        sin = np.concatenate([np.sin(t * inv)] * 2)
        return x * cos + np.concatenate([-x[d // 2:], x[:d // 2]]) * sin

    q = _np_rms((h @ wn["wq"]).reshape(n, H, d), wn["q_norm"])
    k = _np_rms((h @ wn["wk"]).reshape(n, H, d), wn["k_norm"])
    v = (h @ wn["wv"]).reshape(n, H, d)
    o = np.zeros((n, H, d))
    for t in range(n):
        for a in range(H):
            lam = math.exp(-2.0 ** (-8.0 * (a + 1) / H))
            for i in range(t + 1):
                o[t, a] += lam ** (t - i) * (
                    rope(q[t, a], t) @ rope(k[i, a], i) / math.sqrt(d)) \
                    * v[i, a]
    o = _np_rms(o.reshape(n, H * d), wn["o_norm"])
    want = (o / (1 + np.exp(-(h @ wn["wg"])))) @ wn["wo"]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("n", [120, 50])         # selecting, and dense
def test_the_references_sparse_mixer_is_the_equations_in_a_loop(n):
    _, weights = _weights()
    w, H, G, d = weights["layers"][0], 4, 2, 16
    sz = SPARSE
    h = np.random.default_rng(1).standard_normal((n, 64))
    got = np.asarray(ref.sparse_mixer(
        jnp.asarray(h, jnp.float32), w, ref.widths(ref_config())))
    wn = _np(w)
    q = _np_rms((h @ wn["wq"]).reshape(n, H, d), wn["q_norm"])
    k = _np_rms((h @ wn["wk"]).reshape(n, G, d), wn["k_norm"])
    v = (h @ wn["wv"]).reshape(n, G, d)
    nc = (n - sz["kernel_size"]) // sz["kernel_stride"] + 1
    kc = np.stack([k[sz["kernel_stride"] * j:
                     sz["kernel_stride"] * j + sz["kernel_size"]].mean(axis=0)
                   for j in range(nc)])
    nb, ratio = -(-n // sz["block_size"]), 4
    o = np.zeros((n, H, d))
    kept_sizes = []
    for t in range(n):
        own = t // sz["block_size"]
        for g in range(G):
            heads = range(g * (H // G), (g + 1) * (H // G))
            if n > sz["dense_len"]:
                seen = [j for j in range(nc) if sz["kernel_stride"] * j
                        + sz["kernel_size"] - 1 <= t]
                P = np.zeros(nc)
                for a in heads:
                    if seen:
                        e = np.exp([q[t, a] @ kc[j, g] / math.sqrt(d)
                                    for j in seen])
                        P[seen] += e / e.sum()
                score = np.full(nb, -np.inf)
                for b in range(own + 1):
                    js = [j for j in range(ratio * b - 1, ratio * b + ratio)
                          if 0 <= j < nc]
                    if js:
                        score[b] = P[js].max()
                    if b < sz["init_blocks"] or \
                            b > own - sz["window_size"] // sz["block_size"]:
                        score[b] = np.inf
                order = sorted(range(own + 1), key=lambda b: (-score[b], b))
                kept = set(order[:sz["topk"]])
                kept_sizes.append(len(kept))
            else:
                kept = set(range(own + 1))
            keys = [i for i in range(t + 1) if i // sz["block_size"] in kept]
            for a in heads:
                e = np.exp([q[t, a] @ k[i, g] / math.sqrt(d) for i in keys])
                o[t, a] = (e / e.sum()) @ v[keys, g]
    want = (o.reshape(n, H * d) / (1 + np.exp(-(h @ wn["wg"])))) @ wn["wo"]
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=3e-4)
    if n > sz["dense_len"]:
        assert max(kept_sizes) == sz["topk"] and min(kept_sizes) == 1


# -- lightning attention through the chunk scan -------------------------------

def _quadratic(q, k, v, log_g):
    """float32 ``((q k^T) * decay / sqrt(d)) v`` of bfloat16-exact inputs."""
    B, n, H, d = q.shape
    t = np.arange(n)
    gap = (t[:, None] - t[None, :])[None]
    decay = np.where(gap >= 0, np.exp(np.asarray(log_g)[:, None, None]
                                      * np.maximum(gap, 0)), 0.0)
    s = np.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
    return np.einsum("bhts,bshd->bthd", s * decay[None], v)


def _qkv(seed, n, H=4, d=16):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, n, H, d)), jnp.bfloat16)
                 for _ in range(3))


@pytest.mark.parametrize("n,chunk", [(96, 16), (70, 32), (40, 64)])
def test_the_chunked_lightning_scan_is_the_quadratic_form(n, chunk):
    """chunk < n: the state is carried; n not whole chunks: padded."""
    q, k, v = _qkv(5, n)
    log_g = jnp.asarray(minicpm_sala.lightning_log_decay(4))
    got = retention.power_retention(q, k, v, log_g, degree=1, chunk=chunk)
    want = _quadratic(*(np.asarray(a, np.float32) for a in (q, k, v)), log_g)
    # the in-chunk weights and the output are rounded to bfloat16 once:
    # 2^-8 of values up to ~40
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=0.2)
    # the gated form (a decay a position) is the same program
    per_position = jnp.broadcast_to(log_g, (1, n, 4))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(retention.power_retention(
            q, k, v, per_position, degree=1, chunk=chunk), np.float32))


def test_a_dropped_or_bfloat16_lightning_state_is_caught():
    q, k, v = _qkv(6, 256)
    # the slowest head alone: it remembers ~256 positions
    log_g = jnp.full((4,), float(minicpm_sala.lightning_log_decay(32)[-1]))
    want = _quadratic(*(np.asarray(a, np.float32) for a in (q, k, v)), log_g)

    def off(**kw):
        # float32 queries: the output is not rounded, the operands are
        got = retention.power_retention(q.astype(jnp.float32), k, v, log_g,
                                        degree=1, chunk=16, **kw)
        return float(np.max(np.abs(np.asarray(got, np.float32) - want)))

    carried = off()
    assert carried < 0.15               # of values up to 48 (read: 0.114)
    # sixteen updates of a bfloat16 state lose what float32 keeps (0.255)
    assert off(_state_dtype=jnp.bfloat16) > 2 * carried
    # no state at all: every chunk on its own
    alone = np.concatenate([_quadratic(*(np.asarray(a[:, lo:lo + 16],
                                                    np.float32)
                                         for a in (q, k, v)), log_g)
                            for lo in range(0, 256, 16)], axis=1)
    assert float(np.max(np.abs(alone - want))) > 10 * carried


def test_power_retention_of_degree_2_is_byte_for_byte_what_it_was():
    """The digest is of the parent commit's output (3a5a627) for these
    inputs on the CPU: degree 1 shares the layout and the scan and must
    not have moved degree 2."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 80, 4, 16)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((2, 80, 2, 16)), jnp.bfloat16)
            for _ in range(2))
    log_g = jnp.asarray(-np.abs(rng.standard_normal((2, 80, 2))) * 0.05,
                        jnp.float32)
    out = retention.power_retention(q, k, v, log_g, degree=2, chunk=32)
    assert hashlib.sha256(np.asarray(out, np.float32).tobytes()).hexdigest() \
        == DEGREE_2_DIGEST
    with pytest.raises(NotImplementedError, match="degree 1 .* degree 2"):
        retention.power_retention(q, k, v, log_g, degree=3)


DEGREE_2_DIGEST = "c9246bd5b56c5e36c44a288894f088695de9e230ec5b9f37a3dcf46649c0cbb4"


@pytest.mark.parametrize("name", list(cases.CASES))
def test_power_retention_is_byte_for_byte_the_parents(name):
    """PR 37 moved the cast into the scan's step, the way out into the
    step's last write and the layout into one function: values do not
    move. The digests are the parent commit's outputs (3ae3a70) on the
    CPU: both degrees, R = 1 and R > 1, S not a whole number of chunks,
    B = 2, a constant decay and a gate a position, bfloat16 and float32
    callers."""
    case = cases.CASES[name]
    q, k, v, log_g = cases.inputs(name, **case)
    out = retention.power_retention(q, k, v, log_g, degree=case["degree"],
                                    chunk=case["chunk"])
    assert out.shape == q.shape and out.dtype == q.dtype
    assert cases.digest(out) == cases.PARENT[name][0]
    # a caller that takes no gradient gets the same bytes on the CPU
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(retention.power_retention(
            q, k, v, log_g, degree=case["degree"], chunk=case["chunk"],
            inference=True), np.float32))


def test_a_forward_pass_builds_the_rope_tables_once():
    """cos and sin are made where the program starts, not once a layer
    and array (four lightning layers here: eight rotations)."""
    cfg, weights = _weights(sala={**WIDTHS, "mixer_types": [
        "lightning-attn"] * 4})
    assert cfg.sala.mixer_types.count("lightning-attn") == 4
    seqs = jnp.asarray(_history(1)[None, :64])
    for inference in (True, False):
        jaxpr = str(jax.make_jaxpr(
            lambda w: seqrec.BLOCKS["minicpm_sala"].forward(
                w, seqs, cfg, None, "seq", inference))(weights))
        assert jaxpr.count(" cos ") == jaxpr.count(" sin ") == 1
        assert jaxpr.count("optimization_barrier") == 4     # a way out each


def test_the_lightning_mixers_way_in_is_the_parents_formulation():
    """One lightning layer's q through ``power_retention``'s way in
    against norm, RoPE by ``concatenate``, cast and
    ``reshape().transpose()`` written out: bit for bit (no rounding
    between norm and rotation)."""
    cfg, weights = _weights()
    layer = weights["layers"][1]
    rng = np.random.default_rng(4)
    B, n, H, d, C = 2, 70, 4, 16, 32
    q, k = (jnp.asarray(rng.standard_normal((B, n, H * d)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, n, H, d)), jnp.bfloat16)
    way_in = retention.WayIn(layer["q_norm"], layer["k_norm"], 1e-6,
                             qk_norm.rope_tables(n, d, 10000.0))
    qc, kc, vc, gc = retention._chunk_major(
        q.reshape(B, n, H, d), k.reshape(B, n, H, d), v, None, C, way_in)
    assert gc is None and qc.shape == (3, B, H, 1, C, d)
    pad = ((0, 0), (0, 3 * C - n), (0, 0))
    for got, x, weight in ((qc[:, :, :, 0], q, layer["q_norm"]),
                           (kc, k, layer["k_norm"])):
        # the parent normed and rotated, then padded: padding is zeros
        want = cases.plain_way_in(x, weight, heads=H, eps=1e-6, theta=10000.0)
        want = jnp.pad(want.reshape(B, n, H * d), pad).reshape(
            B, 3, C, H, d).transpose(1, 0, 3, 2, 4)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    # and the mixing over them is the mixing over the parent's operands
    log_decay = jnp.asarray(minicpm_sala.lightning_log_decay(H))
    plain = [cases.plain_way_in(x, w, heads=H, eps=1e-6, theta=10000.0)
             for x, w in ((q, layer["q_norm"]), (k, layer["k_norm"]))]
    np.testing.assert_array_equal(
        np.asarray(retention.power_retention(
            q.reshape(B, n, H, d), k.reshape(B, n, H, d), v, log_decay,
            degree=1, chunk=C, way_in=way_in), np.float32),
        np.asarray(retention.power_retention(
            *plain, v, log_decay, degree=1, chunk=C), np.float32))


# -- the sparse kernels in interpret mode -------------------------------------

KSZ = sa.SparseSizes(**{**SPARSE, "topk": 5})


def _sparse_inputs(seed=8, n=256, G=2, R=2, d=128):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, n, G * R * d)) * 3 / math.sqrt(d),
                    jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((1, n, G * d)), jnp.bfloat16)
            for _ in range(2))
    return q, k, v


def test_the_selection_kernel_scores_blocks_as_the_plain_form_does():
    q, k, _ = _sparse_inputs()
    n, G, R, d = 256, 2, 2, 128
    got = sa.selection_scores(q, k, sz=KSZ, groups=G, interpret=True, tile=32)
    kc = sa.compressed_keys(k.reshape(1, n, G, d), KSZ)
    want = sa.block_scores(q.reshape(1, n, G, R, d), kc, jnp.arange(n), KSZ,
                           sa.n_blocks(n, KSZ))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(want.max()) > 0.5      # probabilities of R heads, summed


@pytest.mark.parametrize("tq,tk", [(64, 128), (16, 32), (32, 256)])
def test_the_attention_kernel_is_stage_2_on_the_same_selection(tq, tk):
    q, k, v = _sparse_inputs()
    n, G, R, d = 256, 2, 2, 128
    want, counts, kept = sa.attend(q, k, v, KSZ, groups=G)
    visits = sa.visit_map(kept, n, tq, tk, KSZ.block_size)
    got = sa.attention(q, k, v, kept, visits, groups=G, block=KSZ.block_size,
                       interpret=True, tile_q=tq, tile_k=tk)
    # both round probabilities and the output to bfloat16 once
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.03)
    # a visit the map leaves out changes nothing it should not: the map
    # holds every tile with a kept block, and smaller tiles skip more
    by_hand = np.asarray(kept).reshape(1, G, n // tq, tq, n // tk,
                                       tk // KSZ.block_size).any(axis=(3, 5))
    np.testing.assert_array_equal(np.asarray(visits), by_hand)
    assert visits.mean() < 1.0 or (tq, tk) == (32, 256)
    # without a selection the same kernel is plain causal attention
    causal = sa.visit_map(None, n, tq, tk, KSZ.block_size)
    dense = sa.attention(q, k, v, None, causal, groups=G,
                         block=KSZ.block_size, interpret=True, tile_q=tq,
                         tile_k=tk)
    plain = sa._attend_plain(q.reshape(1, n, G, R, d), k.reshape(1, n, G, d),
                             v.reshape(1, n, G, d), None, KSZ)
    np.testing.assert_allclose(np.asarray(dense, np.float32),
                               np.asarray(plain).reshape(1, n, -1), atol=0.03)


def test_the_counters_are_a_count_of_the_selection_by_hand():
    q, k, v = _sparse_inputs()
    n, G = 256, 2
    valid = jnp.arange(n)[None, :] < 200
    _, counts, kept = sa.attend(q, k, v, KSZ, groups=G, valid=valid)
    kept = np.asarray(kept)
    rows = blocks = scored = 0
    for t in range(200):
        own = t // KSZ.block_size
        for g in range(G):
            rows += 1
            blocks += int(kept[0, g, t].sum())
            assert kept[0, g, t, 0] and kept[0, g, t, own] \
                and not kept[0, g, t, own + 1:].any()
            assert kept[0, g, t].sum() == min(own + 1, KSZ.topk)
            scored += own + 1           # the plain form scores every key
    assert np.asarray(counts).tolist() == [rows, blocks, scored]
    # the kernel's count is of the map it is given
    tq, tk = 64, 128
    visits = sa.visit_map(jnp.asarray(kept), n, tq, tk, KSZ.block_size)
    per_position = jnp.repeat(visits.sum(axis=-1, dtype=jnp.int32)
                              * (tk // KSZ.block_size), tq, axis=-1)
    by_map = sa.visit_counts(jnp.asarray(kept), per_position, valid)
    hand = sum(int(np.asarray(visits)[0, g, t // tq].sum())
               * (tk // KSZ.block_size) for t in range(200) for g in range(G))
    assert np.asarray(by_map).tolist() == [rows, blocks, hand]
    ids = np.asarray(sa.kept_ids(jnp.asarray(kept[0, :, 199]), KSZ.topk))
    assert [sorted(np.flatnonzero(kept[0, g, 199]).tolist())
            for g in range(G)] == ids.tolist()


def test_only_a_compiled_backend_at_an_eligible_shape_runs_the_kernels(
        monkeypatch):
    sz = sa.SparseSizes()
    assert not sa.uses_kernel(32768, True, sz)           # the CPU
    monkeypatch.setattr(sa.pallas_attention, "_mode", lambda: "compiled")
    assert sa.uses_kernel(32768, True, sz) and sa.uses_kernel(8192, True, sz)
    assert not sa.uses_kernel(32768, False, sz)          # may differentiate
    assert not sa.uses_kernel(32768 + 64, True, sz)      # not whole tiles
    assert not sa.uses_kernel(512, True, sz)
    assert not sa.uses_kernel(32768, True, sz, d=64)
    assert not sa.uses_kernel(1 << 18, True, sz)         # K, V outside VMEM
    assert sa.kernel_names(32768, sz) == ("sparse_block_selection",
                                          "sparse_block_attention")
    assert sa.kernel_names(8192, sz) == ("sparse_block_attention",)
    cfg, _ = _weights()
    assert seqrec.BLOCKS["minicpm_sala"].kernels(cfg, S) == ()
    full = sessionrec.AlgorithmParams(
        backbone="minicpm_sala", d_model=4096, n_heads=32, n_kv_heads=2,
        head_dim=128, d_ff=16384, n_layers=32, max_len=32768,
        sala={}).seqrec_config(vocab=73448)
    assert full.sala.mixer_types.count("minicpm4") == 8
    assert seqrec.BLOCKS["minicpm_sala"].kernels(full, 32768) == \
        sa.kernel_names(32768, sz) + ("qk_norm_rope",)
    assert "qk_norm_rope" in seqrec.kernels(full, 32768)
    assert "qk_norm_rope" not in seqrec.kernels(cfg, S)     # heads of 16


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("n,dense_len", [(S, 64), (150, 64), (60, 64),
                                         (S, 4096)])
def test_forward_matches_the_reference(n, dense_len):
    """Above and under ``dense_len``, and a length that is neither whole
    blocks nor whole chunks (150)."""
    sparse = {**SPARSE, "dense_len": dense_len}
    cfg, weights = _weights(sala={**WIDTHS, **sparse}, max_len=n)
    history = _history(11, n)
    got, counts, kept = _program_logits(weights, cfg, history)
    config = ref_config(sparse)
    want = np.asarray(ref.last_logits(weights, history, config))
    assert 0.15 < want.std() < 0.4
    assert float(np.max(np.abs(got - want))) < LOGIT_TOL
    if n <= dense_len:
        assert counts.tolist() == [0, 0, 0] and (kept == -1).all()
        return
    nb = -(-n // sparse["block_size"])
    assert counts[0] == n * 2 * 2                   # positions x layers x G
    assert counts[1] == 2 * 2 * sum(min(t // 16 + 1, 6) for t in range(n))
    assert counts[2] == 2 * 2 * sum(t // 16 + 1 for t in range(n))
    # the blocks the program's last position kept are the reference's, up
    # to a near tie at the edge of the top-k
    for scores, layer_kept in zip(ref.last_kept(weights, history, config),
                                  kept):
        for s, ids in zip(scores, layer_kept):
            own = np.argsort(-s, kind="stable")[:sparse["topk"]]
            assert len(ids) == sparse["topk"] and ids.max() < nb
            edge = s[own[-1]]
            for b in set(ids.tolist()) ^ set(own.tolist()):
                assert abs(math.log(s[b] / edge)) < 0.1


def test_padding_after_the_history_changes_nothing():
    cfg, weights = _weights()
    history = _history(12, 130)
    short, _, _ = _program_logits(weights, cfg, history)
    want = np.asarray(ref.last_logits(weights, history, ref_config()))
    assert float(np.max(np.abs(short - want))) < LOGIT_TOL


def test_resolutions_start_at_the_references_own_choice_and_give_up_margin():
    cfg, weights = _weights()
    history = _history(11)
    config = ref_config()
    own = np.asarray(ref.last_logits(weights, history, config))
    found = list(ref.resolutions(weights, history, config, near_tie=0.5,
                                 max_steps=80))
    np.testing.assert_array_equal(np.asarray(found[0][0]), own)
    costs = [cost for _, cost in found]
    assert found[0][1] == 0.0 and costs == sorted(costs) and len(found) > 1
    assert all(0 < c < 4 * 0.5 for c in costs[1:])
    # another choice of blocks is another answer, by more than rounding
    assert float(np.max(np.abs(np.asarray(found[1][0]) - own))) > 1e-4
    # with no near tie allowed there is one resolution
    assert len(list(ref.resolutions(weights, history, config))) == 1
    picks = ref.selections(np.asarray([np.inf, 0.5, 0.49, 0.2, np.inf, -np.inf]),
                           {"topk": 3}, near_tie=0.1)
    assert picks[0] == (0.0, (0, 1, 4)) and picks[1][1] == (0, 2, 4)
    assert 0 < picks[1][0] < 0.1 and len(picks) == 2


@pytest.mark.parametrize("control", [
    {"sparse_config": {**SPARSE, "dense_len": 4096}},       # stage 2 dense
    {"sparse_config": {**SPARSE, "topk": 3}},               # forced blocks only
    {"control_lightning_cut": 64},                          # no carried state
])
def test_each_mechanism_moves_the_logits_by_more_than_the_tolerance(control):
    """Controls: were stage 2 plain causal attention, the selection the
    forced blocks alone, or the lightning state dropped at chunk edges,
    the check would say so."""
    _, weights = _weights()
    history = _history(11)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    other = np.asarray(ref.last_logits(weights, history,
                                       ref_config(**control)))
    assert float(np.max(np.abs(other - own))) > LOGIT_TOL


def test_operands_rounded_to_8_bits_fail():
    _, weights = _weights()
    history = _history(11)
    own = np.asarray(ref.last_logits(weights, history, ref_config()))
    try:
        ref.set_lower("operands")
        low = np.asarray(ref.last_logits(weights, history, ref_config()))
    finally:
        ref.set_lower(None)
    assert float(np.max(np.abs(low - own))) > 2 * LOGIT_TOL


def test_the_cut_stack_is_layers_9_to_16_of_the_uncut_model():
    """The depth cut changes which layers are held, not what a layer
    computes: the uncut 32-layer reference run to layer 17 equals the
    program over layers 9-16 alone, fed layer 9's input activations
    (through an embedding table that holds them), with c = 1.4 /
    sqrt(32) in both."""
    uncut = list(minicpm_sala._SALA_MIXERS)
    n = 96
    full_cfg, full = _weights(seed=4, dtype=jnp.float32, n_layers=32,
                              sala={**WIDTHS, "mixer_types": uncut})
    config = ref_config(mixers=uncut)
    del config["published"]                     # the uncut model: depth 32
    x0 = ref.embed(full, _history(13, n), ref.widths(config))
    x9 = ref.hidden_states(full["layers"][:9], x0, config, uncut[:9])
    x17 = ref.hidden_states(full["layers"][9:17], x9, config, uncut[9:17])
    held = uncut[9:17]
    assert held == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    cut_cfg = sessionrec.AlgorithmParams(**{
        **PARAMS, "n_layers": 8, "max_len": n,
        "sala": {**WIDTHS, "mixer_types": held}}).seqrec_config(vocab=n + 1)
    cut_cfg = cut_cfg.__class__(**{**cut_cfg.__dict__, "dtype": jnp.float32})
    table = jnp.concatenate([jnp.zeros((1, 64)), x9 / 12.0])
    cut = {"item_emb": table, "head": table,
           "out_norm": full["out_norm"], "layers": full["layers"][9:17]}
    with jax.default_matmul_precision("highest"):
        hidden = seqrec.forward(cut, np.arange(1, n + 1)[None], cut_cfg)
    want = ref.rmsnorm(x17, full["out_norm"], 1e-6) * (16 / 64)
    # float32 on both sides; the program's products still take bfloat16
    # operands inside the scan and stage 2
    np.testing.assert_allclose(np.asarray(hidden[0]), np.asarray(want),
                               atol=0.02)
    assert cut_cfg.sala.residual_scale == pytest.approx(1.4 / math.sqrt(32))


# -- the served path ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine_model():
    params = sessionrec.AlgorithmParams(**PARAMS)
    cfg = params.seqrec_config(vocab=ITEMS + 1)
    weights = jax.tree.map(np.array, seqrec.init_params(
        jax.random.PRNGKey(21), cfg))           # float32, as a trained one
    rng = np.random.default_rng(22)
    histories = {f"u{u}": rng.integers(1, ITEMS + 1, size=n).astype(np.int32)
                 for u, n in enumerate([S, S, 100, 7, 250, S, S, S])}
    model = sessionrec.SeqRecEngineModel(
        params=weights, cfg=cfg,
        item_index=BiMap({f"i{k}": k + 1 for k in range(ITEMS)}),
        histories=histories)
    return sessionrec.SeqRecAlgorithm(params), model


def _check_against_reference(model, user, item_scores, num):
    history = model.histories[user][-S:]
    want = np.asarray(ref.last_logits(sessionrec._as_device_tree(model),
                                      history, ref_config()))
    assert len(item_scores) == num
    ids = [int(s["item"][1:]) + 1 for s in item_scores]
    assert not set(ids) & set(history.tolist()) and 0 not in ids
    got = np.asarray([s["score"] for s in item_scores])
    assert float(np.max(np.abs(got - want[ids]))) < LOGIT_TOL
    allowed = want.copy()
    allowed[0] = -np.inf
    allowed[history] = -np.inf
    tenth = np.sort(allowed)[-num]
    assert float(np.max(tenth - allowed[ids])) < 2 * LOGIT_TOL


def test_batch_predict_matches_the_reference_and_reports_the_selection(
        engine_model):
    algo, model = engine_model
    seen = []
    model.set_dispatch_observer(seen.append)
    try:
        users = ["u0", "u2", "u4", "nobody"]
        out = dict(algo.batch_predict(model, [
            (i, sessionrec.Query(user=u, num=10))
            for i, u in enumerate(users)]))
    finally:
        model.set_dispatch_observer(None)
    assert out[3].item_scores == ()
    for i, u in enumerate(users[:3]):
        _check_against_reference(
            model, u, [{"item": s.item, "score": s.score}
                       for s in out[i].item_scores], 10)
    (report,) = seen
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree.leaves(model.device_tree))
    # u0 192 events, u2 100, u4's last 192, in buckets of two and one:
    # real positions x 2 sparse layers x 2 key/value heads
    assert report.programs == 2 and report.padded_tokens == 3 * S
    assert report.sparse_rows == (192 + 100 + 192) * 4
    assert report.sparse_blocks_selected == 4 * sum(
        min(t // 16 + 1, 6) for n in (192, 100, 192) for t in range(n))
    assert report.sparse_keys_scored == 4 * 16 * sum(
        t // 16 + 1 for n in (192, 100, 192) for t in range(n))
    assert report.moe_tokens == 0
    stats = ServingStats()
    stats.record_seq_dispatch(report)
    stats.record_seq_dispatch(report)
    assert stats.count("seq_sparse_rows") == 2 * report.sparse_rows
    assert stats.count("seq_sparse_keys_scored") == \
        2 * report.sparse_keys_scored


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
        with urllib.request.urlopen(req, timeout=120) as resp:
            answer = json.loads(resp.read())
        _check_against_reference(model, "u1", answer["itemScores"], 10)
        serving = json.loads(get("/stats.json"))["serving"]
        assert serving["seqPrograms"] == 1
        assert serving["seqSparseRows"] == S * 4
        assert serving["seqSparseBlocksSelected"] == 4 * sum(
            min(t // 16 + 1, 6) for t in range(S))
        assert serving["seqSparseKeysScored"] >= \
            16 * serving["seqSparseBlocksSelected"]
        assert serving["seqMoeTokens"] == 0
        metrics = get("/metrics")
        assert b"pio_serving_seq_sparse_rows_total %d" % (S * 4) in metrics
        assert b"pio_serving_seq_sparse_keys_scored_total" in metrics
    finally:
        server.stop()
        model.set_dispatch_observer(None)


def test_train_deploy_query_with_the_minicpm_sala_backbone(
        storage, monkeypatch, tmp_path):
    """engine.json -> pio train -> model store -> pio deploy --batching
    -> /queries.json, as every engine is reached. Training takes the
    plain forms (the kernels are forward only)."""
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.core.event import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_MODEL_DIR", str(tmp_path))
    app_id = storage.get_meta_data_apps().insert(App(0, "SalaApp"))
    events = storage.get_events()
    events.init(app_id)
    t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
    rng = np.random.default_rng(0)
    for u in range(32):
        start = int(rng.integers(10))
        for t in range(12):
            events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + t) % 10}",
                event_time=t0 + timedelta(minutes=u * 100 + t)), app_id)
    variant = {
        "id": "sala-sess",
        "engineFactory": "predictionio_tpu.templates.sessionrec.engine_factory",
        "datasource": {"params": {"app_name": "SalaApp"}},
        "algorithms": [{"name": "seqrec", "params": {
            "backbone": "minicpm_sala", "d_model": 32, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 8, "d_ff": 64, "n_layers": 2,
            "max_len": 16, "rope_theta": 10000.0, "tie_embeddings": False,
            "param_dtype": "bfloat16", "epochs": 30, "batch_size": 16,
            "lr": 3e-3, "seed": 0,
            "sala": {"mixer_types": ["minicpm4", "lightning-attn"],
                     "lightning_nh": 4, "lightning_nkv": 4,
                     "lightning_head_dim": 8, "kernel_size": 4,
                     "kernel_stride": 2, "block_size": 4, "topk": 3,
                     "init_blocks": 1, "window_size": 4, "dense_len": 8,
                     "dim_model_base": 32, "published_layers": 2}}}],
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
        assert model.cfg.block == "minicpm_sala"
        assert model.cfg.sala.mixer_types == ("minicpm4", "lightning-attn")
        assert all(a.dtype == jnp.bfloat16
                   for a in jax.tree.leaves(model.device_tree))
    finally:
        server.stop()
