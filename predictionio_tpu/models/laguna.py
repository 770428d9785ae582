"""The "laguna" block kind of the session engine (``models/seqrec``'s
``BLOCKS``): the Laguna block as Laguna-S-2.1 publishes it, whose plain
float32 reference is benchmarks/reference/laguna_jnp.py.

A layer of type full or sliding, with its own count H of query heads
over G key/value heads of d, then a dense or an expert feed-forward::

    h = rmsnorm(x);  q = h wq, k = h wk, v = h wv;  g = sigmoid(h wg)   (H gates)
    q, k: rmsnorm per head (q_norm, k_norm), then positions
        full:    YaRN over the first half of a head's lanes, cos and sin
                 times attention_factor; the other half passed through
        sliding: RoPE over all of a head's lanes
    a_h(t) = softmax over i of q_h(t) . k(i) / sqrt(d), then . v(i)
        full:    i <= t;   sliding: t - W < i <= t
    x = x + concat_h(g_h(t) a_h(t)) wo
    h = rmsnorm(x)
        dense:  x = x + swiglu(h)
        sparse: p = softmax(h router) over every published expert; the
                top k, weights scaling p_e / their sum; x = x + the held
                experts' swiglu(h) by their weights + the shared swiglu(h)

The full layers' rotary half is turned by reordering the columns of
``wq``, ``wk`` and the norms' weights (``ops/qk_norm.partial_order``),
so both layer types go through ``ops/qk_norm.prepare`` and its kernel;
full attention is ``ops/sparse_attention``'s core with nothing to
select (as the "nemotron_h" kind's attention), sliding attention its
windowed core (``attend_window``: the kernel ``window_attention``);
the routed layers are ``ops/moe``'s, told which experts this chip holds.
Positions come from the rotary embeddings and every mixer is causal, so
right padding needs no mask.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import qk_norm


def _layers(cfg):
    """(attention type, query heads, feed-forward type) of each layer."""
    w = cfg.laguna
    lists = (w.layer_types, w.num_attention_heads_per_layer,
             w.mlp_layer_types)
    if any(len(a) != cfg.n_layers for a in lists) or \
            set(w.layer_types) - {"full_attention", "sliding_attention"} or \
            set(w.mlp_layer_types) - {"dense", "sparse"}:
        raise ValueError(
            "layer_types, num_attention_heads_per_layer and "
            f"mlp_layer_types must each give {cfg.n_layers} layers of "
            "full_attention | sliding_attention and dense | sparse")
    return tuple(zip(*lists))


def init(key, cfg, dtype):
    """Every matrix normal / sqrt(fan-in), norm weights one, both tables
    normal / sqrt(d_model); drawn in float32 and rounded once. A layer's
    projections have its own head count; a dense layer carries a SwiGLU
    of width ``cfg.ff``, an expert layer a router over every published
    expert, the ``experts_held`` count of routed experts (stacked) and
    the shared expert."""
    w = cfg.laguna
    d, G, hd = cfg.d_model, cfg.kv_heads, cfg.hd
    held = w.experts_held[1]
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def dense(k, *shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def swiglu(ks, ff, *lead):
        return {"w_gate": dense(ks[0], *lead, d, ff),
                "w_up": dense(ks[1], *lead, d, ff),
                "w_down": dense(ks[2], *lead, ff, d)}

    def table(k):
        return (jax.random.normal(k, (cfg.vocab, d), dtype=jnp.float32)
                / math.sqrt(d)).astype(dtype)

    params = {"item_emb": table(keys[0]), "head": table(keys[1]),
              "out_norm": jnp.ones((d,), dtype), "layers": []}
    for i, (_, H, mlp) in enumerate(_layers(cfg)):
        lk = jax.random.split(keys[2 + i], 12)
        layer = {
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_norm": jnp.ones((hd,), dtype),
            "k_norm": jnp.ones((hd,), dtype),
            "wq": dense(lk[0], d, H * hd),
            "wk": dense(lk[1], d, G * hd),
            "wv": dense(lk[2], d, G * hd),
            "wg": dense(lk[3], d, H),
            "wo": dense(lk[4], H * hd, d),
        }
        if mlp == "dense":
            layer["ffn"] = swiglu(lk[5:8], cfg.ff)
        else:
            layer["router"] = dense(lk[5], d, w.num_experts)
            layer["experts"] = swiglu(lk[6:9], w.moe_intermediate_size, held)
            layer["shared"] = swiglu(lk[9:12],
                                     w.shared_expert_intermediate_size)
        params["layers"].append(layer)
    return params


def rotary_lanes(cfg, layer_type: str) -> int:
    """The lanes of a head the layer type's rotary embedding turns."""
    w = cfg.laguna
    factor = w.full_partial_rotary_factor if layer_type == "full_attention" \
        else w.sliding_partial_rotary_factor
    return int(cfg.hd * factor)


def rope(cfg, layer_type: str, seq_len: int):
    """(lane order of a head, (cos, sin) tables of ``ops/qk_norm``) of a
    layer type: the sliding layers' plain RoPE, the full layers' YaRN on
    their rotary lanes."""
    from predictionio_tpu.ops import mla_attention

    w, hd = cfg.laguna, cfg.hd
    rotary = rotary_lanes(cfg, layer_type)
    if layer_type == "full_attention":
        inv = mla_attention.yarn_inv_freq(rotary, w.full_rope_theta,
                                          w.full_rope_scaling)
        magnitude = w.full_attention_factor
    else:
        inv = mla_attention.yarn_inv_freq(rotary, w.sliding_rope_theta)
        magnitude = 1.0
    return (qk_norm.partial_order(hd, rotary),
            qk_norm.partial_rope_tables(seq_len, hd, inv, magnitude))


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """The Laguna stack: returns (hidden, assignments per expert layer
    and held expert, the sliding layers' counts (2,): rows and keys
    scored, the experts chosen at every position (expert layers, B, S,
    top_k): all int32). The expert layers compute this chip's experts'
    part and leave the rest out, as the "deepseek_v2" kind's do."""
    from predictionio_tpu.models.seqrec import PAD, _rms, _swiglu
    # Pallas and the grouped matmul are imported with the first program
    # of this kind, not with the module
    from predictionio_tpu.ops import moe, sparse_attention

    if mesh is not None and seq_axis in mesh.shape and \
            int(mesh.shape[seq_axis]) > 1:
        raise NotImplementedError(
            "the laguna block has no sequence-parallel form: use a mesh "
            f"without a {seq_axis!r} axis")
    w = cfg.laguna
    B, S = seqs.shape
    G, hd, dt, eps, f32 = cfg.kv_heads, cfg.hd, cfg.dtype, cfg.rms_eps, \
        jnp.float32
    valid = seqs != PAD
    # once a program: every layer of a type turns by the same tables
    ropes = {t: rope(cfg, t, S) for t in set(w.layer_types)}
    # full attention under the sparse layers' kernel with nothing to
    # select: plain causal attention over grouped key/value heads
    causal = sparse_attention.SparseSizes(dense_len=S)

    def attention(h, layer, layer_type, H):
        order, tables = ropes[layer_type]
        cols = (np.arange(H)[:, None] * hd + order).ravel()
        q = qk_norm.prepare(h @ layer["wq"].astype(dt)[:, cols],
                            layer["q_norm"][order], heads=H, eps=eps,
                            scale=1.0 / math.sqrt(hd), rope=tables,
                            inference=inference).reshape(B, S, H * hd)
        cols = (np.arange(G)[:, None] * hd + order).ravel()
        k = qk_norm.prepare(h @ layer["wk"].astype(dt)[:, cols],
                            layer["k_norm"][order], heads=G, eps=eps,
                            rope=tables, inference=inference
                            ).reshape(B, S, G * hd)
        v = h @ layer["wv"].astype(dt)
        counts = None
        if layer_type == "full_attention":
            o, _, _ = sparse_attention.attend(q, k, v, causal, groups=G,
                                              inference=inference)
        else:
            o, counts = sparse_attention.attend_window(
                q, k, v, groups=G, window=w.sliding_window, valid=valid,
                inference=inference)
        with jax.named_scope("head_gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", h, layer["wg"].astype(dt),
                preferred_element_type=f32))
            o = (o.reshape(B, S, H, hd).astype(f32) * gate[..., None]) \
                .astype(dt).reshape(B, S, H * hd)
        return o @ layer["wo"].astype(dt), counts

    def experts(h, layer):
        tokens = h.reshape(B * S, -1)
        with jax.named_scope("moe.route"):
            ids, weights, _ = moe.route(
                tokens, layer["router"], n_group=1, topk_group=1,
                top_k=w.num_experts_per_tok,
                scaling=w.moe_routed_scaling_factor, renormalise=True)
        with jax.named_scope("moe.experts"):
            ex = layer["experts"]
            routed, counts = moe.routed_experts(
                tokens, ids, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
                w.experts_held[0], inference=inference)
        with jax.named_scope("moe.shared"):
            shared = _swiglu(h, layer["shared"], dt)
        y = routed.reshape(B, S, -1) + shared.astype(f32)
        return y.astype(dt), (counts, ids.reshape(B, S, -1))

    def block(x, layer, layer_type, H, mlp):
        with jax.named_scope(layer_type):
            y, window = attention(_rms(x, layer["in_norm"], eps), layer,
                                  layer_type, H)
        x = x + y
        h = _rms(x, layer["post_norm"], eps)
        if mlp == "dense":
            with jax.named_scope("swiglu"):
                return x + _swiglu(h, layer["ffn"], dt), window, None
        y, routed = experts(h, layer)
        return x + y, window, routed

    if cfg.remat:
        block = jax.checkpoint(block, static_argnums=(2, 3, 4))
    x = params["item_emb"][seqs].astype(dt)
    window = jnp.zeros((2,), jnp.int32)
    routed = []
    for layer, (layer_type, H, mlp) in zip(params["layers"], _layers(cfg)):
        x, counts, got = block(x, layer, layer_type, H, mlp)
        if counts is not None:
            window = window + counts
        if got is not None:
            routed.append(got)
    if routed:
        assignments, choices = (jnp.stack(a) for a in zip(*routed))
    else:
        assignments = jnp.zeros((0, w.experts_held[1]), jnp.int32)
        choices = jnp.zeros((0, B, S, w.num_experts_per_tok), jnp.int32)
    return _rms(x, params["out_norm"], eps), assignments, window, choices


def bytes_per_token(cfg) -> int:
    """The residual stream twice beside the widest phase's arrays, in
    cfg.dtype: an expert layer's (per assignment the gathered row, gate,
    up, the hidden row and the expert's output, and the float32 sum in
    token order), an attention layer's (q and k as projected and as
    prepared, v, the output and its gated copy, the widest layer's
    heads) or a dense layer's SwiGLU (gate and up). Compiled for a
    described v5e at the published widths and S = 16,384 the program
    holds 210 KB a token (3.44 GB of temporaries beside 6.00 GB of
    weights); this says 221."""
    w, it = cfg.laguna, jnp.dtype(cfg.dtype).itemsize
    heads = max(w.num_attention_heads_per_layer)
    attention = (4 * heads + 3 * cfg.kv_heads) * cfg.hd * it
    routed = w.num_experts_per_tok * (
        2 * cfg.d_model + 3 * w.moe_intermediate_size) * it \
        + 2 * cfg.d_model * 4
    dense = 2 * cfg.ff * it
    return 2 * cfg.d_model * it + max(
        routed if w.count("sparse") else 0, attention,
        dense if w.count("dense") else 0)


def kernels(cfg, seq_len: int) -> tuple:
    """The Pallas kernels a serving program over ``seq_len`` positions
    engages, by the rules the forward pass applies."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import sparse_attention

    w = cfg.laguna

    def groups(layer_type):
        """The query heads a key/value head has in the layers of a type."""
        return {H // cfg.kv_heads for t, H in zip(
            w.layer_types, w.num_attention_heads_per_layer) if t == layer_type}

    causal = sparse_attention.SparseSizes(dense_len=seq_len)
    names = ()
    if any(sparse_attention.uses_window_kernel(seq_len, True, cfg.hd, R)
           for R in groups("sliding_attention")):
        names += ("window_attention",)
    if any(sparse_attention.uses_kernel(seq_len, True, causal, cfg.hd, R)
           for R in groups("full_attention")):
        names += ("sparse_block_attention",)
    if any(qk_norm.fuses(cfg.hd, H * cfg.hd, seq_len, inference=True)
           for H in set(w.num_attention_heads_per_layer)):
        names += ("qk_norm_rope",)
    return names + (seqrec._routed_kernels(cfg, w.moe_intermediate_size)
                    if w.count("sparse") else ())


def tally(cfg, tokens: int, per_expert, window) -> dict:
    """The expert layers of one program as the "deepseek_v2" kind reports
    its own, and the sliding layers beside them: rows (real position x
    sliding layer x key/value head) and the keys scored for them."""
    from predictionio_tpu.models import seqrec

    return {**seqrec._tally_deepseek_v2(cfg, tokens, per_expert),
            "window_rows": int(window[0]),
            "window_keys_scored": int(window[1])}
