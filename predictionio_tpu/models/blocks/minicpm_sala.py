"""The "minicpm_sala" block kind of the session engine: the block of
MiniCPM-SALA (https://huggingface.co/openbmb/MiniCPM-SALA), a list of
mixers a layer: block-sparse attention chosen per query position from
ops/sparse_attention.py beside lightning attention, the degree-1 member
of ops/retention.py, under depth, embedding and head scalings. Its
reference is benchmarks/reference/minicpm_sala_jnp.py.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models.blocks.common import (
    PAD, BlockKind, dense, ffn, residual_and_ff_bytes, rms, stack, swiglu)
from predictionio_tpu.ops import qk_norm
from predictionio_tpu.ops.retention import WayIn, pick_chunk, power_retention

#: MiniCPM-SALA's published layer list (``mixer_types``)
_SALA_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class SalaWidths:
    """What the "minicpm_sala" kind adds to the widths every kind has
    (``d_model``, ``n_heads`` over ``n_kv_heads`` of ``head_dim`` for
    the sparse mixer, ``d_ff``, ``rope_theta``, ``rms_eps``): keys as
    published, defaults MiniCPM-SALA's; the seven sparse sizes are the
    MiniCPM4 family's ``sparse_config`` (``engine.json``: ``"sala":
    {...}``)."""
    #: the mixer of each layer held here, in order: "minicpm4" (sparse
    #: attention) or "lightning-attn"
    mixer_types: tuple = _SALA_MIXERS
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    #: the published depth: a layer's residual scale is ``scale_depth /
    #: sqrt`` of it, however many layers are held here
    published_layers: int = 32
    #: what the ``q_norm`` / ``k_norm`` weights are drawn at (a choice of
    #: weights: trained models sharpen their attention by these)
    qk_norm_init: float = 1.0

    @property
    def sparse(self):
        from predictionio_tpu.ops.sparse_attention import SparseSizes

        return SparseSizes(**{f.name: getattr(self, f.name)
                              for f in dataclasses.fields(SparseSizes)})

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)


def init(key: jax.Array, cfg, dtype) -> dict:
    """Every matrix normal / sqrt(fan-in), norm weights one but
    ``q_norm`` and ``k_norm``, drawn at the constant ``qk_norm_init``. A
    layer's projections have the widths of its mixer: ``n_heads`` over
    ``kv_heads`` of ``hd`` for "minicpm4", ``lightning_nh`` over
    ``lightning_nkv`` of ``lightning_head_dim`` (and a norm over the
    mixer's whole output) for "lightning-attn"; both gate their output
    at full width."""
    w = cfg.sala
    d, ff = cfg.d_model, cfg.ff
    if len(w.mixer_types) != cfg.n_layers:
        raise ValueError(f"{len(w.mixer_types)} mixers for {cfg.n_layers} "
                         "layers")
    params, layer_keys = stack(key, cfg, dtype, 8)
    for lk, mixer in zip(layer_keys, w.mixer_types):
        H, G, hd = (cfg.n_heads, cfg.kv_heads, cfg.hd) \
            if mixer == "minicpm4" else \
            (w.lightning_nh, w.lightning_nkv, w.lightning_head_dim)
        layer = {
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_norm": jnp.full((hd,), w.qk_norm_init, dtype),
            "k_norm": jnp.full((hd,), w.qk_norm_init, dtype),
            "wq": dense(lk[0], dtype, d, H * hd),
            "wk": dense(lk[1], dtype, d, G * hd),
            "wv": dense(lk[2], dtype, d, G * hd),
            "wg": dense(lk[3], dtype, d, H * hd),
            "wo": dense(lk[4], dtype, H * hd, d),
            **swiglu(lk[5:8], dtype, d, ff),
        }
        if mixer != "minicpm4":
            layer["o_norm"] = jnp.ones((H * hd,), dtype)
        params["layers"].append(layer)
    return params


def lightning_log_decay(heads: int) -> np.ndarray:
    """Lightning Attention's per-head slopes (arXiv:2401.04658), the
    same in every layer: head a = 1..H decays by ``exp(-2**(-8 a / H))``
    a position; the log of it, float32."""
    return -(2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float32)
                     / heads)).astype(np.float32)


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """The MiniCPM-SALA stack: returns (hidden, counts, kept ids) —
    int32 (3,) rows that selected, blocks they kept and key blocks
    scored for them over the sparse layers and real positions
    (ops/sparse_attention.visit_counts), and (B, sparse layers, key/value
    heads, topk) the blocks each history's last position kept (-1 after
    the last; all -1 where the layer does not select). The hidden states
    carry the head's 1 / (d_model / dim_model_base). Sparse layers have
    no positions and lightning layers RoPE, both mix causally: right
    padding needs no mask."""
    from predictionio_tpu.ops import sparse_attention

    w = cfg.sala
    if w.lightning_nh != w.lightning_nkv:
        raise NotImplementedError(
            "lightning attention decays per key/value head: "
            f"{w.lightning_nh} heads over {w.lightning_nkv}")
    sz = w.sparse
    B, S = seqs.shape
    dt, eps, f32 = cfg.dtype, cfg.rms_eps, jnp.float32
    c = w.residual_scale
    valid = seqs != PAD
    last = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
    log_decay = jnp.asarray(lightning_log_decay(w.lightning_nkv))
    # once a program: the lightning layers' q and k turn by the same
    # tables
    rope = qk_norm.rope_tables(S, w.lightning_head_dim, cfg.rope_theta)

    def gated(o, h, layer):
        gate = jax.nn.sigmoid((h @ layer["wg"].astype(dt)).astype(f32))
        return (o.astype(f32) * gate).astype(dt) @ layer["wo"].astype(dt)

    def sparse_mixer(h, layer):
        H, G, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
        v = h @ layer["wv"].astype(dt)
        # QK-norm (no positions: the sparse layers are NoPE), float32
        # inside one pass, bfloat16 in and out
        q = qk_norm.prepare(h @ layer["wq"].astype(dt), layer["q_norm"],
                            heads=H, eps=eps, scale=1.0 / math.sqrt(hd),
                            inference=inference)
        k = qk_norm.prepare(h @ layer["wk"].astype(dt), layer["k_norm"],
                            heads=G, eps=eps, inference=inference)
        o, counts, kept = sparse_attention.attend(
            q.reshape(B, S, H * hd), k.reshape(B, S, G * hd), v, sz,
            groups=G, valid=valid, inference=inference)
        if kept is None:
            ids = jnp.full((B, G, sz.topk), -1, jnp.int32)
        else:
            ids = sparse_attention.kept_ids(jnp.take_along_axis(
                kept, last[:, None, None, None], axis=2)[:, :, 0], sz.topk)
        return gated(o, h, layer), counts, ids

    def lightning_mixer(h, layer):
        H, hd = w.lightning_nh, w.lightning_head_dim
        q = (h @ layer["wq"].astype(dt)).reshape(B, S, H, hd)
        k = (h @ layer["wk"].astype(dt)).reshape(B, S, H, hd)
        v = (h @ layer["wv"].astype(dt)).reshape(B, S, H, hd)
        # q and k go in as projected: QK-norm x RoPE in float32 with no
        # rounding between them, rounded once, written in chunk order
        o = power_retention(
            q, k, v, log_decay, degree=1, inference=inference,
            way_in=WayIn(layer["q_norm"], layer["k_norm"], eps, rope))
        o = rms(o.reshape(B, S, H * hd), layer["o_norm"], eps)
        return gated(o, h, layer)

    def residual(x, y):
        return (x.astype(f32) + c * y.astype(f32)).astype(dt)

    def block(x, layer, mixer):
        h = rms(x, layer["in_norm"], eps)
        counts = ids = None
        if mixer == "minicpm4":
            with jax.named_scope("minicpm4_mixer"):
                y, counts, ids = sparse_mixer(h, layer)
        else:
            with jax.named_scope("lightning_mixer"):
                y = lightning_mixer(h, layer)
        x = residual(x, y)
        with jax.named_scope("swiglu"):
            h = rms(x, layer["post_norm"], eps)
            return residual(x, ffn(h, layer, dt)), counts, ids

    if cfg.remat:
        block = jax.checkpoint(block, static_argnums=(2,))
    x = (params["item_emb"][seqs].astype(f32) * w.scale_emb).astype(dt)
    counts, kept_ids = jnp.zeros((3,), jnp.int32), []
    for layer, mixer in zip(params["layers"], w.mixer_types):
        x, layer_counts, ids = block(x, layer, mixer)
        if ids is not None:
            counts = counts + layer_counts
            kept_ids.append(ids)
    kept_ids = jnp.stack(kept_ids, axis=1) if kept_ids else \
        jnp.zeros((B, 0, cfg.kv_heads, sz.topk), jnp.int32)
    head_scale = w.dim_model_base / cfg.d_model
    hidden = (rms(x, params["out_norm"], eps).astype(f32)
              * head_scale).astype(dt)
    return hidden, counts, kept_ids


def kernels(cfg, seq_len: int) -> tuple:
    from predictionio_tpu.ops import sparse_attention

    w = cfg.sala
    names = ()
    if "minicpm4" in w.mixer_types and sparse_attention.uses_kernel(
            seq_len, True, w.sparse, cfg.hd, cfg.n_heads // cfg.kv_heads):
        names = sparse_attention.kernel_names(seq_len, w.sparse)
    # each mixer's way in: head width, projection width, and the rows a
    # step must divide (retention's chunk; the sequence, token-major)
    ways_in = {
        "lightning-attn": (w.lightning_head_dim,
                           w.lightning_nh * w.lightning_head_dim,
                           pick_chunk(seq_len)),
        "minicpm4": (cfg.hd, cfg.n_heads * cfg.hd, seq_len)}
    if any(qk_norm.fuses(*ways_in[m], inference=True)
           for m in set(w.mixer_types)):
        names += ("qk_norm_rope",)
    return names


def tally(cfg, tokens: int, counts, kept_ids) -> dict:
    """The sparse layers of one program: rows that selected (position x
    sparse layer x key/value head; 0 where the history is at or under
    ``dense_len``), the blocks they kept, and the keys whose scores
    stage 2 computed for them (from the visit map the kernel was given;
    the device counts whole blocks)."""
    return {"sparse_rows": int(counts[0]),
            "sparse_blocks_selected": int(counts[1]),
            "sparse_keys_scored": int(counts[2]) * cfg.sala.block_size}


KIND = BlockKind(init, forward, residual_and_ff_bytes, kernels, tally=tally)
