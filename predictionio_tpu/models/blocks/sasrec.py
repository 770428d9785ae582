"""The "sasrec" block kind of the session engine: the pre-LN
multi-head block of SASRec (Kang & McAuley, "Self-Attentive Sequential
Recommendation", ICDM 2018) with a learned position table and a head
tied to the item table. It has no reference under
``benchmarks/reference/`` and no cell; the session engine's tests train
and serve it.

TPU-first design:
- matmuls run in bf16 on the MXU (params and softmax/LN statistics stay
  f32); logits against the tied item-embedding table accumulate f32.
- parallelism: batch shards over the mesh "data" axis; long sequences
  shard over a "seq" axis using ring attention (ops/attention.py) —
  K/V blocks rotate over ICI with lax.ppermute, so no device ever
  materialises full-sequence attention. It is the one kind with that
  form.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from predictionio_tpu.models.blocks.common import (
    PAD, BlockKind, dense, residual_and_ff_bytes)
from predictionio_tpu.ops.attention import (
    blockwise_attention,
    full_attention,
    ring_attention,
)


def init(key: jax.Array, cfg, dtype) -> dict:
    keys = jax.random.split(key, 3 + cfg.n_layers)
    d, h = cfg.d_model, cfg.mlp_mult * cfg.d_model
    scale = 1.0 / math.sqrt(d)
    params = {
        "item_emb": (jax.random.normal(
            keys[0], (cfg.vocab, d), dtype=jnp.float32) * scale).astype(dtype),
        "pos_emb": (jax.random.normal(
            keys[1], (cfg.max_len, d), dtype=jnp.float32) * scale
        ).astype(dtype),
        "out_ln": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[3 + i], 6)
        params["layers"].append({
            "ln1": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
            "ln2": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
            "wqkv": dense(lk[0], dtype, d, 3 * d),
            "wo": dense(lk[1], dtype, d, d),
            "w1": dense(lk[2], dtype, d, h),
            "b1": jnp.zeros((h,), dtype),
            "w2": dense(lk[3], dtype, h, d),
            "b2": jnp.zeros((d,), dtype),
        })
    return params


def _ln(x: jax.Array, g: jax.Array, b: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * g + b).astype(x.dtype)


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """When ``mesh`` has a ``seq_axis``, attention runs as ring
    attention over it.

    ``inference=True`` routes single-device attention through
    ops/pallas_attention.flash_attention, which engages its kernel for
    causal 2048<=S<=16384 on a compiled TPU backend inside its VMEM
    envelope (its module docstring has what the chip has shown) and is
    XLA full attention otherwise. Serving stays a distinct dispatch
    point from the differentiable training paths — the kernel is
    forward-only."""
    B, S = seqs.shape
    d, H = cfg.d_model, cfg.n_heads
    hd = d // H
    mask = (seqs != PAD).astype(jnp.float32)           # (B, S)

    x = params["item_emb"][seqs].astype(cfg.dtype)     # (B, S, D)
    x = x + params["pos_emb"][None, :S].astype(cfg.dtype)
    x = x * mask[..., None].astype(cfg.dtype)

    use_ring = mesh is not None and seq_axis in mesh.shape and \
        int(mesh.shape[seq_axis]) > 1

    def block(x, layer):
        hpre = _ln(x, layer["ln1"]["g"], layer["ln1"]["b"])
        qkv = hpre @ layer["wqkv"].astype(cfg.dtype)   # (B, S, 3D)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        if use_ring:
            att = ring_attention(q, k, v, mesh, seq_axis=seq_axis,
                                 causal=True, kv_mask=mask)
        elif inference:
            from predictionio_tpu.ops.pallas_attention import flash_attention

            att = flash_attention(q, k, v, causal=True, kv_mask=mask)
        elif S >= 4096 and S % 128 == 0:
            # single-device long-context TRAINING: full_attention's
            # (S, S) logits OOM from ~16k; blockwise is differentiable
            # with O(S * q_block) peak. q_block=128 from the r5 sweep
            # (1.8x over 512 at S=4096; table in the
            # ops/attention.blockwise_attention docstring)
            att = blockwise_attention(q, k, v, causal=True, kv_mask=mask,
                                      q_block=128)
        else:
            att = full_attention(q, k, v, causal=True, kv_mask=mask)
        att = att.transpose(0, 2, 1, 3).reshape(B, S, d)
        x = x + att @ layer["wo"].astype(cfg.dtype)

        hpre = _ln(x, layer["ln2"]["g"], layer["ln2"]["b"])
        hmid = jax.nn.gelu(hpre @ layer["w1"].astype(cfg.dtype)
                           + layer["b1"].astype(cfg.dtype))
        return x + hmid @ layer["w2"].astype(cfg.dtype) + \
            layer["b2"].astype(cfg.dtype)

    if cfg.remat:
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x = block(x, layer)

    return _ln(x, params["out_ln"]["g"], params["out_ln"]["b"])


def bytes_per_token(cfg) -> int:
    # and one float32 row of attention logits per head
    return residual_and_ff_bytes(cfg) + 4 * cfg.n_heads * cfg.max_len


KIND = BlockKind(init, forward, bytes_per_token, seq_parallel=True)
