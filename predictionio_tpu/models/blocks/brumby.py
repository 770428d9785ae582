"""The "brumby" block kind of the session engine: the block of
Brumby-14B-Base (https://huggingface.co/manifestai/Brumby-14B-Base):
RMSNorm, grouped key/value heads with QK-norm and RoPE, gated power
retention from ops/retention.py, SwiGLU, an untied head. Its plain
float32 reference is benchmarks/reference/brumby_jnp.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from predictionio_tpu.models.blocks.common import (
    BlockKind, dense, ffn, residual_and_ff_bytes, rms, stack, swiglu)
from predictionio_tpu.ops import qk_norm
from predictionio_tpu.ops.retention import (
    WayIn, fuses_state_pass, pick_chunk, power_retention)


def init(key: jax.Array, cfg, dtype) -> dict:
    """Every matrix normal / sqrt(fan-in), norm weights one, the
    embedding at SASRec's scale; drawn in float32 and rounded once."""
    d, ff, hd = cfg.d_model, cfg.ff, cfg.hd
    H, G = cfg.n_heads, cfg.kv_heads
    params, layer_keys = stack(key, cfg, dtype, 8,
                               head=not cfg.tie_embeddings)
    for lk in layer_keys:
        params["layers"].append({
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_norm": jnp.ones((hd,), dtype),
            "k_norm": jnp.ones((hd,), dtype),
            "wq": dense(lk[0], dtype, d, H * hd),
            "wk": dense(lk[1], dtype, d, G * hd),
            "wv": dense(lk[2], dtype, d, G * hd),
            "wg": dense(lk[3], dtype, d, G),
            "wo": dense(lk[4], dtype, H * hd, d),
            **swiglu(lk[5:8], dtype, d, ff),
        })
    return params


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """The Brumby-14B-Base stack. Positions come from RoPE and the mixing
    is causal, so right padding changes nothing before it and needs no
    mask."""
    B, S = seqs.shape
    H, G, hd, dt = cfg.n_heads, cfg.kv_heads, cfg.hd, cfg.dtype
    # once a program: every layer's q and k turn by the same tables
    rope = qk_norm.rope_tables(S, hd, cfg.rope_theta)

    def block(x, layer):
        h = rms(x, layer["in_norm"], cfg.rms_eps)
        q = (h @ layer["wq"].astype(dt)).reshape(B, S, H, hd)
        k = (h @ layer["wk"].astype(dt)).reshape(B, S, G, hd)
        v = (h @ layer["wv"].astype(dt)).reshape(B, S, G, hd)
        log_g = jax.nn.log_sigmoid(
            jnp.einsum("bsd,dg->bsg", h, layer["wg"].astype(dt),
                       preferred_element_type=jnp.float32)
            + cfg.gate_init_logit)
        # q and k go in as projected: QK-norm (rounded to the stream's
        # type, as rms rounds), RoPE and the rounding to operands
        # happen in retention's pass into chunk order
        y = power_retention(
            q, k, v, log_g, degree=cfg.retention_degree, inference=inference,
            way_in=WayIn(layer["q_norm"], layer["k_norm"], cfg.rms_eps, rope,
                         dt))
        x = x + y.reshape(B, S, H * hd) @ layer["wo"].astype(dt)
        with jax.named_scope("swiglu"):
            return x + ffn(rms(x, layer["post_norm"], cfg.rms_eps), layer,
                           dt)

    if cfg.remat:
        block = jax.checkpoint(block)
    x = params["item_emb"][seqs].astype(dt)
    for layer in params["layers"]:
        x = block(x, layer)
    return rms(x, params["out_norm"], cfg.rms_eps)


def kernels(cfg, seq_len: int) -> tuple:
    chunk = pick_chunk(seq_len)
    fused = fuses_state_pass(cfg.hd, cfg.n_heads // cfg.kv_heads, chunk,
                             inference=True)
    way_in = qk_norm.fuses(cfg.hd, cfg.n_heads * cfg.hd, chunk,
                           inference=True)
    return (("retention_state_pass",) if fused else ()) \
        + (("qk_norm_rope",) if way_in else ())


KIND = BlockKind(init, forward, residual_and_ff_bytes, kernels)
