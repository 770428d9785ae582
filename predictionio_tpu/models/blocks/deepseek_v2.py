"""The "deepseek_v2" block kind of the session engine: the block of
DeepSeek-V2 (https://huggingface.co/deepseek-ai/DeepSeek-V2): latent
attention from ops/mla_attention.py, one leading dense SwiGLU layer,
then routed + shared experts from ops/moe.py, of which this chip holds a
share. Its reference is benchmarks/reference/deepseek_v2_jnp.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models.blocks.common import (
    BlockKind, YarnScaling, dense, ffn, rms, routed, routed_kernels,
    stack, stack_routed, swiglu, tally_routed)


@dataclasses.dataclass(frozen=True)
class MlaMoeWidths:
    """What the "deepseek_v2" kind adds to the widths every kind has
    (``d_model``, ``n_heads``, ``d_ff`` for the dense layers,
    ``rope_theta``, ``rms_eps``): latent attention and routed + shared
    experts, keys as published, defaults DeepSeek-V2's (``engine.json``:
    ``"mla_moe": {...}``)."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_intermediate_size: int = 1536
    #: the router's width: the experts of the published layer
    n_routed_experts: int = 160
    #: (first, count): the experts this chip holds of them
    experts_held: tuple = (0, 160)
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    rope_scaling: YarnScaling | None = None


def init(key: jax.Array, cfg, dtype) -> dict:
    """Every matrix normal / sqrt(fan-in), norm weights one. The first
    ``first_k_dense_replace`` layers carry a dense SwiGLU of width
    ``cfg.ff``; the others a router over all published experts, the
    ``experts_held`` count of routed experts (stacked), and the shared
    experts as one SwiGLU."""
    w = cfg.mla_moe
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim
    held, ffe = w.experts_held[1], w.moe_intermediate_size
    params, layer_keys = stack(key, cfg, dtype, 15)
    for i, lk in enumerate(layer_keys):
        layer = {
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_a_norm": jnp.ones((w.q_lora_rank,), dtype),
            "kv_a_norm": jnp.ones((w.kv_lora_rank,), dtype),
            "wq_a": dense(lk[0], dtype, d, w.q_lora_rank),
            "wq_b": dense(lk[1], dtype, w.q_lora_rank, H * (dn + dr)),
            "wkv_a": dense(lk[2], dtype, d, w.kv_lora_rank + dr),
            "wkv_b": dense(lk[3], dtype, w.kv_lora_rank, H * (dn + dv)),
            "wo": dense(lk[4], dtype, H * dv, d),
        }
        if i < w.first_k_dense_replace:
            layer["ffn"] = swiglu(lk[5:8], dtype, d, cfg.ff)
        else:
            layer["router"] = dense(lk[8], dtype, d, w.n_routed_experts)
            layer["experts"] = swiglu(lk[9:12], dtype, d, ffe, held)
            layer["shared"] = swiglu(lk[12:15], dtype, d,
                                     w.n_shared_experts * ffe)
        params["layers"].append(layer)
    return params


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """The DeepSeek-V2 stack: returns (hidden, assignments per expert
    layer and held expert, int32). RoPE and causal mixing: right padding
    needs no mask. The routed layer computes this chip's experts' part
    and leaves the rest out (ops/moe.py); there is no expert axis on the
    mesh and no exchange here."""
    # Pallas and the grouped matmul are imported with the first program
    # of this kind, not with the module
    from predictionio_tpu.ops import mla_attention as mla

    w = cfg.mla_moe
    B, S = seqs.shape
    H, dt, eps = cfg.n_heads, cfg.dtype, cfg.rms_eps
    dn, dr, dv = w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim
    yarn = w.rope_scaling
    inv_freq = mla.yarn_inv_freq(dr, cfg.rope_theta, yarn)
    scale = mla.softmax_scale(dn + dr, yarn)
    magnitude = 1.0 if yarn is None else (
        mla.yarn_mscale(yarn.factor, yarn.mscale)
        / mla.yarn_mscale(yarn.factor, yarn.mscale_all_dim))

    # the queries come out of their projection as all heads' nope
    # slices, then all heads' rotary slices with the published pairs
    # (2i, 2i+1) taken apart, then each rotary lane's partner: the
    # weights' columns are reordered, not the activations
    q_cols = mla.query_columns(H, dn, dr)
    kv_cols = np.concatenate([
        np.arange(w.kv_lora_rank), w.kv_lora_rank + mla.pairs_apart(dr),
        w.kv_lora_rank + mla.halves_swapped(dr)])
    pe = H * dn                                    # where q's rotary part starts

    def attention(h, layer):
        c_q = rms(h @ layer["wq_a"].astype(dt), layer["q_a_norm"], eps)
        q = c_q @ layer["wq_b"].astype(dt)[:, q_cols]
        c_kv = h @ layer["wkv_a"].astype(dt)[:, kv_cols]
        c, k_pe = c_kv[..., :w.kv_lora_rank], c_kv[..., w.kv_lora_rank:]
        kv = rms(c, layer["kv_a_norm"], eps) @ layer["wkv_b"].astype(dt)
        q_pe = mla.rope_apart(q[..., pe:pe + H * dr], q[..., pe + H * dr:],
                              inv_freq, dr, magnitude)
        k_pe = mla.rope_apart(k_pe[..., :dr], k_pe[..., dr:], inv_freq, dr,
                              magnitude)
        att = mla.attend(q[..., :pe], q_pe, kv, k_pe, heads=H, dn=dn,
                         dv=dv, scale=scale, inference=inference)
        return att @ layer["wo"].astype(dt)

    def block(x, layer):
        with jax.named_scope("mla_attention"):
            x = x + attention(rms(x, layer["in_norm"], eps), layer)
        h = rms(x, layer["post_norm"], eps)
        if "ffn" in layer:
            with jax.named_scope("swiglu"):
                return x + ffn(h, layer["ffn"], dt), None
        y, got = routed(
            h, layer, w.experts_held[0], inference=inference,
            n_group=w.n_group, topk_group=w.topk_group,
            top_k=w.num_experts_per_tok, scaling=w.routed_scaling_factor)
        return x + y, got

    if cfg.remat:
        block = jax.checkpoint(block)
    x = params["item_emb"][seqs].astype(dt)
    per_layer = []
    for layer in params["layers"]:
        x, got = block(x, layer)
        if got is not None:
            per_layer.append(got)
    assignments, _ = stack_routed(per_layer, w.experts_held[1],
                                  (B, S, w.num_experts_per_tok))
    return rms(x, params["out_norm"], eps), assignments


def bytes_per_token(cfg) -> int:
    """The two phases a layer goes through, in cfg.dtype beside the
    residual stream twice: attention (q, the joint k/v projection and
    the output, each with a temporary of its size: the rotary slices'
    partners and their float32 rotation) and the routed layer (per
    assignment the gathered row, gate, up and hidden, the expert's
    output and its copy in token order). The sum, not the larger: the
    compiled program makes the routed layer's arrays while attention's
    are live. At the published widths it holds 460 KB a token (3.77 GB
    of temporaries at S = 8,192 beside 10.33 GB of weights, compiled for
    a described v5e); this says 555."""
    w, it = cfg.mla_moe, jnp.dtype(cfg.dtype).itemsize
    qk = w.qk_nope_head_dim + w.qk_rope_head_dim
    attn = cfg.n_heads * (2 * qk + 2 * (w.qk_nope_head_dim + w.v_head_dim)
                          + 2 * w.v_head_dim)
    experts = w.num_experts_per_tok * (3 * cfg.d_model
                                       + 3 * w.moe_intermediate_size)
    return (2 * cfg.d_model + max(attn + experts, 2 * cfg.ff)) * it


def kernels(cfg, seq_len: int) -> tuple:
    from predictionio_tpu.ops import mla_attention

    w = cfg.mla_moe
    return (("mla_flash_attention",) if mla_attention.uses_kernel(
        seq_len, True, w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim,
        cfg.n_heads) else ()) + routed_kernels(cfg, w.moe_intermediate_size)


KIND = BlockKind(init, forward, bytes_per_token, kernels, tally=tally_routed)
