"""The "nemotron_h" block kind of the session engine: the block of
Nemotron-H as NVIDIA-Nemotron-3-Nano-30B-A3B publishes it
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), the
first whose layer is one part alone (``x + part(norm(x))``, the part by
the layer's letter: a Mamba-2 mixer from ops/ssm.py and the state-space
member of ops/retention.py, sigmoid-routed relu2 experts from ops/moe.py
of which this chip holds a share, or grouped-query attention without
positions). Its reference is benchmarks/reference/nemotron_h_jnp.py.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.models.blocks.common import (
    BlockKind, dense, rms, routed, routed_kernels, stack, stack_routed,
    tally_routed)
from predictionio_tpu.ops.retention import pick_chunk, ssd_scan

#: Nemotron-3-Nano-30B-A3B's published ``hybrid_override_pattern``
_NEMOTRON_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHWidths:
    """What the "nemotron_h" kind adds to the widths every kind has
    (``d_model``, ``n_heads`` over ``n_kv_heads`` of ``head_dim`` for
    the attention layers, ``rms_eps``): keys as published, defaults
    Nemotron-3-Nano-30B-A3B's (``engine.json``: ``"nemotron_h":
    {...}``)."""
    #: the part of each layer held here, in order: "M" (Mamba-2), "E"
    #: (routed + shared experts) or "*" (attention)
    hybrid_override_pattern: str = _NEMOTRON_PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    #: the scan's chunk length (the result does not depend on it); a
    #: shorter history takes ``ops/retention.pick_chunk``'s
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    #: the router's width: the experts of the published layer
    n_routed_experts: int = 128
    #: (first, count): the experts this chip holds of them
    experts_held: tuple = (0, 128)
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    #: the family's ``dt_bias`` init: the inverse softplus of steps
    #: drawn log-uniform between these, floored
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @property
    def inner(self) -> int:
        """The Mamba mixer's width: heads x head width."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs over: x, B and C."""
        return self.inner + 2 * self.n_groups * self.ssm_state_size

    def scan_chunk(self, seq_len: int) -> int:
        return min(self.chunk_size, pick_chunk(seq_len))

    def count(self, part: str) -> int:
        return self.hybrid_override_pattern.count(part)


def init(key: jax.Array, cfg, dtype) -> dict:
    """Every matrix and table normal / sqrt(fan-in), norm weights one,
    and the family's own init for what a Mamba-2 mixer has beside them:
    ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse
    softplus of steps drawn log-uniform in [``time_step_min``,
    ``time_step_max``] and floored, the convolution's weights and bias
    uniform within 1 / sqrt(its width) (what the family's code leaves
    them at), ``e_score_correction_bias = 0``. A layer holds the
    parameters of its one part, by its letter in the pattern."""
    w = cfg.nemotron_h
    d, H, G, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd
    if len(w.hybrid_override_pattern) != cfg.n_layers or \
            set(w.hybrid_override_pattern) - set("ME*"):
        raise ValueError(f"pattern {w.hybrid_override_pattern!r} for "
                         f"{cfg.n_layers} layers of M, E or *")
    held, ffe = w.experts_held[1], w.moe_intermediate_size
    f32 = jnp.float32

    def mamba(lk):
        heads = w.mamba_num_heads
        bound = 1.0 / math.sqrt(w.conv_kernel)
        step = jnp.exp(jax.random.uniform(lk[2], (heads,), f32) * (
            math.log(w.time_step_max) - math.log(w.time_step_min))
            + math.log(w.time_step_min))
        step = jnp.maximum(step, w.time_step_floor)
        return {
            "w_in": dense(lk[0], dtype, d, w.inner + w.conv_dim + heads),
            "conv_w": jax.random.uniform(
                lk[1], (w.conv_dim, w.conv_kernel), f32, -bound, bound
            ).astype(dtype),
            "conv_b": jax.random.uniform(
                lk[3], (w.conv_dim,), f32, -bound, bound).astype(dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=f32)
                             ).astype(dtype),
            "D": jnp.ones((heads,), dtype),
            "gated_norm": jnp.ones((w.inner,), dtype),
            "w_out": dense(lk[4], dtype, w.inner, d)}

    def experts(lk):
        shared = w.moe_shared_expert_intermediate_size
        return {
            "router": dense(lk[0], dtype, d, w.n_routed_experts),
            "router_bias": jnp.zeros((w.n_routed_experts,), dtype),
            "experts": {"w_up": dense(lk[1], dtype, held, d, ffe),
                        "w_down": dense(lk[2], dtype, held, ffe, d)},
            "shared": {"w_up": dense(lk[3], dtype, d, shared),
                       "w_down": dense(lk[4], dtype, shared, d)}}

    def attention(lk):
        return {"wq": dense(lk[0], dtype, d, H * hd),
                "wk": dense(lk[1], dtype, d, G * hd),
                "wv": dense(lk[2], dtype, d, G * hd),
                "wo": dense(lk[3], dtype, H * hd, d)}

    draw = {"M": mamba, "E": experts, "*": attention}
    params, layer_keys = stack(key, cfg, dtype, 5)
    for lk, part in zip(layer_keys, w.hybrid_override_pattern):
        params["layers"].append({"norm": jnp.ones((d,), dtype),
                                 **draw[part](lk)})
    return params


def forward(params, seqs, cfg, mesh, seq_axis, inference):
    """The Nemotron-H stack: returns (hidden, assignments per expert
    layer and held expert, the chunk steps the Mamba layers' scans
    counted, the experts chosen at every position (expert layers, B, S,
    top_k): all int32). Every layer is one part, ``x +
    part(rmsnorm(x))``, by its letter in the pattern. No part has
    positions and every one mixes causally (the convolution looks back,
    the scan runs in order, attention is causal): right padding needs
    no mask. The expert layers compute this chip's experts' part and
    leave the rest out, as the "deepseek_v2" kind's do."""
    from predictionio_tpu.ops import moe, sparse_attention, ssm

    w = cfg.nemotron_h
    B, S = seqs.shape
    dt, eps, f32 = cfg.dtype, cfg.rms_eps, jnp.float32
    heads, p = w.mamba_num_heads, w.mamba_head_dim
    groups, n = w.n_groups, w.ssm_state_size
    inner, conv_dim = w.inner, w.conv_dim
    chunk = w.scan_chunk(S)
    # attention under the sparse layers' kernel with nothing to select:
    # plain causal attention over grouped key/value heads
    dense_sizes = sparse_attention.SparseSizes(dense_len=S)

    def mamba(u, layer):
        # [z | xBC | dt] as three products of the one matrix's columns:
        # each output is an array of its own, and the steps come
        # straight into float32 from the same operands
        w_in = layer["w_in"].astype(dt)
        z = u @ w_in[:, :inner]
        xbc = u @ w_in[:, inner:inner + conv_dim]
        step = jax.nn.softplus(
            jnp.einsum("bsd,dh->bsh", u, w_in[:, inner + conv_dim:],
                       preferred_element_type=f32)
            + layer["dt_bias"].astype(f32))
        xbc = ssm.causal_conv_silu(xbc, layer["conv_w"], layer["conv_b"])
        gn = groups * n
        y, steps = ssd_scan(
            xbc[..., :inner].reshape(B, S, heads, p), step,
            -jnp.exp(layer["A_log"].astype(f32)),
            xbc[..., inner:inner + gn].reshape(B, S, groups, n),
            xbc[..., inner + gn:].reshape(B, S, groups, n), layer["D"],
            chunk=chunk)
        v = ssm.gated_group_norm(y.reshape(B, S, inner), z,
                                 layer["gated_norm"], groups=groups, eps=eps)
        return v @ layer["w_out"].astype(dt), steps

    def experts(u, layer):
        return routed(
            u, layer, w.experts_held[0], inference=inference,
            activation=moe.relu2, n_group=1, topk_group=1,
            top_k=w.num_experts_per_tok, scaling=w.routed_scaling_factor,
            scoring="sigmoid", renormalise=True)

    def attention(u, layer):
        with jax.named_scope("attention"):
            q = ((u @ layer["wq"].astype(dt)).astype(f32)
                 * (1.0 / math.sqrt(cfg.hd))).astype(dt)
            o, _, _ = sparse_attention.attend(
                q, u @ layer["wk"].astype(dt), u @ layer["wv"].astype(dt),
                dense_sizes, groups=cfg.kv_heads, inference=inference)
            return o @ layer["wo"].astype(dt), None

    parts = {"M": mamba, "E": experts, "*": attention}

    def block(x, layer, part):
        y, counted = parts[part](rms(x, layer["norm"], eps), layer)
        return x + y, counted

    if cfg.remat:
        block = jax.checkpoint(block, static_argnums=(2,))
    x = params["item_emb"][seqs].astype(dt)
    counted = {"M": [], "E": [], "*": []}
    for layer, part in zip(params["layers"], w.hybrid_override_pattern):
        x, got = block(x, layer, part)
        counted[part].append(got)
    assignments, choices = stack_routed(counted["E"], w.experts_held[1],
                                        (B, S, w.num_experts_per_tok))
    steps = sum(counted["M"], jnp.zeros((), jnp.int32))
    return rms(x, params["out_norm"], eps), assignments, steps, choices


def bytes_per_token(cfg) -> int:
    """The residual stream twice beside the widest part's arrays: an
    expert layer's (per assignment the gathered row, the up product,
    the hidden row and the expert's output in cfg.dtype, and the float32
    sum in token order twice) counted **twice**, a Mamba layer's (z,
    xBC before and after the convolution, the scan's output and the
    gated norm's) or attention's (q, k, v and the output). Twice: the
    compiled program at the published widths keeps a second expert
    layer's rows live while one works. Compiled for a described v5e at
    S = 16,384 it holds 274 KB a token (4.49 GB of temporaries beside
    6.33 GB of weights; an expert layer alone 139, a Mamba layer 38,
    the attention layer 22); this says 272."""
    w, it = cfg.nemotron_h, jnp.dtype(cfg.dtype).itemsize
    mamba = (3 * w.inner + 2 * w.conv_dim) * it
    attention = 2 * cfg.hd * (cfg.n_heads + cfg.kv_heads) * it
    experts = w.num_experts_per_tok * 2 * (
        cfg.d_model + w.moe_intermediate_size) * it + 2 * cfg.d_model * 4
    return 2 * cfg.d_model * it + max(
        2 * experts if w.count("E") else 0, mamba if w.count("M") else 0,
        attention if w.count("*") else 0)


def kernels(cfg, seq_len: int) -> tuple:
    from predictionio_tpu.ops import sparse_attention

    w = cfg.nemotron_h
    causal = sparse_attention.SparseSizes(dense_len=seq_len)
    return (("sparse_block_attention",) if w.count("*")
            and sparse_attention.uses_kernel(
                seq_len, True, causal, cfg.hd, cfg.n_heads // cfg.kv_heads)
            else ()) \
        + (routed_kernels(cfg, w.moe_intermediate_size) if w.count("E")
           else ())


def tally(cfg, tokens: int, per_expert, scan_steps) -> dict:
    """The expert layers of one program as the "deepseek_v2" kind
    reports its own, and the Mamba layers' scans beside them: the tokens
    they scanned (program's tokens x Mamba layers) and the chunk steps
    their loops counted on the device (a program's histories scan side
    by side; the ratio is the chunk the program ran)."""
    return {**tally_routed(cfg, tokens, per_expert),
            "ssm_tokens": tokens * cfg.nemotron_h.count("M"),
            "ssm_scan_steps": int(scan_steps)}


KIND = BlockKind(init, forward, bytes_per_token, kernels, tally=tally,
                 choices=True)
