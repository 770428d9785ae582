"""Self-attentive sequential recommendation (SASRec-family) on TPU.

Next-item prediction over per-user event sequences — the neural upgrade
of the reference's e2 MarkovChain (e2/.../engine/MarkovChain.scala:26-84,
top-N transition model): where MarkovChain keeps first-order transition
counts, this trains a causal transformer over full session histories.

The stack is built from a configuration: ``SeqRecConfig.block`` names a
kind in ``BLOCKS``. "sasrec" is the pre-LN multi-head block described
below; "brumby" is the Brumby-14B-Base block (RMSNorm, grouped
key/value heads with QK-norm and RoPE, gated power retention from
ops/retention.py, SwiGLU, an untied head), whose plain float32
reference is benchmarks/reference/brumby_jnp.py; "deepseek_v2" is the
DeepSeek-V2 block (latent attention from ops/mla_attention.py, one
leading dense SwiGLU layer, then routed + shared experts from
ops/moe.py, of which this chip holds a share), whose reference is
benchmarks/reference/deepseek_v2_jnp.py; "minicpm_sala" is the
MiniCPM-SALA block (a list of mixers a layer: block-sparse attention
chosen per query position from ops/sparse_attention.py beside lightning
attention, the degree-1 member of ops/retention.py, under depth, embedding
and head scalings), whose reference is
benchmarks/reference/minicpm_sala_jnp.py.

TPU-first design:
- matmuls run in bf16 on the MXU (params and softmax/LN statistics stay
  f32); logits against the tied item-embedding table accumulate f32.
- fixed (batch, max_len) shapes — sessions are truncated/left-padded on
  the host, so there is exactly one compile per config.
- parallelism: batch shards over the mesh "data" axis; long sequences
  shard over a "seq" axis using ring attention (ops/attention.py) —
  K/V blocks rotate over ICI with lax.ppermute, so no device ever
  materialises full-sequence attention.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import qk_norm
from predictionio_tpu.ops.attention import (
    blockwise_attention,
    full_attention,
    ring_attention,
)
from predictionio_tpu.ops.retention import (
    WayIn, fuses_state_pass, pick_chunk, power_retention)

logger = logging.getLogger(__name__)

PAD = 0  # item id 0 is reserved for padding; real ids start at 1


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type "yarn", keys as published."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class MlaMoeWidths:
    """What the "deepseek_v2" kind adds to the widths every kind has
    (``d_model``, ``n_heads``, ``d_ff`` for the dense layers,
    ``rope_theta``, ``rms_eps``): latent attention and routed + shared
    experts, keys as published, defaults DeepSeek-V2's. One frozen,
    hashable record, held in one field by ``SeqRecConfig`` and by the
    session template's ``AlgorithmParams`` (``engine.json``:
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

    @classmethod
    def of(cls, obj) -> "MlaMoeWidths | None":
        """The record from what ``engine.json`` carries (a JSON object,
        ``rope_scaling`` an object whose ``type`` is "yarn"), from
        itself, or None."""
        if obj is None or isinstance(obj, cls):
            return obj
        obj = dict(obj)
        yarn = obj.get("rope_scaling")
        if isinstance(yarn, Mapping):
            yarn = dict(yarn)
            if yarn.pop("type", "yarn") != "yarn":
                raise ValueError("rope_scaling: only type 'yarn' is known")
            obj["rope_scaling"] = YarnScaling(**yarn)
        if "experts_held" in obj:
            obj["experts_held"] = tuple(int(n) for n in obj["experts_held"])
        return cls(**obj)


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
    MiniCPM4 family's ``sparse_config``. One frozen, hashable record,
    held in one field by ``SeqRecConfig`` and by the session template's
    ``AlgorithmParams`` (``engine.json``: ``"sala": {...}``)."""
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

    @classmethod
    def of(cls, obj) -> "SalaWidths | None":
        """The record from what ``engine.json`` carries (a JSON object),
        from itself, or None."""
        if obj is None or isinstance(obj, cls):
            return obj
        obj = dict(obj)
        if "mixer_types" in obj:
            obj["mixer_types"] = tuple(obj["mixer_types"])
        return cls(**obj)

    @property
    def sparse(self):
        from predictionio_tpu.ops.sparse_attention import SparseSizes

        return SparseSizes(**{f.name: getattr(self, f.name)
                              for f in dataclasses.fields(SparseSizes)})

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)


@dataclasses.dataclass(frozen=True)
class SeqRecConfig:
    vocab: int              # number of items + 1 (pad)
    max_len: int = 64
    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    mlp_mult: int = 4
    dtype: Any = jnp.bfloat16
    #: rematerialize each transformer block under grad (jax.checkpoint):
    #: activations are recomputed in the backward pass instead of stored,
    #: trading ~30% FLOPs for O(layers) less HBM — the long-context
    #: training knob alongside the "seq" mesh axis
    remat: bool = False
    #: the kind of block the stack is built from (``BLOCKS``): "sasrec"
    #: is the pre-LN multi-head block with a learned position table and
    #: a tied head; "brumby" is the Brumby-14B-Base block (RMSNorm,
    #: grouped key/value heads, QK-norm, RoPE, gated power retention,
    #: SwiGLU); "deepseek_v2" is the DeepSeek-V2 block (latent
    #: attention, a leading dense layer, then routed + shared experts),
    #: whose own widths are ``mla_moe``; "minicpm_sala" is the
    #: MiniCPM-SALA block (sparse or lightning attention by layer),
    #: whose own widths are ``sala``. The fields below are widths the
    #: later kinds read; 0 means "as the first kind derives it"
    block: str = "sasrec"
    n_kv_heads: int = 0         # 0: one key/value head per query head
    head_dim: int = 0           # 0: d_model // n_heads
    d_ff: int = 0               # 0: mlp_mult * d_model
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    retention_degree: int = 2
    #: added inside the gate's log-sigmoid: ln 999, so that a zero
    #: projection gives a gate of 0.999 and the state remembers
    gate_init_logit: float = 6.906768
    tie_embeddings: bool = True
    #: the type serving holds the weights in on the device
    #: (templates/sessionrec._as_device_tree); training keeps float32
    param_dtype: Any = jnp.float32
    #: the "deepseek_v2" kind's widths; None for the other kinds
    mla_moe: MlaMoeWidths | None = None
    #: the "minicpm_sala" kind's widths; None for the other kinds
    sala: SalaWidths | None = None

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def ff(self) -> int:
        return self.d_ff or self.mlp_mult * self.d_model


def activation_bytes_per_token(cfg: SeqRecConfig) -> int:
    """What one token of a serving program holds at the program's peak,
    estimated from the widths by the kind's own rule
    (``BlockKind.bytes_per_token``)."""
    return BLOCKS[cfg.block].bytes_per_token(cfg)


def _residual_and_ff_bytes(cfg: SeqRecConfig) -> int:
    """The residual stream twice and the widest dense layer's two
    projections (SwiGLU's gate and up; twice the MLP's hidden for
    SASRec), in cfg.dtype. The compiled brumby program at the published
    widths holds 91 KB a token; this says 90."""
    return 2 * (cfg.d_model + cfg.ff) * jnp.dtype(cfg.dtype).itemsize


def _bytes_sasrec(cfg: SeqRecConfig) -> int:
    # and one float32 row of attention logits per head
    return _residual_and_ff_bytes(cfg) + 4 * cfg.n_heads * cfg.max_len


def init_params(key: jax.Array, cfg: SeqRecConfig,
                dtype: Any = jnp.float32) -> dict:
    """Parameter pytree of ``cfg.block``'s kind in ``dtype`` (float32
    for training; compute casts to cfg.dtype per-op). Jittable: a
    serving-sized tree is drawn on the device."""
    if cfg.block not in BLOCKS:
        raise ValueError(f"unknown block kind {cfg.block!r} "
                         f"(have {sorted(BLOCKS)})")
    return BLOCKS[cfg.block].init(key, cfg, dtype)


def _init_sasrec(key: jax.Array, cfg: SeqRecConfig, dtype: Any) -> dict:
    keys = jax.random.split(key, 3 + cfg.n_layers)
    d, h = cfg.d_model, cfg.mlp_mult * cfg.d_model
    scale = 1.0 / math.sqrt(d)

    def dense(k, m, n):
        return jax.random.normal(k, (m, n), dtype=jnp.float32) / math.sqrt(m)

    params = {
        "item_emb": jax.random.normal(
            keys[0], (cfg.vocab, d), dtype=jnp.float32) * scale,
        "pos_emb": jax.random.normal(
            keys[1], (cfg.max_len, d), dtype=jnp.float32) * scale,
        "out_ln": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[3 + i], 6)
        params["layers"].append({
            "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "wqkv": dense(lk[0], d, 3 * d),
            "wo": dense(lk[1], d, d),
            "w1": dense(lk[2], d, h),
            "b1": jnp.zeros((h,)),
            "w2": dense(lk[3], h, d),
            "b2": jnp.zeros((d,)),
        })
    return jax.tree.map(lambda a: a.astype(dtype), params)


def _init_brumby(key: jax.Array, cfg: SeqRecConfig, dtype: Any) -> dict:
    """Every matrix normal / sqrt(fan-in), norm weights one, the
    embedding at SASRec's scale; drawn in float32 and rounded once."""
    d, ff, hd = cfg.d_model, cfg.ff, cfg.hd
    H, G = cfg.n_heads, cfg.kv_heads
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def dense(k, m, n):
        return (jax.random.normal(k, (m, n), dtype=jnp.float32)
                / math.sqrt(m)).astype(dtype)

    def table(k):
        return (jax.random.normal(k, (cfg.vocab, d), dtype=jnp.float32)
                / math.sqrt(d)).astype(dtype)

    params = {"item_emb": table(keys[0]),
              "out_norm": jnp.ones((d,), dtype), "layers": []}
    if not cfg.tie_embeddings:
        params["head"] = table(keys[1])
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 8)
        params["layers"].append({
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_norm": jnp.ones((hd,), dtype),
            "k_norm": jnp.ones((hd,), dtype),
            "wq": dense(lk[0], d, H * hd),
            "wk": dense(lk[1], d, G * hd),
            "wv": dense(lk[2], d, G * hd),
            "wg": dense(lk[3], d, G),
            "wo": dense(lk[4], H * hd, d),
            "w_gate": dense(lk[5], d, ff),
            "w_up": dense(lk[6], d, ff),
            "w_down": dense(lk[7], ff, d),
        })
    return params


def _init_deepseek_v2(key: jax.Array, cfg: SeqRecConfig, dtype: Any) -> dict:
    """As ``_init_brumby``: every matrix normal / sqrt(fan-in), norm
    weights one. The first ``first_k_dense_replace`` layers carry a dense
    SwiGLU of width ``cfg.ff``; the others a router over all published
    experts, the ``experts_held`` count of routed experts (stacked), and
    the shared experts as one SwiGLU."""
    w = cfg.mla_moe
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim
    held, ffe = w.experts_held[1], w.moe_intermediate_size
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
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 15)
        layer = {
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_a_norm": jnp.ones((w.q_lora_rank,), dtype),
            "kv_a_norm": jnp.ones((w.kv_lora_rank,), dtype),
            "wq_a": dense(lk[0], d, w.q_lora_rank),
            "wq_b": dense(lk[1], w.q_lora_rank, H * (dn + dr)),
            "wkv_a": dense(lk[2], d, w.kv_lora_rank + dr),
            "wkv_b": dense(lk[3], w.kv_lora_rank, H * (dn + dv)),
            "wo": dense(lk[4], H * dv, d),
        }
        if i < w.first_k_dense_replace:
            layer["ffn"] = swiglu(lk[5:8], cfg.ff)
        else:
            layer["router"] = dense(lk[8], d, w.n_routed_experts)
            layer["experts"] = swiglu(lk[9:12], ffe, held)
            layer["shared"] = swiglu(lk[12:15], w.n_shared_experts * ffe)
        params["layers"].append(layer)
    return params


def _init_minicpm_sala(key: jax.Array, cfg: SeqRecConfig, dtype: Any) -> dict:
    """As ``_init_brumby``: every matrix normal / sqrt(fan-in), norm
    weights one but ``q_norm`` and ``k_norm``, drawn at the constant
    ``qk_norm_init``. A layer's projections have the widths of its
    mixer: ``n_heads`` over ``kv_heads`` of ``hd`` for "minicpm4",
    ``lightning_nh`` over ``lightning_nkv`` of ``lightning_head_dim``
    (and a norm over the mixer's whole output) for "lightning-attn";
    both gate their output at full width."""
    w = cfg.sala
    d, ff = cfg.d_model, cfg.ff
    if len(w.mixer_types) != cfg.n_layers:
        raise ValueError(f"{len(w.mixer_types)} mixers for {cfg.n_layers} "
                         "layers")
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def dense(k, m, n):
        return (jax.random.normal(k, (m, n), dtype=jnp.float32)
                / math.sqrt(m)).astype(dtype)

    def table(k):
        return (jax.random.normal(k, (cfg.vocab, d), dtype=jnp.float32)
                / math.sqrt(d)).astype(dtype)

    params = {"item_emb": table(keys[0]), "head": table(keys[1]),
              "out_norm": jnp.ones((d,), dtype), "layers": []}
    for i, mixer in enumerate(w.mixer_types):
        H, G, hd = (cfg.n_heads, cfg.kv_heads, cfg.hd) \
            if mixer == "minicpm4" else \
            (w.lightning_nh, w.lightning_nkv, w.lightning_head_dim)
        lk = jax.random.split(keys[2 + i], 8)
        layer = {
            "in_norm": jnp.ones((d,), dtype),
            "post_norm": jnp.ones((d,), dtype),
            "q_norm": jnp.full((hd,), w.qk_norm_init, dtype),
            "k_norm": jnp.full((hd,), w.qk_norm_init, dtype),
            "wq": dense(lk[0], d, H * hd),
            "wk": dense(lk[1], d, G * hd),
            "wv": dense(lk[2], d, G * hd),
            "wg": dense(lk[3], d, H * hd),
            "wo": dense(lk[4], H * hd, d),
            "w_gate": dense(lk[5], d, ff),
            "w_up": dense(lk[6], d, ff),
            "w_down": dense(lk[7], ff, d),
        }
        if mixer != "minicpm4":
            layer["o_norm"] = jnp.ones((H * hd,), dtype)
        params["layers"].append(layer)
    return params


def _ln(x: jax.Array, g: jax.Array, b: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * g + b).astype(x.dtype)


def forward(
    params: Mapping,
    seqs: jax.Array,           # (B, S) int32 item ids, right-padded with PAD
    cfg: SeqRecConfig,
    mesh: Mesh | None = None,
    seq_axis: str = "seq",
    inference: bool = False,
) -> jax.Array:
    """Hidden states (B, S, D) in cfg.dtype from ``cfg.block``'s stack."""
    kind = BLOCKS[cfg.block]
    out = kind.forward(params, seqs, cfg, mesh, seq_axis, inference)
    return out[0] if kind.tally else out


def _forward_sasrec(params, seqs, cfg, mesh, seq_axis, inference):
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


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return qk_norm.normed(x, w, eps).astype(x.dtype)


def _forward_brumby(params, seqs, cfg, mesh, seq_axis, inference):
    """The Brumby-14B-Base stack. Positions come from RoPE and the mixing
    is causal, so right padding changes nothing before it and needs no
    mask. One program on one device (or batch-sharded by the caller's
    jit): retention has no sequence-parallel form here yet."""
    if mesh is not None and seq_axis in mesh.shape and \
            int(mesh.shape[seq_axis]) > 1:
        raise NotImplementedError(
            "the brumby block has no sequence-parallel form: use a mesh "
            f"without a {seq_axis!r} axis")
    B, S = seqs.shape
    H, G, hd, dt = cfg.n_heads, cfg.kv_heads, cfg.hd, cfg.dtype
    f32 = jnp.float32
    # once a program: every layer's q and k turn by the same tables
    rope = qk_norm.rope_tables(S, hd, cfg.rope_theta)

    def block(x, layer):
        h = _rms(x, layer["in_norm"], cfg.rms_eps)
        q = (h @ layer["wq"].astype(dt)).reshape(B, S, H, hd)
        k = (h @ layer["wk"].astype(dt)).reshape(B, S, G, hd)
        v = (h @ layer["wv"].astype(dt)).reshape(B, S, G, hd)
        log_g = jax.nn.log_sigmoid(
            jnp.einsum("bsd,dg->bsg", h, layer["wg"].astype(dt),
                       preferred_element_type=f32) + cfg.gate_init_logit)
        # q and k go in as projected: QK-norm (rounded to the stream's
        # type, as _rms rounds), RoPE and the rounding to operands
        # happen in retention's pass into chunk order
        y = power_retention(
            q, k, v, log_g, degree=cfg.retention_degree, inference=inference,
            way_in=WayIn(layer["q_norm"], layer["k_norm"], cfg.rms_eps, rope,
                         dt))
        x = x + y.reshape(B, S, H * hd) @ layer["wo"].astype(dt)
        with jax.named_scope("swiglu"):
            h = _rms(x, layer["post_norm"], cfg.rms_eps)
            gate = (h @ layer["w_gate"].astype(dt)).astype(f32)
            up = (h @ layer["w_up"].astype(dt)).astype(f32)
            return x + (jax.nn.silu(gate) * up).astype(dt) \
                @ layer["w_down"].astype(dt)

    if cfg.remat:
        block = jax.checkpoint(block)
    x = params["item_emb"][seqs].astype(dt)
    for layer in params["layers"]:
        x = block(x, layer)
    return _rms(x, params["out_norm"], cfg.rms_eps)


def _swiglu(h, ffn, dt):
    gate = (h @ ffn["w_gate"].astype(dt)).astype(jnp.float32)
    up = (h @ ffn["w_up"].astype(dt)).astype(jnp.float32)
    return (jax.nn.silu(gate) * up).astype(dt) @ ffn["w_down"].astype(dt)


def _forward_deepseek_v2(params, seqs, cfg, mesh, seq_axis, inference):
    """The DeepSeek-V2 stack: returns (hidden, assignments per expert
    layer and held expert, int32). RoPE and causal mixing: right padding
    needs no mask. The routed layer computes this chip's experts' part
    and leaves the rest out (ops/moe.py); there is no expert axis on the
    mesh and no exchange here."""
    # Pallas and the grouped matmul are imported with the first program
    # of this kind, not with the module
    from predictionio_tpu.ops import mla_attention as mla, moe

    if mesh is not None and seq_axis in mesh.shape and \
            int(mesh.shape[seq_axis]) > 1:
        raise NotImplementedError(
            "the deepseek_v2 block has no sequence-parallel form: use a "
            f"mesh without a {seq_axis!r} axis")
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
        c_q = _rms(h @ layer["wq_a"].astype(dt), layer["q_a_norm"], eps)
        q = c_q @ layer["wq_b"].astype(dt)[:, q_cols]
        c_kv = h @ layer["wkv_a"].astype(dt)[:, kv_cols]
        c, k_pe = c_kv[..., :w.kv_lora_rank], c_kv[..., w.kv_lora_rank:]
        kv = _rms(c, layer["kv_a_norm"], eps) @ layer["wkv_b"].astype(dt)
        q_pe = mla.rope_apart(q[..., pe:pe + H * dr], q[..., pe + H * dr:],
                              inv_freq, dr, magnitude)
        k_pe = mla.rope_apart(k_pe[..., :dr], k_pe[..., dr:], inv_freq, dr,
                              magnitude)
        att = mla.attend(q[..., :pe], q_pe, kv, k_pe, heads=H, dn=dn,
                         dv=dv, scale=scale, inference=inference)
        return att @ layer["wo"].astype(dt)

    def experts(h, layer):
        tokens = h.reshape(B * S, -1)
        with jax.named_scope("routed_experts"):
            ids, weights, _ = moe.route(
                tokens, layer["router"], n_group=w.n_group,
                topk_group=w.topk_group, top_k=w.num_experts_per_tok,
                scaling=w.routed_scaling_factor)
            ex = layer["experts"]
            routed, counts = moe.routed_experts(
                tokens, ids, weights, ex["w_gate"], ex["w_up"],
                ex["w_down"], w.experts_held[0], inference=inference)
        with jax.named_scope("shared_experts"):
            shared = _swiglu(h, layer["shared"], dt)
        y = routed.reshape(B, S, -1) + shared.astype(jnp.float32)
        return y.astype(dt), counts

    def block(x, layer):
        with jax.named_scope("mla_attention"):
            x = x + attention(_rms(x, layer["in_norm"], eps), layer)
        h = _rms(x, layer["post_norm"], eps)
        if "ffn" in layer:
            with jax.named_scope("swiglu"):
                return x + _swiglu(h, layer["ffn"], dt), None
        y, counts = experts(h, layer)
        return x + y, counts

    if cfg.remat:
        block = jax.checkpoint(block)
    x = params["item_emb"][seqs].astype(dt)
    per_layer = []
    for layer in params["layers"]:
        x, counts = block(x, layer)
        if counts is not None:
            per_layer.append(counts)
    assignments = jnp.stack(per_layer) if per_layer else \
        jnp.zeros((0, w.experts_held[1]), jnp.int32)
    return _rms(x, params["out_norm"], eps), assignments


def lightning_log_decay(heads: int) -> np.ndarray:
    """Lightning Attention's per-head slopes (arXiv:2401.04658), the
    same in every layer: head a = 1..H decays by ``exp(-2**(-8 a / H))``
    a position; the log of it, float32."""
    return -(2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float32)
                     / heads)).astype(np.float32)


def _forward_minicpm_sala(params, seqs, cfg, mesh, seq_axis, inference):
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

    if mesh is not None and seq_axis in mesh.shape and \
            int(mesh.shape[seq_axis]) > 1:
        raise NotImplementedError(
            "the minicpm_sala block has no sequence-parallel form: use a "
            f"mesh without a {seq_axis!r} axis")
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
        o = _rms(o.reshape(B, S, H * hd), layer["o_norm"], eps)
        return gated(o, h, layer)

    def residual(x, y):
        return (x.astype(f32) + c * y.astype(f32)).astype(dt)

    def block(x, layer, mixer):
        h = _rms(x, layer["in_norm"], eps)
        counts = ids = None
        if mixer == "minicpm4":
            with jax.named_scope("minicpm4_mixer"):
                y, counts, ids = sparse_mixer(h, layer)
        else:
            with jax.named_scope("lightning_mixer"):
                y = lightning_mixer(h, layer)
        x = residual(x, y)
        with jax.named_scope("swiglu"):
            h = _rms(x, layer["post_norm"], eps)
            return residual(x, _swiglu(h, layer, dt)), counts, ids

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
    hidden = (_rms(x, params["out_norm"], eps).astype(f32)
              * head_scale).astype(dt)
    return hidden, counts, kept_ids


def _kernels_minicpm_sala(cfg: SeqRecConfig, seq_len: int) -> tuple:
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


def _tally_deepseek_v2(cfg: SeqRecConfig, tokens: int, per_expert) -> dict:
    """The routed layers of one program: assignments to held experts
    over all expert layers, the tokens routed (once, not per layer) and
    the fullest (layer, expert)'s assignments."""
    return {"moe_assignments": int(per_expert.sum()), "moe_tokens": tokens,
            "moe_max_expert_load": int(per_expert.max(initial=0))}


def _tally_minicpm_sala(cfg: SeqRecConfig, tokens: int, counts,
                        kept_ids) -> dict:
    """The sparse layers of one program: rows that selected (position x
    sparse layer x key/value head), the blocks they kept, and the keys
    whose scores stage 2 computed for them (the device counts whole
    blocks)."""
    return {"sparse_rows": int(counts[0]),
            "sparse_blocks_selected": int(counts[1]),
            "sparse_keys_scored": int(counts[2]) * cfg.sala.block_size}


def _bytes_deepseek_v2(cfg: SeqRecConfig) -> int:
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
    routed = w.num_experts_per_tok * (3 * cfg.d_model
                                      + 3 * w.moe_intermediate_size)
    return (2 * cfg.d_model + max(attn + routed, 2 * cfg.ff)) * it


def _kernels_brumby(cfg: SeqRecConfig, seq_len: int) -> tuple:
    chunk = pick_chunk(seq_len)
    fused = fuses_state_pass(cfg.hd, cfg.n_heads // cfg.kv_heads, chunk,
                             inference=True)
    way_in = qk_norm.fuses(cfg.hd, cfg.n_heads * cfg.hd, chunk,
                           inference=True)
    return (("retention_state_pass",) if fused else ()) \
        + (("qk_norm_rope",) if way_in else ())


def _kernels_deepseek_v2(cfg: SeqRecConfig, seq_len: int) -> tuple:
    from predictionio_tpu.ops import mla_attention, moe

    w = cfg.mla_moe
    return (("mla_flash_attention",) if mla_attention.uses_kernel(
        seq_len, True, w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim,
        cfg.n_heads) else ()) \
        + (("gmm",) if moe.uses_kernel(inference=True) else ())


def fuses_retention(cfg: SeqRecConfig, seq_len: int) -> bool:
    """Whether a serving program over ``seq_len``-long histories
    (:func:`predict_topk_batch`, ``inference=True``) runs retention's
    state pass in the fused kernel: the kind's own rule, for the
    counters of whoever launches the program."""
    return "retention_state_pass" in BLOCKS[cfg.block].kernels(cfg, seq_len)


def fuses_qk_norm(cfg: SeqRecConfig, seq_len: int) -> bool:
    """Whether such a program runs its mixers' way in (QK-norm, RoPE,
    the rounding, retention's chunk order) in the fused kernel
    (``ops/qk_norm.fuses``): the kind's own rule, for the same
    counters."""
    return "qk_norm_rope" in BLOCKS[cfg.block].kernels(cfg, seq_len)


@dataclasses.dataclass(frozen=True)
class BlockKind:
    init: Any       # (key, cfg, dtype) -> parameter pytree
    #: (params, seqs, cfg, mesh, seq_axis, inference) -> hidden; a kind
    #: with a ``tally`` returns (hidden, *int32 arrays of what the
    #: program counted on the device)
    forward: Any
    #: cfg -> bytes one token of a serving program holds at its peak
    bytes_per_token: Any
    #: (cfg, seq_len) -> names of the Pallas kernels a serving program
    #: over histories that long engages, by the rules the forward pass
    #: itself applies (static shape and backend)
    kernels: Any = lambda cfg, seq_len: ()
    #: None, or (cfg, tokens of the program, *those arrays on the host)
    #: -> {field of templates/sessionrec.SeqDispatch: what to add}: the
    #: one road by which a kind's program reports counters beside scores
    #: and ids ("deepseek_v2": the routed layers' assignments;
    #: "minicpm_sala": the sparse layers' selection)
    tally: Any = None


#: the block kinds a configuration can name (``SeqRecConfig.block``)
BLOCKS = {
    "sasrec": BlockKind(_init_sasrec, _forward_sasrec, _bytes_sasrec),
    "brumby": BlockKind(_init_brumby, _forward_brumby,
                        _residual_and_ff_bytes, _kernels_brumby),
    "deepseek_v2": BlockKind(_init_deepseek_v2, _forward_deepseek_v2,
                             _bytes_deepseek_v2, _kernels_deepseek_v2,
                             tally=_tally_deepseek_v2),
    "minicpm_sala": BlockKind(_init_minicpm_sala, _forward_minicpm_sala,
                              _residual_and_ff_bytes, _kernels_minicpm_sala,
                              tally=_tally_minicpm_sala),
}


def head_table(params: Mapping) -> jax.Array:
    """(V, D) output projection: the untied head where the stack has
    one, else the item-embedding table."""
    return params["head"] if "head" in params else params["item_emb"]


def logits_from_hidden(params: Mapping, h: jax.Array) -> jax.Array:
    """Output projection (tied unless the stack has a head), f32
    accumulation: (B, S, V)."""
    return jnp.einsum("bsd,vd->bsv", h, head_table(params).astype(h.dtype),
                      preferred_element_type=jnp.float32)


#: flat-path budget for the (B, S, V) f32 logits. Tiling is an
#:  OOM-avoidance mechanism, not a default: the rematerialised scan
#:  recomputes the logits matmul in the backward pass, measured ~18%
#:  slower at the bench shape (371k vs 453k tokens/sec) — so the flat
#:  path stands whenever it plausibly fits HBM and tiling engages only
#:  for genuinely oversized (long-context / huge-vocab) configs
_LOSS_TILE_BYTES = 4 << 30


def _pick_loss_tile(b: int, s: int, v: int) -> int | None:
    """Largest divisor of ``s`` whose (b, T, v) f32 logits fit the tile
    budget; None when even the flat path fits (no tiling needed)."""
    if b * s * v * 4 <= _LOSS_TILE_BYTES:
        return None
    for t in (128, 64, 32, 16, 8, 4, 2, 1):
        if s % t == 0 and b * t * v * 4 <= _LOSS_TILE_BYTES:
            return t
    return 1


def next_item_loss(
    params: Mapping,
    seqs: jax.Array,     # (B, S) inputs
    targets: jax.Array,  # (B, S) next item per position, PAD=ignore
    cfg: SeqRecConfig,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Mean masked softmax cross-entropy of next-item prediction.

    Big-vocab configs compute the loss in sequence tiles
    (rematerialised scan): peak logits memory drops from O(B*S*V) to
    O(B*T*V) with the backward pass recomputing per-tile logits.
    Tiling is skipped only when the sequence dim is actually sharded
    (a mesh "seq" axis) — re-tiling a sharded axis would force
    gathers; a data-only mesh leaves S unsharded, so tiling is safe
    and still needed for huge vocabularies. The budget check uses the
    global batch (conservative under data sharding)."""
    h = forward(params, seqs, cfg, mesh)
    seq_sharded = mesh is not None and "seq" in mesh.shape \
        and int(mesh.shape["seq"]) > 1
    tile = None if seq_sharded else _pick_loss_tile(
        h.shape[0], h.shape[1], head_table(params).shape[0])
    tmask = (targets != PAD).astype(jnp.float32)
    if tile is None:
        logits = logits_from_hidden(params, h)         # (B, S, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * tmask) / jnp.maximum(jnp.sum(tmask), 1.0)

    B, S, D = h.shape
    n = S // tile
    h_t = h.reshape(B, n, tile, D).transpose(1, 0, 2, 3)
    tg_t = targets.reshape(B, n, tile).transpose(1, 0, 2)
    m_t = tmask.reshape(B, n, tile).transpose(1, 0, 2)

    def body(acc, xs):
        ht, tt, mt = xs
        logits = logits_from_hidden(params, ht)        # (B, T, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tt[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(nll * mt), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), jnp.float32), (h_t, tg_t, m_t))
    return total / jnp.maximum(jnp.sum(tmask), 1.0)


@dataclasses.dataclass
class SeqRecModel:
    params: dict
    cfg: SeqRecConfig
    item_index: Any = None  # utils.bimap.BiMap id <-> dense index (set by caller)


def _adam_update(params, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        params, m, v,
    )
    return params, m, v


def make_train_step(cfg: SeqRecConfig, mesh: Mesh | None = None):
    """One jitted Adam step. Under a mesh, batch shards over "data" and
    (when present) sequence over "seq"; parameters stay replicated and
    XLA inserts the gradient psums over ICI."""

    def step_fn(params, opt_m, opt_v, step, seqs, targets, lr):
        loss, grads = jax.value_and_grad(next_item_loss)(
            params, seqs, targets, cfg, mesh)
        params, opt_m, opt_v = _adam_update(
            params, grads, opt_m, opt_v, step, lr)
        return params, opt_m, opt_v, loss

    if mesh is not None:
        batch_spec = P("data", "seq") if "seq" in mesh.shape else P("data")
        rep = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, batch_spec)
        return jax.jit(
            step_fn,
            in_shardings=(rep, rep, rep, None, data_sh, data_sh, None),
            out_shardings=(rep, rep, rep, None),
        )
    return jax.jit(step_fn)


def pad_sequences(
    sequences: list[list[int]], max_len: int
) -> tuple[np.ndarray, np.ndarray]:
    """Keep each sequence's most recent max_len+1 items and produce
    (inputs, targets): inputs are seq[:-1] right-padded with PAD,
    targets the shifted next items."""
    B = len(sequences)
    inputs = np.zeros((B, max_len), dtype=np.int32)
    targets = np.zeros((B, max_len), dtype=np.int32)
    for i, seq in enumerate(sequences):
        seq = seq[-(max_len + 1):]
        ins, tgt = seq[:-1], seq[1:]
        inputs[i, : len(ins)] = ins
        targets[i, : len(tgt)] = tgt
    return inputs, targets


def train(
    sequences: list[list[int]],
    cfg: SeqRecConfig,
    *,
    epochs: int = 20,
    batch_size: int = 64,
    lr: float = 1e-3,
    seed: int = 0,
    mesh: Mesh | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
) -> dict:
    """Full training loop over dense-indexed item sequences (ids >= 1).

    Mid-training checkpoint/resume (beyond the reference, whose
    persistence is model-level only — SURVEY.md §5): with
    ``checkpoint_dir`` + ``checkpoint_every`` N, the full training state
    (params, Adam moments, epoch counter) is written atomically every N
    epochs, and a later call with the same dir/config resumes from the
    last completed checkpoint instead of epoch 0."""
    inputs, targets = pad_sequences(sequences, cfg.max_len)
    n = inputs.shape[0]
    # checkpoint identity from the PRE-batch-padding arrays, so a resume
    # after a batch_size or mesh-topology change still *loads* (the
    # fingerprint matches). The continuation is exact only for unchanged
    # batch/mesh: the replayed rng.permutation stream and the restored
    # Adam step counter are batch-size-dependent, so a changed batch_size
    # yields valid training but a different data order/step alignment
    fingerprint = (
        _train_fingerprint(cfg, inputs, targets, lr, seed)
        if checkpoint_dir else None
    )
    # static batch shape: pad the set so every step uses the same compile
    bs = min(batch_size, n)
    if mesh is not None:
        mult = int(mesh.shape.get("data", 1))
        bs = max(mult, (bs // mult) * mult)
    pad_rows = (-n) % bs
    if pad_rows:
        inputs = np.concatenate([inputs, np.zeros((pad_rows, cfg.max_len),
                                                  np.int32)])
        targets = np.concatenate([targets, np.zeros((pad_rows, cfg.max_len),
                                                    np.int32)])
        n = inputs.shape[0]

    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg)
    opt_m = jax.tree.map(jnp.zeros_like, params)
    opt_v = jax.tree.map(jnp.zeros_like, params)
    start_epoch, it = 0, 0
    if checkpoint_dir:
        resumed = _load_train_state(checkpoint_dir, params, fingerprint)
        if resumed is not None:
            params, opt_m, opt_v, start_epoch, it = resumed
            logger.info("seqrec: resumed from %s at epoch %d",
                        checkpoint_dir, start_epoch)
            if start_epoch >= epochs:
                logger.warning(
                    "seqrec: checkpoint already at epoch %d >= requested "
                    "epochs %d — returning checkpointed weights with no "
                    "further training", start_epoch, epochs)
    step = make_train_step(cfg, mesh)

    rng = np.random.default_rng(seed)
    for epoch in range(epochs):
        if epoch < start_epoch:
            rng.permutation(n)  # keep the data order stream aligned
            continue
        order = rng.permutation(n)
        losses = []
        for s in range(0, n, bs):
            idx = order[s : s + bs]
            it += 1
            params, opt_m, opt_v, loss = step(
                params, opt_m, opt_v, it,
                jnp.asarray(inputs[idx]), jnp.asarray(targets[idx]),
                jnp.float32(lr),
            )
            losses.append(loss)
        if epoch == 0 or (epoch + 1) % 5 == 0:
            logger.info("seqrec epoch %d loss %.4f", epoch + 1,
                        float(jnp.mean(jnp.stack(losses))))
        if checkpoint_dir and checkpoint_every and \
                (epoch + 1) % checkpoint_every == 0:
            _save_train_state(checkpoint_dir, params, opt_m, opt_v,
                              epoch + 1, it, fingerprint)
    return params


# ---------------------------------------------------------------------------
# Mid-training checkpoint state (atomic flat-npz; resume-safe)
# ---------------------------------------------------------------------------


def _flat_paths(tree) -> dict:
    import jax.tree_util as jtu

    leaves = jtu.tree_flatten_with_path(tree)[0]
    return {jtu.keystr(path): leaf for path, leaf in leaves}


def _train_fingerprint(cfg, inputs, targets, lr, seed) -> str:
    """Identity of a training run: config (incl. n_heads/remat, which leaf
    shapes can't distinguish) + the exact dataset + lr/seed. A checkpoint
    only resumes a run with the same fingerprint — a new fold split,
    fresh events, or changed architecture starts fresh instead of
    silently reusing stale weights."""
    import hashlib

    h = hashlib.sha1()
    h.update(repr(dataclasses.asdict(cfg)).encode())
    h.update(np.ascontiguousarray(inputs).tobytes())
    h.update(np.ascontiguousarray(targets).tobytes())
    h.update(np.float64(lr).tobytes())  # pio: lint-ignore[dtype-discipline]: checkpoint-identity serialization — 8 stable bytes, never a compute dtype
    h.update(np.int64(seed).tobytes())
    return h.hexdigest()


def _save_train_state(directory, params, opt_m, opt_v, epoch, it,
                      fingerprint) -> None:
    import os as _os

    _os.makedirs(directory, exist_ok=True)
    arrays = {"__epoch__": np.int64(epoch), "__it__": np.int64(it),
              "__fingerprint__": np.bytes_(fingerprint.encode())}
    for prefix, tree in (("p", params), ("m", opt_m), ("v", opt_v)):
        for path, leaf in _flat_paths(tree).items():
            arrays[f"{prefix}{path}"] = np.asarray(leaf)
    tmp = _os.path.join(directory, ".train_state.npz.tmp")
    final = _os.path.join(directory, "train_state.npz")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    # ONE atomic replace covers params+moments+epoch counter together;
    # a crash can never leave weights and epoch out of step
    _os.replace(tmp, final)


def _load_train_state(directory, template_params, fingerprint):
    """(params, opt_m, opt_v, epoch, it) or None when absent/mismatched."""
    import os as _os

    state_path = _os.path.join(directory, "train_state.npz")
    if not _os.path.exists(state_path):
        return None
    data = np.load(state_path)
    paths = _flat_paths(template_params)
    try:
        import jax.tree_util as jtu

        saved_fp = bytes(data["__fingerprint__"]).decode()
        if saved_fp != fingerprint:
            raise KeyError("__fingerprint__")
        # key paths AND shapes must match the template — belt and braces
        # on top of the fingerprint
        for p, leaf in paths.items():
            if data[f"p{p}"].shape != np.shape(leaf):
                raise KeyError(p)

        def rebuild(prefix):
            flat = {p: jnp.asarray(data[f"{prefix}{p}"]) for p in paths}
            leaves_paths = jtu.tree_flatten_with_path(template_params)[0]
            treedef = jtu.tree_structure(template_params)
            return jtu.tree_unflatten(
                treedef, [flat[jtu.keystr(p)] for p, _ in leaves_paths])

        params = rebuild("p")
        opt_m = rebuild("m")
        opt_v = rebuild("v")
        epoch = int(data["__epoch__"])
        it = int(data["__it__"])
    except KeyError:
        logger.warning("seqrec: checkpoint at %s is from a different "
                       "run (config, dataset, lr, or seed changed); "
                       "starting fresh", directory)
        return None
    return params, opt_m, opt_v, epoch, it


@partial(jax.jit, static_argnames=("k", "cfg"))
def predict_topk_batch(
    params: Mapping, history: jax.Array, k: int, cfg: SeqRecConfig,
    vocab_masks: jax.Array
) -> tuple[jax.Array, ...]:
    """Like :func:`predict_topk` but with a per-query additive logit mask
    ``vocab_masks`` (B, V) — the batched eval path, where each query
    carries its own seen/black-list exclusions. A kind whose program
    counts on the device (``BlockKind.tally``) returns its int32 arrays
    after scores and ids: "deepseek_v2" the (expert layers, experts
    held) assignments, "minicpm_sala" the selection's counts and the
    blocks each last position kept."""
    mask = (history != PAD)
    last = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
    kind = BLOCKS[cfg.block]
    h = kind.forward(params, history, cfg, None, "seq", True)
    h, *counted = h if kind.tally else (h,)
    hl = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum("bd,vd->bv", hl, head_table(params).astype(h.dtype),
                        preferred_element_type=jnp.float32)
    logits = logits + vocab_masks
    return (*jax.lax.top_k(logits, k), *counted)


def predict_topk(
    params: Mapping, history: jax.Array, k: int, cfg: SeqRecConfig,
    vocab_mask: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Top-k next items for (B, S) histories (the serving hot path; one
    compile per (shape, k, cfg)). ``vocab_mask`` (V,) f32 is added to
    the logits — 0 for allowed ids, a large negative for pad/seen/
    disallowed ids. Thin wrapper over :func:`predict_topk_batch` (the
    (1, V) mask broadcasts), so both paths share one kernel."""
    return predict_topk_batch(params, history, k, cfg,
                              vocab_mask[None, :])[:2]
