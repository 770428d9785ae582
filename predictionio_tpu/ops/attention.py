"""Attention kernels: full causal attention and ring attention for
sequence/context parallelism.

The reference has no sequence models (SURVEY.md §5 "long-context:
absent") — this is the TPU build's own scale axis, powering the
session-based sequential recommendation engine (models/seqrec.py). Long
sessions shard over a mesh "seq" axis: each device holds one block of
the sequence, and K/V blocks rotate around the ring with
``lax.ppermute`` while a flash-style online softmax accumulates partial
results — compute overlaps the ICI transfer and no device ever holds
the full sequence (Liu et al., Ring Attention; blockwise transformers).

All logits accumulate in f32 regardless of input dtype (bf16 inputs
recommended on TPU — the matmuls tile onto the MXU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# large-negative instead of -inf: keeps exp() NaN-free. A NumPy scalar:
# a jnp one would start the JAX backend when this module is imported
_NEG = np.float32(-1e30)


def full_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, H, S, D)
    v: jax.Array,  # (B, H, S, D)
    *,
    causal: bool = True,
    kv_mask: jax.Array | None = None,  # (B, S) 1=real, 0=pad
) -> jax.Array:
    """Reference single-device attention; returns (B, H, S, D) in q.dtype."""
    d = q.shape[-1]
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.float32(math.sqrt(d))
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((s, t), dtype=bool))
        logits = jnp.where(cmask[None, None], logits, _NEG)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :].astype(bool), logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


def blockwise_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, H, S, D)
    v: jax.Array,  # (B, H, S, D)
    *,
    causal: bool = True,
    kv_mask: jax.Array | None = None,  # (B, S)
    q_block: int | None = None,
) -> jax.Array:
    """Memory-bounded, DIFFERENTIABLE attention: lax.scan over query
    tiles, each tile computing its (q_block, S) logits and softmax; the
    rematerialised body recomputes tile logits in the backward pass, so
    peak memory is O(B*H*q_block*S) instead of O(B*H*S^2).

    ``q_block=None`` (default) auto-picks the largest divisor of S
    that is <= 128 (falling back to S itself, one full tile), so
    default calls work at any S. The 128 target comes from the r5
    sweep on the real chip (S=4096 B=4 seqrec TRAIN step, fwd+bwd,
    order-independent across two sessions): 1024 → 168k, 512 → 170k,
    256 → 254k, 128 → 306-319k, 64 → 321k tokens/sec — smaller query
    tiles keep the remat backward's (q_block, S) logits VMEM-resident,
    and the curve is flat below 128. The old 512 default cost 1.8x.
    An EXPLICIT q_block must divide S (raises otherwise).

    This is the single-device long-context TRAINING path: full_attention
    materializes the (S, S) logits (~8.6 GB at S=16384, OOM on one
    v5e), the pallas flash kernel (ops/pallas_attention) is
    forward-only, and ring_attention needs a mesh "seq" axis. Matches
    full_attention to f32 rounding in both values and gradients
    (tests/test_attention.py). ``S`` must divide by ``q_block``; pad
    with ``kv_mask`` otherwise.
    """
    B, H, S, D = q.shape
    if kv_mask is None:
        kv_mask = jnp.ones((B, S), dtype=jnp.float32)
    if q_block is None:
        q_block = next((b for b in (128, 64, 32, 16, 8) if S % b == 0), S)
    q_block = min(q_block, S)
    if S % q_block:
        raise ValueError(f"S={S} must divide by q_block={q_block}")
    n_tiles = S // q_block
    scale = jnp.float32(1.0 / math.sqrt(D))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = lax.iota(jnp.int32, S)
    valid_k = kv_mask[:, None, None, :].astype(bool)       # (B, 1, 1, S)

    qt = q.reshape(B, H, n_tiles, q_block, D).transpose(2, 0, 1, 3, 4)

    def tile(_, xs):
        q_tile, t = xs                                     # (B, H, Tq, D)
        logits = jnp.einsum("bhsd,bhtd->bhst", q_tile.astype(jnp.float32),
                            kf) * scale                    # (B, H, Tq, S)
        valid = valid_k
        if causal:
            q_pos = t * q_block + lax.iota(jnp.int32, q_block)
            valid = valid & (q_pos[None, None, :, None] >= k_pos[None, None, None, :])
        logits = jnp.where(valid, logits, _NEG)
        probs = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (padding queries) get zero output
        any_valid = jnp.any(valid, axis=-1, keepdims=True)
        probs = jnp.where(any_valid, probs, 0.0)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, vf)
        return None, out.astype(q.dtype)

    _, tiles = lax.scan(
        jax.checkpoint(tile), None,
        (qt, jnp.arange(n_tiles, dtype=jnp.int32)))
    # the value head may be narrower than the query/key head (MLA)
    return tiles.transpose(1, 2, 0, 3, 4).reshape(B, H, S, v.shape[-1])


def _ring_attention_local(
    q: jax.Array,        # (B, H, Sl, D) local query block
    k: jax.Array,        # (B, H, Sl, D) local key block (rotates)
    v: jax.Array,        # (B, H, Sl, D) local value block (rotates)
    kv_mask: jax.Array,  # (B, Sl) local key padding mask (rotates)
    *,
    axis_name: str,
    causal: bool,
) -> jax.Array:
    """Per-device body run under shard_map: online-softmax accumulation
    over ring-rotated K/V blocks."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    scale = jnp.float32(1.0 / math.sqrt(D))

    q_pos = idx * Sl + lax.iota(jnp.int32, Sl)          # global query positions
    block_pos = lax.iota(jnp.int32, Sl)

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, Sl), _NEG, dtype=jnp.float32)   # running max
    l0 = jnp.zeros((B, H, Sl), dtype=jnp.float32)        # running denominator
    o0 = jnp.zeros((B, H, Sl, D), dtype=jnp.float32)     # running numerator

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        m, l, o, k_blk, v_blk, mask_blk = carry
        # the block arriving at step i originated on device (idx - i) mod n
        src = (idx - i) % n
        k_pos = src * Sl + block_pos
        logits = jnp.einsum("bhsd,bhtd->bhst", qf, k_blk.astype(jnp.float32))
        logits = logits * scale
        valid = mask_blk[:, None, None, :].astype(bool)
        if causal:
            valid = valid & (q_pos[None, None, :, None] >= k_pos[None, None, None, :])
        logits = jnp.where(valid, logits, _NEG)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # blocks that are entirely masked contribute nothing; alpha/p stay
        # finite because _NEG - _NEG == 0 and exp(0)=1 is cancelled by the
        # seen-mask below
        seen = m_new > _NEG / 2
        alpha = jnp.where(seen, jnp.exp(m - m_new), 0.0)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(valid & seen[..., None], p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhst,bhtd->bhsd", p, v_blk.astype(jnp.float32))

        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        return m_new, l, o, k_blk, v_blk, mask_blk

    m, l, o, *_ = lax.fori_loop(0, n, step, (m0, l0, o0, k, v, kv_mask))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    causal: bool = True,
    kv_mask: jax.Array | None = None,
) -> jax.Array:
    """Sequence-parallel attention: (B, H, S, D) arrays whose S dimension
    is sharded over ``mesh`` axis ``seq_axis``. S must divide evenly by
    the axis size. Works inside jit (shard_map composes with pjit)."""
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:1] + q.shape[2:3], dtype=jnp.float32)
    spec4 = P(None, None, seq_axis, None)
    spec2 = P(None, seq_axis)
    fn = functools.partial(_ring_attention_local, axis_name=seq_axis,
                           causal=causal)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2),
        out_specs=spec4,
        check_vma=False,
    )(q, k, v, kv_mask)
