"""The core of latent attention (MLA): causal softmax attention whose
query/key head is wider than its value head, the key's rotary slice
shared by all heads, positions by YaRN-scaled RoPE.

A head's query and key are ``[nope | rotary]`` (128 + 64 at the
published widths of models/seqrec's ``deepseek_v2`` kind), its value 128
wide; the rotary slice of the key is one head that every query head
reads. Logits are ``q_nope k_nope^T + q_pe k_pe^T``.

Everything here works on the layouts the projections give, (B, S,
heads x width), so nothing is transposed to heads and back: the queries
arrive as two arrays (all heads' nope slices, all heads' rotary slices:
the columns of the up-projection are put in that order, and the rotary
pairs (2i, 2i+1) taken apart, by reordering the **weights**), keys and
values as the joint up-projection leaves them (per head ``[k_nope |
v]``), the shared rotary key as (B, S, width).

Two paths, one rule (``uses_kernel``: static shape and backend, no
option, flag or environment variable):

- ``flash`` — a Pallas TPU kernel tiled over query **and** key/value
  blocks: grid (batch, head groups, query tiles, key tiles), a group the
  heads whose rotary slices fill 128 lanes (2 at the published widths),
  running maximum, denominator and accumulator per head in VMEM scratch
  across the key axis, bfloat16 operands into the matrix unit, float32
  logits, softmax and accumulators. The shared rotary key is read by
  every group from the same block, once per head of the group with
  zeros in the other heads' lanes, so the rotary product contracts over
  whole lanes and no lane is sliced. Key tiles wholly above the
  diagonal are neither computed nor fetched (their block index is
  clamped to the last one the query tile sees, so Pallas finds the
  block unchanged and skips the copy); tiles wholly below it skip the
  mask. K and V are never whole in VMEM, so the length is bound by HBM
  alone (S = 8,192 at 128 heads: 3 MiB of keys a head, outside
  ops/pallas_attention's envelope). Forward only. Named
  ``mla_flash_attention`` in the device trace.
- ``plain`` — ops/attention.full_attention (or blockwise_attention from
  S = 4,096) on heads cut out of the same arrays: differentiable, any
  backend.

What PR 31 measured on one v5e is in PERF.md sections 5 and 6.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_attention
from predictionio_tpu.ops.attention import (
    blockwise_attention, full_attention)

_NEG = -1e30            # python float: a jnp scalar would be a captured const
#: query and key tile of the kernel (PERF.md section 6 has the sweep)
TILE_Q = 1024
TILE_K = 1024
#: below this a query tile is the whole sequence and the kernel has
#: nothing to stream: XLA's fused attention serves
_MIN_SEQ = 1024
#: the kernel's scoped VMEM: a (TILE_Q, TILE_K) float32 tile of logits
#: and one of probabilities beside the double-buffered blocks pass the
#: compiler's default of 16 MiB; a v5e core has 128 MiB
_VMEM_LIMIT = 64 << 20


# -- positions ---------------------------------------------------------------


def yarn_correction_range(dim: int, base: float, original_len: int,
                          beta_fast: float, beta_slow: float) -> tuple[int, int]:
    """(low, high): the rotary pairs between which YaRN blends from
    extrapolation to interpolation. ``cd(r) = dim ln(L / (2 pi r)) /
    (2 ln base)`` is the pair that turns ``r`` times over the original
    length; 10 and 23 at the published keys."""
    def cd(r):
        return dim * math.log(original_len / (r * 2 * math.pi)) \
            / (2 * math.log(base))

    return (max(math.floor(cd(beta_fast)), 0),
            min(math.ceil(cd(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, base: float, scaling=None) -> np.ndarray:
    """(dim/2,) float32 rotary frequencies; with ``scaling`` (factor,
    original length, beta_fast, beta_slow) pair i keeps ``base**(-2i/dim)``
    below ``low``, takes it over ``factor`` above ``high`` and a linear
    blend between."""
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)  # pio: lint-ignore[dtype-discipline]: a host-side table of dim/2 frequencies, worked once at trace time and rounded to float32 below; never on the device
    if scaling is None:
        return freq.astype(np.float32)
    low, high = yarn_correction_range(
        dim, base, scaling.original_max_position_embeddings,
        scaling.beta_fast, scaling.beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (freq * (1 - ramp) + freq / scaling.factor * ramp).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(qk_dim: int, scaling=None) -> float:
    """``qk_dim**-0.5`` times the square of YaRN's ``mscale_all_dim``
    magnitude (0.11472 at the published keys)."""
    m = 1.0 if scaling is None else yarn_mscale(scaling.factor,
                                                scaling.mscale_all_dim)
    return qk_dim ** -0.5 * m * m


def pairs_apart(dim: int) -> np.ndarray:
    """The order that takes a rotary slice's published pairs (2i, 2i+1)
    apart: evens first, then odds. Applied to the **columns of the
    weights** that produce the slice (a 75 MB gather a program), not to
    the activations (a stride-2 lane shuffle of every token). The same
    order on queries and keys, so every dot product is the published
    one."""
    return np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])


def halves_swapped(dim: int) -> np.ndarray:
    """:func:`pairs_apart` with the halves changed over: odds first."""
    return np.concatenate([np.arange(1, dim, 2), np.arange(0, dim, 2)])


def query_columns(heads: int, dn: int, dr: int) -> np.ndarray:
    """The order of the query up-projection's columns (published: per
    head ``[nope | rotary]``) that gives all heads' nope slices, then all
    heads' rotary slices with their pairs apart, then the rotary slices
    once more with the halves changed over (:func:`rope_apart` takes
    each lane's partner from there)."""
    per = np.arange(heads)[:, None] * (dn + dr)
    return np.concatenate([(per + np.arange(dn)).ravel(),
                           (per + dn + pairs_apart(dr)).ravel(),
                           (per + dn + halves_swapped(dr)).ravel()])


def rope_apart(x: jax.Array, partner: jax.Array, inv_freq: np.ndarray,
               width: int, magnitude: float = 1.0) -> jax.Array:
    """Rotate (B, S, heads x width) by position 0..S-1 where every
    ``width`` lanes are one head's ``[evens | odds]`` of the published
    pairs (:func:`pairs_apart`) and ``partner`` is the same with the
    halves changed over (``[odds | evens]``, from the projection's own
    reordered columns: a lane shuffle of the activations costs five
    times the extra columns, PERF.md section 6, PR 31):
    ``[e cos - o sin | o cos + e sin]``, float32, lane-dense."""
    S, reps = x.shape[1], x.shape[-1] // width
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos = jnp.cos(ang) * magnitude
    sin = jnp.sin(ang) * magnitude
    cos = jnp.tile(jnp.concatenate([cos, cos], axis=-1), (1, reps))[None]
    sin = jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, reps))[None]
    return x.astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin


# -- the kernel --------------------------------------------------------------


def _flash_kernel(qn_ref, qp_ref, kv_ref, kp_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, tq: int, tk: int, group: int, dn: int, dv: int):
    """Grid (batch, head groups, query tiles, key tiles); the key axis
    is the innermost and carries the scratch. Blocks, for a group of
    ``group`` heads: qn (tq, group x dn), qp (tq, group x dr), kv (tk,
    group x (dn + dv)), kp (group, tk, group x dr: the shared rotary key
    in head j's lanes, zeros elsewhere), o (tq, group x dv). Queries
    arrive scaled."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = (qi * tq + tq - 1) // tk         # the last key tile this one sees

    @pl.when(ki == 0)
    def _start():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def fold(masked: bool):
        contract_last = (((1,), (1,)), ((), ()))
        if masked:
            q_pos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            k_pos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            seen = q_pos >= k_pos
        for j in range(group):
            k0 = j * (dn + dv)
            s = jax.lax.dot_general(
                qn_ref[:, j * dn:(j + 1) * dn], kv_ref[:, k0:k0 + dn],
                contract_last, preferred_element_type=jnp.float32)
            s += jax.lax.dot_general(qp_ref[...], kp_ref[j], contract_last,
                                     preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(seen, s, _NEG)
            m_prev = m_sc[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_sc[j] = alpha * l_sc[j] + jnp.sum(p, axis=-1, keepdims=True)
            cols = slice(j * dv, (j + 1) * dv)
            acc_sc[:, cols] = alpha * acc_sc[:, cols] + jax.lax.dot_general(
                p.astype(kv_ref.dtype), kv_ref[:, k0 + dn:k0 + dn + dv],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_sc[j] = m_new

    # every key of the tile at or before every query of the tile
    below = ki * tk + tk - 1 <= qi * tq

    @pl.when(below)
    def _whole():
        fold(False)

    @pl.when(jnp.logical_and(jnp.logical_not(below), ki <= last))
    def _diagonal():
        fold(True)

    @pl.when(ki == last)
    def _finish():
        # key 0 is visible to every query, so l > 0
        for j in range(group):
            cols = slice(j * dv, (j + 1) * dv)
            o_ref[:, cols] = (acc_sc[:, cols] / l_sc[j]).astype(o_ref.dtype)


def heads_per_step(heads: int, dr: int) -> int:
    """The heads a grid step works: as many as fill 128 lanes with their
    rotary slices (2 at the published 64), held to a divisor of the
    head count."""
    most = max(1, 128 // dr)
    return max(g for g in range(1, most + 1) if heads % g == 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dn", "dv", "interpret", "tile_q", "tile_k"))
def flash(q_nope, q_pe, kv, k_pe, *, heads: int, dn: int, dv: int,
          interpret: bool = False, tile_q: int = TILE_Q,
          tile_k: int = TILE_K):
    """Causal attention of scaled queries (B, S, heads x dn) and (B, S,
    heads x dr) over keys and values (B, S, heads x (dn + dv)), per head
    ``[k_nope | v]``, and the shared rotary key (B, S, dr): (B, S,
    heads x dv) in ``kv.dtype``."""
    B, S, _ = q_nope.shape
    dr = k_pe.shape[-1]
    group = heads_per_step(heads, dr)
    tq, tk = min(tile_q, S), min(tile_k, S)
    if S % tq or S % tk:
        raise ValueError(f"S={S} is not whole tiles of ({tq}, {tk})")
    # the shared key once per head of a group, in that head's lanes
    kp = jnp.stack([jnp.pad(k_pe, ((0, 0), (0, 0),
                                   (j * dr, (group - 1 - j) * dr)))
                    for j in range(group)], axis=1)

    def seen(qi, ki):           # clamp: a tile above the diagonal is not fetched
        return jnp.minimum(ki, (qi * tq + tq - 1) // tk)

    def q_map(b, g, qi, ki):
        return b, qi, g

    return pl.pallas_call(
        functools.partial(_flash_kernel, tq=tq, tk=tk, group=group, dn=dn,
                          dv=dv),
        grid=(B, heads // group, S // tq, S // tk),
        in_specs=[pl.BlockSpec((None, tq, group * dn), q_map),
                  pl.BlockSpec((None, tq, group * dr), q_map),
                  pl.BlockSpec((None, tk, group * (dn + dv)),
                               lambda b, g, qi, ki: (b, seen(qi, ki), g)),
                  pl.BlockSpec((None, group, tk, group * dr),
                               lambda b, g, qi, ki: (b, 0, seen(qi, ki), 0))],
        out_specs=pl.BlockSpec((None, tq, group * dv), q_map),
        out_shape=jax.ShapeDtypeStruct((B, S, heads * dv), kv.dtype),
        scratch_shapes=[pltpu.VMEM((group, tq, 1), jnp.float32),
                        pltpu.VMEM((group, tq, 1), jnp.float32),
                        pltpu.VMEM((tq, group * dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="mla_flash_attention",
        interpret=interpret,
    )(q_nope, q_pe, kv, kp)


def uses_kernel(seq_len: int, inference: bool, dn: int = 128,
                dr: int = 64, dv: int = 128, heads: int = 2) -> bool:
    """Whether :func:`attend` runs the kernel: a serving program
    (``inference``; the kernel has no backward pass) on a compiled TPU
    backend over whole tiles of at least ``_MIN_SEQ`` positions, head
    widths in whole lane tiles."""
    return (inference and pallas_attention._mode() == "compiled"
            and seq_len >= _MIN_SEQ and seq_len % min(TILE_Q, seq_len) == 0
            and seq_len % min(TILE_K, seq_len) == 0
            and dn % 128 == 0 and dv % 128 == 0
            and (heads_per_step(heads, dr) * dr) % 128 == 0)


def attend(q_nope, q_pe, kv, k_pe, *, heads: int, dn: int, dv: int,
           scale: float, inference: bool = False) -> jax.Array:
    """Causal ``softmax((q_nope k_nope^T + q_pe k_pe^T) scale) v`` per
    head, on the projections' own layouts (:func:`flash` says which);
    positions are already rotated in. Returns (B, S, heads x dv) in
    ``kv.dtype``. The scale is folded into the queries in float32 and
    they are rounded once."""
    B, S, _ = q_nope.shape
    dt, dr = kv.dtype, k_pe.shape[-1]
    k_pe = k_pe.astype(dt)
    if uses_kernel(S, inference, dn, dr, dv, heads):
        q_nope = (q_nope.astype(jnp.float32) * scale).astype(dt)
        q_pe = (q_pe.astype(jnp.float32) * scale).astype(dt)
        return flash(q_nope, q_pe, kv, k_pe, heads=heads, dn=dn, dv=dv)
    # the plain forms work by head and divide by sqrt(width) themselves
    fold = scale * math.sqrt(dn + dr)
    by_head = kv.reshape(B, S, heads, dn + dv).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q_nope.reshape(B, S, heads, dn),
                         q_pe.reshape(B, S, heads, dr)], axis=-1)
    q = (q.astype(jnp.float32) * fold).astype(dt).transpose(0, 2, 1, 3)
    k = jnp.concatenate([by_head[..., :dn], jnp.broadcast_to(
        k_pe[:, None], (B, heads, S, dr))], axis=-1)
    plain = functools.partial(blockwise_attention, q_block=128) \
        if S >= 4096 and S % 128 == 0 else full_attention
    out = plain(q, k, by_head[..., dn:], causal=True)
    return out.transpose(0, 2, 1, 3).reshape(B, S, heads * dv)
