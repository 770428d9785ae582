"""Pallas TPU kernel: flash attention — auto-dispatched for causal
serving shapes of the ``sasrec`` block.

Tile-streamed causal attention with the standard flash online softmax:
for each query tile, K/V tiles stream through the MXU and a running
(max, denominator, numerator) carry folds each tile — the S x S logits
matrix never exists in HBM. K and V of one (batch, head) are **whole in
VMEM** and the loop over their tiles runs inside the kernel, computing
in float32, one head size for q, k and v; the loop's bound stops at the
diagonal (causal KV-tile skip) and tiles are 512 x 512 from S = 4,096.
That bounds the length by VMEM (the envelope below). The ``deepseek_v2``
block's attention (query/key heads wider than value heads, 128 heads at
S = 8,192: 3 MiB of keys a head) is ops/mla_attention.py, a kernel
tiled over query **and** key blocks by the grid, with bfloat16 operands;
PERF.md section 7 says what keeps the two apart.

**What the chip has shown on today's code.** PR 21 (one v5e): the
kernel compiles through Mosaic and matches ``full_attention`` at both
ends of the envelope at the sessionrec serving shape (B=1 H=4 D=64 bf16
causal; ``chip_smoke.py`` re-proves it on every run), and Mosaic refuses
any shape whose K/V block reaches 4 MiB. PR 31 (one v5e, B=1 H=4 D=64
causal, wall clock per call in a chain of 20 calls, so nothing under the
~1 ms a call costs the host can be told apart):

=======  ========  ==================  ===============
S        dtype     pallas, ms a call   XLA, ms a call
=======  ========  ==================  ===============
2048     bfloat16  1.006               0.282
4096     bfloat16  1.049               1.179
8192     bfloat16  1.044               4.845
16384    bfloat16  3.152               18.957
2048     float32   1.079               0.273
4096     float32   1.203               1.203
8192     float32   1.011               4.715
=======  ========  ==================  ===============

So from S = 4,096 the kernel is level or ahead and at 16,384 six times
ahead; at 2,048, the bottom of the envelope, XLA's fused attention is
ahead of what a call costs the host at all (the device time of the
kernel there is not measured: no cell runs the ``sasrec`` block). The
tables of rounds 2-5, taken on a chip attachment that no longer exists,
are gone (``git show fc20129:predictionio_tpu/ops/pallas_attention.py``).

**Auto-dispatch:** CAUSAL attention on a compiled TPU backend at
2048 <= S <= 16384 with K/V blocks of at most 2 MiB each (the skip
only helps causal, and non-causal remains unmeasured -> force-only).
``force=True`` still runs the kernel at any shape (incl. interpret mode
for CPU tests). Sequences beyond a chip shard over the mesh "seq" axis
instead (ops/attention.ring_attention).

Forward-only: no VJP — training paths (models/seqrec.next_item_loss,
ring attention local blocks) use ops/attention.full_attention /
blockwise_attention, which are differentiable.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.attention import full_attention

_TILE_Q = 128
_TILE_K = 128
#: 512x512 tiles from S=4096 (a sweep of round 5, not re-measured)
_TILE_BIG = 512
_TILE_BIG_FROM = 4096
_NEG = -1e30  # python float: jnp scalars would be captured consts in the kernel
#: auto-dispatch envelope (causal only — module docstring): sequence
#: lengths the dispatcher promises, and the K/V residency Mosaic
#: accepts. K and V are whole-sequence (1, S, D) blocks, double
#: buffered, so 4 * S * D * itemsize of the 16 MiB scoped VMEM is
#: theirs: on a v5e every probed shape with a 2 MiB block compiled and
#: every one with a 4 MiB block was refused (PR 21 chip run: bf16 D=64
#: to S=16384, bf16 D=128 and f32 D=64 to 8192, f32 D=128 to 4096).
_MIN_SEQ = 2048
_MAX_SEQ = 16384
_MAX_KV_BLOCK_BYTES = 2 << 20


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, causal: bool,
                  seq_len: int, tile_k: int):
    """Grid: (batch*heads, seq_len // TILE_Q). Blocks:
    q (TILE_Q, D), k/v (seq_len, D) resident per bh, mask (1, seq_len),
    o (TILE_Q, D)."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                    # (TQ, D)
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    tq = q.shape[0]
    q_pos = qi * tq + jax.lax.iota(jnp.int32, tq)       # global query rows

    n_kv = seq_len // tile_k
    if causal:
        # causal KV-tile skip (r5 optimization pass): tiles entirely
        # above the diagonal are fully masked — don't visit them. The
        # loop bound is traced (depends on program_id); lowers to a
        # while_loop. Halves the visited tiles on average.
        n_kv = jnp.minimum(n_kv, ((qi + 1) * tq + tile_k - 1) // tile_k)

    def body(t, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(t * tile_k, tile_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(t * tile_k, tile_k), :].astype(jnp.float32)
        msk = mask_ref[0, 0, pl.ds(t * tile_k, tile_k)]  # (TK,)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # (TQ, TK)
        k_pos = t * tile_k + jax.lax.iota(jnp.int32, tile_k)
        valid = msk[None, :] > 0
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        logits = jnp.where(valid, logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        seen = m_new > _NEG / 2
        alpha = jnp.where(seen, jnp.exp(m - m_new), 0.0)
        p = jnp.exp(logits - m_new[:, None])
        p = jnp.where(valid & seen[:, None], p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m0 = jnp.full((tq,), _NEG, dtype=jnp.float32)
    l0 = jnp.zeros((tq,), dtype=jnp.float32)
    a0 = jnp.zeros((tq, d), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-20)[:, None]
    out = jnp.where((l > 0)[:, None], out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


@partial(jax.jit,
         static_argnames=("causal", "interpret", "tile_q_", "tile_k_"))
def _flash_call(q, k, v, kv_mask, causal: bool, interpret: bool,
                tile_q_: int | None = None, tile_k_: int | None = None):
    B, H, S, D = q.shape
    bh = B * H
    qf = q.reshape(bh, S, D)
    kf = k.reshape(bh, S, D)
    vf = v.reshape(bh, S, D)
    # (bh, 1, S): the singleton keeps the block's trailing dims equal to
    # the array's (TPU lowering requires trailing block dims divisible by
    # (8, 128) or exactly equal)
    maskf = jnp.repeat(kv_mask.astype(jnp.float32), H, axis=0)[:, None, :]
    big = S >= _TILE_BIG_FROM and S % _TILE_BIG == 0
    tile_q = min(tile_q_ or (_TILE_BIG if big else _TILE_Q), S)
    tile_k = min(tile_k_ or (_TILE_BIG if big else _TILE_K), S)
    if S % tile_q or S % tile_k:
        # an explicit override must never silently truncate the grid
        # (grid = S // tile_q drops trailing query tiles otherwise)
        raise ValueError(
            f"S={S} not divisible by tiles ({tile_q}, {tile_k})")
    grid = (bh, S // tile_q)
    kernel = functools.partial(
        _flash_kernel, causal=causal, seq_len=S, tile_k=tile_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, maskf)
    return out.reshape(B, H, S, D)


@functools.cache
def _mode() -> str:
    """'compiled' on a TPU backend, 'interpret' elsewhere. A backend
    that cannot start raises from ``jax.devices()`` — never a quiet
    switch to the XLA path."""
    on_tpu = jax.devices()[0].platform == "tpu"
    return "compiled" if on_tpu else "interpret"


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_mask: jax.Array | None = None,
    force: bool = False,
) -> jax.Array:
    """Streaming-tile attention. Auto-dispatches for CAUSAL attention
    on a compiled TPU backend within 2048 <= S <= 16384 where the K/V
    blocks fit VMEM (``_MAX_KV_BLOCK_BYTES``); everything outside that
    envelope is ops/attention.full_attention. Inside it there is no way
    back to XLA: a kernel that fails to build or compile raises.

    ``force=True`` runs the pallas kernel at any shape (interpret mode
    on the CPU for tests); on a TPU a shape Mosaic refuses raises.
    Forward-only — do not call under jax.grad (training uses
    full_attention / ring_attention).
    """
    B, H, S, D = q.shape
    if kv_mask is None:
        kv_mask = jnp.ones((B, S), dtype=jnp.float32)
    mode = _mode()
    auto = (
        mode == "compiled"  # interpret mode is force-only (too slow)
        and causal          # the KV-skip win is causal-only (measured)
        and _MIN_SEQ <= S <= _MAX_SEQ
        and S * D * q.dtype.itemsize <= _MAX_KV_BLOCK_BYTES
    )
    eligible = (force or auto) and S % min(_TILE_Q, S) == 0
    if not eligible:
        return full_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    # a kernel that fails to build or compile raises: answering with
    # XLA's result instead would hide a broken kernel behind a correct
    # one (chip_smoke.py compiles both ends of the envelope)
    return _flash_call(q, k, v, kv_mask, causal, mode == "interpret")
