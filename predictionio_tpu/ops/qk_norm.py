"""A mixer's way in: a q or k projection's output, RMS-normed per head,
rotated by position (RoPE) and rounded once, in the order its consumer
reads.

:func:`prepare` is the one road from a projection to a mixer for the
session engine's ``brumby`` and ``minicpm_sala`` kinds. Two ways
through it, one set of equations:

- the ``jax.numpy`` form (:func:`normed`, :func:`rotate`) is the
  definition: autodiff goes through it, so it is the path of every
  caller that may take a gradient (training enters with
  ``inference=False``), of the CPU, and of every shape outside the
  kernel's envelope. The rotation's halves are two half-width products
  written side by side against tables built once a program
  (:func:`rope_tables`), so no negated, swapped copy of the operand
  exists;
- the fused one (``ops/pallas_qk_norm.py``, forward only) reads the
  projection's bfloat16 output once and writes the bfloat16 result once,
  already in retention's chunk order where ``chunk`` asks for it;
  float32 is in registers only. :func:`fuses` says when it is taken,
  from what the code can observe (backend, ``inference``, shape) and
  with no option.

The rounding points are the caller's: one at the end, and none between
the norm and the rotation but the one ``norm_dtype`` writes for the
``jax.numpy`` form (Brumby's norm returned the stream's type), which
the CPU performs and the TPU does not (:func:`prepare`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import pallas_attention, pallas_qk_norm


def rope_tables(seq_len: int, d: int, theta: float):
    """(cos, sin), each (seq_len, d) float32, for positions
    0..seq_len-1: pairs (i, i + d/2) turn by ``theta**(-2i/d)`` a
    position, so both halves of ``cos`` are the same and ``sin`` carries
    the first half's minus sign (``rotate``'s two products, the
    kernel's one roll). Built where a program starts (a forward pass
    calls this once, before its layers), not per layer and array."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def partial_order(d: int, rotary: int) -> np.ndarray:
    """The lane order of a head that turns RoPE over its first ``rotary``
    lanes (pairs (i, i + rotary/2), the rest passed through, as a
    ``partial_rotary_factor`` below 1 publishes it) into the full-width
    pairs (i, i + d/2) that :func:`rotate` and the kernel turn: each
    rotary half takes the front of one half of the head, the passed-
    through lanes the rest. Applied to the **columns of the weights**
    (q and k alike, and the norm's weight), so every dot product is the
    published one."""
    half, pas = rotary // 2, (d - rotary) // 2
    lanes = np.arange(d)
    return np.concatenate([lanes[:half], lanes[rotary:rotary + pas],
                           lanes[half:rotary], lanes[rotary + pas:]])


def partial_rope_tables(seq_len: int, d: int, inv_freq, magnitude: float):
    """:func:`rope_tables` for heads in :func:`partial_order`: the
    ``len(inv_freq)`` pairs at the front of each half turn by
    ``inv_freq`` a position with cos and sin times ``magnitude`` (YaRN's
    ``attention_factor``), the lanes after them pass through (cos 1,
    sin 0)."""
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    pad = ((0, 0), (0, d // 2 - ang.shape[1]))
    cos = jnp.pad(cos, pad, constant_values=1.0)
    sin = jnp.pad(sin, pad)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def rotate(x: jax.Array, rope) -> jax.Array:
    """RoPE of (B, S, heads, d) by :func:`rope_tables`' tables, in
    float32: ``[x1 cos - x2 sin, x2 cos + x1 sin]``, the halves worked
    apart and written side by side."""
    half = x.shape[-1] // 2
    cos, sin = (t[None, :, None, half:] for t in rope)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def normed(x: jax.Array, weight: jax.Array, eps: float,
           scale: float = 1.0) -> jax.Array:
    """RMS norm over the last axis in float32, times ``scale`` and the
    weight; float32 out."""
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if scale != 1.0:
        r = r * scale
    return x32 * r * weight.astype(jnp.float32)


def fuses(d: int, width: int, rows: int, *, inference: bool) -> bool:
    """Whether :func:`prepare` runs the fused kernel for heads of width
    ``d`` across a projection ``width`` wide, ``rows`` the chunk (or the
    sequence, for a token-major result): on a compiled TPU backend, for
    a caller that does not differentiate, inside the kernel's envelope.
    Inside it there is no way back to XLA: a kernel that fails to build
    raises."""
    return (inference and pallas_attention._mode() == "compiled"
            and pallas_qk_norm.in_envelope(d, width, rows))


def fused(x: jax.Array, weight: jax.Array, *, heads: int, eps: float,
          scale: float = 1.0, rope=None, chunk: int | None = None,
          interpret: bool = False) -> jax.Array:
    """:func:`prepare`'s equations in the kernel, where :func:`fuses`
    holds (``interpret``: tests, on the CPU), as the TPU computes them:
    float32 from the norm to the one rounding (see ``norm_dtype``
    there). With ``chunk`` (S a whole number of chunks) the result is
    (S / chunk, B, heads, chunk, d), the order
    ``ops/retention.chunk_order`` defines: retention's ``_chunk_major``
    is the one caller that asks for it."""
    cos, sin = rope if rope is not None else (None, None)
    out = pallas_qk_norm.qk_norm_rope(
        x, weight, cos, sin, heads=heads, eps=eps, scale=scale, chunk=chunk,
        interpret=interpret)
    return out if chunk else out.reshape(*x.shape[:2], heads, -1)


def prepare(x: jax.Array, weight: jax.Array, *, heads: int, eps: float,
            scale: float = 1.0, rope=None, norm_dtype=None,
            inference: bool = False) -> jax.Array:
    """``x`` (B, S, heads * d) as projected -> (B, S, heads, d) in
    ``x.dtype``, the mixer's operand: per head ``normed``, ``rotate`` by
    ``rope`` (:func:`rope_tables`; None: no positions), one rounding.

    ``norm_dtype`` writes a rounding between the norm and the rotation
    where the model's code had one (Brumby's norm returned the stream's
    type). What becomes of it is the backend's: XLA's CPU backend
    rounds; its TPU backend carries the float32 value through such a
    pair inside a fusion and does not (measured: with and without it
    the compiled results are equal bit for bit, PERF.md section 6,
    PR 37). The kernel runs on the TPU alone and does as the TPU does,
    so each platform's values are what they were."""
    B, S, width = x.shape
    d = width // heads
    if fuses(d, width, S, inference=inference):
        return fused(x, weight, heads=heads, eps=eps, scale=scale, rope=rope)
    y = normed(x.reshape(B, S, heads, d), weight, eps, scale)
    if norm_dtype is not None:
        y = y.astype(norm_dtype)
    if rope is not None:
        y = rotate(y, rope)
    return y.astype(x.dtype)
