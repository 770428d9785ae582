"""Pallas TPU kernel: a mixer's way in, in one pass over HBM.

Between a q or k projection and the mixer that reads it stand a
per-head RMS norm, RoPE and one rounding, and for retention the move
into chunk order. In plain XLA the float32 values between those steps
become arrays: the rotation's 64-lane halves are sliced and joined as
arrays of their own and every relayout is a copy, six to seven passes
over a ``(S, heads, d)`` array, most of them float32 (PERF.md section 5,
PR 37, has the parent's compiled text). Here a ``(rows, heads * d)``
bfloat16 tile of the projection's output is read once; each head's
``(rows, d)`` slice (a lane-aligned slice of the tile) is widened,
normed, rotated by one lane roll against full-width tables, rounded and
written where its consumer reads it: token-major, or retention's chunk
order (``ops/retention.chunk_order`` defines that layout; this kernel's
``chunk`` output is that function's, written from the output blocks'
index map; a test holds the two equal). Float32 lives in registers only.

The equations are ``ops/qk_norm.prepare``'s, which is the definition:
``x * (rsqrt(mean(x^2) + eps) * scale) * weight``, then ``x * cos +
swap_halves(x) * sin`` with ``sin`` carrying the first half's minus
sign, rounded once to the output type. No rounding between the norm and
the rotation: where ``prepare`` writes one (``norm_dtype``: Brumby's
norm returned bfloat16) the CPU backend performs it and XLA's TPU
backend does not — it carries the float32 value through a float32 →
bfloat16 → float32 pair inside a fusion — so on the chip this kernel
computes what the ``jax.numpy`` form computes there, bit for bit at the
cells' widths (``chip_smoke.py``; PERF.md section 6, PR 37). In
interpret mode on the CPU only a 128-lane sum's order may differ: a
last bit of bfloat16.

Forward only: ``ops/qk_norm.prepare`` takes it when its caller does not
differentiate, and autodiff goes through the ``jax.numpy`` form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: what the kernel's blocks may take of VMEM, double buffers included
#: (the tile in and the tile out, each twice): rows are halved until
#: they fit
_VMEM_BUDGET = 8 << 20
_MAX_ROWS = 256
#: a bfloat16 tile is 16 rows
_MIN_ROWS = 16


def rows_per_step(rows: int, width: int) -> int:
    """The rows of a grid step: the largest power of two up to
    ``_MAX_ROWS`` that divides ``rows`` (a chunk, or the sequence) and
    whose blocks fit ``_VMEM_BUDGET``; 0 where none of at least
    ``_MIN_ROWS`` does."""
    ts = _MAX_ROWS
    while ts >= _MIN_ROWS:
        if rows % ts == 0 and 8 * ts * width <= _VMEM_BUDGET:
            return ts
        ts //= 2
    return 0


def in_envelope(d: int, width: int, rows: int) -> bool:
    """The shapes the kernel is built for: lane-wide heads (the roll
    swaps the halves of one 128-lane row), whole heads across the tile,
    and a step of at least a bfloat16 tile's rows. ``rows`` is what a
    step's rows must divide: the chunk for a chunk-order output, else
    the sequence."""
    return d == _LANES and width % d == 0 and rows_per_step(rows, width) > 0


def _kernel(*refs, heads, d, eps, scale, rotate, chunked):
    """Straight-line code over the heads: each is a lane-aligned slice
    of the tile in and a tile of its own (chunk order) or the same
    slice (token-major) out."""
    if rotate:
        x_ref, w_ref, cos_ref, sin_ref, o_ref = refs
        cos, sin = cos_ref[...], sin_ref[...]
    else:
        x_ref, w_ref, o_ref = refs
    f32 = jnp.float32
    w = w_ref[...]                                          # (1, d)
    for h in range(heads):
        at = slice(h * d, (h + 1) * d)
        x = x_ref[0, :, at].astype(f32)                     # (rows, d)
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        if scale != 1.0:
            r = r * scale
        y = x * r * w
        if rotate:
            y = y * cos + pltpu.roll(y, d // 2, 1) * sin
        if chunked:
            o_ref[0, 0, h] = y.astype(o_ref.dtype)
        else:
            o_ref[0, :, at] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "scale", "chunk", "interpret"))
def qk_norm_rope(x: jax.Array, weight: jax.Array, cos, sin, *, heads: int,
                 eps: float, scale: float = 1.0, chunk: int | None = None,
                 interpret: bool):
    """``x`` (B, S, heads * d), a projection's output; ``weight`` (d,)
    the norm's; ``cos`` and ``sin`` (S, d) float32 as
    ``ops/qk_norm.rope_tables`` makes them, or None for no rotation.
    Returns x's type: (B, S, heads * d) or, with ``chunk`` (S a whole
    number of chunks), (S / chunk, B, heads, chunk, d).

    Jitted so that the layers of a stack share one trace and one Mosaic
    lowering of the unrolled kernel."""
    B, S, width = x.shape
    d = width // heads
    rows = chunk or S
    ts = rows_per_step(rows, width)
    if not in_envelope(d, width, rows) or S % rows:
        raise ValueError(
            f"{heads} heads of width {d} over {S} positions"
            + (f" in chunks of {chunk}" if chunk else "")
            + ": the kernel takes 128-wide heads and steps of at least "
            f"{_MIN_ROWS} rows that divide the chunk or the sequence")
    rotate = cos is not None
    steps = rows // ts                                  # of a chunk
    in_specs = [pl.BlockSpec((1, ts, width), lambda b, t: (b, t, 0)),
                pl.BlockSpec((1, d), lambda b, t: (0, 0))]
    operands = [x, weight.astype(jnp.float32).reshape(1, d)]
    if rotate:
        in_specs += [pl.BlockSpec((ts, d), lambda b, t: (t, 0))] * 2
        operands += [cos, sin]
    if chunk:
        out_shape = (S // chunk, B, heads, chunk, d)
        out_spec = pl.BlockSpec(
            (1, 1, heads, ts, d),
            lambda b, t: (t // steps, b, 0, t % steps, 0))
    else:
        out_shape = x.shape
        out_spec = pl.BlockSpec((1, ts, width), lambda b, t: (b, t, 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, heads=heads, d=d, eps=eps, scale=scale, rotate=rotate,
            chunked=bool(chunk)),
        grid=(B, S // ts),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="qk_norm_rope",
        interpret=interpret,
    )(*operands)
