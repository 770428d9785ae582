"""Pallas TPU kernel: the state pass of one chunk step of power retention.

``ops/retention.py`` carries, per key/value head, a float32 state ``S``
of ``phi_width(d)`` feature rows by ``d`` value columns. A chunk step
reads it (``phi(q)^T S / d`` for every query position of the chunk)
and updates it (``keep * S + phi(k) (v * left)``). In plain XLA the
features ``phi(q)`` — as wide as the state and ``R * C`` positions long
— cross HBM on their way into the product. Here one kernel walks the
state tile by tile: each tile is loaded once, the matching rows of
``phi(q)`` and ``phi(k)`` are formed in registers from the ``(d, R*C)``
and ``(d, C)`` tiles of q and k the kernel holds, the read is added to a
float32 accumulator, the tile is updated and written back in place.
Nothing as wide as the state but the state itself touches HBM.

The features are those of ``retention._phi_blocks`` (the upper block
triangle of ``u u^T`` in 16-coordinate blocks, off-diagonal blocks
doubled on the query side), products taken in float32 and rounded to
bfloat16 once, products on the matrix unit accumulated in float32. Only
their order differs: a tile holds ``_PAIRS_PER_TILE`` pairs of
coordinate blocks ``(ib <= jb)``, each pair its 16 x 16 products, so
every tile has the same height whatever the pair. The state in this
order is private to the scan that carries it. q and k arrive positions
first, as the rest of the step uses them; the kernel transposes them
once per key/value head.

Forward only: ``retention.power_retention`` takes this pass when its
caller does not differentiate, and autodiff goes through the
``jax.numpy`` step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: coordinates in a block of ``phi`` (``retention._PHI_BLOCK``)
_BLOCK = 16
#: feature rows of one pair of blocks: their 16 x 16 products
_ROWS = _BLOCK * _BLOCK
#: block pairs a grid step takes, unrolled in the kernel (PERF.md §6,
#: PR 28, has the sweep): a 1.1 MiB tile of the float32 state at d = 128
_PAIRS_PER_TILE = 9
#: what the kernel's blocks and scratch may take of VMEM, double buffers
#: included: the session cell's shape (d = 128, R * C = 1280: 7.4 MiB)
#: is the largest the chip has built, well inside Mosaic's 16 MiB
_VMEM_BUDGET = 8 << 20


def block_pairs(d: int) -> np.ndarray:
    """(2, pairs) int32: the coordinate blocks ``ib <= jb`` of each pair,
    in the order the fused state keeps their features."""
    nb = d // _BLOCK
    return np.array([(ib, jb) for ib in range(nb) for jb in range(ib, nb)],
                    np.int32).T


def vmem_bytes(d: int, rc: int, c: int) -> int:
    """VMEM the kernel's blocks and scratch take at head width ``d``,
    ``rc`` query positions and ``c`` key positions a step."""
    tile = _PAIRS_PER_TILE * _ROWS
    blocks = (2 * tile * d * 4                  # the state tile, in and out
              + (rc + 2 * c) * d * 2            # q, k, v * left
              + rc * d * 4)                     # the read's accumulator
    return 2 * blocks + d * (rc + c) * 4        # q^T and k^T in float32


def in_envelope(d: int, rc: int, c: int) -> bool:
    """The shapes the kernel is built for: the head width whose pairs
    fill whole tiles (128, the one anything runs: 36 pairs), a chunk in
    whole lane tiles, blocks inside ``_VMEM_BUDGET``. ``chip_smoke.py``
    compiles and runs both ends on the chip."""
    return (d == 128 and c % 128 == 0 and rc % c == 0
            and vmem_bytes(d, rc, c) <= _VMEM_BUDGET)


def _features(a: jax.Array, b: jax.Array) -> jax.Array:
    """(16, n) and (16, n) float32 -> (256, n) bfloat16: row ``16 i + j``
    is ``a[i] * b[j]``, rounded once."""
    return jnp.concatenate(
        [(a[i:i + 1, :] * b).astype(jnp.bfloat16) for i in range(_BLOCK)],
        axis=0)


def _kernel(pairs_ref, keep_ref, q_ref, k_ref, vl_ref, s_ref,
            num_ref, s_out_ref, qt_ref, kt_ref):
    """Straight-line code on purpose: a loop over pairs in the kernel
    runs each pair's products, transposes and matmul one after the
    other (2.8x the time, PERF.md §6); unrolled, the compiler overlaps
    them."""
    t = pl.program_id(1)
    f32, bf16 = jnp.float32, jnp.bfloat16
    scale = 1.0 / qt_ref.shape[0]                       # retention's 1 / d

    @pl.when(t == 0)
    def _():
        qt_ref[...] = q_ref[0].astype(f32).T            # (d, rc)
        kt_ref[...] = k_ref[0].astype(f32).T            # (d, c)
        num_ref[...] = jnp.zeros_like(num_ref)

    pairs = []
    for p in range(_PAIRS_PER_TILE):
        ib = pairs_ref[0, t * _PAIRS_PER_TILE + p]
        jb = pairs_ref[1, t * _PAIRS_PER_TILE + p]
        # exact in float32 (powers of two), as retention._coef applies it
        pairs.append((pl.multiple_of(ib * _BLOCK, _BLOCK),
                      pl.multiple_of(jb * _BLOCK, _BLOCK),
                      jnp.where(ib == jb, scale, 2.0 * scale),
                      slice(p * _ROWS, (p + 1) * _ROWS)))

    # the read, a chunk's worth of positions at a time: the accumulator
    # stays in registers across the tile's pairs
    rc, c = qt_ref.shape[1], kt_ref.shape[1]
    state16 = [s_ref[0, rows, :].astype(bf16) for *_, rows in pairs]
    for r in range(rc // c):
        at = slice(r * c, (r + 1) * c)
        acc = None
        for (i0, j0, coef, _), tile16 in zip(pairs, state16):
            phi_q = _features(qt_ref[pl.ds(i0, _BLOCK), at],
                              qt_ref[pl.ds(j0, _BLOCK), at] * coef)
            part = jax.lax.dot_general(
                phi_q, tile16, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)             # (c, d)
            acc = part if acc is None else acc + part
        num_ref[0, at, :] += acc

    keep = keep_ref[pl.program_id(0)]
    for i0, j0, _, rows in pairs:
        phi_k = _features(kt_ref[pl.ds(i0, _BLOCK), :],
                          kt_ref[pl.ds(j0, _BLOCK), :])
        s_out_ref[0, rows, :] = keep * s_ref[0, rows, :] + jnp.dot(
            phi_k, vl_ref[0], preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_pass(q: jax.Array, k: jax.Array, vl: jax.Array, keep: jax.Array,
               state: jax.Array, *, interpret: bool):
    """One chunk step's read and update of the state, for ``N = B * G``
    key/value heads.

    ``q`` (N, R*C, d) and ``k`` (N, C, d) bfloat16; ``vl`` (N, C, d)
    bfloat16, the values times the decay to the chunk's end; ``keep``
    (N,) float32, the decay over the whole chunk; ``state`` (N, F, d)
    float32 in :func:`block_pairs` order. Returns ``phi(q)^T state / d``
    (N, R*C, d) float32 and the new state, written over the old.

    Jitted so that the layers of a stack share one trace and one Mosaic
    lowering of the unrolled kernel: each costs half a second of host
    time at every process start, compile cache or not (the cache's key
    is the lowered program)."""
    n, rc, d = q.shape
    c = k.shape[1]
    pairs = block_pairs(d)
    tile = _PAIRS_PER_TILE * _ROWS
    tiles = pairs.shape[1] // _PAIRS_PER_TILE
    if tiles * tile != state.shape[1] or rc % c:
        raise ValueError(
            f"a state of {state.shape[1]} features at head width {d}, {rc} "
            f"query positions over chunks of {c}: the kernel walks {tiles} "
            f"tiles of {tile} features and whole chunks of positions")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rc, d), lambda b, t, _: (b, 0, 0)),
            pl.BlockSpec((1, c, d), lambda b, t, _: (b, 0, 0)),
            pl.BlockSpec((1, c, d), lambda b, t, _: (b, 0, 0)),
            pl.BlockSpec((1, tile, d), lambda b, t, _: (b, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rc, d), lambda b, t, _: (b, 0, 0)),
            pl.BlockSpec((1, tile, d), lambda b, t, _: (b, t, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, rc), jnp.float32),
            pltpu.VMEM((d, c), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, rc, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count the scalar-prefetch table: the state is the 6th
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="retention_state_pass",
        interpret=interpret,
    )(jnp.asarray(pairs), keep, q, k, vl, state)
