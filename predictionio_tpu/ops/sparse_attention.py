"""Causal attention in which every query position keeps its own key
blocks (InfLLM-V2 as MiniCPM4 and MiniCPM-SALA publish it,
arXiv:2506.07900, arXiv:2509.24663), over grouped key/value heads.

Two stages a layer, for H query heads over G key/value heads (R = H / G
heads a group), head width d, and the sizes ``sz`` (``kernel_size`` 32,
``kernel_stride`` 16, ``block_size`` 64, ``topk`` 64, ``init_blocks`` 1,
``window_size`` 2048 as published for the family):

1. **Selection.** Compressed keys are means of ``kernel_size`` keys
   every ``kernel_stride`` (no weights). Position t and head a score
   them by ``softmax_j(q[t, a] . kc[j, g] / sqrt(d))`` over the windows
   that end at or before t; the group's heads' probabilities are summed;
   key block b (``block_size`` keys) scores the maximum over the
   compressed keys j = ratio b - 1 .. ratio b + ratio - 1 (ratio =
   block / stride: a max-pool of width ratio + 1, stride ratio, padding
   1). The first ``init_blocks`` blocks and the ``window_size /
   block_size`` blocks ending at t's own score +inf, blocks after t's
   own are never taken, and the ``topk`` highest are kept (all visible
   ones where fewer are visible). Scores are float32 from bfloat16
   operands; ties go to the lower block.
2. **Attention** over the keys i <= t of the kept blocks, shared by the
   group's heads. An unselected key contributes nothing.

Everything works on the projections' layouts: q (B, S, H x d) already
normed and scaled by 1/sqrt(d), k and v (B, S, G x d).

Two paths, one rule (:func:`uses_kernel`: static shape and backend, no
option, flag or environment variable):

- the **kernels** (forward only; a serving program on a compiled TPU
  backend). ``sparse_block_selection`` scores a tile of positions of
  one group against all of the group's compressed keys in VMEM (laid
  out pool-slab-major by :func:`_slab_keys`, so the pool is a maximum
  of five lane-aligned slices) and writes block scores only: the
  (H, S, S / stride) probabilities never exist. ``sparse_block_
  attention`` holds one group's K and V whole in VMEM (16.8 MB at
  S = 32,768) and, for a tile of ``tile_q`` positions x R heads as its
  rows, loops over key tiles of ``tile_k`` keys: a tile is **visited**
  only where the visit map (one bit a (query tile, key tile), worked on
  the device from the selection and handed over in scalar memory) says
  some position of the query tile kept some block of the key tile, and
  inside a visited tile the positions' own blocks are a mask (the
  selection's 0/1 rows times a constant expansion, one small product).
  So the result is exactly stage 2's, and how much unselected work is
  skipped is what the data allow at the tile size: with selections that
  differ from position to position the union over a query tile is
  nearly every visible block (PERF.md section 6, PR 34, has the table).
  :func:`visit_counts` counts the keys scored from that same map.
  Without a selection (S <= ``dense_len``) the same kernel is plain
  causal attention.
- the **plain** forms (``jax.numpy``: differentiable, any backend, any
  S): the same equations in query tiles under ``lax.map``.

A sliding-window layer (Laguna's, ``models/laguna.py``) has its own
core, :func:`attend_window`: position t sees keys ``t - W < i <= t``.
Its kernel ``window_attention`` holds a group's K and V resident as
``sparse_block_attention`` does, and scores a query tile's whole band in
one pass: the band is the most tiles of ``TILE_W`` keys any query tile's
window reaches (:func:`window_band`: 640 keys for a query tile of 128
under a window of 512), one product gives its scores, the window is a
mask over the band, and one exact softmax and one product with the
band's values follow; no running maximum, no loop. The rule is
:func:`uses_window_kernel`; the plain form is ``_attend_plain`` with the
window as a mask.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_attention

_NEG = -1e30
#: positions a step of the attention kernel works (x R heads = its rows)
#: and keys a visit covers; PERF.md section 6 (PR 34) has the sweep
TILE_Q = 128
TILE_K = 1024
#: the windowed kernel's band is whole tiles of this many keys: a query
#: tile of 128 under a window of 512 reaches 5 of them, 640 keys a
#: position
TILE_W = 128
#: positions a step of the selection kernel scores
TILE_S = 64
#: positions a step of the plain forms works
_PLAIN_TILE = 256
#: one group's K and V stay whole in VMEM, double-buffered by the
#: pipeline: 4 x S x d x 2 bytes of a v5e core's 128 MiB
_VMEM_LIMIT = 100 << 20
_MAX_RESIDENT_BYTES = 48 << 20
_LANES = 128


@dataclasses.dataclass(frozen=True)
class SparseSizes:
    """``sparse_config`` as the MiniCPM4 family publishes it."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    @property
    def ratio(self) -> int:
        return self.block_size // self.kernel_stride


def n_blocks(seq_len: int, sz) -> int:
    return -(-seq_len // sz.block_size)


def n_compressed(seq_len: int, sz) -> int:
    return max((seq_len - sz.kernel_size) // sz.kernel_stride + 1, 0)


def selects(seq_len: int, sz) -> bool:
    """Whether positions choose their blocks at this length: above
    ``dense_len``; at or under it the layer is plain causal attention."""
    return seq_len > sz.dense_len


def compressed_keys(k: jax.Array, sz) -> jax.Array:
    """(B, S, G, d) -> (B, Nc, G, d) float32: the mean of every
    ``kernel_size`` keys, every ``kernel_stride``."""
    B, S, G, d = k.shape
    s, m = sz.kernel_stride, sz.kernel_size // sz.kernel_stride
    if sz.kernel_size % s:
        raise ValueError("kernel_size must be whole strides")
    nc = n_compressed(S, sz)
    if nc == 0:
        return jnp.zeros((B, 0, G, d), jnp.float32)
    strides = k[:, :(nc + m - 1) * s].astype(jnp.float32) \
        .reshape(B, nc + m - 1, s, G, d).mean(axis=2)
    return sum(strides[:, i:i + nc] for i in range(m)) / m


# -- selection: the plain form ------------------------------------------------


def _pool(P: jax.Array, nb: int, ratio: int) -> jax.Array:
    """(..., Nc) summed probabilities (>= 0) -> (..., nb) block scores:
    the maximum over j = ratio b - 1 .. ratio b + ratio - 1 that exist
    (0 where none does: such a block is forced or not yet visible)."""
    nc = P.shape[-1]
    padded = jnp.pad(P, [(0, 0)] * (P.ndim - 1) + [(1, nb * ratio - nc)])
    first = padded[..., :nb * ratio].reshape(*P.shape[:-1], nb, ratio)
    return jnp.maximum(first.max(axis=-1), padded[..., ratio::ratio])


def block_scores(q, kc, positions, sz, nb: int) -> jax.Array:
    """Steps 2 to 4 for some positions: q (B, T, G, R, d) scaled, kc
    (B, Nc, G, d), positions (T,) -> (B, G, T, nb) float32."""
    f32 = jnp.float32
    s = jnp.einsum("btgrd,bjgd->bgrtj", q, kc.astype(q.dtype),
                   preferred_element_type=f32)
    ends = sz.kernel_stride * jnp.arange(kc.shape[1]) + sz.kernel_size - 1
    seen = ends[None, :] <= positions[:, None]                  # (T, Nc)
    s = jnp.where(seen, s, _NEG)
    p = jnp.where(seen, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return _pool(p.sum(axis=2), nb, sz.ratio)                   # (B, G, T, nb)


def select_blocks(scores: jax.Array, positions: jax.Array, sz) -> jax.Array:
    """Steps 5 and 6: (B, G, T, nb) block scores -> the kept blocks as a
    bool mask of that shape. Forced blocks count among the ``topk``."""
    nb = scores.shape[-1]
    blocks = jnp.arange(nb)
    own = (positions // sz.block_size)[:, None]                 # (T, 1)
    local = sz.window_size // sz.block_size
    forced = (blocks < sz.init_blocks) | (blocks > own - local)
    visible = blocks <= own
    scores = jnp.where(forced, jnp.inf, scores)
    scores = jnp.where(visible, scores, -jnp.inf)
    _, ids = lax.top_k(scores, min(sz.topk, nb))
    kept = jnp.any(ids[..., None] == blocks, axis=-2)
    return kept & visible


def _query_tiles(q):
    """(B, S, G, R, d) -> ((n, B, tile, G, R, d), positions (n, tile),
    padded length): the plain forms' query tiles, the last one padded."""
    B, S, G, R, d = q.shape
    tile = min(_PLAIN_TILE, S)
    pad = (-S) % tile
    qt = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3) \
        .reshape(B, (S + pad) // tile, tile, G, R, d).swapaxes(0, 1)
    return qt, jnp.arange(S + pad).reshape(-1, tile), S + pad


def _selection_plain(q, k, sz):
    B, S, G, _, _ = q.shape
    nb = n_blocks(S, sz)
    kc = compressed_keys(k, sz)
    qt, pos, padded = _query_tiles(q)

    def one(args):
        qi, pi = args
        return select_blocks(block_scores(qi, kc, pi, sz, nb), pi, sz)

    kept = lax.map(one, (qt, pos))                  # (n, B, G, tile, nb)
    return kept.transpose(1, 2, 0, 3, 4).reshape(B, G, padded, nb)[:, :, :S]


# -- attention: the plain form ------------------------------------------------


def _attend_plain(q, k, v, kept, sz, window: int = 0):
    """q (B, S, G, R, d) scaled, k and v (B, S, G, d), ``kept`` (B, G,
    S, nb) or None -> (B, S, G, R, d) float32. ``window`` > 0: a
    position sees its own and the ``window - 1`` keys before it."""
    B, S, G, R, d = q.shape
    qt, pos, padded = _query_tiles(q)
    n, tile = pos.shape
    keys = jnp.arange(S)
    xs = (qt, pos)
    if kept is not None:
        xs += (jnp.pad(kept, ((0, 0), (0, 0), (0, padded - S), (0, 0)))
               .reshape(B, G, n, tile, -1).transpose(2, 0, 1, 3, 4),)

    def one(args):
        qi, pi, *ki = args
        s = jnp.einsum("btgrd,bsgd->bgrts", qi, k.astype(qi.dtype),
                       preferred_element_type=jnp.float32)
        ok = keys[None, :] <= pi[:, None]                       # (tile, S)
        if window:
            ok = ok & (keys[None, :] > pi[:, None] - window)
        if ki:
            ok = ok & jnp.repeat(ki[0], sz.block_size, axis=-1)[..., :S] \
                [:, :, None]
        p = jax.nn.softmax(jnp.where(ok, s, _NEG), axis=-1)
        return jnp.einsum("bgrts,bsgd->btgrd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    out = lax.map(one, xs)                          # (n, B, tile, G, R, d)
    return out.swapaxes(0, 1).reshape(B, padded, G, R, d)[:, :S]


# -- the kernels --------------------------------------------------------------


def _stack_heads(q_ref, q_sc, tq: int, R: int, d: int):
    """(tq, R x d) -> rows head-major, (R x tq, d)."""
    for j in range(R):
        q_sc[j * tq:(j + 1) * tq, :] = q_ref[:, j * d:(j + 1) * d]


def _slab_keys(kc: jax.Array, nbp: int, ratio: int):
    """(B, Nc, G, d) -> ((B, G, (ratio + 1) x nbp, d) bfloat16, (8,
    (ratio + 1) x nbp) int32): the compressed keys slab-major. Slab r =
    -1, 0 .. ratio - 1 holds, at column b, compressed key j = ratio b +
    r, so that a block's pool is the maximum over the slabs at its own
    column: lane-aligned slices, no strided access. Slab -1 repeats keys
    of slab ratio - 1 one block on. The second array's first row is j at
    each column, -1 where no such key exists."""
    B, nc, G, d = kc.shape
    j = (ratio * np.arange(nbp)[None, :]
         + np.arange(-1, ratio)[:, None]).reshape(-1)
    exists = (j >= 0) & (j < nc)
    slabs = jnp.where(exists[None, :, None, None],
                      kc[:, np.where(exists, j, 0)], 0.0)
    jcol = np.broadcast_to(np.where(exists, j, -1).astype(np.int32),
                           (8, len(j)))
    return slabs.transpose(0, 2, 1, 3).astype(jnp.bfloat16), jnp.asarray(jcol)


def _selection_kernel(q_ref, kc_ref, j_ref, o_ref, q_sc, *, tq, R, d, nbp,
                      slabs, stride, ksize):
    """Grid (batch, group, query tiles). q (tq, R x d), kc (slabs x nbp,
    d), j (8, slabs x nbp), out (tq, nbp): block scores before the
    forced blocks are set."""
    qi = pl.program_id(2)
    _stack_heads(q_ref, q_sc, tq, R, d)
    width = slabs * nbp
    s = lax.dot_general(q_sc[...], kc_ref[...], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s.reshape(R, tq, width)
    j = j_ref[0:1, :]                                           # (1, width)
    t = qi * tq + lax.broadcasted_iota(jnp.int32, (tq, width), 0)
    seen = jnp.logical_and(j >= 0, stride * j + (ksize - 1) <= t)
    # every compressed key once: the slabs after the first
    once = lax.broadcasted_iota(jnp.int32, (tq, width), 1) >= nbp
    top = jnp.max(jnp.where(jnp.logical_and(seen, once)[None], s, _NEG),
                  axis=-1, keepdims=True)
    p = jnp.where(seen[None], jnp.exp(jnp.minimum(s - top, 0.0)), 0.0)
    den = jnp.sum(jnp.where(once[None], p, 0.0), axis=-1, keepdims=True)
    P = jnp.sum(p / jnp.maximum(den, 1e-30), axis=0)            # (tq, width)
    out = P[:, :nbp]
    for r in range(1, slabs):
        out = jnp.maximum(out, P[:, r * nbp:(r + 1) * nbp])
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("sz", "groups", "interpret",
                                             "tile"))
def selection_scores(q, k, *, sz, groups: int, interpret: bool = False,
                     tile: int = TILE_S):
    """Kernel form of steps 1 to 4: q (B, S, H x d) scaled bfloat16, k
    (B, S, G x d) -> (B, G, S, nb) float32 block scores."""
    B, S, hd = q.shape
    G = groups
    d = k.shape[-1] // G
    R = hd // (G * d)
    nb = n_blocks(S, sz)
    nbp = -(-nb // _LANES) * _LANES
    slabs = sz.ratio + 1
    kc, jcol = _slab_keys(compressed_keys(k.reshape(B, S, G, d), sz), nbp,
                          sz.ratio)
    tq = min(tile, S)
    out = pl.pallas_call(
        functools.partial(_selection_kernel, tq=tq, R=R, d=d, nbp=nbp,
                          slabs=slabs, stride=sz.kernel_stride,
                          ksize=sz.kernel_size),
        grid=(B, G, S // tq),
        in_specs=[pl.BlockSpec((None, tq, R * d), lambda b, g, i: (b, i, g)),
                  pl.BlockSpec((None, None, slabs * nbp, d),
                               lambda b, g, i: (b, g, 0, 0)),
                  pl.BlockSpec((8, slabs * nbp), lambda b, g, i: (0, 0))],
        out_specs=pl.BlockSpec((None, None, tq, nbp),
                               lambda b, g, i: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, S, nbp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((R * tq, d), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="sparse_block_selection",
        interpret=interpret,
    )(q, kc, jcol)
    return out[..., :nb]


def _attention_kernel(bits_ref, q_ref, k_ref, v_ref, *rest, tq, tk, R, d,
                      words, per_lane, masked):
    """Grid (batch, group, query tiles). ``bits``: the visit map, one
    bit a key tile, ``words`` int32 a (batch, group, query tile). q (tq,
    R x d) scaled; k, v (S, d) whole. With a selection: sel (nbp / 128,
    tq, 128), the positions' kept blocks as 0/1, and expand (per_lane,
    128, tk), which spreads the blocks of the key tile at each of its
    ``per_lane`` places in a lane group over the tile's keys."""
    if masked:
        sel_ref, e_ref, o_ref, q_sc, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, q_sc, m_sc, l_sc, acc_sc = rest
    b, g, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row = ((b * pl.num_programs(1) + g) * pl.num_programs(2) + qi) * words
    _stack_heads(q_ref, q_sc, tq, R, d)
    m_sc[...] = jnp.full_like(m_sc, _NEG)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    q_pos = qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    col = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)

    def visit(kt, carry):
        word = bits_ref[row + kt // 32]

        @pl.when(jnp.bitwise_and(jnp.right_shift(word, kt % 32), 1) == 1)
        def _scored():
            start = pl.multiple_of(kt * tk, tk)
            keys = k_ref[pl.ds(start, tk), :]
            s = lax.dot_general(q_sc[...], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            ok = q_pos >= kt * tk + col
            if masked:
                kept = lax.dot_general(
                    sel_ref[kt // per_lane], e_ref[kt % per_lane],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ok = jnp.logical_and(ok, kept > 0.5)
            bias = jnp.where(ok, 0.0, _NEG)
            s = (s.reshape(R, tq, tk) + bias[None]).reshape(R * tq, tk)
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[pl.ds(start, tk), :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_sc[...] = m_new

        return carry

    # key 0 is seen by every position (block 0 is forced, or no selection
    # at all), and key tile 0 is visited first: every row's maximum is a
    # real score before a tile masks the row whole, so such a tile adds 0
    lax.fori_loop(0, (qi * tq + tq - 1) // tk + 1, visit, 0)
    for j in range(R):
        rows = slice(j * tq, (j + 1) * tq)
        o_ref[:, j * d:(j + 1) * d] = (acc_sc[rows, :] / l_sc[rows, :]) \
            .astype(o_ref.dtype)


def _expansion(tk: int, block: int) -> np.ndarray:
    """(per_lane, 128, tk) 0/1: a key tile holds ``tk / block`` blocks,
    which sit at one of ``per_lane = 128 block / tk`` places in their
    group of 128 block lanes; slice p spreads place p's lanes over the
    tile's keys."""
    per_tile = tk // block
    per_lane = _LANES // per_tile
    lane = np.arange(_LANES)[None, :, None]
    key = np.arange(tk)[None, None, :]
    place = np.arange(per_lane)[:, None, None]
    return (lane == place * per_tile + key // block).astype(np.float32)


def visit_map(kept, seq_len: int, tq: int, tk: int, block: int):
    """(B, G, S / tq, S / tk) bool: the key tiles each query tile
    visits. With a selection, those holding a block some position of the
    query tile kept; without, those at or before the diagonal."""
    nq, nk = seq_len // tq, seq_len // tk
    if kept is None:
        last = (np.arange(nq) * tq + tq - 1) // tk
        return jnp.asarray(np.arange(nk)[None, :] <= last[:, None])[None, None]
    B, G, S, nb = kept.shape
    per_tile = tk // block
    kept = jnp.pad(kept, ((0, 0),) * 3 + ((0, nk * per_tile - nb),))
    return kept.reshape(B, G, nq, tq, nk, per_tile).any(axis=(3, 5))


def _pack_bits(visits: jax.Array) -> jax.Array:
    """(..., nk) bool -> (..., ceil(nk / 32)) int32, bit kt % 32 of word
    kt // 32."""
    nk = visits.shape[-1]
    words = -(-nk // 32)
    v = jnp.pad(visits, [(0, 0)] * (visits.ndim - 1) + [(0, words * 32 - nk)])
    v = v.reshape(*visits.shape[:-1], words, 32).astype(jnp.uint32)
    packed = jnp.sum(v << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return lax.bitcast_convert_type(packed, jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "groups", "block", "interpret", "tile_q", "tile_k"))
def attention(q, k, v, kept, visits, *, groups: int, block: int,
              interpret: bool = False, tile_q: int = TILE_Q,
              tile_k: int = TILE_K):
    """Kernel form of stage 2: q (B, S, H x d) scaled, k and v (B, S, G
    x d), ``kept`` (B, G, S, nb) bool or None, ``visits`` from
    :func:`visit_map` at the same tiles -> (B, S, H x d) in ``v.dtype``."""
    B, S, hd = q.shape
    G = groups
    d = k.shape[-1] // G
    R = hd // (G * d)
    tq, tk = min(tile_q, S), min(tile_k, S)
    nq, nk = S // tq, S // tk
    words = -(-nk // 32)
    bits = _pack_bits(jnp.broadcast_to(visits, (B, G, nq, nk))).reshape(-1)
    masked = kept is not None
    per_lane = _LANES // (tk // block) if masked else 1

    def q_map(b, g, i, bits):
        return b, i, g

    def kv_map(b, g, i, bits):
        return b, 0, g

    in_specs = [pl.BlockSpec((None, tq, R * d), q_map),
                pl.BlockSpec((None, S, d), kv_map),
                pl.BlockSpec((None, S, d), kv_map)]
    operands = [q, k, v]
    if masked:
        nb = kept.shape[-1]
        groups_of_lanes = -(-nb // _LANES)
        sel = jnp.pad(kept, ((0, 0),) * 3 + ((0, groups_of_lanes * _LANES
                                              - nb),)) \
            .astype(jnp.bfloat16) \
            .reshape(B, G, nq, tq, groups_of_lanes, _LANES) \
            .transpose(0, 1, 2, 4, 3, 5)
        in_specs += [
            pl.BlockSpec((None, None, None, groups_of_lanes, tq, _LANES),
                         lambda b, g, i, bits: (b, g, i, 0, 0, 0)),
            pl.BlockSpec((per_lane, _LANES, tk),
                         lambda b, g, i, bits: (0, 0, 0))]
        operands += [sel, jnp.asarray(_expansion(tk, block), jnp.bfloat16)]
    return pl.pallas_call(
        functools.partial(_attention_kernel, tq=tq, tk=tk, R=R, d=d,
                          words=words, per_lane=per_lane, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G, nq), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tq, R * d), q_map),
            scratch_shapes=[pltpu.VMEM((R * tq, d), q.dtype),
                            pltpu.VMEM((R * tq, 1), jnp.float32),
                            pltpu.VMEM((R * tq, 1), jnp.float32),
                            pltpu.VMEM((R * tq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, S, hd), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="sparse_block_attention",
        interpret=interpret,
    )(bits, *operands)


def window_tiles(qi, tq: int, tk: int, window: int):
    """(first, last) key tile a query tile ``qi`` of ``tq`` positions
    reaches under a window of ``window`` keys (its own and the ``window
    - 1`` before it). Works on Python ints, numpy arrays and traced
    scalars alike."""
    lo = qi * tq - (window - 1)
    first = (lo + abs(lo)) // 2 // tk                   # max(lo, 0) // tk
    return first, (qi * tq + tq - 1) // tk


def window_band(seq_len: int, tq: int, tk: int, window: int) -> int:
    """The keys the windowed kernel scores for each query tile, static:
    the most key tiles any query tile's window reaches
    (:func:`window_tiles`) times ``tk``. A query tile's band starts at
    ``min(first x tk, seq_len - band)``: at the first tile its window
    reaches, pulled back where it would run past the history."""
    first, last = window_tiles(np.arange(seq_len // tq), tq, tk, window)
    return int((last - first + 1).max()) * tk


def _window_kernel(q_ref, k_ref, v_ref, o_ref, q_sc, *, tq, tk, R, d,
                   window, band):
    """Grid (batch, group, query tiles). q (tq, R x d) scaled; k, v (S,
    d) whole. Scores the query tile's band of ``band`` keys
    (:func:`window_band`) in one product, masks ``t - window < i <= t``
    over the whole band, and takes one exact softmax and one product
    with the band's values: every row sees its own position, so its
    maximum is a real score. (A mask on the band's first and last tiles
    alone, where it cuts past the first window, was 11% slower on a
    v5e: cutting the scores apart and joining them costs more than the
    mask; PERF.md section 6.)"""
    qi = pl.program_id(2)
    _stack_heads(q_ref, q_sc, tq, R, d)
    first, _ = window_tiles(qi, tq, tk, window)
    start = pl.multiple_of(jnp.minimum(first * tk, k_ref.shape[0] - band), tk)
    s = lax.dot_general(q_sc[...], k_ref[pl.ds(start, band), :],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    back = qi * tq + lax.broadcasted_iota(jnp.int32, (tq, band), 0) \
        - (start + lax.broadcasted_iota(jnp.int32, (tq, band), 1))
    ok = jnp.logical_and(back >= 0, back < window)
    s = jnp.where(ok[None], s.reshape(R, tq, band), _NEG) \
        .reshape(R * tq, band)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    out = lax.dot_general(
        p.astype(v_ref.dtype), v_ref[pl.ds(start, band), :],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) \
        / jnp.sum(p, axis=-1, keepdims=True)
    for j in range(R):
        o_ref[:, j * d:(j + 1) * d] = \
            out[j * tq:(j + 1) * tq].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "groups", "window", "interpret", "tile_q", "tile_k"))
def window_attention(q, k, v, *, groups: int, window: int,
                     interpret: bool = False, tile_q: int = TILE_Q,
                     tile_k: int = TILE_W):
    """Causal attention of each position over its own and the ``window
    - 1`` keys before it: q (B, S, H x d) scaled, k and v (B, S, G x d)
    -> (B, S, H x d) in ``v.dtype``. One group's K and V whole in VMEM,
    as in :func:`attention`; a query tile reads its band of them."""
    B, S, hd = q.shape
    G = groups
    d = k.shape[-1] // G
    R = hd // (G * d)
    tq, tk = min(tile_q, S), min(tile_k, S)
    band = window_band(S, tq, tk, window)

    def q_map(b, g, i):
        return b, i, g

    def kv_map(b, g, i):
        return b, 0, g

    return pl.pallas_call(
        functools.partial(_window_kernel, tq=tq, tk=tk, R=R, d=d,
                          window=window, band=band),
        grid=(B, G, S // tq),
        in_specs=[pl.BlockSpec((None, tq, R * d), q_map),
                  pl.BlockSpec((None, S, d), kv_map),
                  pl.BlockSpec((None, S, d), kv_map)],
        out_specs=pl.BlockSpec((None, tq, R * d), q_map),
        out_shape=jax.ShapeDtypeStruct((B, S, hd), v.dtype),
        scratch_shapes=[pltpu.VMEM((R * tq, d), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="window_attention",
        interpret=interpret,
    )(q, k, v)


# -- the layer's core ---------------------------------------------------------


def uses_kernel(seq_len: int, inference: bool, sz, d: int = 128,
                heads_per_group: int = 16) -> bool:
    """Whether :func:`attend` runs the kernels: a serving program
    (``inference``: they have no backward pass) on a compiled TPU
    backend, whole tiles, lane-wide heads, one group's K and V inside
    the VMEM envelope, and a first block that every position keeps
    (the kernel's running maximum starts there)."""
    tq, tk = min(TILE_Q, seq_len), min(TILE_K, seq_len)
    return (inference and pallas_attention._mode() == "compiled"
            and seq_len >= TILE_K and seq_len % tq == 0 and seq_len % tk == 0
            and seq_len % min(TILE_S, seq_len) == 0
            and d % _LANES == 0 and tq % 16 == 0
            and tk % sz.block_size == 0
            and _LANES % (tk // sz.block_size) == 0
            and 8 * seq_len * d <= _MAX_RESIDENT_BYTES
            and sz.init_blocks >= 1)


def kernel_names(seq_len: int, sz) -> tuple:
    """The kernels a serving program over ``seq_len`` positions engages
    in one sparse layer, where :func:`uses_kernel` holds."""
    return (("sparse_block_selection",) if selects(seq_len, sz) else ()) \
        + ("sparse_block_attention",)


def visit_counts(kept, scored, valid):
    """int32 (3,): over the positions ``valid`` (B, S) of a selecting
    layer — rows that selected (position x group), blocks they kept, and
    the key blocks whose scores stage 2 computed for them: ``scored``
    (B | 1, G | 1, S), whole blocks a position and group, is what the
    caller read off the visit map it gave the kernel (keys = blocks x
    ``block_size``; counted in blocks so that int32 holds a program)."""
    B, G, S, _ = kept.shape
    rows = jnp.sum(valid) * G
    blocks = jnp.sum(kept & valid[:, None, :, None])
    keys = jnp.sum(jnp.broadcast_to(scored, (B, G, S)) * valid[:, None, :])
    return jnp.stack([rows, blocks, keys]).astype(jnp.int32)


def kept_ids(kept_row: jax.Array, topk: int) -> jax.Array:
    """(..., nb) bool -> (..., topk) int32: the kept blocks in ascending
    order, -1 after the last."""
    nb = kept_row.shape[-1]
    ids = jnp.sort(jnp.where(kept_row, jnp.arange(nb), nb), axis=-1)
    ids = jnp.pad(ids, [(0, 0)] * (ids.ndim - 1) + [(0, max(topk - nb, 0))],
                  constant_values=nb)[..., :topk]
    return jnp.where(ids < nb, ids, -1).astype(jnp.int32)


def attend(q, k, v, sz, *, groups: int, valid=None, inference: bool = False):
    """One layer's core: q (B, S, H x d) normed and scaled by 1/sqrt(d),
    k (normed) and v (B, S, G x d). Returns (out (B, S, H x d) in
    ``v.dtype``, counts int32 (3,) as :func:`visit_counts` has them —
    zeros where the layer does not select — and the kept blocks (B, G,
    S, nb) bool, or None). ``valid`` (B, S) says which positions the
    counts are over (all, when not given)."""
    B, S, hd = q.shape
    G = groups
    d = k.shape[-1] // G
    R = hd // (G * d)
    valid = jnp.ones((B, S), bool) if valid is None else valid
    kernel = uses_kernel(S, inference, sz, d, R)
    tq, tk = min(TILE_Q, S), min(TILE_K, S)
    counts, kept = jnp.zeros((3,), jnp.int32), None
    heads = (B, S, G, R, d)
    if selects(S, sz):
        with jax.named_scope("sparse_selection"):
            if kernel:
                scores = selection_scores(q, k, sz=sz, groups=G)
                kept = select_blocks(scores, jnp.arange(S), sz)
            else:
                kept = _selection_plain(q.reshape(heads),
                                        k.reshape(B, S, G, d), sz)
    with jax.named_scope("sparse_attention"):
        if kernel:
            visits = visit_map(kept, S, tq, tk, sz.block_size)
            out = attention(q, k, v, kept, visits, groups=G,
                            block=sz.block_size)
            scored = jnp.repeat(visits.sum(axis=-1, dtype=jnp.int32)
                                * (tk // sz.block_size), tq, axis=-1)
        else:
            out = _attend_plain(q.reshape(heads), k.reshape(B, S, G, d),
                                v.reshape(B, S, G, d), kept, sz) \
                .reshape(B, S, hd).astype(v.dtype)
            # every key at or before a position is scored: its own block
            # counts whole
            scored = (jnp.arange(S, dtype=jnp.int32) // sz.block_size
                      + 1)[None, None]
        if kept is not None:
            counts = visit_counts(kept, scored, valid)
    return out, counts, kept


# -- the windowed layer's core ------------------------------------------------


def uses_window_kernel(seq_len: int, inference: bool, d: int = 128,
                       heads_per_group: int = 9) -> bool:
    """Whether :func:`attend_window` runs the kernel ``window_attention``:
    a serving program on a compiled TPU backend, whole tiles, lane-wide
    heads and one group's K and V inside the VMEM envelope."""
    tq, tk = min(TILE_Q, seq_len), min(TILE_W, seq_len)
    return (inference and pallas_attention._mode() == "compiled"
            and seq_len % tq == 0 and seq_len % tk == 0
            and d % _LANES == 0 and tq % 16 == 0 and heads_per_group >= 1
            and 8 * seq_len * d <= _MAX_RESIDENT_BYTES)


def window_scored(seq_len: int, window: int, kernel: bool) -> np.ndarray:
    """(seq_len,) int64: the keys scored for each position of a group.
    The kernel scores its band (:func:`window_band`) at every position;
    the plain form the keys its mask lets through, ``min(t + 1,
    window)``."""
    if not kernel:
        return np.minimum(np.arange(seq_len) + 1, window)
    tq, tk = min(TILE_Q, seq_len), min(TILE_W, seq_len)
    return np.full(seq_len, window_band(seq_len, tq, tk, window))


def attend_window(q, k, v, *, groups: int, window: int, valid=None,
                  inference: bool = False):
    """One sliding layer's core: q (B, S, H x d) normed and scaled by
    1/sqrt(d), k (normed) and v (B, S, G x d); position t sees keys
    ``t - window < i <= t``. Returns (out (B, S, H x d) in ``v.dtype``,
    counts int32 (2,): rows (real position x group) and the keys scored
    for them, read off the band the kernel is given, or what the plain
    form's mask lets through). ``valid`` (B, S) says which
    positions the counts are over (all, when not given)."""
    B, S, hd = q.shape
    G = groups
    d = k.shape[-1] // G
    R = hd // (G * d)
    valid = jnp.ones((B, S), bool) if valid is None else valid
    kernel = uses_window_kernel(S, inference, d, R)
    with jax.named_scope("window_attention"):
        if kernel:
            out = window_attention(q, k, v, groups=G, window=window)
        else:
            out = _attend_plain(q.reshape(B, S, G, R, d),
                                k.reshape(B, S, G, d), v.reshape(B, S, G, d),
                                None, None, window) \
                .reshape(B, S, hd).astype(v.dtype)
        scored = jnp.asarray(window_scored(S, window, kernel), jnp.int32)
        counts = jnp.stack([jnp.sum(valid, dtype=jnp.int32) * G,
                            jnp.sum(jnp.where(valid, scored[None], 0),
                                    dtype=jnp.int32) * G])
    return out, counts
