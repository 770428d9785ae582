"""Gated power retention: linear-cost causal mixing with a carried state.

For query head ``a`` over key/value head ``b = a // (H // G)`` and
positions ``t >= i``::

    w[t, i] = exp(sum_{j=i+1..t} log_g[j, b]) * (q[t, a] . k[i, b] / sqrt(d))**2
    y[t, a] = sum_i w[t, i] v[i, b] / (sum_i w[t, i] + eps)

The degree-2 kernel is an inner product of features:
``(q.k)**2 = phi(q).phi(k)`` with ``phi(u)`` the products ``u_i u_j``, so
the sum over the past folds into a state ``S = sum_i decay * phi(k_i)
v_i^T`` of fixed size and the cost per token does not grow with the
history. The program is chunked: inside a chunk the quadratic form (a
``C x C`` block of weights per head), between chunks the state, carried
by ``lax.scan`` in float32. The normaliser needs no ``phi``:
``sum_i decay * (q.k_i)**2 = q^T (sum_i decay * k_i k_i^T) q``, a
``d x d`` state per key/value head.

Matrix products take bfloat16 operands and accumulate in float32; the
state, the normaliser, the gates' cumulative sums and every weight are
float32. Padding after a history's last event cannot change an earlier
position: the mixing is causal and chunks are scanned in order.

The family's degree-1 member is lightning attention (arXiv:2401.04658),
unnormalised: ``y[t, a] = sum_i decay(i..t) (q[t, a] . k[i, b] /
sqrt(d)) v[i, b]``, no feature map (the state is ``sum_i decay * k_i
v_i^T``, ``d x d`` per key/value head), no normaliser, and a decay that
may be one constant per head (``log_g`` of shape ``(G,)``). It shares
:func:`pick_chunk`, the chunk-major layout, the in-chunk decay matrix and
the ``lax.scan`` carry with degree 2 and nothing else: its state pass is
two small products in ``jax.numpy`` and is not fused (at d = 128 it is
under a fortieth of degree 2's). So the family holds degrees 1 and 2:
degree 2 normalised, its state pass fused on a compiled TPU backend;
degree 1 unnormalised, ``jax.numpy`` throughout.

Two ways through a degree-2 chunk step's state pass (the read ``phi(q)^T S`` and
the update ``keep * S + phi(k) (v * left)``), one set of equations. The
``jax.numpy`` one (:func:`_read_state`, :func:`_phi_keys`) is the
definition: JAX's autodiff differentiates it, so it is the path of
every caller that may take a gradient (training enters with
``inference=False``), of the CPU, and of every shape outside the
kernel's envelope. The fused one (``ops/pallas_retention.py``, forward
only) forms the features in fast memory beside the state tile they
meet, so that nothing as wide as the state but the state crosses HBM;
:func:`fuses_state_pass` says when it is taken, from what the code can
observe and with no option. Everything else of the step — the in-chunk
quadratic form, the normaliser, the gates — and the ``lax.scan`` over
chunks are ``jax.numpy`` on both.

The layouts, and who owns them. Every (S, heads, d) array between a
mixer's projections and its output projection crosses HBM once each
way, in the operands' type. **In**: :func:`chunk_order` defines the
chunk-major layout and :func:`_chunk_major` is its one caller, for both
degrees; given a :class:`WayIn` it also norms, rotates and rounds q and
k in that same pass (``ops/qk_norm``: the fused kernel writes chunk
order itself on a compiled backend, the ``jax.numpy`` form elsewhere),
so no float32 copy of q or k is ever an array. **The scan** stacks what
a step returns: each step rounds its float32 sum once to the caller's
type and lays it as rows of the way out (:func:`_rows`), so the stack is
(chunks, B, C, H * d) in bfloat16 for a bfloat16 caller, half of what a
float32 stack was. **Out**: :func:`_token_major` merges the chunks and
pins the narrow array with a barrier. The scan stays one top-level
``while`` a layer in the compiled program: the benchmark's
``power_retention_*`` and ``lightning_attention_*`` readers time that op
(``benchmarks/layer_metrics``) and read nothing if it is unrolled,
nested or renamed; what they time includes the step's write of its rows.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.ops import pallas_attention, pallas_retention, qk_norm

#: ``phi`` keeps the upper block triangle of ``u u^T`` in blocks of this
#: many coordinates (diagonal blocks whole, the others doubled): at
#: d = 128 that is 9,216 features where the full square has 16,384 and
#: the monomials alone 8,256
_PHI_BLOCK = 16
_CHUNK_MAX = 256


def pick_chunk(seq_len: int) -> int:
    """The chunk length for a sequence: the largest power of two up to
    ``_CHUNK_MAX`` that does not exceed it. A chunk costs ``C`` weights
    per token inside it and one state update per ``C`` tokens."""
    c = 1
    while c * 2 <= min(seq_len, _CHUNK_MAX):
        c *= 2
    return c


def phi_width(d: int) -> int:
    b = _phi_block(d)
    return sum(b * (d - i0) for i0 in range(0, d, b))


def _phi_block(d: int) -> int:
    return _PHI_BLOCK if d % _PHI_BLOCK == 0 else d


def _phi_blocks(d: int):
    """(first coordinate, block, width to the end, offset among the
    features) of each block of ``phi``."""
    b, offset = _phi_block(d), 0
    for i0 in range(0, d, b):
        yield i0, b, d - i0, offset
        offset += b * (d - i0)


def _coef(width: int, block: int, off_diagonal: float, scale: float):
    coef = np.full((width, 1), off_diagonal * scale, np.float32)
    coef[:block] = scale
    return coef


def _phi_keys(k: jax.Array) -> jax.Array:
    """(..., C, d) -> (..., phi_width(d), C) bfloat16, features before
    positions: for each block of coordinates its products with itself
    and with every later coordinate, taken in float32 and rounded once.
    ``phi(q) . phi(k) == (q.k)**2`` when the query side doubles the
    products across blocks (:func:`_read_state`)."""
    kt = jnp.swapaxes(k.astype(jnp.float32), -1, -2)            # (..., d, C)
    parts = [
        (kt[..., i0:i0 + b, None, :] * kt[..., None, i0:, :])
        .astype(jnp.bfloat16).reshape(*kt.shape[:-2], b * w, kt.shape[-1])
        for i0, b, w, _ in _phi_blocks(kt.shape[-2])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-2)


def _read_state(q: jax.Array, state: jax.Array, scale: float) -> jax.Array:
    """``scale * phi(q)^T state``: q (B, G, R, C, d), state (B, G, F, e)
    bfloat16 -> (B, G, R, C, e) float32. One product per block of
    ``phi``, its features formed where the product is taken: ``phi(q)``
    as one array would cross memory once more than everything else in
    the layer together."""
    B, G, _, _, d = q.shape
    qt = jnp.swapaxes(q.astype(jnp.float32), -1, -2)            # (..., d, C)
    out = 0.0
    for i0, b, w, offset in _phi_blocks(d):
        feats = (qt[..., i0:i0 + b, None, :]
                 * (qt[..., i0:, :] * _coef(w, b, 2.0, scale))[..., None, :, :])
        out = out + jnp.einsum(
            "bgriwt,bgiwe->bgrte", feats.astype(jnp.bfloat16),
            state[:, :, offset:offset + b * w].reshape(B, G, b, w, -1),
            preferred_element_type=jnp.float32)
    return out


def fuses_state_pass(d: int, ratio: int, chunk: int, *, inference: bool,
                     state_dtype=jnp.float32) -> bool:
    """Whether :func:`power_retention` reads and updates its state in
    the fused kernel (``ops/pallas_retention.py``) at head width ``d``,
    ``ratio`` query heads per key/value head and this chunk length: on a
    compiled TPU backend, for a caller that does not differentiate, at a
    shape inside the kernel's envelope. Inside it there is no way back
    to XLA: a kernel that fails to build raises."""
    return (inference and pallas_attention._mode() == "compiled"
            and jnp.dtype(state_dtype) == jnp.float32
            and pallas_retention.in_envelope(d, ratio * chunk, chunk))


def power_retention(
    q: jax.Array,        # (B, S, H, d)
    k: jax.Array,        # (B, S, G, d), H a multiple of G
    v: jax.Array,        # (B, S, G, d)
    log_g: jax.Array,    # (B, S, G) log of the gate in (0, 1]
    *,
    degree: int = 2,
    chunk: int | None = None,
    eps: float = 1e-6,
    inference: bool = False,
    way_in: WayIn | None = None,
    _state_dtype=jnp.float32,
) -> jax.Array:
    """The mixing above; returns (B, S, H, d) in ``q.dtype``.

    ``degree`` 2 is the normalised form of the module's first equations;
    ``degree`` 1 the unnormalised linear form (lightning attention),
    whose ``log_g`` may also be ``(G,)``: one constant decay per
    key/value head. ``eps`` belongs to the normaliser and so to degree 2
    alone. ``chunk`` is chosen from the sequence length when not given (tests
    pass small ones). ``inference`` says that the caller takes no
    gradient: the state pass may then run in the forward-only kernel
    (:func:`fuses_state_pass`), and so may the way in. With ``way_in``
    q and k are their projections' outputs, and the norm, the rotation
    and the one rounding that make them operands happen in the pass
    that writes chunk order (:class:`WayIn`, :func:`_chunk_major`).
    ``_state_dtype`` exists for one test, which shows that a state
    accumulated in bfloat16 is caught."""
    if degree not in (1, 2):
        raise NotImplementedError(
            f"power retention of degree {degree}: the family holds degree "
            "1 (unnormalised, no feature map, jax.numpy state pass) and "
            "degree 2 (normalised, the block-triangular feature map, "
            "state pass fused on a compiled TPU backend)")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"{q.shape[2]} query heads over {k.shape[2]} key/value heads")
    chunk = chunk or pick_chunk(q.shape[1])
    # degree 1's constant decay is no operand of the scan
    constant = degree == 1 and log_g.ndim == 1
    with jax.named_scope(
            "lightning_attention" if degree == 1 else "power_retention"):
        operands = _chunk_major(q, k, v, None if constant else log_g, chunk,
                                way_in, inference)
        if degree == 1:
            y = _linear_retention(*operands, log_g if constant else None,
                                  _state_dtype, q.dtype)
        else:
            kernel = "compiled" if fuses_state_pass(
                q.shape[3], q.shape[2] // k.shape[2], chunk,
                inference=inference, state_dtype=_state_dtype) else None
            y = _power_retention(*operands, eps, _state_dtype, kernel,
                                 q.dtype)
        return _token_major(y, *q.shape[1:3])


class WayIn(NamedTuple):
    """What stands between the q and k projections and the scan when
    :func:`power_retention` is given the projections' outputs: a
    per-head RMS norm with these weights, RoPE by these tables
    (``ops/qk_norm.rope_tables``, built once a program; None: no
    positions) and one rounding to the operands' type; ``norm_dtype``
    is ``ops/qk_norm.prepare``'s: the rounding Brumby's code writes
    between norm and rotation, which the CPU performs and the TPU (XLA's
    backend and the kernel alike) does not; MiniCPM-SALA's code has
    none."""
    q_weight: jax.Array
    k_weight: jax.Array
    eps: float
    rope: tuple | None = None
    norm_dtype: Any = None


def chunk_order(x, C):
    """**The layout, defined here and nowhere else**: (B, S, heads, d),
    S a whole number of chunks of ``C``, to chunk-major with heads
    before positions, (S / C, B, heads, C, d). The scan reads one
    leading index a step; a head's (C, d) tile is contiguous. The fused
    way in (``ops/pallas_qk_norm.py``) writes this order from its
    output blocks' index map; a test holds the two equal."""
    B, S, heads, d = x.shape
    return x.reshape(B, S // C, C, heads, d).transpose(1, 0, 3, 2, 4)


def _chunk_major(q, k, v, log_g, C, way_in=None, inference=False):
    """Both degrees' way in, the one caller of :func:`chunk_order`: the
    sequence padded to whole chunks of ``C``, then bfloat16 operands in
    chunk order with the query heads split by group, q (n, B, G, R, C,
    d), k and v (n, B, G, C, d), and float32 gates, ``log_g`` (B, S, G)
    as (n, B, G, C) (None stays None: degree 1's constant decay).

    With ``way_in`` q and k arrive as projected and become operands
    here, in one pass each: on a compiled TPU backend for a caller that
    takes no gradient (``ops/qk_norm.fuses``) the fused kernel reads the
    bfloat16 projection and writes normed, rotated, rounded tiles
    straight into chunk order; elsewhere ``ops/qk_norm.prepare``'s
    ``jax.numpy`` form runs token-major and :func:`chunk_order` moves
    its bfloat16 result. v is only moved."""
    B, S, H, d = q.shape
    G = k.shape[2]
    pad = (-S) % C
    rope = way_in.rope if way_in is not None else None
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        if log_g is not None:
            log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
        if rope is not None:
            rope = tuple(jnp.pad(t, ((0, pad), (0, 0))) for t in rope)
    n = (S + pad) // C
    bf16, f32 = jnp.bfloat16, jnp.float32

    def prepared(x, weight):
        heads = x.shape[2]
        how = dict(heads=heads, eps=way_in.eps, rope=rope)
        flat = x.reshape(B, S + pad, heads * d)
        if qk_norm.fuses(d, heads * d, C, inference=inference):
            out = qk_norm.fused(flat, weight, chunk=C, **how)
        else:
            out = chunk_order(qk_norm.prepare(
                flat, weight, norm_dtype=way_in.norm_dtype, **how), C)
        return out.astype(bf16)

    if way_in is None:
        qc, kc = (chunk_order(x.astype(bf16), C) for x in (q, k))
    else:
        qc, kc = prepared(q, way_in.q_weight), prepared(k, way_in.k_weight)
    qc = qc.reshape(n, B, G, H // G, C, d)
    vc = chunk_order(v.astype(bf16), C)
    gc = None if log_g is None else log_g.astype(f32) \
        .reshape(B, n, C, log_g.shape[2]).transpose(1, 0, 3, 2)
    return qc, kc, vc, gc


def _rows(y, dtype):
    """A chunk step's float32 output (B, G, R, C, d), rounded once to
    the caller's type and laid as the way out wants it: (B, C, H * d),
    a position's heads side by side. The step's last fusion writes its
    (C, d) tiles in that order at no cost; stacked in chunk order the
    same tiles need a pass of their own to be moved."""
    B, G, R, C, d = y.shape
    return y.astype(dtype).transpose(0, 3, 1, 2, 4).reshape(B, C, G * R * d)


def _token_major(y, S, heads):
    """The way out: (n, B, C, H * d) as the scan stacked :func:`_rows`
    (already in the caller's type: a step rounds its float32 sum once)
    -> (B, S, H, d). At B = 1 nothing moves. The barrier keeps the
    array narrow: without it XLA may fuse the consumer's widening (an
    output norm, a gate) into the move and write, relay out and read a
    float32 array twice the size (PERF.md section 5, PR 37)."""
    n, B, C, width = y.shape
    y = y.transpose(1, 0, 2, 3).reshape(B, n * C, width)
    return lax.optimization_barrier(y[:, :S]).reshape(B, S, heads, -1)


def _power_retention(qc, kc, vc, gc, eps, state_dtype, kernel, out_dtype):
    """Degree 2 over :func:`_chunk_major`'s operands; returns the
    chunks' outputs stacked, (n, B, C, H * d) in ``out_dtype``
    (:func:`_rows`).
    ``kernel``: None for the ``jax.numpy`` state pass, ``"compiled"``
    or ``"interpret"`` (tests, on the CPU) for the fused one."""
    _, B, G, R, C, d = qc.shape
    bf16, f32 = jnp.bfloat16, jnp.float32
    causal = np.tril(np.ones((C, C), bool))
    inv_d = 1.0 / d
    hi = lax.Precision.HIGHEST

    def step(carry, xs):
        state, norm = carry            # (B, G, F, d), (B, G, d, d)
        qi, ki, vi, gi = xs
        cum = jnp.cumsum(gi, axis=-1)                       # (B, G, C)
        total = cum[..., -1]
        # inside the chunk: the quadratic form
        s = jnp.einsum("bgrtd,bgsd->bgrts", qi, ki,
                       preferred_element_type=f32)
        decay = cum[..., :, None] - cum[..., None, :]       # (B, G, C, C)
        decay = jnp.exp(jnp.where(causal, decay, -jnp.inf))
        # rounded once, for the numerator and the normaliser alike: the
        # ratio is then an average of v under slightly other weights
        w = (s * s * (inv_d * decay[:, :, None])).astype(bf16)
        den = jnp.sum(w.astype(f32), axis=-1)               # (B, G, R, C)
        num = jnp.einsum("bgrts,bgse->bgrte", w, vi,
                         preferred_element_type=f32)
        # from the chunks before: the state, read and then decayed to
        # the chunk's end with this chunk's keys and values added
        carried = jnp.exp(cum)[:, :, None]                  # (B, G, 1, C)
        left = jnp.exp(total[..., None] - cum)              # (B, G, C)
        keep = jnp.exp(total)[..., None, None]
        vl = (vi.astype(f32) * left[..., None]).astype(bf16)
        if kernel:
            num_s, state = pallas_retention.state_pass(
                qi.reshape(B * G, R * C, d), ki.reshape(B * G, C, d),
                vl.reshape(B * G, C, d), keep.reshape(B * G), state,
                interpret=kernel == "interpret")
            num_s = num_s.reshape(B, G, R, C, d)
        else:
            num_s = _read_state(qi, state.astype(bf16), inv_d)
            state = keep * state.astype(f32) + jnp.einsum(
                "bgfs,bgse->bgfe", _phi_keys(ki), vl,
                preferred_element_type=f32)
        q32 = qi.astype(f32)
        den_s = jnp.sum(jnp.einsum("bgrtd,bgde->bgrte", q32,
                                   norm.astype(f32), precision=hi) * q32,
                        axis=-1)
        num = num + carried[..., None] * num_s
        den = den + carried * den_s
        y = num / (den[..., None] + eps)
        k32 = ki.astype(f32)
        norm = keep * norm.astype(f32) + inv_d * jnp.einsum(
            "bgsd,bgse->bgde", k32 * left[..., None], k32, precision=hi)
        return (state.astype(state_dtype), norm.astype(state_dtype)), \
            _rows(y, out_dtype)

    # the kernel keeps its state per key/value head of the batch, the
    # features in its own order (pallas_retention.block_pairs)
    state_shape = (B * G,) if kernel else (B, G)
    init = (jnp.zeros((*state_shape, phi_width(d), d), state_dtype),
            jnp.zeros((B, G, d, d), state_dtype))
    _, y = lax.scan(step, init, (qc, kc, vc, gc))           # (n, B, C, H * d)
    return y


def _linear_retention(qc, kc, vc, gc, log_decay, state_dtype, out_dtype):
    """Degree 1 through the same chunks (:func:`_chunk_major`'s
    operands; returns the stacked outputs as :func:`_power_retention`
    does): inside a chunk ``((q k^T) * decay / sqrt(d)) v``, between
    chunks one (d, d) float32 state per key/value head, read by the
    chunk's queries and then decayed to the chunk's end with its keys
    and values added. ``log_decay`` (G,) in the place of the gates
    ``gc`` is one constant decay per head: the decay matrix is then the
    same in every chunk and is worked once, outside the scan."""
    _, B, G, _, C, d = qc.shape
    bf16, f32 = jnp.bfloat16, jnp.float32
    constant = gc is None
    causal = np.tril(np.ones((C, C), bool))
    scale = 1.0 / np.sqrt(d)

    def decays(gi):
        """From a chunk's log gates (b, G, C): the in-chunk decay matrix
        times the scale, and what carries the state in (per position),
        the keys to the chunk's end, and the state itself over it."""
        cum = jnp.cumsum(gi, axis=-1)
        total = cum[..., -1]
        inside = cum[..., :, None] - cum[..., None, :]
        inside = jnp.exp(jnp.where(causal, inside, -jnp.inf)) * scale
        return (inside[:, :, None], jnp.exp(cum)[:, :, None, :, None] * scale,
                jnp.exp(total[..., None] - cum)[..., None],
                jnp.exp(total)[..., None, None])

    if constant:
        fixed = decays(jnp.broadcast_to(
            log_decay.astype(f32)[None, :, None], (1, G, C)))

    def step(state, xs):
        qi, ki, vi, gi = xs
        inside, carried, left, keep = fixed if constant else decays(gi)
        s = jnp.einsum("bgrtd,bgsd->bgrts", qi, ki,
                       preferred_element_type=f32)
        y = jnp.einsum("bgrts,bgse->bgrte", (s * inside).astype(bf16), vi,
                       preferred_element_type=f32)
        y = y + carried * jnp.einsum(
            "bgrtd,bgde->bgrte", qi, state.astype(bf16),
            preferred_element_type=f32)
        vl = (vi.astype(f32) * left).astype(bf16)
        state = keep * state.astype(f32) + jnp.einsum(
            "bgsd,bgse->bgde", ki, vl, preferred_element_type=f32)
        return state.astype(state_dtype), _rows(y, out_dtype)

    _, y = lax.scan(step, jnp.zeros((B, G, d, d), state_dtype),
                    (qc, kc, vc, gc))
    return y
