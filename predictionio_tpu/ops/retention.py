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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from predictionio_tpu.ops import pallas_attention, pallas_retention

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
    (:func:`fuses_state_pass`). ``_state_dtype`` exists for one test,
    which shows that a state accumulated in bfloat16 is caught."""
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
    if degree == 1:
        with jax.named_scope("lightning_attention"):
            return _linear_retention(q, k, v, log_g, chunk, _state_dtype)
    kernel = "compiled" if fuses_state_pass(
        q.shape[3], q.shape[2] // k.shape[2], chunk, inference=inference,
        state_dtype=_state_dtype) else None
    with jax.named_scope("power_retention"):
        return _power_retention(q, k, v, log_g, chunk, eps, _state_dtype,
                                kernel)


def _chunk_major(q, k, v, log_g, C):
    """Both degrees' layout: the sequence padded to whole chunks of
    ``C``, then chunk-major with heads before positions, bfloat16
    operands and float32 gates: q (n, B, G, R, C, d), k and v (n, B, G,
    C, d), ``log_g`` (B, S, G) as (n, B, G, C) (degree 1 with a constant
    decay passes none: G = 0 there)."""
    B, S, H, d = q.shape
    G = k.shape[2]
    R = H // G
    pad = (-S) % C
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    n = (S + pad) // C
    bf16, f32 = jnp.bfloat16, jnp.float32
    # chunk-major, heads before positions: (n, B, G, [R,] C, d)
    qc = q.reshape(B, n, C, G, R, d).transpose(1, 0, 3, 4, 2, 5).astype(bf16)
    kc = k.reshape(B, n, C, G, d).transpose(1, 0, 3, 2, 4).astype(bf16)
    vc = v.reshape(B, n, C, G, d).transpose(1, 0, 3, 2, 4).astype(bf16)
    gc = log_g.astype(f32).reshape(B, n, C, log_g.shape[2]) \
        .transpose(1, 0, 3, 2)
    return qc, kc, vc, gc


def _token_major(y, S, dtype):
    """(n, B, G, R, C, d) out of the scan -> (B, S, H, d)."""
    n, B, G, R, C, d = y.shape
    y = y.transpose(1, 0, 4, 2, 3, 5).reshape(B, n * C, G * R, d)
    return y[:, :S].astype(dtype)


def _power_retention(q, k, v, log_g, C, eps, state_dtype, kernel):
    """``kernel``: None for the ``jax.numpy`` state pass, ``"compiled"``
    or ``"interpret"`` (tests, on the CPU) for the fused one."""
    B, S, H, d = q.shape
    G = k.shape[2]
    R = H // G
    bf16, f32 = jnp.bfloat16, jnp.float32
    qc, kc, vc, gc = _chunk_major(q, k, v, log_g, C)
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
        return (state.astype(state_dtype), norm.astype(state_dtype)), y

    # the kernel keeps its state per key/value head of the batch, the
    # features in its own order (pallas_retention.block_pairs)
    state_shape = (B * G,) if kernel else (B, G)
    init = (jnp.zeros((*state_shape, phi_width(d), d), state_dtype),
            jnp.zeros((B, G, d, d), state_dtype))
    _, y = lax.scan(step, init, (qc, kc, vc, gc))           # (n, B, G, R, C, d)
    return _token_major(y, S, q.dtype)


def _linear_retention(q, k, v, log_g, C, state_dtype):
    """Degree 1 through the same chunks: inside a chunk ``((q k^T) *
    decay / sqrt(d)) v``, between chunks one (d, d) float32 state per
    key/value head, read by the chunk's queries and then decayed to the
    chunk's end with its keys and values added. A ``log_g`` of shape
    (G,) is one constant decay per head: the decay matrix is then the
    same in every chunk and is worked once, outside the scan."""
    B, S, H, d = q.shape
    G = k.shape[2]
    bf16, f32 = jnp.bfloat16, jnp.float32
    constant = log_g.ndim == 1
    qc, kc, vc, gc = _chunk_major(
        q, k, v, jnp.zeros((B, S, 0), f32) if constant else log_g, C)
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
        fixed = decays(jnp.broadcast_to(log_g.astype(f32)[None, :, None],
                                        (1, G, C)))

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
        return state.astype(state_dtype), y

    _, y = lax.scan(step, jnp.zeros((B, G, d, d), state_dtype),
                    (qc, kc, vc, gc))
    return _token_major(y, S, q.dtype)
