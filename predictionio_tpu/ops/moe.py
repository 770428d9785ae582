"""A routed feed-forward layer that is told which experts it holds.

The router scores every token over **all** published experts in float32
(softmax, group-limited greedy selection: the best ``topk_group`` of
``n_group`` groups by their best expert, then the ``top_k`` best experts
of those groups; weights are the scores times ``scaling``, not
renormalised). The layer then computes the part of the result its own
experts give — ``held`` experts from ``first`` on — and leaves the rest
out: on a mesh with an expert axis each chip calls it with its own
share and the exchange adds the parts up; on one chip there is no
exchange and nothing stands in for it.

Static shapes, no token dropped: the ``tokens x top_k`` assignments are
sorted by held expert (those of absent experts last), the tokens'
rows gathered in that order, and three grouped matrix products run over
the rows of each expert (``gate``, ``up``, ``down`` of a SwiGLU). The
row count is the bound ``tokens x top_k`` — every assignment could be to
a held expert — and the grouped product visits only the tiles that hold
real rows, so the bound costs no matrix work; what it does cost is the
return to token order, a gather of ``top_k`` rows a token. In a serving
program on a compiled TPU backend the product is the Pallas grouped
matmul that ships with JAX (``jax.experimental.pallas.ops.tpu.megablox.gmm``,
imported only here) and the rows are gathered a chunk at a time as far
as the real ones go; elsewhere ``jax.lax.ragged_dot`` and a plain
gather, which are differentiable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas_attention

#: row tile of the grouped product, and the tile of the larger of its
#: contraction and its columns; the smaller is taken whole (PERF.md
#: section 6 has the sweep): at ~300 rows an expert a row tile of 256
#: keeps the weight re-reads and the matrix work of half-empty tiles
#: level with each other
GMM_ROWS, GMM_LONG = 256, 512


def route(x: jax.Array, w_router: jax.Array, *, n_group: int,
          topk_group: int, top_k: int, scaling: float,
          score_dtype=jnp.float32):
    """(T, D) tokens -> (expert ids (T, top_k) int32, weights (T, top_k)
    float32, scores (T, E) float32). The product accumulates in float32
    from the operands as they are held (bfloat16 values multiply
    exactly); softmax and selection in ``score_dtype`` (float32: the
    argument exists so that a test can show what bfloat16 scores do).
    Ties go to the lower index, for groups and for experts."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1).astype(score_dtype) \
        .astype(jnp.float32)
    T, E = scores.shape
    by_group = scores.reshape(T, n_group, E // n_group)
    _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), topk_group)
    keep = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    kept = jnp.where(keep[:, :, None], by_group, 0.0).reshape(T, E)
    top, ids = jax.lax.top_k(kept, top_k)
    return ids.astype(jnp.int32), top * scaling, scores


def uses_kernel(inference: bool) -> bool:
    """Whether the grouped products run as the Pallas kernel, the rows
    gathered a chunk at a time: a serving program (``inference``: the
    chunked gather's loop has no backward pass) on a compiled TPU
    backend."""
    return inference and pallas_attention._mode() == "compiled"


def _grouped(lhs, rhs, sizes, kernel, out_dtype):
    """rows of group g times rhs[g]; rows past the groups undefined."""
    if kernel is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = rhs.shape[1:]
    tk, tn = (min(GMM_LONG, k), n) if k > n else (k, min(GMM_LONG, n))
    tiling = (min(GMM_ROWS, lhs.shape[0]), tk, tn)
    return gmm(lhs, rhs, sizes, out_dtype, tiling,
               interpret=kernel == "interpret")


def _gather_real_rows(x, token_of, n_real, chunk: int):
    """``x[token_of]`` for the first ``n_real`` rows, a chunk at a time
    (the trip count is traced, so the rows of absent experts behind them
    cost nothing); the rest are zeros the grouped product never reads."""
    rows = token_of.shape[0]

    def body(i, buf):
        idx = jax.lax.dynamic_slice(token_of, (i * chunk,), (chunk,))
        return jax.lax.dynamic_update_slice(buf, x[idx], (i * chunk, 0))

    return jax.lax.fori_loop(0, (n_real + chunk - 1) // chunk, body,
                             jnp.zeros((rows, x.shape[1]), x.dtype))


def routed_experts(x: jax.Array, ids: jax.Array, weights: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   first: int, *, inference: bool = False,
                   kernel: str | None = "auto"):
    """The held experts' part of ``sum_k w_k E_{e_k}(x)``.

    ``x`` (T, D); ``ids``, ``weights`` (T, top_k) from :func:`route`;
    ``w_gate``, ``w_up`` (held, D, F) and ``w_down`` (held, F, D) are
    experts ``first .. first + held - 1``. Returns ((T, D) float32,
    (held,) int32 assignments per held expert). ``kernel``: "auto" takes
    the rule (:func:`uses_kernel`), None the plain form, "interpret" the
    kernel in interpret mode (tests)."""
    if kernel == "auto":
        kernel = "compiled" if uses_kernel(inference) else None
    T, D = x.shape
    top_k, held = ids.shape[1], w_gate.shape[0]
    A = T * top_k
    local = ids.reshape(A) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)              # absent experts last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    dt = x.dtype
    if kernel:
        # the row bound is A, padded to whole row tiles
        tm = min(GMM_ROWS, A)
        rows = -(-A // tm) * tm
        xs = _gather_real_rows(x, jnp.pad(order, (0, rows - A)) // top_k,
                               jnp.sum(counts), tm)
    else:
        xs = x[order // top_k]
    gate = _grouped(xs, w_gate.astype(dt), counts, kernel, dt)
    up = _grouped(xs, w_up.astype(dt), counts, kernel, dt)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(dt)
    y = _grouped(hidden, w_down.astype(dt), counts, kernel, dt)
    # back to token order, a slot at a time: row back[t, k] of the sorted
    # rows answers token t's k-th choice; a choice of an absent expert
    # points at a row that holds nothing defined and is left out
    back = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32)).reshape(T, top_k)
    here = here.reshape(T, top_k)
    out = jnp.zeros((T, D), jnp.float32)
    for k in range(top_k):
        part = y[back[:, k]].astype(jnp.float32) * weights[:, k, None]
        out = out + jnp.where(here[:, k, None], part, 0.0)
    return out, counts
