"""Sharded top-k scoring — the batchPredict/recommendation hot path.

Replaces the reference templates' per-user `recommendProducts` /
item-score sort over RDDs (reference: tests/pio_tests/engines/
recommendation-engine/src/main/scala/ALSAlgorithm.scala:90-120 and
examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala cosine
ranking). One matmul (queries × item-factor table) feeds the
selection — MXU for the scores, fused masking for seen/business-rule
filters, no per-query host loops. The flat path (``recommend_topk``)
selects in two exact stages where the catalog is large against ``k``
(``two_stage_group_width``: the maximum of each group of contiguous
columns, then ``jax.lax.top_k`` over the ``k`` winning groups only) and
with one ``jax.lax.top_k`` otherwise; the chunked scan's merge, the
sharded merge and ``similar_topk`` keep the single call.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs.compile import instrumented_jit

# a NumPy scalar: a jnp one would start the JAX backend (and claim the
# chip) when this module is imported
NEG_INF = np.float32(-np.inf)


@partial(instrumented_jit, static_argnames=("k",))
def topk_scores(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(values, indices) of the top-k per row. ``k`` beyond the
    candidate count clamps (fewer columns back, never an XLA assert) —
    the contract every serving top-k in this module shares: a tiny
    catalog, or a shortlist smaller than the requested width, returns
    what exists."""
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))


@partial(instrumented_jit, static_argnames=("k",))
def recommend_topk(
    user_vecs: jax.Array,    # (B, K) query user factors
    item_f: jax.Array,       # (I, K) item factor table
    seen_cols: jax.Array,    # (B, S) int32 item indices already seen (padded)
    seen_mask: jax.Array,    # (B, S) 1=real, 0=pad
    allow: jax.Array,        # (I,) or (B, I) multiplicative 0/1 eligibility
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Top-k unseen, eligible items per query user.

    ``allow`` carries business rules (category whitelist, unavailable
    items — the ecommerce template's filters) as a precomputed 0/1
    vector; seen items are masked via scatter so padding slots (mask=0)
    leave scores untouched. ``k`` clamps to the catalog size
    (``topk_scores`` contract).

    The selection is a pure function of the static shapes: the single
    ``lax.top_k`` for small catalogs and wide ``k``, else the exact
    two-stage selection (:func:`_two_stage_topk`) over the whole groups
    of the same masked scores, the columns short of a last group scored
    apart as extra candidates. On a v5e at 4.4M items and ``k`` = 10
    the selection is 0.1-0.6 ms of a 3.1-4.1 ms program where the
    single call was 0.3-2.7 of 3.3-6.1 (PERF.md §5, PR 30).
    """
    items = item_f.shape[0]
    k = min(k, items)
    width = two_stage_group_width(items, k)
    if not width:
        return jax.lax.top_k(
            _masked_scores(user_vecs, item_f, seen_cols, seen_mask, allow), k)
    b = user_vecs.shape[0]
    if b > 1 and b % _SUBLANES:
        # whole blocks of 8 query rows: the (8, 128)-tiled score matrix
        # is then laid out by group as it stands, where XLA copies 2 to
        # 7 rows into that tiling first, in a kernel that takes 12-20 s
        # to compile (PERF.md §5, PR 30). Padding rows see nothing and
        # are dropped after stage 1. One row stays one row: XLA scores
        # it on the vector unit, the table's widening fused into the
        # multiply, at the speed of the read from either table width
        # (1.62 ms a program from a bfloat16 table where the 8-row form
        # takes 2.05: PERF.md §5, PR 35)
        user_vecs, seen_cols, seen_mask = (
            _pad_rows(x, -b % _SUBLANES)
            for x in (user_vecs, seen_cols, seen_mask))
        if allow.ndim == 2:
            allow = _pad_rows(allow, -b % _SUBLANES)
    whole = items // width * width
    # the matmul reads a row slice of the table in place (a slice under
    # a reshape of the table would be copied, 1.1 GB a program)
    return _two_stage_topk(
        _grouped_scores(user_vecs, item_f[:whole], seen_cols, seen_mask,
                        allow[..., :whole], width),
        _tail_scores(user_vecs, item_f[whole:], seen_cols, seen_mask,
                     allow[..., whole:], whole)[:b], k)


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """``x`` with ``rows`` rows of zeros after its last."""
    return jax.lax.pad(x, np.zeros((), x.dtype),
                       ((0, rows, 0),) + ((0, 0, 0),) * (x.ndim - 1))


def _scores(user_vecs, item_f):
    """The (B, I) float32 score matrix ``user_vecs @ item_f.T``, read
    from the table in the dtype it is handed: one product of mixed
    operands accumulated in float32, so a bfloat16 table (``ALSModel``'s
    serving copy) is read at 2 bytes an entry and never widened to an
    (I, K) float32 array first, and a float32 table gives the program
    it always gave. The user rows are handed over as they are: the
    matrix unit's default precision rounds them to bfloat16, as it
    always did (two rows or more); where the hardware multiplies in
    float32 (one row on the vector unit, the CPU) they stay float32
    and only the table's rounding is in a score (PERF.md §5, PR 35)."""
    return jnp.einsum("bk,ik->bi", user_vecs, item_f,
                      preferred_element_type=jnp.float32)          # MXU


def _masked_scores(user_vecs, item_f, seen_cols, seen_mask, allow):
    """The (B, I) score matrix with ineligible and seen items at -inf."""
    scores = _scores(user_vecs, item_f)
    scores = jnp.where(allow > 0, scores, NEG_INF)
    rows = jnp.broadcast_to(
        jnp.arange(seen_cols.shape[0])[:, None], seen_cols.shape)
    return scores.at[rows, seen_cols].min(_hide(seen_mask))


def _hide(seen_mask):
    """What to ``min`` into the score of each seen slot: -inf for a
    real one, +inf (a no-op) for padding."""
    return jnp.where(seen_mask > 0, NEG_INF, jnp.float32(jnp.inf))


def _grouped_scores(user_vecs, item_f, seen_cols, seen_mask, allow, width):
    """:func:`_masked_scores` of a table of whole groups, viewed as
    (blocks of 8 rows, row, group, column): a bitcast of the tiled
    matrix for 1 row and for whole blocks. The seen items are scattered
    into the view: on the (B, I) matrix XLA runs wide scatters as
    serial loops over the whole array (7.5 ms at B = 8 and seen width
    128, PERF.md §5), on the view it does not. A seen item past the
    last whole group is out of bounds here and dropped:
    :func:`_tail_scores` hides it."""
    b = user_vecs.shape[0]
    scores = _scores(user_vecs, item_f)
    scores = jnp.where(allow > 0, scores, NEG_INF)
    lanes = _SUBLANES if b % _SUBLANES == 0 else b
    view = scores.reshape(b // lanes, lanes, item_f.shape[0] // width, width)
    rows = np.broadcast_to(
        np.arange(b, dtype=np.int32)[:, None], seen_cols.shape)
    # lax.div / lax.rem: item indices are never negative, and jnp's
    # floor division traces five times the operations
    w = np.asarray(width, seen_cols.dtype)
    return view.at[
        rows // lanes, rows % lanes,
        jax.lax.div(seen_cols, w), jax.lax.rem(seen_cols, w)
    ].min(_hide(seen_mask), mode="drop")


def _tail_scores(user_vecs, item_f, seen_cols, seen_mask, allow, start):
    """:func:`_masked_scores` of the fewer-than-a-group rows that follow
    row ``start`` of the table (none where the catalog is whole
    groups). Seen items are found by comparison, (B, S, tail) being
    small."""
    scores = _scores(user_vecs, item_f)
    ids = start + jnp.arange(item_f.shape[0], dtype=seen_cols.dtype)
    seen = (seen_cols[:, :, None] == ids) & (seen_mask[:, :, None] > 0)
    return jnp.where((allow > 0) & ~seen.any(axis=1), scores, NEG_INF)


#: a group of the two-stage selection is a whole number of 128-lane
#: tiles, so a group never straddles a tile of the score matrix
_LANES = 128
#: rows of a tile of the score matrix
_SUBLANES = 8
#: two stages are taken where the groups outnumber k this many times:
#: the k picked groups are then a small part of the catalog
_MIN_GROUPS_PER_K = 8
#: and up to this k, the widest the tests and the chip's exactness
#: check cover: at 4.4M items k = 320 and 1000 measured faster in two
#: stages too, but XLA sorts there instead of calling TopK and the
#: indices of tied scores came back in another order (PERF.md §7)
_MAX_TWO_STAGE_K = 100


def two_stage_group_width(items: int, k: int) -> int:
    """Group width of the exact two-stage selection of ``k`` out of
    ``items`` scores, or 0 where the single ``lax.top_k`` is taken. A
    pure function of the static shapes: the width nearest
    sqrt(items / k) in whole lane tiles balances the two small top-ks
    (``items / width`` group maxima against ``k * width`` candidates)."""
    if not 1 <= k <= _MAX_TWO_STAGE_K:
        return 0
    width = max(1, round(math.sqrt(items / k) / _LANES)) * _LANES
    return width if items // width >= _MIN_GROUPS_PER_K * k else 0


def _two_stage_topk(view: jax.Array, tail: jax.Array, k: int
                    ) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k`` over rows of scores in two exact stages. ``view``
    (:func:`_grouped_scores`) holds the whole groups of each row, maybe
    rows of padding after them; ``tail`` (rows, fewer than width, maybe
    none) the columns past the last group, and says how many rows are
    real. Stage 1 takes the maximum of each group; stage 2 looks only
    at the ``k`` groups whose maxima win, and at the tail. An item
    among the top ``k`` cannot lie in a group whose maximum ``k`` other
    groups' maxima beat; with the picked groups in index order
    ``lax.top_k``'s lowest-index rule for ties carries over, so values
    and indices equal the single call's on every finite slot."""
    _, lanes, groups, width = view.shape
    b = tail.shape[0]
    _, picked = jax.lax.top_k(view.max(axis=-1).reshape(-1, groups)[:b], k)
    picked = jnp.sort(picked, axis=-1)
    row = np.broadcast_to(np.arange(b, dtype=np.int32)[:, None], (b, k))
    cand = _take(view, row // lanes, row % lanes, picked)
    cand = jnp.concatenate([cand.reshape(b, k * width), tail], axis=1)
    vals, sel = jax.lax.top_k(cand, k)
    w = np.int32(width)
    # the candidate's slot among the picked groups (a tail candidate
    # clamps to the last one and is overridden below)
    slot = jnp.minimum(jax.lax.div(sel, w), k - 1)
    item = _take(picked, row, slot) * w + jax.lax.rem(sel, w)
    return vals, jnp.where(sel < k * width, item, sel + (groups - k) * width)


def _take(x: jax.Array, *index) -> jax.Array:
    """``x[index[0], index[1], ...]`` for index arrays of one shape.
    ``jnp`` indexing and ``jnp.take_along_axis`` say the same and cost
    twice the time to trace and lower, which every serving signature
    pays once a process (``setup_s``: PERF.md §6, PR 30)."""
    n, at = len(index), index[-1].ndim
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(at, at + x.ndim - n)),
        collapsed_slice_dims=tuple(range(n)), start_index_map=tuple(range(n)))
    return jax.lax.gather(x, jnp.stack(index, axis=-1), dims,
                          (1,) * n + x.shape[n:], mode="promise_in_bounds")


@partial(instrumented_jit, static_argnames=("k",))
def recommend_topk_rows(
    user_table: jax.Array,   # (U, K) the whole user factor table
    uixs: jax.Array,         # (B,) int32 rows of it, one per query
    item_f: jax.Array,
    seen_cols: jax.Array,
    seen_mask: jax.Array,
    allow: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """:func:`recommend_topk` over ``user_table[uixs]``, the rows
    gathered inside the program: one device launch per dispatch, where
    an eager gather ahead of it costs several (PERF.md, PR 25)."""
    return recommend_topk(user_table[uixs], item_f, seen_cols, seen_mask,
                          allow, k)


@partial(instrumented_jit, static_argnames=("k", "chunk"))
def recommend_topk_chunked(
    user_vecs: jax.Array,    # (B, K)
    item_f: jax.Array,       # (I, K)
    seen_cols: jax.Array,    # (B, S) int32, padded
    seen_mask: jax.Array,    # (B, S) 1=real, 0=pad
    allow: jax.Array,        # (I,) 0/1 eligibility
    k: int,
    chunk: int = 1 << 18,
) -> tuple[jax.Array, jax.Array]:
    """recommend_topk without materialising the (B, I) score matrix:
    lax.scan over item tiles (dynamic_slice views — the table is never
    copied), per-tile ``lax.top_k``, running merge. Seen items are
    masked with the same O(B x S) scatter as the flat path, translated
    to tile-local coordinates. A non-divisible catalog is covered by a
    final overlapping tile whose already-scored prefix is masked out.

    Matches the flat path's indices on every finite-score slot. Slots
    beyond the eligible-item count carry -inf values and out-of-range
    sentinel indices (>= I, never colliding with a real pick) — callers
    must treat non-finite slots as absent, which both in-repo consumers
    (ALSModel._gather_results, batch_predict) already do. Restricted to
    1-D ``allow``; peak memory O(B x chunk). The dispatcher takes it
    from 24 queries a batch at ~786K items up, an envelope that dates
    from before the chip: on a v5e at 4.4M items and a bfloat16 table
    the flat path with its two-stage selection runs B=16 in 2.6 ms
    where this scan takes 6.6 ms at B=32 (PERF.md §5, PR 35; from a
    float32 table it also casts the whole table on every dispatch,
    5.1 ms more), so whether the envelope should move up is open (no
    cell dispatches a batch that wide, PERF.md §7)."""
    B = user_vecs.shape[0]
    I = item_f.shape[0]
    k = min(k, I)                   # the shared clamp-not-assert contract
    if I <= chunk:
        return recommend_topk(user_vecs, item_f, seen_cols, seen_mask,
                              allow, k)
    n_full = I // chunk
    has_rem = (I % chunk) != 0
    # tile t starts at starts[t]; positions below valid_from[t] were
    # already scored by an earlier tile (only the final overlapping
    # remainder tile has valid_from > start)
    starts = [t * chunk for t in range(n_full)]
    valid_from = [t * chunk for t in range(n_full)]
    if has_rem:
        starts.append(I - chunk)
        valid_from.append(n_full * chunk)
    starts = jnp.asarray(starts, dtype=jnp.int32)
    valid_from = jnp.asarray(valid_from, dtype=jnp.int32)

    rows = jnp.broadcast_to(jnp.arange(B)[:, None], seen_cols.shape)

    def body(carry, xs):
        bv, bi = carry                     # (B, k) running best
        start, vfrom = xs
        tile = jax.lax.dynamic_slice(
            item_f, (start, 0), (chunk, item_f.shape[1]))
        tallow = jax.lax.dynamic_slice(allow, (start,), (chunk,))
        scores = _scores(user_vecs, tile)
        idx = start + jax.lax.iota(jnp.int32, chunk)[None, :]
        scores = jnp.where(tallow[None, :] > 0, scores, NEG_INF)
        scores = jnp.where(idx >= vfrom, scores, NEG_INF)
        # seen scatter in tile-local coordinates (out-of-tile entries
        # clip to column 0 with a no-op +inf update)
        local = seen_cols - start
        in_tile = (local >= 0) & (local < chunk) & (seen_mask > 0)
        hide = jnp.where(in_tile, NEG_INF, jnp.float32(jnp.inf))
        scores = scores.at[rows, jnp.clip(local, 0, chunk - 1)].min(hide)
        v, sel = jax.lax.top_k(jnp.concatenate([bv, scores], axis=1), k)
        alli = jnp.concatenate(
            [bi, jnp.broadcast_to(idx, (B, chunk))], axis=1)
        return (v, jnp.take_along_axis(alli, sel, axis=1)), None

    init = (
        jnp.full((B, k), NEG_INF),
        # out-of-range sentinels: a -inf carry slot must never share an
        # index with a real (finite) pick, or a caller ignoring score
        # finiteness would serve duplicates
        jnp.broadcast_to(I + jnp.arange(k, dtype=jnp.int32), (B, k)),
    )
    (v, i), _ = jax.lax.scan(body, init, (starts, valid_from))
    return v, i


@partial(instrumented_jit, static_argnames=("k", "chunk"))
def recommend_topk_chunked_rows(
    user_table: jax.Array,   # (U, K)
    uixs: jax.Array,         # (B,) int32
    item_f: jax.Array,
    seen_cols: jax.Array,
    seen_mask: jax.Array,
    allow: jax.Array,
    k: int,
    chunk: int = 1 << 18,
) -> tuple[jax.Array, jax.Array]:
    """:func:`recommend_topk_chunked` over ``user_table[uixs]``, the
    rows gathered inside the program."""
    return recommend_topk_chunked(user_table[uixs], item_f, seen_cols,
                                  seen_mask, allow, k, chunk)


#: static seen-array widths shared by batch_predict's menu — a small
#: fixed set keeps the number of compiled kernel shapes bounded
_SEEN_WIDTHS = (8, 32, 128, 512)

#: static BATCH widths (power-of-two menu, serving scale): every
#: distinct batch dim is a fresh jit signature, and the serving
#: micro-batcher produces arbitrary coalesce counts — both the
#: templates' batch_predict padding and the adaptive batch policy
#: (serving/batch_policy.py) snap to this one menu so adaptivity can
#: never mint a batch shape the compiled-program cache hasn't seen
BATCH_WIDTHS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def serving_batch(b: int) -> int:
    """Round a serving batch size up to the ``BATCH_WIDTHS`` menu.

    Batches beyond the menu (eval-scale: engine.eval routes whole folds
    through batch_predict) pass through unchanged — they compile once
    anyway, and padding them would inflate the score matmul for
    nothing."""
    if b <= 0:
        return BATCH_WIDTHS[0]
    if b > BATCH_WIDTHS[-1] or (b & (b - 1)) == 0:
        return b
    return 1 << b.bit_length()

#: static top_k widths shared by every serving path — k is a jit
#: signature arg fed by client-controlled ``query.num``
_K_WIDTHS = (10, 32, 100, 320, 1000)


def serving_k(k: int, n_max: int) -> int:
    """Round a requested top-k width up to the ``_K_WIDTHS`` menu
    (power of two beyond it), clamped to the catalog/vocab size.

    ``k`` feeds jit signatures as a STATIC argument, and ``query.num``
    is client-controlled: without the menu, a client cycling num
    values retraces the serving program per distinct value — behind
    the query micro-batcher that stalls every other client's batch
    for the compile. Callers already trim results to each query's own
    num, so a wider k only widens the ``top_k``. One helper for all
    serving paths (ALS single-query, recommendation batch, sessionrec
    batch) so the trace-width buckets can't drift apart."""
    for cap in _K_WIDTHS:
        if k <= cap:
            return min(cap, n_max)
    return min(1 << (max(k, 2) - 1).bit_length(), n_max)

#: catalog/batch envelope where the chunked-scan formulation is taken
#: over the flat materialize+top_k: large catalogs with batched
#: queries, where the flat path's (B, I) score matrix dominates. The
#: thresholds date from before the chip; no cell dispatches a batch
#: this wide, so they are not measured on today's code (PERF.md §7)
_MIN_ITEMS = 786_432
_MIN_BATCH = 24


def _trim_seen(seen_cols, seen_mask):
    """Shrink the seen-item pad to the smallest static width covering
    the batch's real max seen count. Host-side only: the seen arrays
    originate as NumPy in the templates, and a device reduction here
    would cost one synchronous host<->device scalar fetch per call —
    the same per-dispatch RTT the static lam/alpha args eliminate
    elsewhere. Device arrays / tracers and menu-width inputs pass
    through untouched (templates/recommendation.py already right-sizes
    to the ``_SEEN_WIDTHS`` menu)."""
    if not isinstance(seen_mask, np.ndarray) or seen_mask.ndim != 2 \
            or seen_mask.shape[1] in _SEEN_WIDTHS:
        return seen_cols, seen_mask
    # bound by the last occupied slot (not the count): entries need not
    # be left-packed
    occupied = np.where(
        seen_mask > 0,
        np.arange(1, seen_mask.shape[1] + 1, dtype=np.int64)[None, :],
        0,
    )
    real = int(occupied.max()) if occupied.size else 0
    for width in _SEEN_WIDTHS:
        if real <= width < seen_mask.shape[1]:
            return seen_cols[:, :width], seen_mask[:, :width]
    return seen_cols, seen_mask


def _chunked_wins(allow, item_f, batch: int) -> bool:
    """Inside the envelope where the chunked scan beats the flat path
    (1-D ``allow`` only: the chunked path takes no per-query rules)."""
    return allow.ndim == 1 and item_f.shape[0] >= _MIN_ITEMS \
        and batch >= _MIN_BATCH


def selects_two_stage(allow, item_f, batch: int, k: int) -> bool:
    """Whether :func:`recommend_topk_fused_rows` at these shapes runs
    the flat program with the two-stage selection: the rule the program
    itself applies, for the serving layer's counter."""
    items = item_f.shape[0]
    return not _chunked_wins(allow, item_f, batch) \
        and two_stage_group_width(items, min(k, items)) > 0


def recommend_topk_fused_rows(
    user_table: jax.Array,   # (U, K)
    uixs,                    # (B,) int32 rows of it; NumPy is fine
    item_f: jax.Array,       # (I, K)
    seen_cols,               # (B, S) int32, padded
    seen_mask,               # (B, S) 1=real, 0=pad
    allow: jax.Array,        # (I,) or (B, I)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Top-k recommendation dispatcher: picks between the two XLA
    formulations — flat materialize+select (:func:`recommend_topk_rows`:
    every batch the serving cells dispatch) and the chunked-scan merge
    (:func:`recommend_topk_chunked_rows`, O(B x chunk) memory, taken
    from ~1M items with batched queries). Callers hold row indices, not
    vectors: the chosen program gathers ``user_table[uixs]`` itself, so
    a dispatch is ONE device launch. ``jit`` uploads host index and
    seen arrays with the call; ``_trim_seen`` stays on the host."""
    if _chunked_wins(allow, item_f, uixs.shape[0]):
        seen_cols, seen_mask = _trim_seen(seen_cols, seen_mask)
        return recommend_topk_chunked_rows(
            user_table, uixs, item_f, seen_cols, seen_mask, allow, k)
    return recommend_topk_rows(
        user_table, uixs, item_f, seen_cols, seen_mask, allow, k)


def recommend_topk_sharded(
    user_vecs: jax.Array,    # (B, K) — B divisible by mesh "data"
    item_f: jax.Array,       # (I, K) — I divisible by mesh "model"
    seen_cols: jax.Array,    # (B, S) int32, padded
    seen_mask: jax.Array,    # (B, S) 1=real, 0=pad
    allow: jax.Array,        # (I,) 0/1 eligibility
    k: int,
    mesh,
) -> tuple[jax.Array, jax.Array]:
    """Distributed batch top-k — the EVAL hot path on a mesh
    (reference analogue: Engine.eval's batchPredictBase over RDD
    partitions, Engine.scala:783-799; here the catalog's score space
    is the sharded axis instead of the query RDD).

    Queries shard over ``data``; the item-factor table row-shards over
    ``model``. Each shard computes a LOCAL top-k over its catalog rows
    (with seen/eligibility masks translated to shard-local
    coordinates), then the ``n_model * k`` candidates all-gather over
    ``model`` — k entries per shard, not the (B, I) score matrix — and
    a second ``top_k`` picks the global winners in global item
    coordinates. Per-device traffic is O(B_local * n_model * k), the
    classic distributed top-k merge; ICI carries only candidates.

    Shape contracts match the other top-k paths where the mesh allows:
    ``k`` clamps to the catalog (a shard's local top-k clamps to its
    own rows and the merge recovers the global k — tall-skinny meshes
    like 1×8 serve k > rows-per-shard correctly), and a query batch
    not divisible by the ``data`` axis pads with zero query rows whose
    results are sliced off (B=1 single-query serving works on any
    mesh). The catalog itself MUST divide the ``model`` axis — the
    table is persistent sharded state, so padding it per call would
    copy the one array this path exists to avoid copying; callers pad
    once at staging/load time (models/als.py does)."""
    I = item_f.shape[0]
    n_model = int(mesh.shape["model"])
    if I % n_model:
        raise ValueError(
            f"catalog rows ({I}) must divide the model axis ({n_model}); "
            "pad the item table")
    k = min(k, I)                   # the shared clamp-not-assert contract
    n_data = int(mesh.shape["data"])
    b = user_vecs.shape[0]
    pad = (-b) % n_data
    if pad:
        user_vecs = jnp.concatenate(
            [user_vecs, jnp.zeros((pad, user_vecs.shape[1]),
                                  dtype=user_vecs.dtype)])
        seen_cols = jnp.concatenate(
            [jnp.asarray(seen_cols, dtype=jnp.int32),
             jnp.zeros((pad, seen_cols.shape[1]), dtype=jnp.int32)])
        sm = jnp.asarray(seen_mask)
        seen_mask = jnp.concatenate(
            [sm, jnp.zeros((pad, sm.shape[1]), dtype=sm.dtype)])
    fn = _sharded_topk_fn(mesh, k, I // n_model)
    vals, idxs = fn(user_vecs, item_f, seen_cols, seen_mask, allow)
    if pad:
        vals, idxs = vals[:b], idxs[:b]
    return vals, idxs


@functools.lru_cache(maxsize=16)
def _sharded_topk_fn(mesh, k: int, shard_rows: int):
    """Cached jitted shard_map program — jit caches by function
    identity, so rebuilding the closure per call would retrace and
    recompile the eval hot path on every invocation."""
    from jax.sharding import PartitionSpec as P

    # a shard can only contribute its own rows: on tall-skinny meshes
    # (model axis > I/k, e.g. 1×8 serving a small catalog) the local
    # top-k clamps to shard_rows and the gathered n_model * k_loc >= k
    # candidates still recover the exact global top-k
    k_loc = min(k, shard_rows)

    def local(uv, itf, sc, sm, al):
        start = jax.lax.axis_index("model") * shard_rows
        scores = _scores(uv, itf)                           # (b, rows)
        scores = jnp.where(al > 0, scores, NEG_INF)
        loc = sc - start
        in_shard = (loc >= 0) & (loc < shard_rows) & (sm > 0)
        rows = jnp.broadcast_to(jnp.arange(uv.shape[0])[:, None], sc.shape)
        hide = jnp.where(in_shard, NEG_INF, jnp.float32(jnp.inf))
        scores = scores.at[rows, jnp.clip(loc, 0, shard_rows - 1)].min(hide)
        v, i = jax.lax.top_k(scores, k_loc)                 # local winners
        gi = (i + start).astype(jnp.int32)
        vg = jax.lax.all_gather(v, "model", axis=1, tiled=True)
        ig = jax.lax.all_gather(gi, "model", axis=1, tiled=True)
        vv, sel = jax.lax.top_k(vg, k)
        return vv, jnp.take_along_axis(ig, sel, axis=1)

    specs = dict(
        in_specs=(P("data", None), P("model", None), P("data", None),
                  P("data", None), P("model")),
        out_specs=(P("data", None), P("data", None)),
    )
    # the all-gather makes both outputs replicated over "model", which
    # the static replication checker cannot infer — disable it
    return instrumented_jit(
        jax.shard_map(local, mesh=mesh, check_vma=False, **specs),
        jit_name="sharded_topk")


@partial(instrumented_jit, static_argnames=("k",))
def similar_topk(
    query_vecs: jax.Array,   # (B, K) query item factors
    item_f: jax.Array,       # (I, K)
    exclude_cols: jax.Array,  # (B, E) the query items themselves (padded)
    exclude_mask: jax.Array,  # (B, E)
    allow: jax.Array,         # (I,) or (B, I)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Cosine-similarity top-k — the similarproduct template's ranking."""
    qn = query_vecs / jnp.maximum(
        jnp.linalg.norm(query_vecs, axis=-1, keepdims=True), 1e-9
    )
    itn = item_f / jnp.maximum(
        jnp.linalg.norm(item_f, axis=-1, keepdims=True), 1e-9
    )
    scores = jnp.einsum("bk,ik->bi", qn, itn)
    scores = jnp.where(allow > 0, scores, NEG_INF)
    b = scores.shape[0]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], exclude_cols.shape)
    hide = jnp.where(exclude_mask > 0, NEG_INF, jnp.float32(jnp.inf))
    scores = scores.at[rows, exclude_cols].min(hide)
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))
