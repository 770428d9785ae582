"""ANN maximum-inner-product retrieval: IVF-flat index + exact rescore.

The serving paths in ops/topk score the FULL item table per query —
O(catalog) forever. This module adds the classic sublinear
alternative (FAISS-style IVF-flat, the survey's "shortlist then
rescore" shape):

- **build** (train/persist time, host-side numpy): k-means over the
  item-factor table partitions the catalog into ``nlist`` cells; the
  membership lives in CSR form — ``flat_items`` (item ids grouped by
  cell), ``flat_vecs`` (their vectors in the same order, so each
  cell's block is contiguous), ``cell_offset`` — jit-friendly dense
  arrays, checkpointable through the existing ``utils/checkpoint``
  envelope, and device-resident at serving time. An earlier padded
  ``(nlist, pad, K)`` block layout paid MAX cell size per probe: with
  balanced lists capped at 2x the mean, HALF the gathered bytes were
  padding — the CSR gather of only real members measured 2.1x faster
  on the dominant stage at the 1M point (0.9ms vs 1.9ms) and stores
  one copy of the vectors instead of two;
- **probe** (serving time, one jitted dispatch): score the query
  against the ``nlist`` centroids (a (B, nlist) matmul — tiny), take
  the top ``nprobe`` cells, and walk their CSR runs into a
  statically-budgeted shortlist (:func:`_budget_width`: ~1.25x the
  mean probed mass; overflow truncates the tail of the WORST-scoring
  probed cells, and the quality harness measures the effect rather
  than assuming it away);
- **exact rescore**: the shortlist's item vectors are gathered from
  the SAME factor table brute force uses and scored with the SAME
  inner product — ranking within the shortlist is exact, so quality
  loss is purely recall (did the true top-k land in a probed cell),
  which the quality harness measures instead of assuming.

Seen-item and business-rule masking keep working on the shortlist: the
``allow`` vector is gathered per candidate, and seen lists mask by
membership test in global item coordinates (a ``lax.scan`` over the
seen width — O(B x S) per seen column, never a (B, S, seen) cube in
memory). Sentinel/-inf semantics match ``recommend_topk_chunked``:
slots beyond the eligible candidates carry -inf values and
out-of-range indices (>= n_items), and callers must treat non-finite
slots as absent — which every in-repo consumer already does.

Static-shape discipline (the serving contract): ``k``, ``nprobe`` and
the rescore budget are jit-static and snapped by callers to the shared
serving menus, so a client cycling query parameters can never mint a
fresh compile behind the micro-batcher.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs.compile import instrumented_jit

from predictionio_tpu.ops.topk import NEG_INF

logger = logging.getLogger(__name__)

#: below this catalog size the flat matmul beats any probe+gather trip
#: and the index is pure overhead — build refuses, serving falls back
#: to brute (also the guard that keeps tiny unit-test models index-free)
MIN_INDEX_ITEMS = 1024

#: bounds for the auto nlist heuristic (~sqrt(catalog), power of two)
_MIN_NLIST = 8
_MAX_NLIST = 4096


def auto_nlist(n_items: int) -> int:
    """Power-of-two cell count near 4*sqrt(catalog) — the FAISS-style
    IVF sizing band (4..16 x sqrt(n)). Finer cells beat the sqrt(n)
    textbook point on BOTH axes here: each probed column is likelier
    relevant (recall per rescored byte) and the per-probe run is
    smaller (measured at the 1M point: nlist=4096/nprobe=64 gives
    0.998 recall at 0.8ms where nlist=1024/nprobe=64 gave 0.969 at
    7.8ms); the probe matmul (B x nlist) stays trivial."""
    if n_items <= 0:
        return _MIN_NLIST
    target = 1 << round(math.log2(max(4.0 * math.sqrt(n_items), 2.0)))
    # floor the MEAN cell size at ~128 members: finer cells on small
    # catalogs are noise-dominated (k-means fits the sampling noise,
    # recall per probe drops — measured at 16k items) and their padded
    # blocks waste the probe's streaming advantage
    cap = 1 << max(int(math.log2(n_items // 128)), 3) \
        if n_items >= 1024 else _MIN_NLIST
    return max(_MIN_NLIST, min(_MAX_NLIST, target, cap))


def auto_nprobe(nlist: int) -> int:
    """Default probe count: 1/64 of the cells, floored at 16. At the
    auto nlist (4*sqrt(n) cells) this rescores ~2-3% of the catalog,
    the measured MAP@10-within-1%-of-brute point on factor-shaped data
    (1M items: 64/4096 probes = 0.998 recall; the floor covers small
    catalogs where recall per probed cell is lower); callers clamp to
    nlist via :meth:`AnnIndex.clamp_nprobe`."""
    return max(16, nlist // 64)


#: static shortlist budget = nprobe x mean cell size x this margin.
#: The CSR walk needs a jit-static candidate width; the mean probed
#: mass is nprobe x (n/nlist), and 1.25x absorbs most of the
#: sum-of-probed-cell-sizes variance (cells are capacity-capped at
#: ``balance``x the mean, so the worst case is bounded). When the
#: probed runs overflow the budget, the TAIL — the worst-scoring
#: probed cells, since runs concatenate in probe-score order — is
#: truncated; the quality harness measures that recall cost.
_BUDGET_MARGIN = 1.25


def _budget_width(n_items: int, nlist: int, nprobe: int,
                  rescore: int) -> int:
    """The static candidate-column count of a probe with these knobs
    (:data:`_BUDGET_MARGIN`); ``rescore > 0`` caps it."""
    mean = max(1.0, n_items / max(nlist, 1))
    width = min(n_items, int(math.ceil(nprobe * mean * _BUDGET_MARGIN)))
    if rescore > 0:
        width = min(width, rescore)
    return max(1, width)


@dataclasses.dataclass
class AnnIndex:
    """IVF-flat coarse quantizer over an item-factor table, CSR layout.

    Host numpy arrays are canonical (they serialize through the
    checkpoint envelope); device copies are materialised once on first
    query and cached — the same lazy-device pattern as
    ``ALSModel._default_allow``.
    """

    nlist: int
    n_items: int
    centroids: np.ndarray    # (nlist, K) f32
    #: item ids grouped by cell — cell c's members are
    #: flat_items[cell_offset[c]:cell_offset[c+1]]
    flat_items: np.ndarray   # (n_items,) int32
    #: the member vectors in the SAME cell-grouped order: each probed
    #: cell rescores from one contiguous run, which is the layout win
    #: IVF-flat exists for (module docstring: 2.1x over padded blocks,
    #: and one copy of the vectors instead of balance-x two). Values
    #: are bit-identical to the factor table rows — rescore is EXACT.
    flat_vecs: np.ndarray = None    # (n_items, K) f32
    cell_offset: np.ndarray = None  # (nlist + 1,) int32
    _device: tuple | None = dataclasses.field(default=None, repr=False,
                                              compare=False)

    @property
    def max_cell(self) -> int:
        return int(np.diff(self.cell_offset).max())

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_device"] = None
        return state

    def device_arrays(self) -> tuple:
        """(centroids, flat_items, flat_vecs, cell_offset) as
        device-resident jax.Arrays, uploaded once."""
        if self._device is None:
            self._device = (
                jax.device_put(jnp.asarray(self.centroids)),
                jax.device_put(jnp.asarray(self.flat_items)),
                jax.device_put(jnp.asarray(self.flat_vecs)),
                jax.device_put(jnp.asarray(self.cell_offset)),
            )
        return self._device

    def clamp_nprobe(self, nprobe: int) -> int:
        """Snap a requested probe count into [1, nlist]; 0 = auto."""
        if nprobe <= 0:
            return min(auto_nprobe(self.nlist), self.nlist)
        return min(nprobe, self.nlist)

    def shortlist_width(self, nprobe: int, rescore: int = 0) -> int:
        """The STATIC candidate-column count a query with these knobs
        walks and rescores (budget slots included) — the jit-signature
        width and the observability number `/stats.json` reports."""
        return _budget_width(self.n_items, self.nlist,
                             self.clamp_nprobe(nprobe), rescore)

    # ---- persistence (utils/checkpoint envelope) -----------------------
    def to_arrays(self) -> dict[str, np.ndarray]:
        return {
            "centroids": self.centroids,
            "flat_items": self.flat_items,
            "flat_vecs": self.flat_vecs,
            "cell_offset": self.cell_offset,
        }

    @staticmethod
    def from_arrays(arrays: Mapping[str, Any], n_items: int) -> "AnnIndex":
        # dtype-preserving on the persisted layout: the checkpoint
        # writes these at exactly these dtypes, so asarray is a VIEW —
        # under load_sharded(mmap_mode="r") the arrays (flat_vecs
        # above all) stay page-cache-backed and N prefork workers
        # share one physical copy (docs/serving-performance.md
        # "Model memory: replicated vs mmap")
        centroids = np.asarray(arrays["centroids"], dtype=np.float32)
        return AnnIndex(
            nlist=int(centroids.shape[0]),
            n_items=int(n_items),
            centroids=centroids,
            flat_items=np.asarray(arrays["flat_items"], dtype=np.int32),
            flat_vecs=np.asarray(arrays["flat_vecs"], dtype=np.float32),
            cell_offset=np.asarray(arrays["cell_offset"], dtype=np.int32),
        )


# ---------------------------------------------------------------------------
# build (host-side numpy; train/persist time, never on the query path)
# ---------------------------------------------------------------------------


def _assign(x: np.ndarray, centroids: np.ndarray,
            chunk: int = 65536) -> np.ndarray:
    """Nearest-centroid assignment, chunked so a million-item catalog
    never materialises the full (n, nlist) distance matrix. argmin of
    the L2 distance == argmax of (x·c - |c|^2/2)."""
    half = 0.5 * np.einsum("ck,ck->c", centroids, centroids)
    out = np.empty(len(x), dtype=np.int32)
    for lo in range(0, len(x), chunk):
        scores = x[lo:lo + chunk] @ centroids.T
        scores -= half[None, :]
        out[lo:lo + chunk] = np.argmax(scores, axis=1).astype(np.int32)
    return out


#: ranked alternative cells considered per item by the balanced
#: assignment before the any-cell-with-space fallback. 16 matters:
#: with 4 choices on clustered factors, overflow items landed in
#: geometrically unrelated cells and became unreachable at any sane
#: nprobe — recall PLATEAUED at 0.986 no matter how many cells a 1M
#: query probed; 16 ranked choices keep spills near their cluster and
#: lifted the same sweep to 0.998
_BALANCE_CHOICES = 16


def _assign_balanced(x: np.ndarray, centroids: np.ndarray, cap: int,
                     chunk: int = 65536) -> np.ndarray:
    """Capacity-bounded assignment: every cell holds at most ``cap``
    members. The shortlist budget is sized from the MEAN cell
    (:data:`_BUDGET_MARGIN`), so one hot k-means cell — measured 4x
    the mean on clustered factors — would eat the whole budget and
    truncate every other probed cell out of the rescore. Items
    overflowing their nearest cell spill to the next-nearest with
    space (up to ``_BALANCE_CHOICES`` ranked choices, then any cell
    with room); spilled items stay reachable, costing recall only when
    a query probes the full cell but not the neighbour — which the
    quality harness measures rather than assumes. (Tightening the cap
    toward 1x the mean is NOT free: at 1.05-1.3x, recall plateaued at
    ~0.93 no matter the nprobe — too many items spill beyond their
    cluster's neighbourhood; 2x keeps the 0.998+ sweeps.)"""
    nlist = len(centroids)
    half = 0.5 * np.einsum("ck,ck->c", centroids, centroids)
    n_choices = min(_BALANCE_CHOICES, nlist)
    choices = np.empty((len(x), n_choices), dtype=np.int32)
    for lo in range(0, len(x), chunk):
        scores = x[lo:lo + chunk] @ centroids.T
        scores -= half[None, :]
        top = np.argpartition(scores, -n_choices, axis=1)[:, -n_choices:]
        row = np.arange(len(top))[:, None]
        order = np.argsort(scores[row, top], axis=1)[:, ::-1]
        choices[lo:lo + chunk] = top[row, order].astype(np.int32)
    assign = np.full(len(x), -1, dtype=np.int32)
    counts = np.zeros(nlist, dtype=np.int64)
    for r in range(n_choices):
        unplaced = np.nonzero(assign < 0)[0]
        if not len(unplaced):
            break
        cells = choices[unplaced, r]
        order = np.argsort(cells, kind="stable")
        sorted_cells = cells[order]
        starts = np.searchsorted(sorted_cells, np.arange(nlist))
        rank = np.arange(len(sorted_cells)) - starts[sorted_cells]
        ok = rank < (cap - counts)[sorted_cells]
        assign[unplaced[order[ok]]] = sorted_cells[ok]
        counts += np.bincount(sorted_cells[ok], minlength=nlist)
    leftover = np.nonzero(assign < 0)[0]
    if len(leftover):
        space = np.repeat(np.arange(nlist, dtype=np.int32),
                          np.maximum(cap - counts, 0))
        assign[leftover] = space[:len(leftover)]
    return assign


#: rows per device_get chunk when the index build must gather a
#: sharded factor table to host — bounds the staging buffer to
#: ~chunk*rank*4 bytes (64 MiB at rank 512) regardless of table size
_GATHER_CHUNK_ROWS = 32768


def _host_vectors(item_f: Any) -> np.ndarray:
    """The item-factor table as host float32 rows, WITHOUT assuming it
    already lives on the host. Three sources, three behaviors:

    - plain ndarray / ``np.memmap`` (``--model-mmap`` deploys): pass
      through — ``ascontiguousarray`` on a contiguous f32 memmap is a
      view, so the page-cache sharing survives and no full copy is
      staged up front;
    - replicated / single-device ``jax.Array``: one device_get, as the
      build always did;
    - **row-sharded** ``jax.Array`` (a ``shard_factors`` model): the
      shards are gathered one bounded chunk at a time
      (:data:`_GATHER_CHUNK_ROWS` rows per ``device_get``) into one
      preallocated host buffer, with a pinned WARNING — the k-means
      build is the one consumer that genuinely needs the whole table
      host-resident, and a forced gather should be visible in deploy
      logs. Never replicates on device (the sharded table may not FIT
      replicated) and never stages more than one chunk of transfer at
      a time."""
    if isinstance(item_f, jax.Array) and not isinstance(item_f, np.ndarray):
        shards = list(getattr(item_f, "addressable_shards", ()) or ())
        if len(shards) > 1 and not item_f.is_fully_replicated:
            out = np.empty(item_f.shape, dtype=np.float32)
            logger.warning(
                "ann index build forcing a chunked host gather of the "
                "sharded item table (%d rows x %d, %d shards, %d-row "
                "chunks)", item_f.shape[0], item_f.shape[1],
                len(shards), _GATHER_CHUNK_ROWS)
            done_rows: set[int] = set()
            for shard in shards:
                rows = shard.index[0] if shard.index else slice(None)
                start = int(rows.start or 0)
                if start in done_rows:
                    continue  # data-axis replica of a row block
                done_rows.add(start)
                data = shard.data
                for lo in range(0, int(data.shape[0]), _GATHER_CHUNK_ROWS):
                    hi = min(lo + _GATHER_CHUNK_ROWS, int(data.shape[0]))
                    out[start + lo : start + hi] = np.asarray(
                        data[lo:hi], dtype=np.float32)
            return out
        return np.ascontiguousarray(np.asarray(item_f), dtype=np.float32)
    return np.ascontiguousarray(np.asarray(item_f), dtype=np.float32)


def build_index(item_f: Any, nlist: int = 0, seed: int = 0,
                iters: int = 8, sample: int = 131072,
                balance: float = 2.0) -> AnnIndex | None:
    """K-means coarse quantizer over the item-factor table.

    Lloyd iterations run on a seeded SAMPLE (k-means converges on the
    density, not the row count — a full-catalog fit would spend minutes
    of the persist stage for no recall gain), then ONE chunked
    full-catalog balanced-assignment pass builds the cell membership
    tables: list sizes are capped at ``balance`` x the mean so a hot
    cell cannot inflate every query's padded shortlist (the dense cell
    table gathers pad slots; see :func:`_assign_balanced`). Empty cells
    re-seed from random rows so every probe has members.

    Returns None for catalogs under :data:`MIN_INDEX_ITEMS`, where the
    flat matmul wins outright and an index is pure overhead.
    """
    x = _host_vectors(item_f)
    n = int(x.shape[0])
    if n < MIN_INDEX_ITEMS:
        return None
    nlist = nlist if nlist > 0 else auto_nlist(n)
    nlist = max(1, min(nlist, n))
    rng = np.random.default_rng(seed)
    train = x if n <= sample else x[rng.choice(n, size=sample,
                                               replace=False)]
    # a sampled k-means fit cannot seed more centroids than sample
    # rows: an oversized explicit nlist clamps (degrade-don't-die, like
    # every other config knob) instead of crashing the persist stage
    nlist = min(nlist, len(train))
    centroids = train[rng.choice(len(train), size=nlist,
                                 replace=False)].copy()
    for _ in range(max(1, iters)):
        assign = _assign(train, centroids)
        sums = np.zeros_like(centroids)
        np.add.at(sums, assign, train)
        counts = np.bincount(assign, minlength=nlist)
        nonempty = counts > 0
        centroids[nonempty] = (sums[nonempty]
                               / counts[nonempty, None].astype(np.float32))
        n_empty = int((~nonempty).sum())
        if n_empty:
            centroids[~nonempty] = train[rng.choice(
                len(train), size=n_empty, replace=False)]
    cap = max(1, int(math.ceil(max(balance, 1.0) * n / nlist)))
    assign = _assign_balanced(x, centroids, cap)
    counts = np.bincount(assign, minlength=nlist)
    # CSR cell grouping (class docstring): the stable argsort IS the
    # flat item order, and the vector copy in that order makes every
    # cell's rescore block contiguous
    flat_items = np.argsort(assign, kind="stable").astype(np.int32)
    cell_offset = np.concatenate(
        [[0], np.cumsum(counts)]).astype(np.int32)
    flat_vecs = np.ascontiguousarray(x[flat_items])
    return AnnIndex(nlist=nlist, n_items=n, centroids=centroids,
                    flat_items=flat_items, flat_vecs=flat_vecs,
                    cell_offset=cell_offset)


# ---------------------------------------------------------------------------
# probe + gather + exact rescore (jitted; the serving path)
# ---------------------------------------------------------------------------


def _shortlist(query_vecs, centroids, flat_items, flat_vecs, cell_offset,
               nprobe: int, rescore: int):
    """(candidate ids (B, S) int32, valid mask (B, S), candidate
    vectors (B, S, K)) for the top-nprobe cells per query: the probed
    cells' CSR runs concatenated in probe-score order into the static
    budget width (:func:`_budget_width`). Column j of the budget maps
    to (cell, offset) by binary search over the probed cells' running
    sizes; the vector gather then reads each cell's contiguous run
    from ``flat_vecs`` (module docstring — the 2.1x over padded
    blocks). Columns past the probed mass carry mask 0; probed mass
    past the budget drops from the tail (worst-scoring cells)."""
    n_items = int(flat_items.shape[0])
    nlist = int(cell_offset.shape[0]) - 1
    width = _budget_width(n_items, nlist, nprobe, rescore)
    cell_scores = jnp.einsum("bk,ck->bc", query_vecs, centroids)
    _, probes = jax.lax.top_k(cell_scores, nprobe)        # (B, P)

    def row(probes_r):
        sizes = cell_offset[probes_r + 1] - cell_offset[probes_r]
        cum = jnp.cumsum(sizes)                            # (P,)
        j = jnp.arange(width, dtype=jnp.int32)             # (S,)
        # j lands in probed cell p iff cum[p-1] <= j < cum[p]
        p = jnp.clip(jnp.searchsorted(cum, j, side="right"),
                     0, probes_r.shape[0] - 1)
        prev = jnp.where(p > 0, cum[p - 1], 0)
        valid = j < cum[-1]
        flat = jnp.where(valid, cell_offset[probes_r[p]] + (j - prev), 0)
        return flat, valid

    flat, valid = jax.vmap(row)(probes)                    # (B, S)
    b = query_vecs.shape[0]
    cand = flat_items[flat.reshape(-1)].reshape(b, width)
    vecs = flat_vecs[flat.reshape(-1)].reshape(b, width, -1)
    return cand, valid.astype(query_vecs.dtype), vecs


def _mask_seen(cand, scores, seen_cols, seen_mask):
    """-inf out candidates present in each row's seen list, by sorted
    membership test: sort each row's seen ids (pad slots pushed to
    int32-max, which no catalog index reaches), binary-search every
    candidate, and compare at the insertion point — O(S log seen) per
    row. The two obvious alternatives both lose at serving shapes: a
    ``lax.scan`` over seen columns is seen-pad sequential XLA dispatches
    (512 x ~35µs ≈ 18ms/query of pure scan overhead — 9x the whole
    probe+rescore kernel), and the one-shot (B, S, seen) comparison
    cube is S x seen-pad work per row (~13M compares at the 1M-point
    shortlist, measured ~4ms and linear in the pad)."""
    big = jnp.int32(np.iinfo(np.int32).max)
    seen = jnp.sort(jnp.where(seen_mask > 0, seen_cols, big), axis=1)

    def row(seen_r, cand_r):
        pos = jnp.clip(jnp.searchsorted(seen_r, cand_r), 0,
                       seen_r.shape[0] - 1)
        return seen_r[pos] == cand_r

    hit = jax.vmap(row)(seen, cand)
    return jnp.where(hit, NEG_INF, scores)


def _finish(cand, scores, k: int, n_items: int):
    """Top-k over the shortlist with the chunked-path result contract:
    k clamps to the shortlist width, -inf slots carry out-of-range
    sentinel indices so a caller ignoring score finiteness can never
    serve a pad/duplicate candidate as a real item."""
    k = min(k, scores.shape[1])
    vals, sel = jax.lax.top_k(scores, k)
    idxs = jnp.take_along_axis(cand, sel, axis=1)
    sentinels = n_items + jnp.arange(k, dtype=jnp.int32)[None, :]
    idxs = jnp.where(jnp.isfinite(vals), idxs, sentinels)
    return vals, idxs


def _ann_topk_impl(user_vecs, item_f, centroids, flat_items, flat_vecs,
                   cell_offset, seen_cols, seen_mask, allow, k: int,
                   nprobe: int, rescore: int):
    """Vectorized probe → CSR-run rescore → mask → top-k for one
    (B, ...) group — the body :func:`ann_topk` dispatches to."""
    cand, pad_mask, vecs = _shortlist(user_vecs, centroids, flat_items,
                                      flat_vecs, cell_offset, nprobe,
                                      rescore)
    scores = jnp.einsum("bk,bsk->bs", user_vecs, vecs)     # exact rescore
    scores = jnp.where(pad_mask > 0, scores, NEG_INF)
    if allow.ndim == 1:
        scores = jnp.where(allow[cand] > 0, scores, NEG_INF)
    else:
        scores = jnp.where(
            jnp.take_along_axis(allow, cand, axis=1) > 0, scores, NEG_INF)
    scores = _mask_seen(cand, scores, seen_cols, seen_mask)
    return _finish(cand, scores, k, item_f.shape[0])


@partial(instrumented_jit, static_argnames=("k", "nprobe", "rescore"))
def ann_topk(
    user_vecs: jax.Array,    # (B, K) query user factors
    item_f: jax.Array,       # (I, K) item factor table (the brute table)
    centroids: jax.Array,    # (C, K)
    flat_items: jax.Array,   # (I,) int32, cell-grouped item ids
    flat_vecs: jax.Array,    # (I, K) vectors in the same order
    cell_offset: jax.Array,  # (C + 1,) int32
    seen_cols: jax.Array,    # (B, S) int32, padded
    seen_mask: jax.Array,    # (B, S) 1=real 0=pad
    allow: jax.Array,        # (I,) or (B, I) 0/1 eligibility
    k: int,
    nprobe: int,
    rescore: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """ANN counterpart of :func:`ops.topk.recommend_topk`: probe the
    top-``nprobe`` cells, walk their CSR runs as the shortlist,
    exact-rescore with the true inner product, mask seen/ineligible
    candidates, top-k. One jitted dispatch; results in GLOBAL item
    coordinates. ``item_f`` only provides the sentinel base
    (``n_items``) — the rescore reads the cell-grouped runs.

    Batches run as a ``lax.map`` over rows rather than one vectorized
    gather: each row's probed runs then stream through cache one query
    at a time, where the batched (B, S, K) gather thrashes it —
    measured at the 1M point on the padded layout, 1.8ms/query mapped
    vs 4.4ms/query vectorized at B=24. Batching buys ANN no device
    win (there is no shared full-table traversal to amortize, unlike
    brute) — the map keeps batched callers at the B=1 rate, and the
    serving batcher still amortizes the per-dispatch HOST cost."""
    if user_vecs.shape[0] <= 1:
        return _ann_topk_impl(user_vecs, item_f, centroids, flat_items,
                              flat_vecs, cell_offset, seen_cols, seen_mask,
                              allow, k, nprobe, rescore)

    def one(args):
        if allow.ndim == 1:
            uv, sc, sm = args
            al = allow
        else:
            uv, sc, sm, al = args
        vals, idxs = _ann_topk_impl(
            uv[None], item_f, centroids, flat_items, flat_vecs,
            cell_offset, sc[None], sm[None], al, k, nprobe, rescore)
        return vals[0], idxs[0]

    xs = ((user_vecs, seen_cols, seen_mask) if allow.ndim == 1
          else (user_vecs, seen_cols, seen_mask, allow))
    return jax.lax.map(one, xs)


@partial(instrumented_jit, static_argnames=("k", "nprobe", "rescore"))
def ann_similar_topk(
    query_vecs: jax.Array,   # (B, K) query item factors (unnormalized)
    item_f: jax.Array,       # (I, K)
    centroids: jax.Array,    # (C, K)
    flat_items: jax.Array,   # (I,) int32, cell-grouped item ids
    flat_vecs: jax.Array,    # (I, K) vectors in the same order
    cell_offset: jax.Array,  # (C + 1,) int32
    exclude_cols: jax.Array,  # (B, E) the query items themselves
    exclude_mask: jax.Array,  # (B, E)
    allow: jax.Array,         # (I,) or (B, I)
    k: int,
    nprobe: int,
    rescore: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """ANN counterpart of :func:`ops.topk.similar_topk` (cosine): probe
    and rescore in the normalized space — cosine similarity is the
    inner product of unit vectors, so the SAME index (built on raw
    factors) answers it by normalizing the query, the centroids and the
    streamed candidate runs in-kernel. Ranking within the shortlist
    is exactly similar_topk's."""
    qn = query_vecs / jnp.maximum(
        jnp.linalg.norm(query_vecs, axis=-1, keepdims=True), 1e-9)
    cn = centroids / jnp.maximum(
        jnp.linalg.norm(centroids, axis=-1, keepdims=True), 1e-9)
    cand, pad_mask, vecs = _shortlist(qn, cn, flat_items, flat_vecs,
                                      cell_offset, nprobe, rescore)
    vn = vecs / jnp.maximum(
        jnp.linalg.norm(vecs, axis=-1, keepdims=True), 1e-9)
    scores = jnp.einsum("bk,bsk->bs", qn, vn)
    scores = jnp.where(pad_mask > 0, scores, NEG_INF)
    if allow.ndim == 1:
        scores = jnp.where(allow[cand] > 0, scores, NEG_INF)
    else:
        scores = jnp.where(
            jnp.take_along_axis(allow, cand, axis=1) > 0, scores, NEG_INF)
    scores = _mask_seen(cand, scores, exclude_cols, exclude_mask)
    return _finish(cand, scores, k, item_f.shape[0])


# ---------------------------------------------------------------------------
# quality measurement (the recall/MAP code tests/test_ann.py pins)
# ---------------------------------------------------------------------------


def quality_vs_brute(index: AnnIndex, user_vecs: np.ndarray,
                     item_f: Any, k: int = 10, nprobe: int = 0,
                     rescore: int = 0) -> dict:
    """Recall@shortlist and MAP@k of the ANN ranking against brute
    force as ground truth.

    - ``recall_at_shortlist``: fraction of each query's TRUE top-k
      (exact full-catalog MIPS) whose items landed in the probed
      shortlist at all — the only quality the index can lose, since
      rescoring is exact;
    - ``map_at_k``: mean average precision of the ANN top-k treating
      the brute top-k as the relevant set (brute MAP@k is 1.0 by
      construction, so "within 1% of brute" means map_at_k >= 0.99).
    """
    from predictionio_tpu.ops import topk as topk_ops

    nprobe = index.clamp_nprobe(nprobe)
    uv = jnp.asarray(np.asarray(user_vecs, dtype=np.float32))
    itf = jnp.asarray(item_f)
    b = int(uv.shape[0])
    no_seen_cols = jnp.zeros((b, 1), dtype=jnp.int32)
    no_seen_mask = jnp.zeros((b, 1), dtype=jnp.float32)
    allow = jnp.ones((itf.shape[0],), dtype=jnp.float32)
    bv, bi = topk_ops.recommend_topk(uv, itf, no_seen_cols, no_seen_mask,
                                     allow, min(k, int(itf.shape[0])))
    centroids, flat_items, flat_vecs, cell_offset = index.device_arrays()
    cand, pad_mask, _ = _shortlist(uv, centroids, flat_items, flat_vecs,
                                   cell_offset, nprobe, rescore)
    av, ai = ann_topk(uv, itf, centroids, flat_items, flat_vecs,
                      cell_offset, no_seen_cols, no_seen_mask, allow, k,
                      nprobe, rescore)
    bi_h, bv_h = np.asarray(bi), np.asarray(bv)
    ai_h, av_h = np.asarray(ai), np.asarray(av)
    cand_h = np.where(np.asarray(pad_mask) > 0, np.asarray(cand), -1)
    recalls, aps = [], []
    for row in range(b):
        truth = [int(i) for i, v in zip(bi_h[row], bv_h[row])
                 if np.isfinite(v)]
        if not truth:
            continue
        shortlist = set(int(c) for c in cand_h[row] if c >= 0)
        recalls.append(sum(1 for i in truth if i in shortlist) / len(truth))
        relevant = set(truth)
        hits, precision_sum = 0, 0.0
        ranked = [int(i) for i, v in zip(ai_h[row], av_h[row])
                  if np.isfinite(v)][:k]
        for rank, item in enumerate(ranked, start=1):
            if item in relevant:
                hits += 1
                precision_sum += hits / rank
        aps.append(precision_sum / min(k, len(relevant)))
    return {
        "recall_at_shortlist": float(np.mean(recalls)) if recalls else 1.0,
        "map_at_k": float(np.mean(aps)) if aps else 1.0,
        "k": k,
        "nprobe": nprobe,
        "shortlist_width": index.shortlist_width(nprobe, rescore),
        "queries": len(recalls),
    }
