"""Alternating Least Squares on the MXU — the framework's north-star kernel.

Replaces: org.apache.spark.mllib.recommendation.ALS as invoked by the
reference's recommendation templates (reference: tests/pio_tests/engines/
recommendation-engine/src/main/scala/ALSAlgorithm.scala:79-85 and
examples/scala-parallel-{recommendation,similarproduct,
ecommercerecommendation}). Supports explicit ratings (ALS-WR weighted-λ
regularization) and implicit feedback (Hu-Koren-Volinsky confidence
weighting), like MLlib's `ALS.train` / `ALS.trainImplicit`.

TPU-first design (NOT a translation of MLlib's block solver):

- **Bucketed dense layout.** Ratings are grouped per row (user for the
  user half-step, item for the item half-step) and padded to power-of-two
  lengths, rows of similar degree sharing a bucket. Each bucket is a dense
  ``(rows, pad_len)`` slab, so the normal-equation build
  ``A_u = Σ v_i v_iᵀ`` is one batched matmul ``einsum('blk,blm->bkm')``
  that tiles straight onto the MXU — no scatter/segment ops, which are
  slow on TPU. Padding waste is bounded by the bucket growth factor.
- **Static shapes.** Bucket shapes are the only compile keys; iteration
  count, λ, α are runtime values. lax.scan over fixed-size slabs bounds
  the solver's working set; rating slabs are HBM-resident by default
  (fastest) or streamed per bucket with ``hbm_resident=False`` when the
  padded rating set exceeds device memory.
- **Batched conjugate-gradient solves.** Per-row K×K SPD systems are
  solved with batched-matvec CG (``_cg_solve_batched``) — XLA's batched
  cholesky/triangular_solve lower to sequential scalar loops and run
  ~10-20x slower on TPU; the ridge-regularised systems hit CG's f32
  accuracy floor within ~16-24 steps at every rank.
- **Mesh sharding.** Slab row dimensions carry a NamedSharding over the
  "data" mesh axis while factor tables stay replicated; XLA inserts the
  all-gathers/psums on ICI — the analogue of MLlib's block shuffles,
  without the shuffle.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs.compile import instrumented_jit

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Host-side layout: COO ratings -> padded per-row buckets
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RatingsCOO:
    """Host ratings triple; rows/cols are dense indices (see utils.bimap)."""

    rows: np.ndarray  # int32 (R,)
    cols: np.ndarray  # int32 (R,)
    vals: np.ndarray  # float32 (R,)
    num_rows: int
    num_cols: int

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def transpose(self) -> "RatingsCOO":
        return RatingsCOO(self.cols, self.rows, self.vals, self.num_cols, self.num_rows)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """All rows whose degree pads to ``pad_len``: dense (n, pad_len) slabs.

    Entries are packed to the row prefix, so the pad mask is fully
    determined by ``deg`` and derived on demand."""

    row_ids: np.ndarray  # int32 (n,) original row indices
    cols: np.ndarray     # int32 (n, pad_len)
    vals: np.ndarray     # float32 (n, pad_len)
    deg: np.ndarray      # int32 (n,) real entries per row

    @property
    def pad_len(self) -> int:
        return int(self.cols.shape[1])

    @property
    def mask(self) -> np.ndarray:
        """(n, pad_len) f32 — 1 for real entries, 0 for padding."""
        return (
            np.arange(self.pad_len, dtype=np.int32)[None, :]
            < self.deg[:, None]
        ).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class BucketedRatings:
    buckets: tuple[Bucket, ...]
    num_rows: int
    num_cols: int
    nnz: int


def bucket_rows(
    coo: RatingsCOO, min_len: int = 8, growth: int = 2,
    max_len: int | None = None, use_native: bool = True,
) -> BucketedRatings:
    """Group ratings by row into padded power-of-``growth`` buckets.

    ``max_len`` caps a row's kept ratings (highest-value kept) — the
    recompile-control knob for pathological heavy rows.

    The packing pass runs in native C++ when available (one counting
    sort + one fill over nnz entries, native/bucketize.cc); the NumPy
    path below is the fallback with an identical slab layout.
    """
    if use_native:
        native = _bucket_rows_native(coo, min_len, growth, max_len)
        if native is not None:
            return native
    order = np.argsort(coo.rows, kind="stable")
    rows = coo.rows[order]
    cols = coo.cols[order]
    vals = coo.vals[order]
    uniq, start, counts = np.unique(rows, return_index=True, return_counts=True)

    if max_len is not None:
        capped = np.minimum(counts, max_len)
    else:
        capped = counts
    # bucket length per unique row: min_len * growth^k >= count
    lens = np.maximum(capped, min_len)
    exps = np.ceil(np.log(lens / min_len) / np.log(growth) - 1e-12).astype(np.int64)
    pad_lens = (min_len * growth ** np.maximum(exps, 0)).astype(np.int64)

    buckets = []
    for pl in np.unique(pad_lens):
        sel = np.nonzero(pad_lens == pl)[0]
        n = len(sel)
        b_cols = np.zeros((n, pl), dtype=np.int32)
        b_vals = np.zeros((n, pl), dtype=np.float32)
        for j, ui in enumerate(sel):
            s, c = start[ui], capped[ui]
            if c < counts[ui]:  # keep the top-valued ratings of a capped row
                seg = np.argsort(vals[s : s + counts[ui]])[::-1][:c] + s
            else:
                seg = slice(s, s + c)
            b_cols[j, :c] = cols[seg]
            b_vals[j, :c] = vals[seg]
        buckets.append(
            Bucket(uniq[sel].astype(np.int32), b_cols, b_vals,
                   capped[sel].astype(np.int32))
        )
    return BucketedRatings(tuple(buckets), coo.num_rows, coo.num_cols, coo.nnz)


def _native_i32p():
    import ctypes

    return ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float)


def _native_ptr(a, ty):
    return a.ctypes.data_as(ty)


def _native_coo_args(coo: RatingsCOO):
    """Contiguous input buffers + typed pointers for the native layout
    entry points. The returned arrays must stay referenced while the
    native handle is alive."""
    i32_p, f32_p = _native_i32p()
    rows = np.ascontiguousarray(coo.rows, dtype=np.int32)
    cols = np.ascontiguousarray(coo.cols, dtype=np.int32)
    vals = np.ascontiguousarray(coo.vals, dtype=np.float32)
    return (rows, cols, vals,
            _native_ptr(rows, i32_p), _native_ptr(cols, i32_p),
            _native_ptr(vals, f32_p))


def _native_read_slabs(handle, num_fn, info_fn, fill_fn, free_fn, make):
    """Shared readback loop for the handle-based native layout APIs
    (bucketizer and chunker share the same (ids, cols, vals, deg) slab
    contract): query each slab's shape, let the native side fill
    NumPy-allocated buffers, and free the handle."""
    import ctypes

    i32_p, f32_p = _native_i32p()
    try:
        out = []
        for b in range(num_fn(handle)):
            length = ctypes.c_int32()
            n = ctypes.c_int64()
            if info_fn(handle, b, ctypes.byref(length), ctypes.byref(n)):
                return None
            pl, nn = int(length.value), int(n.value)
            b_ids = np.empty((nn,), dtype=np.int32)
            b_cols = np.empty((nn, pl), dtype=np.int32)
            b_vals = np.empty((nn, pl), dtype=np.float32)
            b_deg = np.empty((nn,), dtype=np.int32)
            if fill_fn(handle, b, _native_ptr(b_ids, i32_p),
                       _native_ptr(b_cols, i32_p), _native_ptr(b_vals, f32_p),
                       _native_ptr(b_deg, i32_p)):
                return None
            out.append(make(b_ids, b_cols, b_vals, b_deg))
        return tuple(out)
    finally:
        free_fn(handle)


def _bucket_rows_native(
    coo: RatingsCOO, min_len: int, growth: int, max_len: int | None
) -> BucketedRatings | None:
    """C++ packing path; None when the native toolchain is unavailable."""
    from predictionio_tpu.native import load_bucketize

    lib = load_bucketize()
    if lib is None or coo.nnz == 0:
        return None
    rows, cols, vals, rp, cp, vp = _native_coo_args(coo)
    handle = lib.pio_bucketize(
        coo.nnz, rp, cp, vp, coo.num_rows, min_len, growth,
        0 if max_len is None else max_len,
    )
    if not handle:
        return None
    buckets = _native_read_slabs(
        handle, lib.pio_bucketize_num_buckets, lib.pio_bucketize_bucket_info,
        lib.pio_bucketize_fill, lib.pio_bucketize_free, Bucket)
    if buckets is None:
        return None
    return BucketedRatings(buckets, coo.num_rows, coo.num_cols, coo.nnz)


# ---------------------------------------------------------------------------
# Chunked layout: rows split into fixed-size chunks, per-row accumulation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChunkSlab:
    """All chunks of one fixed length ``L``: dense (n, L) slabs plus the
    row each chunk belongs to. Multiple chunks may share a row — their
    normal-equation contributions are accumulated on device."""

    row_ids: np.ndarray  # int32 (n,) owning row per chunk
    cols: np.ndarray     # int32 (n, L)
    vals: np.ndarray     # float32 (n, L)
    deg: np.ndarray      # int32 (n,) real entries in this chunk


@dataclasses.dataclass(frozen=True)
class ChunkedRatings:
    slabs: tuple[ChunkSlab, ...]   # one per chunk size, descending L
    num_rows: int
    num_cols: int
    nnz: int


def _chunk_rows_native(
    coo: RatingsCOO, sizes: Sequence[int]
) -> ChunkedRatings | None:
    """C++ chunking path (native/bucketize.cc pio_chunk*); None when the
    native toolchain is unavailable — chunk_rows falls back to NumPy
    with an identical slab layout."""
    from predictionio_tpu.native import load_bucketize

    lib = load_bucketize()
    if lib is None or coo.nnz == 0:
        return None
    i32_p, _ = _native_i32p()
    rows, cols, vals, rp, cp, vp = _native_coo_args(coo)
    sz = np.ascontiguousarray(sizes, dtype=np.int32)
    handle = lib.pio_chunk(
        coo.nnz, rp, cp, vp, coo.num_rows, _native_ptr(sz, i32_p), len(sz))
    if not handle:
        return None
    slabs = _native_read_slabs(
        handle, lib.pio_chunk_num_slabs, lib.pio_chunk_slab_info,
        lib.pio_chunk_fill, lib.pio_chunk_free, ChunkSlab)
    if slabs is None:
        return None
    return ChunkedRatings(slabs, coo.num_rows, coo.num_cols, coo.nnz)


def chunk_rows(
    coo: RatingsCOO, sizes: Sequence[int] = (512, 128),
    use_native: bool = True,
) -> ChunkedRatings:
    """Decompose every row into fixed-size chunks — the recompile- and
    MXU-friendly alternative to :func:`bucket_rows`.

    Greedy: full chunks of the largest size first, cascading down; the
    final remainder pads to the smallest size. Properties that make this
    the default training layout:

    - **No dropped ratings** (bucket_rows' ``max_len`` cap silently
      drops the tail of heavy rows — 14% of the item half at ML-20M
      skew).
    - **Bounded shape count**: ``len(sizes)`` compile keys per side
      regardless of the degree distribution (a growth-2 bucket ladder
      needs ~15), so cold-start compiles stay minutes, not tens of
      minutes, on slow-compile links.
    - **MXU-aligned contraction**: with the smallest size >= 128 every
      normal-equation einsum contracts a full MXU lane width; measured
      on one v5e-class chip this beats the low-padding small-bucket
      layout ~5x despite doing ~1.5x more padded work.
    - **Padding bounded by the smallest size** per row (< 128 entries),
      vs growth-factor multiplicative padding.

    Chunks of one row carry partial sums that :func:`solve_half`
    accumulates per row before a single batched solve.

    The decomposition runs in native C++ when available (one counting
    sort + one packing pass, native/bucketize.cc ``pio_chunk*`` —
    measured 6.2x the NumPy path at ML-20M scale); the NumPy fallback
    below produces an identical slab layout.
    """
    sizes = sorted({int(s) for s in sizes}, reverse=True)
    if not sizes or sizes[-1] < 1:
        raise ValueError(f"invalid chunk sizes {sizes}")
    if use_native:
        native = _chunk_rows_native(coo, sizes)
        if native is not None:
            return native
    order = np.argsort(coo.rows, kind="stable")
    rows_s = coo.rows[order]
    cols_s = coo.cols[order]
    vals_s = coo.vals[order]
    deg = np.bincount(rows_s, minlength=coo.num_rows).astype(np.int64)
    start = np.zeros(coo.num_rows, dtype=np.int64)
    np.cumsum(deg[:-1], out=start[1:])
    # position of each entry within its row
    pos = np.arange(coo.nnz, dtype=np.int64) - start[rows_s]

    slabs = []
    # per-row entry offset where each size-class begins (cascade)
    class_begin = np.zeros(coo.num_rows, dtype=np.int64)
    remaining = deg.copy()
    for i, L in enumerate(sizes):
        if i < len(sizes) - 1:
            n_full = remaining // L           # only full chunks this size
            covered = n_full * L
        else:
            n_full = -(-remaining // L)       # remainder pads to last size
            covered = remaining
        class_end = class_begin + covered
        sel = (pos >= class_begin[rows_s]) & (pos < class_end[rows_s])
        chunk_base = np.zeros(coo.num_rows, dtype=np.int64)
        np.cumsum(n_full[:-1], out=chunk_base[1:])
        total = int(n_full.sum())
        if total:
            p = pos[sel] - class_begin[rows_s[sel]]
            chunk_of = chunk_base[rows_s[sel]] + p // L
            within = p % L
            b_cols = np.zeros((total, L), dtype=np.int32)
            b_vals = np.zeros((total, L), dtype=np.float32)
            b_cols[chunk_of, within] = cols_s[sel]
            b_vals[chunk_of, within] = vals_s[sel]
            b_deg = np.bincount(chunk_of, minlength=total).astype(np.int32)
            # owning row of each chunk
            has = n_full > 0
            b_rows = np.repeat(
                np.nonzero(has)[0].astype(np.int32), n_full[has]
            )
            slabs.append(ChunkSlab(b_rows, b_cols, b_vals, b_deg))
        class_begin = class_end
        remaining = remaining - covered
    return ChunkedRatings(tuple(slabs), coo.num_rows, coo.num_cols, coo.nnz)


@dataclasses.dataclass(frozen=True)
class DeviceChunkSlab:
    row_ids: jax.Array  # int32 (S, B) owning row (0 for pad chunks)
    cols: jax.Array     # int32 (S, B, L)
    vals: jax.Array     # float32 (S, B, L)
    deg: jax.Array      # int32 (S, B) real entries (0 for pad chunks)


@dataclasses.dataclass(frozen=True)
class DeviceChunkedRatings:
    """Chunk slabs resident in HBM; build once with :func:`stage_chunks`."""

    slabs: tuple[DeviceChunkSlab, ...]
    num_rows: int
    num_cols: int
    nnz: int


def pad_chunk_slab(
    slab: ChunkSlab, rank: int, data_axis: int, max_slab_elems: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad one chunk slab to its full (S, B, ...) device shape on the
    host: (row_ids, cols, vals, deg). Pad chunks carry row 0 with zero
    degree — zero contribution. Shared by single-process staging
    (:func:`stage_chunks`) and multi-process staging, where each
    process pads identically and contributes its local B-slice via
    ``jax.make_array_from_process_local_data``
    (tests/multihost_als_child.py)."""
    n, L = slab.cols.shape
    s, b = _slab_shape(n, L, rank, data_axis, max_slab_elems)
    total = s * b

    def pad2(a):
        p = np.zeros((total, a.shape[1]), dtype=a.dtype)
        p[:n] = a
        return p.reshape(s, b, a.shape[1])

    deg = np.zeros((total,), dtype=np.int32)
    deg[:n] = slab.deg
    rids = np.zeros((total,), dtype=np.int32)
    rids[:n] = slab.row_ids
    return (rids.reshape(s, b), pad2(slab.cols), pad2(slab.vals),
            deg.reshape(s, b))


def stage_chunks(
    chunked: ChunkedRatings,
    rank: int,
    mesh: Mesh | None = None,
    max_slab_elems: int = 1 << 24,
) -> DeviceChunkedRatings:
    data_axis = int(mesh.shape["data"]) if mesh is not None else 1
    out = []
    for slab in chunked.slabs:
        rids, cols, vals, deg = pad_chunk_slab(
            slab, rank, data_axis, max_slab_elems)
        if mesh is not None:
            slab_sh = NamedSharding(mesh, P(None, "data", None))
            vec_sh = NamedSharding(mesh, P(None, "data"))
            cols = jax.device_put(cols, slab_sh)
            vals = jax.device_put(vals, slab_sh)
            deg = jax.device_put(deg, vec_sh)
            rids = jax.device_put(rids, vec_sh)
        else:
            cols, vals, deg, rids = map(jax.device_put, (cols, vals, deg, rids))
        out.append(DeviceChunkSlab(rids, cols, vals, deg))
    return DeviceChunkedRatings(
        tuple(out), chunked.num_rows, chunked.num_cols, chunked.nnz
    )


def half_step_flops(
    bucketed: "BucketedRatings | ChunkedRatings",
    rank: int,
    data_axis: int = 1,
    max_slab_elems: int = 1 << 24,
    cg_steps: int | None = None,
    solver: str = "cg",
) -> dict[str, float]:
    """Useful vs executed FLOPs for one ALS half-step on this layout.

    Useful work per *real* rating entry: the normal-equation build costs
    ``2K²`` FLOPs (outer-product accumulate into A) plus ``2K`` (rhs);
    per active row the solve is priced at the ALGORITHMIC MINIMUM —
    Cholesky ``K³/3`` + ``2K²`` (two triangular solves) — regardless of
    the solver actually run, so MFU never earns credit for extra solver
    work. Executed work replaces real entries with padded slab entries
    (chunk/row padding and slab-shape rounding from :func:`_slab_shape`)
    and prices the solve at what the solver actually run executes:
    batched CG at ``steps × (2K² + 8K)`` (one batched matvec + the CG
    vector updates per step, ``steps = cg_steps or min(K+4,
    _CG_STEP_CAP)``), or — when ``solver="cholesky"`` is the path being
    measured — the direct factorization + two triangular solves
    (``K³/3 + 2K²``, i.e. the algorithmic minimum). Pass the same
    ``solver``/``cg_steps`` the measured run used, or MFU/padding_x
    misattribute the solve cost. Executed work also
    replaces real entries with padded slab entries — for the chunked
    layout over every row (inactive rows solve the identity). The
    ratio ``executed / useful`` therefore carries BOTH the layout's
    padding overhead and the solver-vs-minimum overhead (a
    Cholesky-priced executed figure would understate the CG solve's
    executed FLOPs)."""
    if solver not in ("cg", "cholesky"):
        raise ValueError(f"solver must be 'cg' or 'cholesky', got {solver!r}")
    k = float(rank)
    per_entry = 2.0 * k * k + 2.0 * k
    per_solve = (k ** 3) / 3.0 + 2.0 * k * k
    if solver == "cholesky":
        per_solve_exec = per_solve
    else:
        steps = (cg_steps if cg_steps is not None
                 else min(rank + 4, _CG_STEP_CAP))
        per_solve_exec = float(steps) * (2.0 * k * k + 8.0 * k)
    useful = executed = 0.0
    if isinstance(bucketed, ChunkedRatings):
        active = set()
        for slab in bucketed.slabs:
            n, L = slab.cols.shape
            useful += float(slab.deg.sum()) * per_entry
            active.update(np.unique(slab.row_ids).tolist())
            s, rows = _slab_shape(n, L, rank, data_axis, max_slab_elems)
            executed += float(s * rows) * L * per_entry
        useful += len(active) * per_solve
        executed += bucketed.num_rows * per_solve_exec
        return {"useful_flops": useful, "executed_flops": executed}
    for b in bucketed.buckets:
        n = int(b.row_ids.shape[0])
        useful += float(b.deg.sum()) * per_entry + n * per_solve
        s, rows = _slab_shape(n, b.pad_len, rank, data_axis, max_slab_elems)
        executed += float(s * rows) * (b.pad_len * per_entry + per_solve_exec)
    return {"useful_flops": useful, "executed_flops": executed}


# ---------------------------------------------------------------------------
# Ladder layout: MXU-width row buckets for the fused single-program path
# ---------------------------------------------------------------------------

#: pad-length ladder for :func:`ladder_rows`, in units of 128-entry MXU
#: chunks; count-padding is bounded by the gap ratio (<= 1.5x, and only
#: on multi-chunk rows where the absolute slack is small relative to
#: the row)
LADDER_COUNTS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128,
                 192, 256, 384, 512, 768, 1024, 1536, 2048)


def _ladder_rows_native(
    coo: RatingsCOO, width: int, small: int
) -> BucketedRatings | None:
    """C++ packing path (native/bucketize.cc pio_ladder — one counting
    sort + one fill, same handle contract as the bucketizer); None when
    the native toolchain is unavailable."""
    from predictionio_tpu.native import load_bucketize

    lib = load_bucketize()
    if lib is None or coo.nnz == 0:
        return None
    import ctypes

    rows, cols, vals, rp, cp, vp = _native_coo_args(coo)
    ladder = np.ascontiguousarray(LADDER_COUNTS, dtype=np.int64)
    handle = lib.pio_ladder(
        coo.nnz, rp, cp, vp, coo.num_rows, width, small,
        ladder.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(ladder))
    if not handle:
        return None
    buckets = _native_read_slabs(
        handle, lib.pio_bucketize_num_buckets, lib.pio_bucketize_bucket_info,
        lib.pio_bucketize_fill, lib.pio_bucketize_free, Bucket)
    if buckets is None:
        return None
    return BucketedRatings(buckets, coo.num_rows, coo.num_cols, coo.nnz)


def ladder_rows(
    coo: RatingsCOO, width: int = 128, small: int = 64,
    use_native: bool = True,
) -> BucketedRatings:
    """Whole-row buckets padded to the MXU-width ladder — the layout
    behind ``layout="fused"``.

    Every row's entries land in ONE bucket whose pad length is either
    ``small`` (rows with degree <= small; half-lane contraction beats
    2x padding for the light-user mass) or ``width * c`` with ``c`` the
    smallest :data:`LADDER_COUNTS` entry covering ``ceil(deg/width)``.
    Unlike :func:`bucket_rows`'s power-of-``growth`` ladder this keeps
    every contraction at (or at worst half of) the 128-lane MXU width,
    and unlike :func:`chunk_rows` it needs no cross-chunk accumulation
    — each bucket row IS a complete row, so the normal equations can be
    built and solved inside one scan step with no scatter and no
    (num_rows, K, K) accumulator (the two phases measured at 100ms +
    113ms per ML-20M iteration on the chunked path, scratch profile
    r3). No ratings are dropped.

    The packing runs in native C++ when available (one counting sort +
    one fill, native/bucketize.cc ``pio_ladder``); the NumPy fallback
    below is vectorized (one stable argsort over nnz + contiguous
    per-bucket slices) and produces an identical slab layout.
    """
    if coo.nnz == 0:
        return BucketedRatings((), coo.num_rows, coo.num_cols, 0)
    if use_native:
        native = _ladder_rows_native(coo, width, small)
        if native is not None:
            return native
    order = np.argsort(coo.rows, kind="stable")
    rows_s = coo.rows[order]
    cols_s = coo.cols[order]
    vals_s = coo.vals[order]
    deg = np.bincount(rows_s, minlength=coo.num_rows).astype(np.int64)
    start = np.zeros(coo.num_rows, dtype=np.int64)
    np.cumsum(deg[:-1], out=start[1:])
    pos = np.arange(coo.nnz, dtype=np.int64) - start[rows_s]

    counts = list(LADDER_COUNTS)
    need = -(-deg // width)                       # ceil chunks per row
    # rows beyond the base ladder extend it by doubling — arbitrary
    # degrees train, they just land in their own (tiny) buckets
    top = int(need.max()) if len(need) else 1
    while counts[-1] < top:
        counts.append(counts[-1] * 2)
    counts = np.asarray(counts, dtype=np.int64)
    ci = np.searchsorted(counts, need)
    pad_lens = counts[ci] * width
    pad_lens = np.where((deg > 0) & (deg <= small), small, pad_lens)

    # one stable sort groups entries by bucket (row/pos order preserved
    # within); per-bucket work is then a contiguous slice, not an
    # nnz-wide mask per pad length
    ekey = pad_lens[rows_s]
    e_order = np.argsort(ekey, kind="stable")
    key_b = ekey[e_order]
    rows_b, cols_b = rows_s[e_order], cols_s[e_order]
    vals_b, pos_b = vals_s[e_order], pos[e_order]

    # rows grouped the same way; slot = rank of the row within its bucket
    act_rows = np.nonzero(deg > 0)[0]
    r_order = np.argsort(pad_lens[act_rows], kind="stable")
    sorted_rows = act_rows[r_order]
    sorted_pl = pad_lens[sorted_rows]
    slot_of = np.empty(coo.num_rows, dtype=np.int64)

    buckets = []
    for pl in np.unique(sorted_pl):
        rs, re = np.searchsorted(sorted_pl, [pl, pl + 1])
        sel_rows = sorted_rows[rs:re]
        slot_of[sel_rows] = np.arange(re - rs)
        es, ee = np.searchsorted(key_b, [pl, pl + 1])
        b_cols = np.zeros((re - rs, pl), dtype=np.int32)
        b_vals = np.zeros((re - rs, pl), dtype=np.float32)
        slots = slot_of[rows_b[es:ee]]
        b_cols[slots, pos_b[es:ee]] = cols_b[es:ee]
        b_vals[slots, pos_b[es:ee]] = vals_b[es:ee]
        buckets.append(Bucket(
            sel_rows.astype(np.int32), b_cols, b_vals,
            deg[sel_rows].astype(np.int32)))
    return BucketedRatings(tuple(buckets), coo.num_rows, coo.num_cols,
                           coo.nnz)


# ---------------------------------------------------------------------------
# Device staging: pad buckets into slabs ONCE, keep them HBM-resident
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceBucket:
    """One bucket staged on device as (S, B, L) slabs.

    The pad mask is not materialised — each slab row carries its real
    degree and the kernel derives ``mask = iota(L) < deg`` on device,
    saving a third of the transfer and HBM footprint.
    """

    row_ids: jax.Array  # int32 (n,)
    cols: jax.Array     # int32 (S, B, L)
    vals: jax.Array     # float32 (S, B, L) zero-padded
    deg: jax.Array      # int32 (S, B) real entries per row (0 for pad rows)
    n: int
    pad_len: int


@dataclasses.dataclass(frozen=True)
class DeviceBucketedRatings:
    """Bucketed ratings resident in HBM — build once with
    :func:`stage_buckets`, reuse across every ALS iteration. Re-staging
    per half-step (the naive path) moves hundreds of MB over PCIe per
    iteration and dominates wall-clock; HBM-resident slabs leave only
    the MXU work."""

    buckets: tuple[DeviceBucket, ...]
    num_rows: int
    num_cols: int
    nnz: int


def pad_bucket_slabs(
    bucket: Bucket, rank: int, data_axis: int, max_slab_elems: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad one bucket to its full (S, B, L)/(S, B) device shape on the
    host: (cols, vals, deg). Pad rows carry zero degree — zero
    contribution. Shared by single-process staging (:func:`_stage_bucket`)
    and multi-process staging, where each process pads identically and
    contributes its local B-slice via
    ``jax.make_array_from_process_local_data``
    (tests/multihost_fused_child.py) — the ladder-layout analogue of
    :func:`pad_chunk_slab`."""
    n = bucket.row_ids.shape[0]
    s, b = _slab_shape(n, bucket.pad_len, rank, data_axis, max_slab_elems)
    total = s * b

    def pad3(a):
        p = np.zeros((total, a.shape[1]), dtype=a.dtype)
        p[:n] = a
        return p.reshape(s, b, a.shape[1])

    deg = np.zeros((total,), dtype=np.int32)
    deg[:n] = bucket.deg
    return pad3(bucket.cols), pad3(bucket.vals), deg.reshape(s, b)


def _stage_bucket(
    bucket: Bucket,
    rank: int,
    mesh: Mesh | None,
    max_slab_elems: int,
) -> DeviceBucket:
    """Transfer one bucket's slabs to the device (sharded over the mesh's
    data axis when given), padding row counts up to full slabs."""
    data_axis = int(mesh.shape["data"]) if mesh is not None else 1
    n = bucket.row_ids.shape[0]
    cols, vals, deg = pad_bucket_slabs(bucket, rank, data_axis,
                                       max_slab_elems)
    if mesh is not None:
        slab_sh = NamedSharding(mesh, P(None, "data", None))
        deg_sh = NamedSharding(mesh, P(None, "data"))
        cols = jax.device_put(cols, slab_sh)
        vals = jax.device_put(vals, slab_sh)
        deg = jax.device_put(deg, deg_sh)
    else:
        cols, vals, deg = map(jax.device_put, (cols, vals, deg))
    return DeviceBucket(
        row_ids=jax.device_put(jnp.asarray(bucket.row_ids)),
        cols=cols, vals=vals, deg=deg, n=n, pad_len=bucket.pad_len,
    )


def stage_buckets(
    bucketed: BucketedRatings,
    rank: int,
    mesh: Mesh | None = None,
    max_slab_elems: int = 1 << 24,
) -> DeviceBucketedRatings:
    """Stage every bucket HBM-resident. Peak device memory is the full
    padded rating set (~8 bytes x padded nnz per orientation) — for sets
    that don't fit, keep host ``BucketedRatings`` and let ``solve_half``
    stream one bucket at a time instead (``als_train(hbm_resident=False)``)."""
    return DeviceBucketedRatings(
        tuple(_stage_bucket(b, rank, mesh, max_slab_elems)
              for b in bucketed.buckets),
        bucketed.num_rows, bucketed.num_cols, bucketed.nnz,
    )


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST  # normal equations need true f32 accumulation


def _cho_solve_batched(A: jax.Array, b: jax.Array) -> jax.Array:
    """Solve SPD systems A x = b for (..., K, K) / (..., K).

    The exact direct solver — kept as the opt-in ``solver="cholesky"``
    path (als_train) and as the oracle the high-rank CG accuracy test
    measures against (tests/test_als.py). Not the default: XLA's batched
    cholesky/triangular_solve lower to sequential scalar loops on TPU,
    measured 17x slower than :func:`_cg_solve_batched` at rank 32."""
    chol = jnp.linalg.cholesky(A)
    y = jax.lax.linalg.triangular_solve(
        chol, b[..., None], left_side=True, lower=True
    )
    x = jax.lax.linalg.triangular_solve(
        chol, y, left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


#: default CG step cap: batched f32 CG on ridge-regularised ALS normal
#: matrices reaches its float32 accuracy floor (~2e-7 rel err vs an f64
#: oracle) well before K steps. Round-3 measurement on real ALS-WR and
#: Hu-Koren system families (48 systems each, f64 oracle):
#:   explicit K=200 lam*deg ridge, deg 800-2000:  floor by step 6
#:   explicit K=200 lam=0.01 (weak ridge):        floor by step 16
#:   explicit K=32  lam=0.01 (weakest measured):  9.6e-6 @16, floor @24
#:   implicit K=10..32, alpha 5-10, flat lam:     floor by step 12
#: The cap at 16 keeps worst-case solve error ~1e-5 relative — orders
#: below the alternation's own statistical noise — and each step past
#: it only re-streams A (measured ~23ms/step at the ML-20M rank-200
#: shape). Raise via als_train(cg_steps=...) for pathological
#: conditioning; solver="cholesky" is the exact escape hatch.
_CG_STEP_CAP = 16


def _cg_solve_batched(A: jax.Array, b: jax.Array,
                      steps: int | None = None,
                      bf16_matvec: bool = False) -> jax.Array:
    """Solve SPD systems A x = b for (..., K, K) / (..., K) by batched
    conjugate gradients — the TPU-fast solver.

    XLA's cholesky + triangular_solve lower to sequential scalar loops
    for small batched systems: measured 506ms for 138k rank-32 solves on
    one v5e-class chip, vs 30ms for this CG (HBM-bound batched matvecs,
    the layout the VPU/MXU actually likes); at rank 200 the gap is 1154ms
    vs 104ms (20k systems). ``steps`` defaults to ``min(K + 4, 16)`` —
    exact-in-exact-arithmetic for K <= 12, and at the measured f32
    accuracy floor for every larger rank (see ``_CG_STEP_CAP``). The
    ALS normal matrices carry a ``lam * n`` (or flat ``lam``) ridge, so
    they are well-conditioned by construction; inactive rows pass the
    identity. Callers can raise ``steps`` (als_train(cg_steps=...)) for
    pathologically conditioned data.

    ``bf16_matvec=True`` streams A in bfloat16 through the per-step
    matvec (f32 accumulation; the CG vectors and scalars stay f32) —
    halving the A-traffic that dominates high-rank solves. Round-4
    measurement at the ML-20M rank-200 config: 1.51x the iteration
    (731.6 -> 484.4 ms in a controlled A/B); accuracy vs an f64 oracle
    2.4-2.6e-3 relative on both measured system families (f32 matvec:
    ~1.5e-7) — inside the ~5e-3 band the default bf16 normal-equation
    build already accepts. ``als_train(cg_matvec_dtype=...)`` applies
    the "auto" policy: bf16 at rank >= 64 (traffic-bound), f32 below
    (VMEM-resident blocks, nothing to win)."""
    if steps is None:
        steps = min(A.shape[-1] + 4, _CG_STEP_CAP)
    A_mm = A.astype(jnp.bfloat16) if bf16_matvec else A
    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = jnp.sum(r * r, axis=-1)

    def step(carry, _):
        x, r, p, rs = carry
        if bf16_matvec:
            Ap = jnp.einsum("...ij,...j->...i", A_mm,
                            p.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            Ap = jnp.einsum("...ij,...j->...i", A_mm, p)
        denom = jnp.sum(p * Ap, axis=-1)
        # denom <= 0 only from rounding on a (near-)singular system —
        # exact-arithmetic SPD quadratic forms are positive, but the
        # bf16 matvec's ~4e-3 perturbation can cross zero when the
        # ridge is weak. Taking a zero step (not a 1e30 one) freezes
        # that system at its current iterate instead of poisoning the
        # whole training scan with inf/NaN.
        alpha = jnp.where(denom > 0, rs / jnp.where(denom > 0, denom, 1.0),
                          0.0)
        x = x + alpha[..., None] * p
        r = r - alpha[..., None] * Ap
        rs_new = jnp.sum(r * r, axis=-1)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = r + beta[..., None] * p
        return (x, r, p, rs_new), None

    (x, _, _, _), _ = jax.lax.scan(
        step, (x, r, p, rs), None, length=steps)
    return x


def _normal_eq_solve(V, c, v, d, lam, alpha, gram, implicit, mm, prec,
                     cg_steps, solver="cg", cg_bf16=False):
    """Build and solve one slab-row batch of per-row normal equations.

    ``(c, v, d)`` are (B, L) cols/vals plus (B,) degrees for B complete
    rows; returns (B, K) solved factors (zero for empty rows). Shared by
    the per-bucket dispatch path (:func:`_solve_slabs`) and the fused
    single-program path (:func:`_solve_half_fused`)."""
    K = V.shape[1]
    L = c.shape[-1]
    eye = jnp.eye(K, dtype=jnp.float32)
    m = (jnp.arange(L, dtype=jnp.int32)[None, :]
         < d[:, None]).astype(jnp.float32)
    # V arrives pre-cast to ``mm`` by the callers (gather-table width
    # optimization: casting the TABLE once per half-step instead of the
    # gathered rows halves the bytes the gather walks in bf16 mode —
    # measured 8.92 -> 6.11 ns/padded row on the rank-200 item half,
    # where the 110MB f32 table is past the fast-gather tier; the cast
    # commutes with a row-gather, so values are bit-identical); the
    # astype below is a no-op then, and covers direct callers
    F = V[c].astype(mm)                 # (B, L, K) the row-gather
    if implicit:
        # Hu-Koren with MLlib trainImplicit's negative-rating semantics:
        # confidence c_ui = 1 + α|r|, preference p_ui = [r > 0], so a
        # negative rating is a HIGH-CONFIDENCE zero preference (dislike)
        # and r = 0 contributes nothing. A = VᵀV + Σ (c-1) v vᵀ + λI,
        # b = Σ c p v.
        w = (alpha * jnp.abs(v) * m).astype(mm)   # (c - 1) on observed
        A = jnp.einsum("bl,blk,blm->bkm", w, F, F, precision=prec,
                       preferred_element_type=jnp.float32)
        A = A + gram + lam * eye
        bw = jnp.where(v > 0, 1.0 + alpha * v, 0.0) * m    # c * p
        b = jnp.einsum("bl,blk->bk", bw.astype(mm), F,
                       precision=prec, preferred_element_type=jnp.float32)
    else:
        # ALS-WR: A = Σ v vᵀ + λ n_u I ; b = Σ r v
        Fm = F * m[..., None].astype(mm)
        A = jnp.einsum("blk,blm->bkm", Fm, F, precision=prec,
                       preferred_element_type=jnp.float32)
        n_u = jnp.sum(m, axis=1)
        A = A + (lam * n_u)[:, None, None] * eye
        b = jnp.einsum("bl,blk->bk", (v * m).astype(mm), F, precision=prec,
                       preferred_element_type=jnp.float32)
    # rows with zero ratings (padding rows): A = λ'I -> x = 0
    A = jnp.where(d[:, None, None] > 0, A, eye)
    if solver == "cholesky":
        x = _cho_solve_batched(A, b)
    else:
        x = _cg_solve_batched(A, b, steps=cg_steps, bf16_matvec=cg_bf16)
    return jnp.where(d[:, None] > 0, x, 0.0)


@partial(instrumented_jit,
         static_argnames=("implicit", "bf16", "lam", "alpha", "cg_steps",
                          "solver", "cg_bf16"),
         donate_argnums=())
def _solve_slabs(
    V: jax.Array,      # (num_cols, K) opposite factors, replicated
    cols: jax.Array,   # (S, B, L) int32
    vals: jax.Array,   # (S, B, L) f32, zero-padded
    deg: jax.Array,    # (S, B) int32 real entries per row
    lam: float,        # STATIC — baked into the program: a traced scalar
    alpha: float,      # would cost one synchronous host->device transfer
    gram: jax.Array,   # per call
    implicit: bool,
    bf16: bool = False,
    cg_steps: int | None = None,
    solver: str = "cg",
    cg_bf16: bool = False,
) -> jax.Array:
    """Per-slab batched normal-equation solve; scan bounds peak memory.

    ``bf16=True`` feeds the normal-equation einsums bf16 operands with
    f32 accumulation (its speed against f32 is not measured on today's
    code). Factor tables diverge ~5e-3 relative from the f32 path
    after 10 iterations — inside quality-parity tolerances but not
    bit-comparable, so f32-HIGHEST stays the default. The solve and
    regularisation stay f32. Opt in via
    ``als_train(matmul_dtype="bfloat16")``."""
    mm = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else _HI
    V = V.astype(mm)      # narrow gather table (gram is precomputed)

    def body(_, xs):
        c, v, d = xs                    # (B, L), (B, L), (B,)
        x = _normal_eq_solve(V, c, v, d, lam, alpha, gram, implicit,
                             mm, prec, cg_steps, solver, cg_bf16)
        return None, x

    _, X = jax.lax.scan(body, None, (cols, vals, deg))
    return X  # (S, B, K)


@instrumented_jit
def _gramian(V: jax.Array) -> jax.Array:
    return jnp.einsum("ik,im->km", V, V, precision=_HI)


@partial(instrumented_jit,
         static_argnames=("implicit", "bf16", "num_rows", "lam", "alpha",
                          "cg_steps", "cg_bf16"))
def _solve_half_chunked(
    V: jax.Array,           # (num_cols, K) opposite factors
    slabs: tuple,           # per size: (rids(S,B), cols(S,B,L), vals, deg)
    lam: float,             # static — see _solve_slabs note
    alpha: float,
    gram: jax.Array | None,  # VᵀV (implicit only; None otherwise)
    implicit: bool,
    num_rows: int,
    bf16: bool = False,
    cg_steps: int | None = None,
    cg_bf16: bool = False,
) -> jax.Array:
    """One ALS half-step over the chunked layout as a SINGLE program:
    per-chunk partial normal equations (batched einsums on the MXU),
    scatter-accumulated per row, then one batched conjugate-gradient
    solve over all rows (:func:`_cg_solve_batched` — its step count and
    clamps govern solve accuracy). One dispatch per half-step — launch
    count independent of the degree distribution (the bucketed path
    pays one dispatch per bucket, which dominates on high-latency
    links)."""
    K = V.shape[1]
    eye = jnp.eye(K, dtype=jnp.float32)
    mm = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else _HI
    V = V.astype(mm)      # narrow gather table (gram is precomputed)

    A_acc = jnp.zeros((num_rows, K, K), dtype=jnp.float32)
    b_acc = jnp.zeros((num_rows, K), dtype=jnp.float32)
    n_acc = jnp.zeros((num_rows,), dtype=jnp.float32)

    for rids, cols, vals, deg in slabs:
        L = cols.shape[-1]

        def body(carry, xs):
            A_acc, b_acc, n_acc = carry
            r, c, v, d = xs               # (B,), (B, L), (B, L), (B,)
            m = (jnp.arange(L, dtype=jnp.int32)[None, :]
                 < d[:, None]).astype(jnp.float32)
            F = V[c].astype(mm)           # (B, L, K)
            if implicit:
                # same c = 1 + α|r|, p = [r > 0] semantics as
                # _normal_eq_solve (MLlib trainImplicit parity)
                w = (alpha * jnp.abs(v) * m).astype(mm)
                A = jnp.einsum("bl,blk,blm->bkm", w, F, F, precision=prec,
                               preferred_element_type=jnp.float32)
                bw = jnp.where(v > 0, 1.0 + alpha * v, 0.0) * m
                b = jnp.einsum("bl,blk->bk", bw.astype(mm),
                               F, precision=prec,
                               preferred_element_type=jnp.float32)
            else:
                Fm = F * m[..., None].astype(mm)
                A = jnp.einsum("blk,blm->bkm", Fm, F, precision=prec,
                               preferred_element_type=jnp.float32)
                b = jnp.einsum("bl,blk->bk", (v * m).astype(mm), F,
                               precision=prec,
                               preferred_element_type=jnp.float32)
            A_acc = A_acc.at[r].add(A)
            b_acc = b_acc.at[r].add(b)
            n_acc = n_acc.at[r].add(jnp.sum(m, axis=1))
            return (A_acc, b_acc, n_acc), None

        (A_acc, b_acc, n_acc), _ = jax.lax.scan(
            body, (A_acc, b_acc, n_acc), (rids, cols, vals, deg))

    if implicit:
        A = A_acc + gram[None] + jnp.float32(lam) * eye[None]
    else:
        A = A_acc + (jnp.float32(lam) * n_acc)[:, None, None] * eye[None]
    active = n_acc > 0
    A = jnp.where(active[:, None, None], A, eye[None])
    x = _cg_solve_batched(A, b_acc, steps=cg_steps, bf16_matvec=cg_bf16)
    return jnp.where(active[:, None], x, 0.0)


def _solve_half_fused(V, buckets, lam, alpha, implicit, num_rows, bf16,
                      cg_steps, solver="cg", out_sharding=None,
                      cg_bf16=False):
    """One ALS half-step over the ladder layout, traced inline.

    Per bucket slab: build the complete per-row normal equations (every
    bucket row IS a whole row — no cross-chunk accumulation) and solve
    them in the same scan step, so A lives and dies slab-locally
    instead of streaming a (num_rows, K, K) HBM accumulator through the
    build (100ms/iter) and the CG (113ms/iter) as the chunked path does
    (scratch profile, ML-20M rank 32). The only scatter left is the
    (n, K) factor write-back per bucket — row-count-bound like the
    gather, ~0.5ms at ML-20M scale.

    ``out_sharding`` (tensor parallelism): a NamedSharding that pins the
    produced factor table row-sharded over the mesh's "model" axis. The
    opposite table V arrives with the same sharding; XLA inserts ONE
    all-gather of V for the slab gathers (cheaper than psum-of-partials
    whenever avg degree > 1) and scatters the write-back to the owning
    shard, so the PERSISTENT state — both factor tables — stays sharded
    and only one table at a time materialises transiently."""
    K = V.shape[1]
    mm = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else _HI
    gram = jnp.einsum("ik,im->km", V, V, precision=_HI) if implicit else None
    # gramian from the f32 table above; the slab gathers walk the
    # narrow table (see _normal_eq_solve's gather note)
    V = V.astype(mm)
    out = jnp.zeros((num_rows, K), dtype=jnp.float32)
    if out_sharding is not None:
        out = jax.lax.with_sharding_constraint(out, out_sharding)
    for row_ids, cols, vals, deg in buckets:
        n = row_ids.shape[0]   # static: row_ids is the (n,) unpadded id list

        def body(_, xs):
            c, v, d = xs
            x = _normal_eq_solve(V, c, v, d, lam, alpha, gram, implicit,
                                 mm, prec, cg_steps, solver, cg_bf16)
            return None, x

        _, X = jax.lax.scan(body, None, (cols, vals, deg))
        out = out.at[row_ids].set(X.reshape(-1, K)[:n])
    if out_sharding is not None:
        out = jax.lax.with_sharding_constraint(out, out_sharding)
    return out


@partial(instrumented_jit,
         static_argnames=("iterations", "lam", "alpha", "implicit",
                          "num_users", "num_items", "bf16", "cg_steps",
                          "solver", "mesh", "shard_factors", "cg_bf16"),
         donate_argnums=(0,))
def _als_iterate_fused(
    item0: jax.Array,
    user_buckets: tuple,    # per bucket: (row_ids(n,), cols(S,B,L), vals, deg(S,B))
    item_buckets: tuple,
    iterations: int,
    lam: float,
    alpha: float,
    implicit: bool,
    num_users: int,
    num_items: int,
    bf16: bool = False,
    cg_steps: int | None = None,
    solver: str = "cg",
    mesh: Mesh | None = None,
    shard_factors: bool = False,
    cg_bf16: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full ALS training as ONE device program: ``lax.scan`` over
    alternating :func:`_solve_half_fused` half-steps. One dispatch per
    training run, and the scan lets XLA overlap consecutive
    iterations' transfers.

    ``shard_factors=True`` (with a ``mesh`` carrying a "model" axis
    > 1) is the tensor-parallel layout: BOTH carried factor tables stay
    row-sharded over "model" through every scan step (the BASELINE
    DP×MP configuration — MLlib's block-partitioned factors,
    ALSAlgorithm.scala:79-85). See :func:`_solve_half_fused` for the
    collective structure. ``num_users``/``num_items`` must be padded to
    a multiple of the model-axis size by the caller (als_train does)."""
    K = item0.shape[1]
    sh = None
    if shard_factors and mesh is not None and "model" in mesh.shape \
            and int(mesh.shape["model"]) > 1:
        sh = NamedSharding(mesh, P("model", None))
    u0 = jnp.zeros((num_users, K), dtype=jnp.float32)
    if sh is not None:
        u0 = jax.lax.with_sharding_constraint(u0, sh)

    def it_body(carry, _):
        _, item = carry
        user = _solve_half_fused(item, user_buckets, lam, alpha, implicit,
                                 num_users, bf16, cg_steps, solver,
                                 out_sharding=sh, cg_bf16=cg_bf16)
        item = _solve_half_fused(user, item_buckets, lam, alpha, implicit,
                                 num_items, bf16, cg_steps, solver,
                                 out_sharding=sh, cg_bf16=cg_bf16)
        return (user, item), None

    (user, item), _ = jax.lax.scan(
        it_body, (u0, item0), None, length=iterations)
    return user, item


def _fused_bucket_args(staged: DeviceBucketedRatings) -> tuple:
    return tuple((b.row_ids, b.cols, b.vals, b.deg)
                 for b in staged.buckets)


#: cap on the per-slab normal-matrix block: slab_rows * rank^2 floats.
#: 8M floats = 32 MB keeps the (B, K, K) systems VMEM-resident through
#: the in-scan CG at any rank — at rank 200 the default element budget
#: alone allowed B=655 (a 105 MB block that spilled to HBM and was
#: re-streamed by all 24 CG steps: measured 1.15 s/iter at the ML-20M
#: shape vs 0.56 s/iter once the block fits).
_MAX_SOLVE_ELEMS = 8 << 20


def _slab_shape(
    n: int, pad_len: int, rank: int, data_axis: int, max_slab_elems: int
) -> tuple[int, int]:
    """Pick (num_slabs, slab_rows): slab_rows a multiple of the data-axis
    size with slab_rows*pad_len*rank <= max_slab_elems and
    slab_rows*rank^2 <= _MAX_SOLVE_ELEMS (VMEM-sized solve blocks)."""
    per_row = pad_len * rank
    b = max(1, max_slab_elems // per_row)
    b = min(b, max(1, _MAX_SOLVE_ELEMS // (rank * rank)))
    b = max(data_axis, (b // data_axis) * data_axis)
    b = min(b, ((n + data_axis - 1) // data_axis) * data_axis)
    s = (n + b - 1) // b
    return s, b


#: rank at or above which the "auto" CG matvec policy streams A in
#: bfloat16: the per-slab (B, K, K) blocks stop fitting the CG's fast
#: path and each step re-streams A, so halving its width is ~free
#: speedup (1.51x measured at rank 200); below it the blocks are
#: VMEM-resident and f32 costs nothing
_CG_BF16_RANK = 64


def _resolve_cg_matvec(cg_matvec_dtype: str, rank: int) -> bool:
    if cg_matvec_dtype not in ("auto", "float32", "bfloat16"):
        raise ValueError(
            "cg_matvec_dtype must be 'auto', 'float32' or 'bfloat16', "
            f"got {cg_matvec_dtype!r}")
    if cg_matvec_dtype == "auto":
        return rank >= _CG_BF16_RANK
    return cg_matvec_dtype == "bfloat16"


def solve_half(
    V: jax.Array,
    bucketed: "BucketedRatings | DeviceBucketedRatings | ChunkedRatings | DeviceChunkedRatings",
    rank: int,
    lam: float,
    implicit: bool = False,
    alpha: float = 40.0,
    mesh: Mesh | None = None,
    max_slab_elems: int = 1 << 24,
    matmul_dtype: str = "float32",
    shard_factors: bool = False,
    cg_steps: int | None = None,
    solver: str = "cg",
    cg_matvec_dtype: str = "float32",
) -> jax.Array:
    """One ALS half-step: solve all row factors given opposite factors V.

    Returns a (num_rows, K) factor table (replicated under ``mesh``);
    rows with no ratings get zero factors, matching MLlib which simply
    omits them from the factor RDD.

    Dispatches on layout: chunked inputs (:func:`chunk_rows` /
    :func:`stage_chunks`) take the single-dispatch accumulate-then-solve
    program; bucketed inputs take the per-bucket solve.

    ``shard_factors=True`` (with a mesh that has a "model" axis) keeps
    the opposite factor table V row-sharded over that axis — the
    tensor-parallel layout for catalog-scale tables that exceed one
    device's HBM. XLA inserts the gathers for the slab lookups over ICI;
    with ``False`` (default) V is replicated, which is faster whenever
    it fits.

    Pass a :class:`DeviceBucketedRatings` (from :func:`stage_buckets`) /
    :class:`DeviceChunkedRatings` (:func:`stage_chunks`) when calling
    repeatedly — host layouts are staged per call (bounded device
    memory, but re-transferred every call, which is transfer-bound
    across iterations).
    """
    if matmul_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"matmul_dtype must be 'float32' or 'bfloat16', got {matmul_dtype!r}"
        )
    cg_bf16 = _resolve_cg_matvec(cg_matvec_dtype, rank)
    # lam/alpha are STATIC jit args (hashable floats) and gram is None
    # unless needed: a host scalar argument costs one synchronous
    # host->device transfer per call
    lam_a = float(lam)
    alpha_a = float(alpha)
    gram = _gramian(V) if implicit else None

    if isinstance(bucketed, (ChunkedRatings, DeviceChunkedRatings)):
        if isinstance(bucketed, ChunkedRatings):
            bucketed = stage_chunks(bucketed, rank, mesh, max_slab_elems)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            if shard_factors and "model" in mesh.shape and \
                    int(mesh.shape["model"]) > 1:
                axis = int(mesh.shape["model"])
                pad = (-V.shape[0]) % axis
                if pad:
                    V = jnp.concatenate(
                        [V, jnp.zeros((pad, V.shape[1]), dtype=V.dtype)])
                V = jax.device_put(V, NamedSharding(mesh, P("model", None)))
            else:
                V = jax.device_put(V, rep)
        slabs = tuple(
            (s.row_ids, s.cols, s.vals, s.deg) for s in bucketed.slabs
        )
        if solver != "cg":
            raise ValueError(
                "solver='cholesky' is a bucketed/fused-layout option; the "
                "chunked path solves over the scan-carried accumulator")
        return _solve_half_chunked(
            V, slabs, lam_a, alpha_a, gram, implicit, bucketed.num_rows,
            bf16=(matmul_dtype == "bfloat16"), cg_steps=cg_steps,
            cg_bf16=cg_bf16,
        )

    out = jnp.zeros((bucketed.num_rows, rank), dtype=jnp.float32)
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        if shard_factors and "model" in mesh.shape and \
                int(mesh.shape["model"]) > 1:
            axis = int(mesh.shape["model"])
            pad = (-V.shape[0]) % axis
            if pad:
                # zero rows: never indexed by any slab (col ids are
                # < num_cols) and contribute nothing to the gramian
                V = jnp.concatenate(
                    [V, jnp.zeros((pad, V.shape[1]), dtype=V.dtype)])
            V = jax.device_put(V, NamedSharding(mesh, P("model", None)))
        else:
            V = jax.device_put(V, rep)
        out = jax.device_put(out, rep)
    if matmul_dtype == "bfloat16":
        # narrow the gather table ONCE per half-step, not once per
        # bucket dispatch (gram above is taken from the f32 table; the
        # in-jit astype becomes a no-op)
        V = V.astype(jnp.bfloat16)

    streaming = isinstance(bucketed, BucketedRatings)
    for bucket in bucketed.buckets:
        if streaming:  # transient slabs, freed after this bucket's solve
            bucket = _stage_bucket(bucket, rank, mesh, max_slab_elems)
        X = _solve_slabs(V, bucket.cols, bucket.vals, bucket.deg,
                         lam_a, alpha_a, gram, implicit,
                         bf16=(matmul_dtype == "bfloat16"),
                         cg_steps=cg_steps, solver=solver,
                         cg_bf16=cg_bf16)
        X = X.reshape(-1, rank)[: bucket.n]
        out = out.at[bucket.row_ids].set(X)
    return out


# ---------------------------------------------------------------------------
# Training driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ALSFactors:
    user: jax.Array  # (num_users, K)
    item: jax.Array  # (num_items, K)


def resolve_shard_factors(param: bool) -> bool:
    """The engine-params ``shardFactors`` knob with its fleet-wide env
    override applied: ``PIO_TRAIN_SHARD_FACTORS=1`` forces DP×MP factor
    sharding on (retraining a grown catalog without editing every
    engine.json), ``=0`` forces replicated (an incident lever — sharded
    training needs a healthy multi-device mesh), unset defers to the
    param. All the ALS-family templates route through here so the env
    contract cannot drift between them (docs/parallelism.md)."""
    raw = os.environ.get("PIO_TRAIN_SHARD_FACTORS", "").strip().lower()
    if raw in ("1", "true", "on", "yes"):
        return True
    if raw in ("0", "false", "off", "no"):
        return False
    return bool(param)


def als_train(
    ratings: RatingsCOO,
    rank: int,
    iterations: int = 10,
    lam: float = 0.01,
    implicit: bool = False,
    alpha: float = 40.0,
    seed: int = 0,
    mesh: Mesh | None = None,
    min_bucket: int = 8,
    bucket_growth: int = 2,
    max_row_len: int | None = None,
    max_slab_elems: int = 1 << 24,
    hbm_resident: bool = True,
    matmul_dtype: str = "bfloat16",
    layout: str = "auto",
    chunk_sizes: Sequence[int] = (512, 128),
    chunked_acc_budget: int = 4 << 30,
    cg_steps: int | None = None,
    solver: str = "cg",
    shard_factors: bool = False,
    cg_matvec_dtype: str = "auto",
) -> ALSFactors:
    """Full alternating-least-squares training.

    Parity target: `ALS.train(ratings, rank, iterations, lambda)` /
    `ALS.trainImplicit(..., alpha)` semantics from the reference templates
    (ALSAlgorithm.scala:79-85); same hyperparameter meanings.

    ``layout="fused"`` (the ``"auto"`` default) pads whole rows to the
    MXU-width ladder (:func:`ladder_rows`) and runs ALL iterations as
    one device program (:func:`_als_iterate_fused`): normal equations
    are built and CG-solved slab-locally — no (num_rows, K, K)
    accumulator, no K×K scatter, one dispatch per training run. No
    ratings are dropped.
    ``layout="chunked"`` decomposes rows into fixed-size chunks
    (:func:`chunk_rows`): one dispatch per half-step, MXU-width
    contractions, no dropped ratings, ``len(chunk_sizes)`` compile keys
    — but carries a scan-threaded per-row accumulator (the phase
    profile that motivated the fused path: gather 119 / einsum 61 /
    scatter 100 / CG 113 ms per ML-20M rank-32 iteration).
    ``layout="bucketed"`` pads whole rows into a power-of-``bucket_growth``
    ladder (:func:`bucket_rows`) — the only mode supporting
    ``max_row_len``/streaming, at one dispatch per bucket.
    ``layout="auto"`` picks fused unless a bucketed-only knob
    (``max_row_len``, ``hbm_resident=False``) is set.
    ``chunked_acc_budget`` is unused since ``auto`` stopped routing on
    accumulator size (the fused layout is accumulator-free); retained
    for call-site compatibility.

    ``hbm_resident=True`` stages all rating slabs on device once (fast;
    needs ~8 bytes x padded nnz x 2 orientations of HBM).
    ``hbm_resident=False`` streams one slab batch at a time per
    half-step (bucketed layout only) — peak device memory bounded by
    ``max_slab_elems`` at the cost of re-transferring every iteration.

    ``matmul_dtype="bfloat16"`` (default) feeds the normal-equation
    einsums bf16 operands with f32 accumulation — measured 22-27%
    faster at ML-20M rank 32 with factors within ~5e-3 relative of the
    f32 path and every quality gate (RMSE parity, MAP seed band,
    implicit-beats-popularity) holding. Pass
    ``matmul_dtype="float32"`` for f32-HIGHEST bit-for-bit solver
    reproducibility.

    ``solver="cg"`` (default) uses the TPU-fast batched conjugate
    gradients at its measured-f32-plateau step cap (``cg_steps``
    overrides); ``solver="cholesky"`` opts into the exact direct solve
    (``_cho_solve_batched``) — 10-20x slower on TPU, useful as an
    accuracy oracle or for pathologically conditioned data. Fused and
    bucketed layouts only.

    ``cg_matvec_dtype="auto"`` (default) streams the CG's A-matrix in
    bfloat16 (f32 accumulation) at rank >= 64, where the per-slab
    systems are HBM-traffic-bound — measured 1.51x at the ML-20M
    rank-200 config with solve accuracy ~2.5e-3 relative vs an f64
    oracle (inside the band the bf16 normal-equation build already
    accepts; the rank-200 RMSE parity gate holds). ``"float32"`` /
    ``"bfloat16"`` force either way (see ``_cg_solve_batched``).

    ``shard_factors=True`` (with a ``mesh`` whose "model" axis is > 1)
    keeps BOTH factor tables row-sharded over the model axis for the
    whole run — the DP×MP tensor-parallel layout for catalog-scale
    tables that exceed one device's HBM (BASELINE's sharded-embeddings
    configuration). On the fused layout the tables are padded to a
    multiple of the model-axis size, stay sharded across every
    iteration of the scan, and the result tables come back sharded;
    XLA all-gathers one (opposite) table transiently per half-step for
    the slab gathers. Replicated (default) is faster whenever both
    tables fit. See docs/parallelism.md.
    """
    if layout not in ("auto", "fused", "chunked", "bucketed"):
        raise ValueError(
            f"layout must be 'auto', 'fused', 'chunked' or 'bucketed', "
            f"got {layout!r}")
    if layout == "auto":
        if max_row_len is not None or not hbm_resident:
            layout = "bucketed"   # row capping / streaming knobs
        else:
            layout = "fused"
    if layout == "fused" and (max_row_len is not None or not hbm_resident):
        raise ValueError(
            "max_row_len / hbm_resident=False are bucketed-layout knobs; "
            "pass layout='bucketed' (or 'auto') to use them")
    if layout == "fused":
        by_user = ladder_rows(ratings)
        by_item = ladder_rows(ratings.transpose())
        logger.info(
            "ALS(fused): %d ratings, %d users (%d buckets), %d items "
            "(%d buckets), rank %d",
            ratings.nnz, ratings.num_rows, len(by_user.buckets),
            ratings.num_cols, len(by_item.buckets), rank,
        )
        dev_user = stage_buckets(by_user, rank, mesh, max_slab_elems)
        dev_item = stage_buckets(by_item, rank, mesh, max_slab_elems)
        tp = bool(shard_factors and mesh is not None
                  and "model" in mesh.shape and int(mesh.shape["model"]) > 1)
        # table row counts pad to the model-axis size so every device
        # holds an equal shard; padded rows are never indexed by any
        # slab (col ids < num_cols) and are sliced off below
        model_ax = int(mesh.shape["model"]) if tp else 1
        num_users_p = ratings.num_rows + (-ratings.num_rows) % model_ax
        num_items_p = ratings.num_cols + (-ratings.num_cols) % model_ax
        key = jax.random.PRNGKey(seed)
        item0 = jax.random.normal(key, (ratings.num_cols, rank),
                                  dtype=jnp.float32)
        item0 = item0 / jnp.sqrt(jnp.float32(rank))
        if num_items_p != ratings.num_cols:
            # pad rows are ZERO: never gathered (col ids < num_cols),
            # and the implicit-mode gramian sums over every table row
            item0 = jnp.concatenate(
                [item0, jnp.zeros((num_items_p - ratings.num_cols, rank),
                                  dtype=jnp.float32)])
        if tp:
            item0 = jax.device_put(
                item0, NamedSharding(mesh, P("model", None)))
        user, item = _als_iterate_fused(
            item0, _fused_bucket_args(dev_user), _fused_bucket_args(dev_item),
            iterations, float(lam), float(alpha), implicit,
            num_users_p, num_items_p,
            bf16=(matmul_dtype == "bfloat16"), cg_steps=cg_steps,
            solver=solver, mesh=mesh if tp else None, shard_factors=tp,
            cg_bf16=_resolve_cg_matvec(cg_matvec_dtype, rank),
        )
        if num_users_p != ratings.num_rows:
            user = user[: ratings.num_rows]
        if num_items_p != ratings.num_cols:
            item = item[: ratings.num_cols]
        return ALSFactors(user=user, item=item)
    if layout == "chunked" and (max_row_len is not None or not hbm_resident):
        raise ValueError(
            "max_row_len / hbm_resident=False are bucketed-layout knobs "
            "(row capping and streaming); pass layout='bucketed' (or "
            "'auto') to use them — the chunked layout never drops ratings "
            "and stages slabs HBM-resident"
        )
    if layout == "chunked":
        by_user = chunk_rows(ratings, chunk_sizes)
        by_item = chunk_rows(ratings.transpose(), chunk_sizes)
        logger.info(
            "ALS: %d ratings, %d users, %d items, rank %d, chunks %s",
            ratings.nnz, ratings.num_rows, ratings.num_cols, rank,
            tuple(s.cols.shape for s in by_user.slabs),
        )
        by_user = stage_chunks(by_user, rank, mesh, max_slab_elems)
        by_item = stage_chunks(by_item, rank, mesh, max_slab_elems)
        key = jax.random.PRNGKey(seed)
        item = jax.random.normal(key, (ratings.num_cols, rank),
                                 dtype=jnp.float32)
        item = item / jnp.sqrt(jnp.float32(rank))
        user = None
        for _ in range(iterations):
            user = solve_half(item, by_user, rank, lam, implicit, alpha,
                              mesh, max_slab_elems, matmul_dtype,
                              shard_factors=shard_factors,
                              cg_steps=cg_steps, solver=solver,
                              cg_matvec_dtype=cg_matvec_dtype)
            item = solve_half(user, by_item, rank, lam, implicit, alpha,
                              mesh, max_slab_elems, matmul_dtype,
                              shard_factors=shard_factors,
                              cg_steps=cg_steps, solver=solver,
                              cg_matvec_dtype=cg_matvec_dtype)
        return ALSFactors(user=user, item=item)

    by_user = bucket_rows(ratings, min_bucket, bucket_growth, max_row_len)
    by_item = bucket_rows(ratings.transpose(), min_bucket, bucket_growth, max_row_len)
    logger.info(
        "ALS: %d ratings, %d users (%d buckets), %d items (%d buckets), rank %d",
        ratings.nnz, ratings.num_rows, len(by_user.buckets),
        ratings.num_cols, len(by_item.buckets), rank,
    )
    if hbm_resident:
        # stage slabs in HBM once — iterations are then pure device compute
        by_user = stage_buckets(by_user, rank, mesh, max_slab_elems)
        by_item = stage_buckets(by_item, rank, mesh, max_slab_elems)

    # MLlib-style init: scaled gaussian item factors, users solved first
    key = jax.random.PRNGKey(seed)
    item = jax.random.normal(key, (ratings.num_cols, rank), dtype=jnp.float32)
    item = item / jnp.sqrt(jnp.float32(rank))

    user = None
    for it in range(iterations):
        user = solve_half(item, by_user, rank, lam, implicit, alpha, mesh,
                          max_slab_elems, matmul_dtype,
                          shard_factors=shard_factors, cg_steps=cg_steps,
                          solver=solver, cg_matvec_dtype=cg_matvec_dtype)
        item = solve_half(user, by_item, rank, lam, implicit, alpha, mesh,
                          max_slab_elems, matmul_dtype,
                          shard_factors=shard_factors, cg_steps=cg_steps,
                          solver=solver, cg_matvec_dtype=cg_matvec_dtype)
    return ALSFactors(user=user, item=item)


# ---------------------------------------------------------------------------
# Prediction helpers
# ---------------------------------------------------------------------------


@instrumented_jit
def predict_ratings(user_f: jax.Array, item_f: jax.Array,
                    users: jax.Array, items: jax.Array) -> jax.Array:
    """Pointwise predicted ratings for (user, item) pairs."""
    return jnp.einsum("nk,nk->n", user_f[users], item_f[items])


def rmse(factors: ALSFactors, ratings: RatingsCOO, chunk: int = 1 << 20) -> float:
    """Root-mean-square error over the rating set, chunked to bound memory."""
    total = 0.0
    n = ratings.nnz
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        pred = predict_ratings(
            factors.user, factors.item,
            jnp.asarray(ratings.rows[s:e]), jnp.asarray(ratings.cols[s:e]),
        )
        err = np.asarray(pred) - ratings.vals[s:e]
        total += float(np.sum(err * err))
    return math.sqrt(total / max(n, 1))
