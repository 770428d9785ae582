"""ALS model object shared by the recommendation-family templates.

Holds the trained factor tables plus the entity-id ↔ dense-index maps and
per-user seen-item lists needed at serving time. Parity: the `ALSModel`
case classes of the reference templates (reference: tests/pio_tests/
engines/recommendation-engine/src/main/scala/ALSAlgorithm.scala:30-38 and
examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala) which
bundle userFeatures/productFeatures RDDs with the BiMaps.

Serving-time design: factors stay resident as jax.Arrays between
requests (no per-query transfer) and queries are answered by the jitted
fixed-shape kernels in ops/topk — the "models resident in HBM, no
per-query recompile" requirement of SURVEY.md §7 step 7. The factors
are float32; the brute-force recommend paths score from a bfloat16
copy of the item table made once on the device
(``ALSModel.serving_item_factors``), half the bytes of every scan.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from functools import partial as _partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs.compile import instrumented_jit
from predictionio_tpu.obs.trace import span
from predictionio_tpu.ops import ann as ann_ops
from predictionio_tpu.ops import topk as topk_ops
from predictionio_tpu.serving.dispatch_phases import start_copies
from predictionio_tpu.utils.bimap import BiMap, EntityIdIxMap

logger = logging.getLogger(__name__)

# serving-time pad length for seen-item lists: one compiled kernel shape
_SEEN_PAD = 512

#: model-directory subdir holding the ANN index checkpoint (its arrays
#: ride the same checksummed utils/checkpoint envelope as the factors)
_ANN_SUBDIR = "ann"


def _model_shard_ways(arr) -> int:
    """How many ways a factor table is row-sharded over a ``"model"``
    mesh axis — 1 for replicated/host/NumPy tables. Duck-typed over the
    array's ``.sharding`` so host arrays and single-device jax.Arrays
    (SingleDeviceSharding has no mesh) all answer 1."""
    sharding = getattr(arr, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    axes = dict(getattr(mesh, "shape", None) or {})
    if not axes or spec is None or not len(spec):
        return 1
    dim0 = spec[0]
    names = dim0 if isinstance(dim0, tuple) else (dim0,)
    if "model" not in names:
        return 1
    return int(axes.get("model", 1))


def _serving_shard_ways(n_items: int, n_devices: int) -> int:
    """The model-axis width a deployed catalog of ``n_items`` rows can
    shard over: the largest device count whose shards come out equal
    (``device_put`` rejects uneven NamedShardings). 1 = stay
    replicated."""
    for ways in range(min(n_devices, n_items), 1, -1):
        if n_items % ways == 0:
            return ways
    return 1


def _resolve_serving_shardings(meta: Mapping, mesh) -> dict | None:
    """Target shardings for :meth:`ALSModel.load` (None = replicated).

    Sharded serving engages when the caller passes a ``mesh``, when the
    checkpoint meta says the model was persisted sharded, or when
    ``PIO_SERVING_SHARD_FACTORS=1`` forces it; ``=0`` vetoes all three.
    The item table MUST divide the model axis (the sharded top-k
    dispatch is shard_map-even); a table that doesn't stays replicated
    with a warning rather than failing the deploy."""
    env = os.environ.get("PIO_SERVING_SHARD_FACTORS", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return None
    if not (mesh is not None or "sharded" in meta
            or env in ("1", "true", "on", "yes")):
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n_users = len(meta["user_ids"])
    n_items = len(meta["item_ids"])
    if mesh is None:
        devices = jax.devices()
        ways = _serving_shard_ways(n_items, len(devices))
        if ways <= 1:
            logger.warning(
                "sharded serving requested but the catalog (%d rows) has "
                "no >=2-way even split over %d device(s); serving "
                "replicated", n_items, len(devices))
            return None
        # all devices on the model axis: per-device table footprint is
        # 1/ways, and a data axis of 1 admits every query batch size
        mesh = Mesh(np.asarray(devices[:ways]).reshape(1, ways),
                    ("data", "model"))
    axes = dict(mesh.shape)
    ways = int(axes.get("model", 1))
    if ways <= 1 or n_items % ways:
        logger.warning(
            "item table (%d rows) cannot row-shard over the mesh model "
            "axis (%d); serving replicated", n_items, ways)
        return None
    row_sharded = NamedSharding(mesh, PartitionSpec("model", None))
    shardings = {"item": row_sharded}
    if n_users % ways == 0:
        shardings["user"] = row_sharded
    else:
        logger.warning(
            "user table (%d rows) does not divide the model axis (%d); "
            "user factors stay replicated", n_users, ways)
    logger.info("restoring factor tables row-sharded %d-way over the "
                "model axis (sharded top-k serving dispatch)", ways)
    return shardings


@_partial(instrumented_jit, static_argnames=("k",))
def _serve_recommend(user_factors, item_f, packed, allow, k):
    """Single-dispatch, single-transfer serving path.

    One host->device and one device->host transfer per query: the
    query uploads as ONE int32 buffer [uix, seen_cols(512),
    seen_mask(512)] and the result downloads as ONE int32 buffer
    [bitcast(vals,k), idxs(k)]. One blocking copy of a ready 320-byte
    result to the host is 0.43 ms on a v5e, whatever its size: a round
    trip to the device (PERF.md §5, the copy probe of PR 32). The
    batched path starts its copies at launch instead
    (serving/dispatch_phases.start_copies); here one launch is followed
    at once by its one ``np.asarray``, so there is nothing to hide."""
    uix = packed[0]
    cols = packed[1 : 1 + _SEEN_PAD][None, :]
    mask = (packed[1 + _SEEN_PAD : 1 + 2 * _SEEN_PAD] > 0
            ).astype(jnp.float32)[None, :]
    uv = user_factors[uix[None]]                     # (1, K)
    vals, idxs = topk_ops.recommend_topk(uv, item_f, cols, mask, allow, k)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals[0], jnp.int32), idxs[0]])


@_partial(instrumented_jit, static_argnames=("k", "nprobe", "rescore"))
def _serve_recommend_ann(user_factors, item_f, centroids, flat_items,
                         flat_vecs, cell_offset, packed, allow, k, nprobe,
                         rescore):
    """ANN twin of :func:`_serve_recommend`: same packed single-upload
    query buffer, same bitcast single-download result — the dispatch
    inside is probe → shortlist gather → exact rescore (ops/ann)
    instead of the full-catalog matmul."""
    uix = packed[0]
    cols = packed[1 : 1 + _SEEN_PAD][None, :]
    mask = (packed[1 + _SEEN_PAD : 1 + 2 * _SEEN_PAD] > 0
            ).astype(item_f.dtype)[None, :]
    uv = user_factors[uix[None]]                     # (1, K)
    vals, idxs = ann_ops.ann_topk(uv, item_f, centroids, flat_items,
                                  flat_vecs, cell_offset, cols, mask, allow,
                                  k, nprobe, rescore)
    # k clamps to the shortlist width in-kernel; callers recompute the
    # effective k from the (static) index geometry to slice the buffer
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals[0], jnp.int32), idxs[0]])


@_partial(instrumented_jit, static_argnames=("k", "nprobe", "rescore"))
def _serve_similar_ann(item_f, centroids, flat_items, flat_vecs,
                       cell_offset, packed, allow, k, nprobe, rescore):
    """ANN twin of :func:`_serve_similar`: cosine probe + exact cosine
    rescore on the shortlist, query vector and self-exclusion both
    derived in-kernel from the packed [n_real, query_ixs] buffer."""
    n_real = packed[0]
    ixs = packed[1 : 1 + _SEEN_PAD]
    w = (jnp.arange(_SEEN_PAD) < n_real).astype(item_f.dtype)
    gathered = item_f[ixs] * w[:, None]
    qvec = (jnp.sum(gathered, axis=0) /
            jnp.maximum(n_real.astype(item_f.dtype), 1.0))[None, :]
    vals, idxs = ann_ops.ann_similar_topk(
        qvec, item_f, centroids, flat_items, flat_vecs, cell_offset,
        ixs[None, :], w[None, :], allow, k, nprobe, rescore)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals[0], jnp.int32), idxs[0]])


@_partial(instrumented_jit, static_argnames=("k",))
def _serve_similar(item_f, packed, allow, k):
    """Single-dispatch, single-transfer similar-items path. Upload is one
    int32 buffer [n_real, query_ixs(_SEEN_PAD)]; the query vector is the
    mean of the first n_real item rows, and those same rows double as the
    self-exclusion (seen) list — both masks derive from n_real."""
    n_real = packed[0]
    ixs = packed[1 : 1 + _SEEN_PAD]
    w = (jnp.arange(_SEEN_PAD) < n_real).astype(item_f.dtype)
    gathered = item_f[ixs] * w[:, None]
    qvec = (jnp.sum(gathered, axis=0) /
            jnp.maximum(n_real.astype(item_f.dtype), 1.0))[None, :]
    vals, idxs = topk_ops.similar_topk(
        qvec, item_f, ixs[None, :], w[None, :], allow, k)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(vals[0], jnp.int32), idxs[0]])


@instrumented_jit
def _serving_copy(table):
    """The item table as the brute-force recommend programs read it:
    bfloat16, half the bytes of a scan (PERF.md §5, PR 35). One
    elementwise program, so a row-sharded table keeps its sharding."""
    return table.astype(jnp.bfloat16)


@instrumented_jit
def _take_rows(table, ixs):
    """``table[ixs]`` as one device program: eager indexing launches
    seven (index wrap, broadcasts, the gather), 3-4 ms of host work a
    dispatch on the chip (PERF.md, PR 23)."""
    return table[ixs]


@dataclasses.dataclass
class ALSModel:
    """Factors + id maps + seen lists; device-resident while serving."""

    rank: int
    user_factors: jax.Array            # (U, K)
    item_factors: jax.Array            # (I, K)
    user_ids: EntityIdIxMap
    item_ids: EntityIdIxMap
    seen_by_user: Mapping[int, np.ndarray]  # user ix -> seen item ix array
    # device-cached all-ones eligibility vector: building it per query
    # costs ~125ms of host+transfer at a 2M-item catalog (measured);
    # never serialized
    _default_allow: object = dataclasses.field(default=None, repr=False,
                                               compare=False)
    #: device-cached ``(item_factors, its bfloat16 copy)``: the copy is
    #: what every brute-force recommend path scores from
    #: (``serving_item_factors``); made once a table (the pair says which
    #: table: ``dataclasses.replace(model, item_factors=...)`` carries
    #: this field over), never serialized: ``item_factors`` stays the
    #: model
    _serving_items: object = dataclasses.field(default=None, repr=False,
                                               compare=False)
    #: IVF-flat MIPS index over item_factors (ops/ann.AnnIndex), built
    #: at persist time and serialized beside the factor checkpoint;
    #: None = brute force only
    ann_index: object | None = dataclasses.field(default=None, repr=False,
                                                 compare=False)
    #: serving retrieval mode ("brute" | "ann") + probe/rescore knobs —
    #: set by configure_retrieval from ServerConfig, never serialized
    #: as policy (the index is data; the mode is deployment config)
    retrieval: str = dataclasses.field(default="brute", compare=False)
    ann_nprobe: int = dataclasses.field(default=0, compare=False)
    ann_rescore: int = dataclasses.field(default=0, compare=False)
    #: optional callable(shortlist_width, queries) the serving layer
    #: installs to count ANN dispatches (api/stats.ServingStats)
    _ann_observer: object = dataclasses.field(default=None, repr=False,
                                              compare=False)
    #: optional callable() the serving layer installs to count the
    #: brute dispatches whose program selects its top k in two stages
    #: (api/stats.ServingStats.record_two_stage_topk)
    _topk_observer: object = dataclasses.field(default=None, repr=False,
                                               compare=False)
    #: real-time freshness overlay (online/overlay.OnlineOverlay),
    #: installed by the fold-in service under ``pio deploy --online``
    #: — per-user vector deltas + brand-new-item vectors consulted by
    #: the serving paths below (docs/freshness.md); serving wiring,
    #: never serialized
    online_overlay: object = dataclasses.field(default=None, repr=False,
                                               compare=False)

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_default_allow"] = None
        state["_serving_items"] = None
        # the observer is serving wiring (holds the stats lock), not model
        state["_ann_observer"] = None
        state["_topk_observer"] = None
        state["online_overlay"] = None
        return state

    def _allow_or_default(self, allow):
        if allow is not None:
            return jnp.asarray(allow, dtype=jnp.float32)
        if self._default_allow is None:
            self._default_allow = jax.device_put(
                jnp.ones((self.item_factors.shape[0],), dtype=jnp.float32))
        return self._default_allow

    def serving_item_factors(self):
        """The table the brute-force recommend paths score from: the
        bfloat16 copy of ``item_factors`` (``item_factors`` itself where
        it is bfloat16 already), made by one jitted cast on first use
        and kept for as long as ``item_factors`` is that array. Scores are its products with the user rows (rounded
        to bfloat16 too where the matrix unit multiplies), accumulated
        in float32 (ops/topk._scores), so ids may differ from the
        float32 order only where scores tie within 2^-8 |u| |v|; it
        costs 2 bytes an entry of device memory beside the float32
        table, which stays what ``save``, ``predict_rating``,
        ``similar``, the ANN index and the online fold-in read
        (docs/serving-performance.md)."""
        table = self.item_factors
        cached = self._serving_items
        if cached is None or cached[0] is not table:
            cached = self._serving_items = (
                table, table if table.dtype == jnp.bfloat16
                else _serving_copy(table))
        return cached[1]

    @property
    def score_table_bytes_per_entry(self) -> int:
        """Byte width of an entry of the table brute-force dispatches
        read (2, or 4 had the copy stayed float32): the `/stats.json`
        ``serving.scoreTableBytesPerEntry`` signal. Reading it makes the
        serving copy, as the first dispatch would."""
        return int(self.serving_item_factors().dtype.itemsize)

    # ---- sublinear retrieval (ops/ann; docs/serving-performance.md) -----
    def configure_retrieval(self, mode: str = "brute", nprobe: int = 0,
                            rescore: int = 0, nlist: int = 0,
                            observer=None) -> None:
        """Apply the deployment's retrieval knobs (ServerConfig
        ``retrieval`` / ``ann_nprobe`` / ``ann_rescore``). Requesting
        ``ann`` on a model persisted without an index builds one here
        (deploy-time fallback — train/persist is the intended build
        point); a catalog too small to index degrades to brute with a
        warning instead of failing the deploy."""
        if mode == "ann" and self.ann_index is None:
            # build_index gathers sharded/device tables to host itself
            # (chunked, with a pinned warning) — no eager np.asarray
            # here, which would replicate a row-sharded table silently
            built = ann_ops.build_index(self.item_factors, nlist=nlist)
            if built is None:
                logger.warning(
                    "retrieval=ann requested but the catalog has only %d "
                    "items (< %d): serving brute force",
                    self.item_factors.shape[0], ann_ops.MIN_INDEX_ITEMS)
                mode = "brute"
            else:
                logger.info(
                    "retrieval=ann: built IVF index at deploy time "
                    "(nlist=%d, max cell=%d) — persist the model with a "
                    "newer `pio train` to build it once at train time",
                    built.nlist, built.max_cell)
                self.ann_index = built
        self.retrieval = mode
        self.ann_nprobe = max(0, int(nprobe))
        self.ann_rescore = max(0, int(rescore))
        self._ann_observer = observer

    # ---- real-time freshness overlay (online/; docs/freshness.md) -------
    def set_online_overlay(self, overlay) -> None:
        """Install the fold-in service's delta overlay. Queries for
        users with a delta (and, while overlay ITEMS exist, every
        recommendation query — the new items must be mergeable for
        everyone) take the overlay-aware path below."""
        self.online_overlay = overlay

    def online_delta(self, user_id: str):
        """The user's fold-in delta, or None (no overlay / not folded)."""
        overlay = self.online_overlay
        return overlay.user(user_id) if overlay is not None else None

    def needs_online_path(self, user_id: str) -> bool:
        """True when a query for ``user_id`` must take the single-query
        overlay-aware path instead of the batched kernel — the routing
        hook the template ``batch_predict`` implementations use. True
        for folded users, and for EVERYONE while overlay items exist
        (the batched kernel scores only the base catalog; a cold-start
        item would be invisible to batch-path users)."""
        overlay = self.online_overlay
        if overlay is None:
            return False
        return overlay.has_items() or overlay.user(user_id) is not None

    def set_ann_observer(self, observer) -> None:
        """Install the serving layer's ANN dispatch counter
        (callable(shortlist_width, queries) — e.g.
        ``ServingStats.record_ann``) without re-running retrieval
        configuration."""
        self._ann_observer = observer

    def set_topk_observer(self, observer) -> None:
        """Install the serving layer's counter of two-stage top-k
        dispatches (callable() — ``ServingStats.record_two_stage_topk``)."""
        self._topk_observer = observer

    @property
    def ann_enabled(self) -> bool:
        """True when queries are being answered through the ANN index
        (mode configured AND an index exists) — the serving layer's
        `/stats.json` / `/metrics` signal."""
        return self._ann_active()

    def _ann_active(self) -> bool:
        return self.retrieval == "ann" and self.ann_index is not None

    @property
    def factor_shard_ways(self) -> int:
        """Model-axis row-shard width of the deployed item table (1 =
        replicated) — the `/stats.json` / deploy-log signal for whether
        queries dispatch through the distributed top-k merge."""
        return _model_shard_ways(self.item_factors)

    def _serving_mesh(self):
        """The mesh to run :func:`ops.topk.recommend_topk_sharded` over
        when the deployed item table is row-sharded over a ``"model"``
        axis > 1 and the catalog divides it — else None (brute/flat
        dispatch). Sharded tables whose row count stopped dividing the
        axis (it cannot happen through :meth:`load`, which picks the
        axis from the row count) degrade to the flat path rather than
        raising out of the serving loop."""
        ways = _model_shard_ways(self.item_factors)
        if ways <= 1 or int(self.item_factors.shape[0]) % ways:
            return None
        return self.item_factors.sharding.mesh

    def _ann_args(self) -> tuple:
        """(device arrays..., nprobe, rescore) for the jitted kernels —
        nprobe clamped to the index so the static args are always
        legal."""
        index = self.ann_index
        centroids, flat_items, flat_vecs, cell_offset = index.device_arrays()
        return (centroids, flat_items, flat_vecs, cell_offset,
                index.clamp_nprobe(self.ann_nprobe), self.ann_rescore)

    def _record_ann(self, width: int, queries: int) -> None:
        if self._ann_observer is not None:
            self._ann_observer(width, queries)

    # ---- single-query serving ------------------------------------------
    def recommend(
        self,
        user_id: str,
        num: int,
        allow: np.ndarray | None = None,
        exclude_seen: bool = True,
    ) -> list[tuple[str, float]]:
        """Top-``num`` unseen items for one user; [] for unknown users
        (the reference template's behavior for users absent from
        training — unless the online overlay folded a vector for them:
        cold-start-to-served, docs/freshness.md)."""
        overlay = self.online_overlay
        delta = overlay.user(user_id) if overlay is not None else None
        if delta is not None or (overlay is not None
                                 and overlay.has_items()):
            return self._recommend_online(user_id, delta, num, allow,
                                          exclude_seen)
        uix = self.user_ids.get(user_id)
        if uix is None:
            return []
        seen = (
            self.seen_by_user.get(uix, np.empty(0, dtype=np.int32))
            if exclude_seen
            else np.empty(0, dtype=np.int32)
        )
        if len(seen) > _SEEN_PAD:
            # exclude_seen is a correctness contract — overflow beyond
            # the packed buffer folds into the allow vector (exact; one
            # extra (I,) upload only for >512-item histories) instead
            # of silently truncating
            if allow is None:
                allow = np.ones((self.item_factors.shape[0],),
                                dtype=np.float32)
            else:
                allow = np.asarray(allow, dtype=np.float32).copy()
            allow[seen[_SEEN_PAD:]] = 0.0
            seen = seen[:_SEEN_PAD]
        allow_v = self._allow_or_default(allow)
        k = min(_serving_k(num), self.item_factors.shape[0])
        mesh = None if self._ann_active() else self._serving_mesh()
        if mesh is not None:
            # deployed-sharded dispatch: the distributed top-k merge
            # moves n_model*k candidates over ICI instead of gathering
            # the row-sharded table for a (1, I) score row
            cols = np.zeros((1, _SEEN_PAD), dtype=np.int32)
            mask = np.zeros((1, _SEEN_PAD), dtype=np.float32)
            cols[0, : len(seen)] = seen
            mask[0, : len(seen)] = 1.0
            uv = self.user_factors[jnp.asarray([uix], dtype=jnp.int32)]
            vals, idxs = topk_ops.recommend_topk_sharded(
                uv, self.serving_item_factors(), jnp.asarray(cols),
                jnp.asarray(mask), allow_v, k, mesh)
            return self._gather_results(
                np.asarray(vals)[0], np.asarray(idxs)[0], num)
        buf = np.zeros((1 + 2 * _SEEN_PAD,), dtype=np.int32)
        buf[0] = uix
        buf[1 : 1 + len(seen)] = seen
        buf[1 + _SEEN_PAD : 1 + _SEEN_PAD + len(seen)] = 1
        if self._ann_active():
            # sublinear path: probe the IVF cells, exact-rescore the
            # shortlist (ops/ann) — same packed single-dispatch contract
            centroids, flat_items, flat_vecs, cell_offset, nprobe, rescore = \
                self._ann_args()
            width = self.ann_index.shortlist_width(nprobe, rescore)
            k_eff = min(k, width)
            out = np.asarray(_serve_recommend_ann(
                self.user_factors, self.item_factors, centroids,
                flat_items, flat_vecs, cell_offset, jnp.asarray(buf),
                allow_v, k, nprobe, rescore,
            ))
            self._record_ann(width, 1)
            return self._gather_results(
                out[:k_eff].view(np.float32), out[k_eff:], num)
        # one jitted dispatch, one upload, one download end-to-end; B=1
        # always takes the flat XLA kernel — the chunked-scan dispatch
        # engages only for batched prediction (batch_predict) at scale
        out = np.asarray(_serve_recommend(
            self.user_factors, self.serving_item_factors(), jnp.asarray(buf),
            allow_v, k,
        ))
        return self._gather_results(out[:k].view(np.float32), out[k:], num)

    def _recommend_online(self, user_id: str, delta, num: int,
                          allow: np.ndarray | None,
                          exclude_seen: bool) -> list[tuple[str, float]]:
        """The overlay-aware recommendation path (docs/freshness.md):
        the query vector is the FOLDED one when a delta exists (falling
        back to the base row), seen-exclusion unions the base history
        with the post-training item indices the fold recorded, and —
        for unfiltered queries — the overlay's brand-new items are
        brute-scored on the host (a tiny ``(m, K) @ (K,)`` product)
        and merged into the device top-k. The base catalog is still
        ranked by the configured retrieval (brute or ANN), so the IVF
        index is never rebuilt online and unchanged items rank
        identically (the recall-neutrality pin in tests/test_ann.py)."""
        uix = self.user_ids.get(user_id)
        if delta is not None:
            uv = np.asarray(delta.vector, dtype=np.float32)
        elif uix is not None:
            # one K-float host read of the base row — the overlay-items
            # window's cost for non-folded users
            uv = np.asarray(self.user_factors[uix], dtype=np.float32)
        else:
            return []
        # captured BEFORE any overflow fold below: delta items bypass
        # the catalog-indexed allow vector, so business-rule-filtered
        # queries serve the base catalog only (documented caveat)
        caller_filtered = allow is not None
        seen = np.empty(0, dtype=np.int32)
        if exclude_seen:
            parts = [self.seen_by_user.get(uix, np.empty(0, dtype=np.int32))
                     ] if uix is not None else []
            if delta is not None and delta.extra_seen:
                parts.append(np.asarray(delta.extra_seen, dtype=np.int32))
            if parts:
                seen = np.unique(np.concatenate(parts)).astype(np.int32)
        if len(seen) > _SEEN_PAD:
            # same overflow contract as the base path: beyond the
            # packed width the exclusion folds into the allow vector
            if allow is None:
                allow = np.ones((self.item_factors.shape[0],),
                                dtype=np.float32)
            else:
                allow = np.asarray(allow, dtype=np.float32).copy()
            allow[seen[_SEEN_PAD:]] = 0.0
            seen = seen[:_SEEN_PAD]
        allow_v = self._allow_or_default(allow)
        k = min(_serving_k(num), self.item_factors.shape[0])
        cols = np.zeros((1, _SEEN_PAD), dtype=np.int32)
        mask = np.zeros((1, _SEEN_PAD), dtype=np.float32)
        cols[0, : len(seen)] = seen
        mask[0, : len(seen)] = 1.0
        uvj = jnp.asarray(uv[None, :])
        if self._ann_active():
            centroids, flat_items, flat_vecs, cell_offset, nprobe, \
                rescore = self._ann_args()
            vals, idxs = ann_ops.ann_topk(
                uvj, self.item_factors, centroids, flat_items,
                flat_vecs, cell_offset, jnp.asarray(cols),
                jnp.asarray(mask), allow_v, k, nprobe, rescore)
            self._record_ann(
                self.ann_index.shortlist_width(nprobe, rescore), 1)
        else:
            vals, idxs = topk_ops.recommend_topk(
                uvj, self.serving_item_factors(), jnp.asarray(cols),
                jnp.asarray(mask), allow_v, k)
        base = self._gather_results(
            np.asarray(vals)[0], np.asarray(idxs)[0], num)
        if caller_filtered:
            return base[:num]
        overlay = self.online_overlay
        snap = overlay.delta_matrix() if overlay is not None else None
        if snap is None:
            return base[:num]
        ids, matrix = snap
        scores = matrix @ uv
        hidden = (set(delta.delta_seen)
                  if (delta is not None and exclude_seen) else ())
        merged = base + [(iid, float(s)) for iid, s in zip(ids, scores)
                         if iid not in hidden]
        merged.sort(key=lambda kv: kv[1], reverse=True)
        return merged[:num]

    def similar(
        self,
        item_id_list: Sequence[str],
        num: int,
        allow: np.ndarray | None = None,
    ) -> list[tuple[str, float]]:
        """Top-``num`` items most similar (cosine) to the query items —
        the similarproduct template's query contract; unknown items are
        skipped, all-unknown queries return []."""
        ixs = [self.item_ids.get(i) for i in item_id_list]
        ixs = [i for i in ixs if i is not None]
        if not ixs:
            return []
        allow_v = self._allow_or_default(allow)
        k = min(_serving_k(num), self.item_factors.shape[0])
        if len(ixs) <= _SEEN_PAD:
            # fast path: one packed upload, mean + exclusion in-kernel
            buf = np.zeros((1 + _SEEN_PAD,), dtype=np.int32)
            buf[0] = len(ixs)
            buf[1 : 1 + len(ixs)] = np.asarray(ixs, dtype=np.int32)
            if self._ann_active():
                # cosine probe + exact cosine rescore (ops/ann): the
                # SAME index answers the similarproduct ranking
                centroids, flat_items, flat_vecs, cell_offset, nprobe, \
                    rescore = self._ann_args()
                width = self.ann_index.shortlist_width(nprobe, rescore)
                k_eff = min(k, width)
                out = np.asarray(_serve_similar_ann(
                    self.item_factors, centroids, flat_items, flat_vecs,
                    cell_offset, jnp.asarray(buf), allow_v, k, nprobe,
                    rescore,
                ))
                self._record_ann(width, 1)
                return self._gather_results(
                    out[:k_eff].view(np.float32), out[k_eff:], num)
            out = np.asarray(_serve_similar(
                self.item_factors, jnp.asarray(buf), allow_v, k,
            ))
            return self._gather_results(
                out[:k].view(np.float32), out[k:], num)
        # rare giant queries: mean over the FULL list (reference contract);
        # the exclusion list clips to the kernel width like before
        qvec = jnp.mean(self.item_factors[jnp.asarray(ixs)], axis=0,
                        keepdims=True)
        cols = np.zeros((1, _SEEN_PAD), dtype=np.int32)
        mask = np.zeros((1, _SEEN_PAD), dtype=np.float32)
        cols[0] = np.asarray(ixs[:_SEEN_PAD], dtype=np.int32)
        mask[0] = 1.0
        vals, idxs = topk_ops.similar_topk(
            qvec, self.item_factors, jnp.asarray(cols), jnp.asarray(mask),
            allow_v, k,
        )
        return self._gather_results(
            np.asarray(vals)[0], np.asarray(idxs)[0], num)

    def batch_topk(self, uixs: np.ndarray, seen_cols, seen_mask, allow,
                   k: int) -> tuple:
        """Batched masked top-k over dense user indices — the
        recommendation template's batch_predict hot path. Dispatches to
        the configured retrieval: brute routes through the
        flat/chunked-scan dispatcher (ops/topk.recommend_topk_fused_rows),
        whose program gathers the users' rows itself from the table and
        the index array — ONE device launch a dispatch; ann (the IVF
        probe + exact-rescore kernel, ops/ann) and the deployed-sharded
        merge take vectors, which one small jitted gather
        (:func:`_take_rows`) hands them. The brute and sharded branches
        score from :meth:`serving_item_factors`, as the single-query
        ``recommend`` does; ann keeps the float32 table. ``allow=None``
        uses the device-cached all-ones vector. Whichever launched, the
        copy of its ``(vals, idxs)`` to the host is started before this
        returns (serving/dispatch_phases.start_copies); the caller
        collects it."""
        # dispatch.gather / dispatch.enqueue: ambient spans on the
        # batcher's per-dispatch trace (no-ops with tracing off). Both
        # time the HOST side only — upload + launch return before the
        # device finishes; batch_predict's dispatch.device_wait awaits it.
        # gather holds a launch on the ann / sharded branches only
        with span("dispatch.gather"):
            uixs = np.asarray(uixs, dtype=np.int32)
            allow_v = self._allow_or_default(allow)
            ann = self._ann_active()
            mesh = None if ann else self._serving_mesh()
            sharded = mesh is not None and allow_v.ndim == 1
            if ann or sharded:
                uv = _take_rows(self.user_factors, uixs)
        with span("dispatch.enqueue"):
            if ann:
                centroids, flat_items, flat_vecs, cell_offset, nprobe, \
                    rescore = self._ann_args()
                self._record_ann(
                    self.ann_index.shortlist_width(nprobe, rescore),
                    int(uv.shape[0]))
                launched = ann_ops.ann_topk(
                    uv, self.item_factors, centroids, flat_items, flat_vecs,
                    cell_offset, jnp.asarray(seen_cols),
                    jnp.asarray(seen_mask), allow_v, k, nprobe, rescore)
            elif sharded:
                # deployed-sharded dispatch (docs/parallelism.md): local
                # top-k per model shard, candidate all-gather, global merge
                launched = topk_ops.recommend_topk_sharded(
                    uv, self.serving_item_factors(),
                    jnp.asarray(np.asarray(seen_cols, dtype=np.int32)),
                    jnp.asarray(np.asarray(seen_mask, dtype=np.float32)),
                    allow_v, k, mesh)
            else:
                if (self._topk_observer is not None
                        and topk_ops.selects_two_stage(
                            allow_v, self.item_factors, uixs.shape[0], k)):
                    self._topk_observer()
                launched = topk_ops.recommend_topk_fused_rows(
                    self.user_factors, uixs, self.serving_item_factors(),
                    # NumPy stays NumPy on purpose: the dispatcher's
                    # host-side _trim_seen can only right-size concrete
                    # host arrays, and jit uploads them, with the
                    # indices, in the one launch
                    seen_cols, seen_mask, allow_v, k)
            # the one way out of all three branches: the copies of
            # (vals, idxs) to the host start behind the program, first
            # thing after its launch (dispatch.copy_start)
            return start_copies(launched)

    def predict_rating(self, user_id: str, item_id: str) -> float | None:
        uix = self.user_ids.get(user_id)
        iix = self.item_ids.get(item_id)
        if uix is None or iix is None:
            return None
        return float(
            jnp.dot(self.user_factors[uix], self.item_factors[iix])
        )

    def _gather_results(
        self, vals: jax.Array, idxs: jax.Array, num: int
    ) -> list[tuple[str, float]]:
        vals = np.asarray(vals)
        idxs = np.asarray(idxs)
        inv = self.item_ids.inverse
        out = []
        for v, i in zip(vals[:num], idxs[:num]):
            if not np.isfinite(v):
                break  # masked slots sort last; stop at the first -inf
            out.append((inv[int(i)], float(v)))
        return out

    # ---- persistence ----------------------------------------------------
    def save(self, directory: str) -> None:
        """Factor tables via utils/checkpoint.save_sharded (orbax: sharded
        jax.Arrays write shard-locally, no gather-to-host — the SURVEY §7
        sharded-persistence contract) + JSON id maps.

        The ANN index is built HERE (the train/persist stage) when the
        catalog is big enough to benefit — serving then loads a ready
        index instead of paying k-means at deploy. Its arrays ride the
        same checksummed checkpoint envelope as the factors, in the
        ``ann/`` subdirectory; ``PIO_SERVING_ANN_NLIST`` overrides the
        auto cell count at build time and ``PIO_SERVING_ANN_BUILD=0``
        skips the build (brute-only fleets)."""
        from predictionio_tpu.utils.checkpoint import save_sharded

        os.makedirs(directory, exist_ok=True)
        save_sharded(directory, {
            "user": self.user_factors,
            "item": self.item_factors,
        })
        # only after the new checkpoint is fully written: drop a legacy
        # factors.npz so the directory holds a single source of truth
        legacy = os.path.join(directory, "factors.npz")
        if os.path.exists(legacy):
            os.remove(legacy)
        # PIO_SERVING_ANN_BUILD=0 skips the persist-time index build
        # (and its flat_vecs copy of the item table in the checkpoint)
        # for fleets that only ever serve brute; deploy --retrieval ann
        # can still build at load time
        build = os.environ.get("PIO_SERVING_ANN_BUILD", "1").strip().lower()
        if self.ann_index is None and build not in ("0", "false", "off"):
            try:
                nlist = int(os.environ.get("PIO_SERVING_ANN_NLIST", "0"))
            except ValueError:
                nlist = 0
            # build_index gathers sharded tables to host itself
            # (chunked per-shard device_get, pinned warning)
            self.ann_index = ann_ops.build_index(self.item_factors,
                                                 nlist=nlist)
        if self.ann_index is not None:
            save_sharded(os.path.join(directory, _ANN_SUBDIR),
                         self.ann_index.to_arrays())
        # a model trained with shard_factors persists the fact: load()
        # reads it to restore straight onto a serving mesh (row-sharded
        # tables, sharded top-k dispatch) instead of replicating
        ways = max(_model_shard_ways(self.user_factors),
                   _model_shard_ways(self.item_factors))
        meta = {
            "rank": self.rank,
            "user_ids": self.user_ids.id_to_ix.to_dict(),
            "item_ids": self.item_ids.id_to_ix.to_dict(),
            "seen": {str(k): np.asarray(v).tolist() for k, v in self.seen_by_user.items()},
            **({"ann": {"nlist": self.ann_index.nlist,
                        "n_items": self.ann_index.n_items}}
               if self.ann_index is not None else {}),
            **({"sharded": {"axis": "model", "ways": ways}}
               if ways > 1 else {}),
        }
        with open(os.path.join(directory, "model.json"), "w") as f:
            json.dump(meta, f)

    @staticmethod
    def load(directory: str, shardings: dict | None = None,
             mesh=None) -> "ALSModel":
        """``shardings`` optionally maps "user"/"item" to target
        ``NamedSharding``s so factors restore straight onto a mesh.

        ``mesh`` is the higher-level knob: row-shard both tables over
        its ``"model"`` axis (tables whose row count does not divide
        the axis stay replicated, with a warning — degrade-don't-die).
        With neither argument, a model *persisted* sharded (``sharded``
        in model.json — it was trained with ``shardFactors``) restores
        straight back onto a serving mesh over the available devices,
        so `pio deploy` serves it through the sharded top-k dispatch
        without any template change; ``PIO_SERVING_SHARD_FACTORS=1``
        forces that for replicated-persisted models too (a grown
        catalog that stopped fitting), ``=0`` disables it."""
        from predictionio_tpu.utils.checkpoint import (
            default_mmap_mode,
            load_sharded,
        )

        with open(os.path.join(directory, "model.json")) as f:
            meta = json.load(f)
        if shardings is None:
            shardings = _resolve_serving_shardings(meta, mesh)
        # an orbax dir without meta means a crash interrupted save() after
        # the checkpoint write — still newer than any legacy factors.npz
        has_new = os.path.exists(
            os.path.join(directory, "checkpoint_meta.json")
        ) or os.path.isdir(os.path.join(directory, "orbax"))
        if not has_new and os.path.exists(os.path.join(directory, "factors.npz")):
            # legacy single-file layout
            legacy = np.load(os.path.join(directory, "factors.npz"))
            data = {"user": legacy["user"], "item": legacy["item"]}
            if shardings:
                data = {
                    k: jax.device_put(v, shardings[k]) if k in shardings else v
                    for k, v in data.items()
                }
        else:
            data = load_sharded(directory, shardings=shardings)
            if not shardings:
                # orbax restores a sharded-persisted checkpoint with
                # its SAVED layout when no target is given; a vetoed
                # (PIO_SERVING_SHARD_FACTORS=0) or degraded resolution
                # means replicated, so gather any sharded table to host
                # and let the constructor re-put it on the default
                # device
                data = {
                    k: np.asarray(v) if _model_shard_ways(v) > 1 else v
                    for k, v in data.items()
                }
        ann_index = None
        if "ann" in meta:
            # the meta names an index: a missing/corrupt ann/ payload is
            # CheckpointCorruptError (load_sharded), surfaced — never a
            # silent fall-back to brute on a torn checkpoint.
            # --model-mmap covers this payload too: flat_vecs is the
            # index's big allocation (a full f32 copy of the item
            # table), and from_arrays keeps the mapping (asarray on a
            # dtype-matching memmap is a view, not a copy), so N pool
            # workers share ONE page-cache copy of the vectors exactly
            # like the factor tables. Passed explicitly — the ann/
            # checkpoint must ride the same knob as the factors even if
            # a caller someday threads a per-call mode through.
            ann_index = ann_ops.AnnIndex.from_arrays(
                load_sharded(os.path.join(directory, _ANN_SUBDIR),
                             mmap_mode=default_mmap_mode()),
                n_items=int(meta["ann"]["n_items"]))
        return ALSModel(
            rank=int(meta["rank"]),
            user_factors=jnp.asarray(data["user"]),
            item_factors=jnp.asarray(data["item"]),
            user_ids=EntityIdIxMap(BiMap({k: int(v) for k, v in meta["user_ids"].items()})),
            item_ids=EntityIdIxMap(BiMap({k: int(v) for k, v in meta["item_ids"].items()})),
            seen_by_user={
                int(k): np.asarray(v, dtype=np.int32)
                for k, v in meta["seen"].items()
            },
            ann_index=ann_index,
        )


def build_allow_vector(
    item_ids,
    *,
    categories=None,
    category_map=None,
    white_list=None,
    black_list=None,
) -> np.ndarray | None:
    """Dense 0/1 eligibility vector from the template business rules
    (shared by recommendation/similarproduct/ecommerce — one place for
    the Option[Set] semantics: None = no restriction; an EMPTY white
    list or category set means nothing is eligible)."""
    n = len(item_ids)
    if categories is None and white_list is None and not black_list:
        return None
    allow = None  # built in one buffer; all-ones only if no positive rule
    if categories is not None:
        wanted = set(categories)
        allow = np.zeros(n, dtype=np.float32)
        # no category map known -> nothing can match the restriction
        for item_id, cats in (category_map or {}).items():
            ix = item_ids.get(item_id)
            if ix is not None and wanted & set(cats):
                allow[ix] = 1.0
    if white_list is not None:
        wl = np.zeros(n, dtype=np.float32)
        for item_id in white_list:
            ix = item_ids.get(item_id)
            if ix is not None:
                wl[ix] = 1.0
        allow = wl if allow is None else allow * wl
    if allow is None:
        allow = np.ones(n, dtype=np.float32)
    for item_id in black_list or ():
        ix = item_ids.get(item_id)
        if ix is not None:
            allow[ix] = 0.0
    return allow


def _serving_k(k: int) -> int:
    """Round k up to the shared serving top-k menu so a new ``num``
    never retraces (SURVEY.md §7 hard-parts: fixed top-k buckets;
    ops/topk.serving_k is the one menu for every serving path)."""
    from predictionio_tpu.ops.topk import serving_k

    return serving_k(k, 1 << 62)   # call sites clamp to the catalog
