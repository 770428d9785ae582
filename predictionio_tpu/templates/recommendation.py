"""Recommendation engine template: ALS over rate/buy events.

Parity: examples/scala-parallel-recommendation/ and the canonical copy at
tests/pio_tests/engines/recommendation-engine/ — DataSource reads "rate"
and "buy" events (DataSource.scala:38-105; buy counts as rating 4.0),
ALSAlgorithm trains MLlib ALS over BiMap-indexed ratings
(ALSAlgorithm.scala:40-120), queries are {user, num} answered with
ranked item scores, and readEval provides k-fold splits for Precision@K
evaluation (Evaluation.scala).

TPU design: the Preparator is the ragged→static boundary (builds dense
indices + padded rating buckets); the algorithm is a ShardedAlgorithm
whose factor tables are computed by ops/als on the mesh and stay
device-resident for serving; top-k ranking is one jitted matmul+top_k
(ops/topk) instead of per-user RDD sorts.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from predictionio_tpu.controller import (
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    MetricEvaluator,
    OptionAverageMetric,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
    SanityCheck,
    ShardedAlgorithm,
)
from predictionio_tpu.controller.base import PersistentModelManifest
from predictionio_tpu.models.als import ALSModel, build_allow_vector
from predictionio_tpu.obs.trace import span
from predictionio_tpu.ops import topk as topk_ops
from predictionio_tpu.ops.als import (
    RatingsCOO,
    als_train,
    resolve_shard_factors,
)
from predictionio_tpu.serving.dispatch_phases import await_and_fetch
from predictionio_tpu.utils.bimap import EntityIdIxMap


# ---------------------------------------------------------------------------
# Data types (Query/PredictedResult parity with the reference template JSON)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Query:
    """{user, num} plus the custom-query variant's optional id filters
    (reference: examples/scala-parallel-recommendation/custom-query —
    whiteList/blackList narrowing; category-based filtering is the
    ecommerce template's role)."""

    user: str
    num: int = 10
    white_list: tuple | None = None  # None = no restriction; [] = none eligible
    black_list: tuple | None = None  # always excluded


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...] = ()


@dataclasses.dataclass(frozen=True)
class TrainingData(SanityCheck):
    """Raw (user, item, rating) triples as host object arrays."""

    users: np.ndarray
    items: np.ndarray
    ratings: np.ndarray

    def sanity_check(self) -> None:
        if len(self.users) == 0:
            raise ValueError(
                "ratings are empty; ingest rate/buy events first "
                "(reference DataSource.scala sanity: train with events)"
            )


@dataclasses.dataclass(frozen=True)
class PreparedData:
    """Dense-index ratings + id maps + per-user seen items: everything the
    mesh kernels need, all static-shaped."""

    coo: RatingsCOO
    user_ids: EntityIdIxMap
    item_ids: EntityIdIxMap
    seen_by_user: dict[int, np.ndarray]


# ---------------------------------------------------------------------------
# DataSource
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: tuple = ("rate", "buy")
    buy_rating: float = 4.0  # reference: buy event treated as rating 4
    entity_type: str = "user"
    target_entity_type: str = "item"
    eval_k: int = 0
    eval_query_num: int = 10
    seed: int = 3


def ratings_from_columns(cols, buy_rating: float):
    """One EventColumns batch -> (users, items, ratings) arrays, or
    None when nothing survives. The columnar rating rule, vectorized:
    rows need a target entity (code compare against the batch's None
    code), ``rate`` events take their properties' ``rating`` (rows
    whose rating is missing/malformed are dropped — the row-path rule),
    everything else is an implicit signal worth ``buy_rating``."""
    n = len(cols)
    if n == 0:
        return None
    none_code = cols.target_entity_id.code_of(None)
    keep = np.ones(n, dtype=bool)
    if none_code is not None:
        keep &= cols.target_entity_id.codes != none_code
    ratings = np.full(n, buy_rating, dtype=np.float32)
    rate_code = cols.event.code_of("rate")
    if rate_code is not None:
        for i in np.nonzero(keep & (cols.event.codes == rate_code))[0]:
            try:
                ratings[i] = float(cols.properties_raw(int(i)).get("rating"))
            except (KeyError, TypeError, ValueError):
                keep[i] = False
    idx = np.nonzero(keep)[0]
    if len(idx) == 0:
        return None
    return (cols.entity_id.decode()[idx],
            cols.target_entity_id.decode()[idx],
            ratings[idx])


class RecommendationDataSource(DataSource):
    """Reads rate/buy events into rating triples.

    Parity: recommendation-engine DataSource.scala:38-105 (getRatings:
    rate -> rating value, buy -> fixed 4.0; latest event wins per pair is
    NOT applied — the reference keeps all, MLlib averages duplicates;
    here duplicates are kept and the ALS solve sees each occurrence).
    """

    params_class = DataSourceParams

    def _ratings(self, ctx) -> TrainingData:
        """Columnar train read: EventStore.scan hands struct-of-arrays
        batches (core/columns.py), and per batch the entity/target
        columns land in the output arrays by vectorized code selection
        — no per-event Python loop over Event objects. The only row
        work left is the properties parse for ``rate`` events (the
        rating value lives in the lazy JSON column), touched solely for
        the rows that survive the mask."""
        p = self.params
        user_parts: list[np.ndarray] = []
        item_parts: list[np.ndarray] = []
        rating_parts: list[np.ndarray] = []
        for cols in ctx.event_store().scan(
            p.app_name,
            entity_type=p.entity_type,
            event_names=list(p.event_names),
            target_entity_type=p.target_entity_type,
        ):
            part = ratings_from_columns(cols, p.buy_rating)
            if part is None:
                continue
            user_parts.append(part[0])
            item_parts.append(part[1])
            rating_parts.append(part[2])
        if not user_parts:
            empty = np.asarray([], dtype=object)
            return TrainingData(
                users=empty, items=empty.copy(),
                ratings=np.asarray([], dtype=np.float32))
        return TrainingData(
            users=np.concatenate(user_parts),
            items=np.concatenate(item_parts),
            ratings=np.concatenate(rating_parts),
        )

    def read_training(self, ctx) -> TrainingData:
        return self._ratings(ctx)

    def read_eval(self, ctx):
        """k-fold split of ratings; per-fold queries are the test-fold
        users, actuals their test-fold items. Parity: DataSource.readEval
        (DataSource.scala:82-105, zipWithUniqueId % kFold)."""
        p = self.params
        full = self._ratings(ctx)
        n = len(full.users)
        rng = np.random.default_rng(p.seed)
        fold_of = rng.integers(0, p.eval_k, size=n)
        folds = []
        for k in range(p.eval_k):
            test = fold_of == k
            td = TrainingData(
                users=full.users[~test],
                items=full.items[~test],
                ratings=full.ratings[~test],
            )
            by_user: dict[str, list[str]] = {}
            for u, i in zip(full.users[test], full.items[test]):
                by_user.setdefault(u, []).append(i)
            qa = [
                (Query(user=u, num=p.eval_query_num), tuple(items))
                for u, items in sorted(by_user.items())
            ]
            folds.append((td, {"fold": k}, qa))
        return folds


# ---------------------------------------------------------------------------
# Preparator
# ---------------------------------------------------------------------------


class ALSPreparator(Preparator):
    """String ids -> dense indices + COO ratings (the BiMap step the
    reference did inside ALSAlgorithm.train, ALSAlgorithm.scala:46-63,
    moved to the Preparator where the ragged→static conversion belongs)."""

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        user_ids = EntityIdIxMap.from_ids(td.users)
        item_ids = EntityIdIxMap.from_ids(td.items)
        rows = user_ids.to_index(td.users)
        cols = item_ids.to_index(td.items)
        seen: dict[int, set[int]] = {}
        for r, c in zip(rows, cols):
            seen.setdefault(int(r), set()).add(int(c))
        return PreparedData(
            coo=RatingsCOO(
                rows=rows,
                cols=cols,
                vals=np.asarray(td.ratings, dtype=np.float32),
                num_rows=len(user_ids),
                num_cols=len(item_ids),
            ),
            user_ids=user_ids,
            item_ids=item_ids,
            seen_by_user={
                u: np.asarray(sorted(s), dtype=np.int32) for u, s in seen.items()
            },
        )


# ---------------------------------------------------------------------------
# Algorithm
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    """Parity: ALSAlgorithmParams (ALSAlgorithm.scala:30-38): rank,
    numIterations, lambda, seed."""

    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    seed: int = 3
    implicit_prefs: bool = False
    alpha: float = 1.0
    use_mesh: bool = True
    exclude_seen: bool = True
    #: row-shard the factor tables over the mesh's "model" axis (DP×MP
    #: tensor parallelism, engine.json "shardFactors";
    #: env PIO_TRAIN_SHARD_FACTORS=1/0 overrides fleet-wide) — for catalogs
    #: whose tables exceed one device's HBM; see docs/parallelism.md
    shard_factors: bool = False


class ALSAlgorithm(ShardedAlgorithm):
    """ALS matrix factorization on the device mesh.

    Parity: ALSAlgorithm (ALSAlgorithm.scala:40-120) — MLlib `ALS.train`
    becomes ops/als.als_train; `model.recommendProducts` becomes the
    jitted masked top-k.
    """

    params_class = ALSAlgorithmParams
    query_class = Query

    def train(self, ctx, pd: PreparedData) -> ALSModel:
        p = self.params
        mesh = ctx.mesh_if_parallel if p.use_mesh else None
        factors = als_train(
            pd.coo,
            rank=p.rank,
            iterations=p.num_iterations,
            lam=p.lambda_,
            implicit=p.implicit_prefs,
            alpha=p.alpha,
            seed=p.seed,
            mesh=mesh,
            shard_factors=resolve_shard_factors(p.shard_factors),
        )
        return ALSModel(
            rank=p.rank,
            user_factors=factors.user,
            item_factors=factors.item,
            user_ids=pd.user_ids,
            item_ids=pd.item_ids,
            seen_by_user=pd.seen_by_user,
        )

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        recs = model.recommend(
            query.user, query.num,
            allow=build_allow_vector(model.item_ids,
                                     white_list=query.white_list,
                                     black_list=query.black_list),
            exclude_seen=self.params.exclude_seen,
        )
        return PredictedResult(
            item_scores=tuple(ItemScore(item=i, score=s) for i, s in recs)
        )

    def batch_predict(self, model: ALSModel, queries):
        """All queries scored in one matmul + top_k — the RDD-join
        analogue (ALSAlgorithm batchPredict path). Queries carrying
        white/black-list filters need a per-query eligibility vector, so
        they take the single-query path; the unfiltered rest batch.

        Each phase is an ambient ``dispatch.*`` span (obs/trace.span: a
        no-op unless the batcher bound its per-dispatch trace), recorded
        here and in ``ALSModel.batch_topk`` where the work happens:
        prepare → gather → enqueue (⊃ copy_start: the results' copies
        to the host start behind the launch) → device_wait → fetch →
        results."""
        if not queries:
            return []
        with span("dispatch.prepare"):
            single, unknown, known = self._route(model, queries)
            batch = self._pad_batch(model, known) if known else None
        out = [(qi, self.predict(model, q)) for qi, q in single]
        out += [(qi, PredictedResult()) for qi in unknown]
        if batch is None:
            return out
        uixs, cols, mask, k = batch
        # the model dispatches by its configured retrieval: brute picks
        # flat vs chunked-scan (ops/topk), ann probes the IVF index and
        # exact-rescores the shortlist (ops/ann); seen arrays stay
        # NumPy so the brute dispatcher's host-side _trim_seen can
        # right-size them
        vals, idxs = model.batch_topk(uixs, cols, mask, None, k)
        vals, idxs = await_and_fetch((vals, idxs))
        with span("dispatch.results"):
            inv = model.item_ids.inverse
            for j, (qi, _, num) in enumerate(known):
                scores = []
                for v, i in zip(vals[j][:num], idxs[j][:num]):
                    if not np.isfinite(v):
                        break
                    scores.append(ItemScore(item=inv[int(i)], score=float(v)))
                out.append((qi, PredictedResult(item_scores=tuple(scores))))
        return out

    @staticmethod
    def _route(model: ALSModel, queries):
        """(single-path ``(qi, query)``, unknown-user ``qi``, known
        ``(qi, user index, num)``) — which queries the batched kernel
        can score."""
        single, unknown, known = [], [], []
        for qi, q in queries:
            # per-query eligibility vectors AND online-overlay users
            # (folded vector / cold-start items — the batched kernel
            # scores only the base tables; models/als.needs_online_path)
            if (q.white_list is not None or bool(q.black_list)
                    or model.needs_online_path(q.user)):
                single.append((qi, q))
            elif q.user in model.user_ids:
                known.append((qi, model.user_ids[q.user], q.num))
            else:
                unknown.append(qi)
        return single, unknown, known

    def _pad_batch(self, model: ALSModel, known):
        """(uixs, seen cols, seen mask, k) for ``batch_topk``, every
        axis padded to its compile-shape menu."""
        uixs = np.asarray([u for _, u, _ in known], dtype=np.int32)
        max_num = max(n for _, _, n in known)
        # right-size the seen arrays to the smallest menu width covering
        # the real counts (smaller uploads, bounded compile-shape menu);
        # a batch whose heaviest user exceeds the menu gets the next
        # power of two instead — exclude_seen is a correctness contract,
        # so the seen list must NEVER silently truncate (a >512-item
        # history would otherwise re-recommend already-seen items)
        pad = topk_ops._SEEN_WIDTHS[0]
        if self.params.exclude_seen:
            widest = max(
                (len(model.seen_by_user.get(int(u), ())) for _, u, _ in known),
                default=0,
            )
            for cap in topk_ops._SEEN_WIDTHS:
                pad = cap
                if widest <= cap:
                    break
            while pad < widest:
                pad *= 2
        B = len(known)
        # pad the BATCH dimension to the shared power-of-two menu
        # (ops/topk.BATCH_WIDTHS): every distinct B is a fresh jit
        # signature and a fresh compile — the serving micro-batcher
        # produces arbitrary batch sizes, so without this a
        # varying-concurrency workload keeps compiling instead of
        # dispatching (padding rows repeat row 0 and are sliced off
        # the result). Eval-scale batches
        # pass through unpadded (serving_batch docstring). The
        # recompile sentinel (obs/compile.py) watches this contract in
        # production: a post-warmup width that misses the compiled
        # menu counts on pio_serving_recompile_total with a WARN, and
        # tests/test_compile_obs.py pins on-menu == zero /
        # off-menu == one through this exact path
        padB = topk_ops.serving_batch(B)
        if padB != B:
            uixs = np.concatenate(
                [uixs, np.full(padB - B, uixs[0], dtype=np.int32)])
        cols = np.zeros((padB, pad), dtype=np.int32)
        mask = np.zeros((padB, pad), dtype=np.float32)
        if self.params.exclude_seen:
            for j, (_, u, _) in enumerate(known):
                s = model.seen_by_user.get(int(u), np.empty(0, dtype=np.int32))[:pad]
                cols[j, : len(s)] = s
                mask[j, : len(s)] = 1.0
        n_items = model.item_factors.shape[0]
        # menu-ized STATIC top_k width (ops/topk.serving_k: client-
        # controlled num must not retrace; results trim per query below)
        k = topk_ops.serving_k(min(max_num, n_items), n_items)
        return uixs, cols, mask, k

    # -- persistence: orbax-style directory checkpoint + manifest ----------
    def make_persistent_model(self, ctx, model: ALSModel):
        """Unlike the reference's PAlgorithm (forced retrain-on-deploy for
        RDD models, PAlgorithm.scala:89-101), sharded factors persist via
        a directory checkpoint + manifest (SURVEY.md §7 hard-parts)."""
        from predictionio_tpu.controller.persistent_model import checkpoint_location

        location = checkpoint_location(ctx, "als")
        model.save(location)
        return PersistentModelManifest(
            class_name=f"{type(self).__module__}.{type(self).__name__}",
            location=location,
        )

    def load_model(self, ctx, manifest: PersistentModelManifest) -> ALSModel:
        return ALSModel.load(manifest.location)


def engine_factory() -> Engine:
    return Engine(
        data_source_class_map=RecommendationDataSource,
        preparator_class_map=ALSPreparator,
        algorithm_class_map={"als": ALSAlgorithm, "": ALSAlgorithm},
        serving_class_map=FirstServing,
    )


# ---------------------------------------------------------------------------
# Evaluation: Precision@K + params grid (reference: the recommendation
# template's Evaluation.scala — PrecisionAtK OptionAverageMetric and the
# rank x numIterations EngineParamsList; tests/pio_tests/engines/
# recommendation-engine/src/main/scala/Evaluation.scala)
# ---------------------------------------------------------------------------


class PrecisionAtK(OptionAverageMetric):
    """Fraction of the top-k recommendations that are in the user's
    held-out item set (read_eval's answer is the tuple of test-fold
    items). Returns None (excluded from the average) for users with no
    held-out items — the reference's OptionAverageMetric contract."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"Precision@{self.k}"

    def calculate_qpa(self, q, p, a) -> float | None:
        relevant = set(a)
        if not relevant:
            return None
        top = [s.item for s in p.item_scores[: self.k]]
        if not top:
            return 0.0
        hits = sum(1 for item in top if item in relevant)
        # reference parity: tpCount / min(k, |relevant|) (Evaluation.scala)
        return hits / min(self.k, len(relevant))


class MAPAtK(OptionAverageMetric):
    """Mean Average Precision at k — the BASELINE.md north-star quality
    gate ("matching MAP@10"). Average of precision@i over the ranks i of
    relevant items inside the top-k, divided by min(k, |relevant|);
    None (skip) for users with no held-out items."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"MAP@{self.k}"

    def calculate_qpa(self, q, p, a) -> float | None:
        relevant = set(a)
        if not relevant:
            return None
        top = [s.item for s in p.item_scores[: self.k]]
        hits, precision_sum = 0, 0.0
        for rank, item in enumerate(top, start=1):
            if item in relevant:
                hits += 1
                precision_sum += hits / rank
        return precision_sum / min(self.k, len(relevant))


class RecommendationEvaluation(Evaluation):
    """`pio eval predictionio_tpu.templates.recommendation.RecommendationEvaluation
    predictionio_tpu.templates.recommendation.DefaultParamsList`"""

    def __init__(self, k: int = 10, output_path: str | None = "best.json"):
        super().__init__()
        self.engine_evaluator = (
            engine_factory(),
            MetricEvaluator(PrecisionAtK(k=k),
                            other_metrics=[MAPAtK(k=k)],
                            output_path=output_path),
        )


class DefaultParamsList(EngineParamsGenerator):
    """rank x iterations grid like the reference's EngineParamsList."""

    def __init__(self, app_name: str = "RecApp", eval_k: int = 2):
        super().__init__([
            EngineParams.of(
                data_source=DataSourceParams(app_name=app_name, eval_k=eval_k),
                algorithms=[(
                    "als",
                    ALSAlgorithmParams(rank=rank, num_iterations=it,
                                       lambda_=0.05, seed=3),
                )],
            )
            for rank in (8, 16)
            for it in (5, 10)
        ])
