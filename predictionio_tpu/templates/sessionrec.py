"""Session-based sequential recommendation engine template.

Next-item prediction over each user's time-ordered event history with a
causal transformer (models/seqrec, SASRec-family) — the neural
counterpart of the reference's MarkovChain transition model
(e2/.../engine/MarkovChain.scala:26-84) and its experimental
complementary-purchase template family (examples/experimental). Query
{"user": ..., "num": N} (or {"items": [recent ids], "num": N}) answers
with the N most likely next items.

Long sessions are first-class: with engine.json mesh axes
{"data": D, "seq": S} the attention runs as ring attention over the
"seq" mesh axis (ops/attention.py), so context length scales across
devices over ICI.

Usage (engine.json):
    {"engineFactory":
       "predictionio_tpu.templates.sessionrec.engine_factory",
     "datasource": {"params": {"app_name": "MyApp",
                               "event_names": ["view", "buy"]}},
     "algorithms": [{"name": "seqrec",
                     "params": {"d_model": 64, "n_layers": 2,
                                "max_len": 64, "epochs": 20}}]}
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np

from predictionio_tpu.controller import (
    DataSource,
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    FirstServing,
    HostModelAlgorithm,
    IdentityPreparator,
    MetricEvaluator,
    OptionAverageMetric,
    Params,
    SanityCheck,
)
from predictionio_tpu.models import seqrec
from predictionio_tpu.obs.trace import span
from predictionio_tpu.ops.topk import serving_k
from predictionio_tpu.serving.dispatch_phases import (
    await_and_fetch,
    start_copies,
)
from predictionio_tpu.utils.bimap import BiMap

_NEG = np.float32(-1e30)


@dataclasses.dataclass(frozen=True)
class Query:
    user: str = ""
    items: tuple = ()        # explicit recent-item history (overrides user)
    num: int = 10
    black_list: tuple = ()


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple = ()


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: tuple = ("view", "buy")
    entity_type: str = "user"
    target_entity_type: str = "item"
    min_sequence_len: int = 2
    eval_k: int = 0


@dataclasses.dataclass
class TrainingData(SanityCheck):
    sequences: dict  # user id -> [item ids, time-ordered]

    def sanity_check(self) -> None:
        assert self.sequences, "no user event sequences found"


class SessionDataSource(DataSource):
    """Reads per-user time-ordered item interaction sequences.

    The event scan mirrors the reference recommendation DataSource
    (tests/pio_tests/engines/recommendation-engine/src/main/scala/
    DataSource.scala:38-105) but keeps event order instead of folding
    to ratings."""

    params_class = DataSourceParams

    def _read(self, ctx) -> TrainingData:
        p = self.params
        events = ctx.event_store().find(
            p.app_name,
            entity_type=p.entity_type,
            event_names=list(p.event_names),
            target_entity_type=p.target_entity_type,
        )
        per_user: dict[str, list] = {}
        for ev in events:
            if not ev.target_entity_id:
                continue
            per_user.setdefault(ev.entity_id, []).append(
                (ev.event_time, ev.target_entity_id)
            )
        sequences = {
            user: [item for _, item in sorted(pairs, key=lambda t: t[0])]
            for user, pairs in per_user.items()
        }
        sequences = {
            u: seq for u, seq in sequences.items()
            if len(seq) >= self.params.min_sequence_len
        }
        return TrainingData(sequences=sequences)

    def read_training(self, ctx) -> TrainingData:
        return self._read(ctx)

    def read_eval(self, ctx):
        """Leave-one-out per fold: hold out each user's final item
        (the standard sequential-recommendation protocol)."""
        p = self.params
        full = self._read(ctx)
        folds = []
        users = sorted(full.sequences)
        k = max(p.eval_k, 1)
        for fold in range(k):
            train_seqs, qa = {}, []
            for i, u in enumerate(users):
                seq = full.sequences[u]
                if i % k == fold and len(seq) > p.min_sequence_len:
                    train_seqs[u] = seq[:-1]
                    qa.append((Query(user=u), seq[-1]))
                else:
                    train_seqs[u] = seq
            folds.append((TrainingData(sequences=train_seqs), {"fold": fold}, qa))
        return folds


@dataclasses.dataclass(frozen=True)
class AlgorithmParams(Params):
    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    max_len: int = 64
    epochs: int = 20
    batch_size: int = 64
    lr: float = 1e-3
    seed: int = 0
    use_mesh: bool = True
    remat: bool = False  # jax.checkpoint each block (long-context memory)
    # mid-training checkpoint/resume (models/seqrec): state written every
    # N epochs to checkpoint_dir; a re-run resumes from the last one
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    # the block the stack is built from (models/seqrec.BLOCKS) and the
    # widths the later kinds read; 0 keeps what the SASRec block derives
    backbone: str = "sasrec"
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    retention_degree: int = 2
    tie_embeddings: bool = True
    # the type serving holds the weights in on the device
    param_dtype: str = "float32"
    # the "deepseek_v2" backbone's own widths (seqrec.MlaMoeWidths;
    # engine.json carries a JSON object with the published keys)
    mla_moe: Any = None
    # the "minicpm_sala" backbone's own widths (seqrec.SalaWidths), the
    # same way
    sala: Any = None

    def __post_init__(self):
        object.__setattr__(self, "mla_moe",
                           seqrec.MlaMoeWidths.of(self.mla_moe))
        object.__setattr__(self, "sala", seqrec.SalaWidths.of(self.sala))

    def seqrec_config(self, vocab: int) -> seqrec.SeqRecConfig:
        import jax.numpy as jnp

        return seqrec.SeqRecConfig(
            vocab=vocab, max_len=self.max_len, d_model=self.d_model,
            n_heads=self.n_heads, n_layers=self.n_layers, remat=self.remat,
            block=self.backbone, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, d_ff=self.d_ff,
            rope_theta=self.rope_theta, rms_eps=self.rms_eps,
            retention_degree=self.retention_degree,
            tie_embeddings=self.tie_embeddings,
            param_dtype=jnp.dtype(self.param_dtype), mla_moe=self.mla_moe,
            sala=self.sala)


@dataclasses.dataclass(frozen=True)
class SeqDispatch:
    """What one ``batch_predict`` reports to ``dispatch_observer``
    (``ServingStats.record_seq_dispatch``): always whole, a count that
    does not apply is 0."""
    programs: int               # device programs launched
    tokens: int                 # events of the histories scored
    padded_tokens: int          # tokens the programs ran over
    split: int                  # 1 if the token budget split the dispatch
    #: of the programs, those whose retention layers ran the fused
    #: state pass (seqrec.fuses_retention)
    fused_retention_programs: int = 0
    #: of the programs, those whose mixers' way in (QK-norm, RoPE, chunk
    #: order) ran the fused kernel (seqrec.fuses_qk_norm)
    fused_qk_norm_programs: int = 0
    #: what a kind's programs counted on the device, by the kind's own
    #: ``BlockKind.tally``, summed over the programs. The routed layers
    #: ("deepseek_v2"): assignments to held experts over all expert
    #: layers, the tokens routed (once, not per layer), and the fullest
    #: (layer, expert)'s assignments
    moe_assignments: int = 0
    moe_tokens: int = 0
    moe_max_expert_load: int = 0
    #: the sparse layers ("minicpm_sala"): rows that selected (position
    #: x sparse layer x key/value head; 0 where the history is at or
    #: under ``dense_len``), the key blocks they kept, and the keys
    #: whose scores stage 2 computed for them (from the visit map the
    #: kernel was given)
    sparse_rows: int = 0
    sparse_blocks_selected: int = 0
    sparse_keys_scored: int = 0


@dataclasses.dataclass
class SeqRecEngineModel:
    params: dict            # stack weights (host numpy pytree)
    cfg: seqrec.SeqRecConfig
    item_index: BiMap       # item id string -> dense index (1-based)
    histories: dict         # user -> int32 array of dense item indices
    # device-resident weight cache, populated on first predict; never
    # serialized (recreated after checkpoint load / reload)
    device_tree: Any = dataclasses.field(default=None, repr=False,
                                         compare=False)
    # called once per batch_predict with one SeqDispatch: the engine
    # server points it at ServingStats.record_seq_dispatch
    dispatch_observer: Any = dataclasses.field(default=None, repr=False,
                                               compare=False)
    # token_budget()'s answer for this model on this device (0: not
    # worked out yet); never serialized
    budget: int = dataclasses.field(default=0, repr=False, compare=False)

    def __getstate__(self):
        state = self.__dict__.copy()
        state["device_tree"] = None
        state["dispatch_observer"] = None
        state["budget"] = 0
        return state

    def set_dispatch_observer(self, observer) -> None:
        self.dispatch_observer = observer


class SeqRecAlgorithm(HostModelAlgorithm):
    """Trains the causal transformer on the mesh; serves jitted top-k."""

    params_class = AlgorithmParams
    query_class = Query

    def train(self, ctx, pd: TrainingData) -> SeqRecEngineModel:
        p = self.params
        items = sorted({i for seq in pd.sequences.values() for i in seq})
        # dense ids start at 1: index 0 is the PAD token
        item_index = BiMap({item: i + 1 for i, item in enumerate(items)})
        dense = {
            u: np.asarray([item_index[i] for i in seq], np.int32)
            for u, seq in pd.sequences.items()
        }
        cfg = p.seqrec_config(vocab=len(items) + 1)
        mesh = ctx.mesh_if_parallel if p.use_mesh else None
        if mesh is not None and "seq" in mesh.shape and \
                p.max_len % int(mesh.shape["seq"]):
            raise ValueError(
                f"max_len {p.max_len} must be a multiple of the seq mesh "
                f"axis size ({int(mesh.shape['seq'])})"
            )
        weights = seqrec.train(
            [seq.tolist() for seq in dense.values()], cfg,
            epochs=p.epochs, batch_size=p.batch_size, lr=p.lr,
            seed=p.seed, mesh=mesh,
            checkpoint_dir=p.checkpoint_dir or None,
            checkpoint_every=p.checkpoint_every,
        )
        import jax

        return SeqRecEngineModel(
            params=jax.tree.map(np.asarray, weights),
            cfg=cfg,
            item_index=item_index,
            histories=dense,
        )

    # -- serving ------------------------------------------------------------

    def _history_for(self, model: SeqRecEngineModel, query: Query):
        """int32 dense item indices, oldest first (empty: nothing known)."""
        if query.items:
            return np.asarray(
                [ix for ix in map(model.item_index.get, query.items)
                 if ix is not None], np.int32)
        return np.asarray(model.histories.get(query.user, ()), np.int32)

    def predict(self, model: SeqRecEngineModel, query: Query) -> PredictedResult:
        # single-query serving is the B=1 case of the batched path —
        # one mask/history implementation keeps the two in lockstep
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(self, model: SeqRecEngineModel, queries):
        """Whatever the batcher (or ``Engine.eval``) hands over, scored
        in device programs of at most ``token_budget`` tokens each:
        power-of-two batch buckets of ``max_len``-long histories through
        one jitted forward (seqrec.predict_topk_batch with per-query
        masks), as few programs as the budget allows.

        Phases are the ambient ``dispatch.*`` spans the recommendation
        template records (no-ops unless the batcher bound its
        per-dispatch trace): prepare (routing, the histories looked
        up), gather (the padded history matrix and the seen masks the
        programs take), then per program enqueue (the launch, and the
        start of its results' copies to the host) -> device_wait ->
        fetch -> results."""
        S = model.cfg.max_len
        with span("dispatch.prepare"):
            out, rows, hist = [], [], []
            for i, q in queries:
                history = self._history_for(model, q)
                if history.size == 0:
                    out.append((i, PredictedResult()))
                    continue
                rows.append((i, q))
                hist.append(history[-S:])
            if not rows:
                return out
            # menu-ized STATIC top-k width (ops/topk.serving_k: client-
            # controlled num must not retrace predict_topk_batch;
            # results trim per query below)
            k = serving_k(max(q.num for _, q in rows), model.cfg.vocab - 1)
            tree = _as_device_tree(model)
            widest = max(1, min(token_budget(model) // S, _MAX_BUCKET))
        with span("dispatch.gather"):
            lengths = np.fromiter(map(len, hist), np.int64, len(hist))
            padded = np.zeros((len(rows), S), np.int32)
            padded[np.arange(S)[None, :] < lengths[:, None]] = \
                np.concatenate(hist)
            # the session's own items and PAD never come back
            # (padded's zeros are PAD itself)
            masks = np.zeros((len(rows), model.cfg.vocab), np.float32)
            np.put_along_axis(masks, padded, _NEG, axis=1)
            masks[:, seqrec.PAD] = _NEG
            for r, (_, q) in enumerate(rows):
                for item in q.black_list:
                    di = model.item_index.get(item)
                    if di is not None:
                        masks[r, di] = _NEG
        inv = model.item_index.inverse
        programs = pos = 0
        # one answer for every program: the rule looks at the widths and
        # at the history length, not at the batch
        fused = seqrec.fuses_retention(model.cfg, S)
        fused_way_in = seqrec.fuses_qk_norm(model.cfg, S)
        tally = seqrec.BLOCKS[model.cfg.block].tally
        counted = collections.Counter()     # SeqDispatch field -> sum
        while pos < len(rows):
            bucket = 1
            while bucket * 2 <= min(len(rows) - pos, widest):
                bucket *= 2
            part = slice(pos, pos + bucket)
            pos += bucket
            programs += 1
            with span("dispatch.enqueue"):
                program = start_copies(seqrec.predict_topk_batch(
                    tree, padded[part], k, model.cfg, masks[part]))
            scores, ids, *arrays = await_and_fetch(program)
            if tally:
                counted.update(tally(model.cfg, bucket * S, *arrays))
            with span("dispatch.results"):
                for (i, q), svals, sids in zip(rows[part], scores, ids):
                    items = []
                    for v, ix in zip(svals[: q.num], sids[: q.num]):
                        if v <= _NEG / 2:
                            continue
                        item = inv.get(int(ix))
                        if item is not None:
                            items.append(ItemScore(item=item, score=float(v)))
                    out.append((i, PredictedResult(item_scores=tuple(items))))
        if model.dispatch_observer is not None:
            model.dispatch_observer(SeqDispatch(
                programs=programs, tokens=int(lengths.sum()),
                padded_tokens=len(rows) * S, split=int(len(rows) > widest),
                fused_retention_programs=programs if fused else 0,
                fused_qk_norm_programs=programs if fused_way_in else 0,
                **counted))
        return out


#: the widest batch bucket, whatever the token budget allows
_MAX_BUCKET = 256
#: what a program's activations may take of the device's memory; the
#: rest is the weights, the logits and masks, and room for the next
#: program's arguments while this one runs
_ACTIVATION_SHARE = 0.25
#: assumed where the backend does not say (the CPU): one v5e chip
_DEFAULT_DEVICE_BYTES = 16e9


def _device_memory_bytes() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return float(stats.get("bytes_limit", _DEFAULT_DEVICE_BYTES))


def token_budget(model: SeqRecEngineModel) -> int:
    """The most tokens (queries x max_len) one device program may hold:
    a power of two, from the stack's widths (seqrec.activation_bytes_
    per_token) and what the device has left beside the weights. At
    least one history always goes. Worked out once per model."""
    if not model.budget:
        import jax

        limit = _device_memory_bytes()
        weights = sum(a.nbytes
                      for a in jax.tree.leaves(_as_device_tree(model)))
        room = min(limit * _ACTIVATION_SHARE, 0.9 * limit - weights)
        tokens = int(max(room, 0)
                     // seqrec.activation_bytes_per_token(model.cfg))
        model.budget = max(1 << max(tokens, 1).bit_length() - 1,
                           model.cfg.max_len)
    return model.budget


def _as_device_tree(model: SeqRecEngineModel):
    """Device-put the weight pytree once per model instance (serving keeps
    models HBM-resident between requests — SURVEY.md §7 stage 7), in
    ``cfg.param_dtype``: a bfloat16 stack is rounded on the host and
    only that copy reaches the device. Cached on the model object
    itself, so a hot-swap (/reload) naturally drops the old device
    weights with the old model."""
    if model.device_tree is None:
        import jax

        dtype = model.cfg.param_dtype
        model.device_tree = jax.tree.map(
            lambda a: jax.device_put(a if a.dtype == dtype
                                     else np.asarray(a).astype(dtype)),
            dict(model.params))
    return model.device_tree


def engine_factory() -> Engine:
    return Engine(
        data_source_class_map=SessionDataSource,
        preparator_class_map=IdentityPreparator,
        algorithm_class_map={"seqrec": SeqRecAlgorithm},
        serving_class_map=FirstServing,
    )


# ---------------------------------------------------------------------------
# Evaluation: HitRate@K over leave-one-out folds (the standard
# sequential-recommendation protocol; read_eval holds out each user's
# final item). Role of the per-template Evaluation.scala in the
# reference template families.
# ---------------------------------------------------------------------------


class HitRateAtK(OptionAverageMetric):
    """1.0 when the held-out next item appears in the top-k, else 0."""

    def __init__(self, k: int = 10):
        self.k = k

    @property
    def header(self) -> str:
        return f"HitRate@{self.k}"

    def calculate_qpa(self, q, p, a) -> float | None:
        # the held-out item always exists, so an empty prediction is a
        # miss (0.0), never a skip — None would inflate the average
        top = [s.item for s in p.item_scores[: self.k]]
        return 1.0 if a in top else 0.0


class SessionRecEvaluation(Evaluation):
    """`pio eval predictionio_tpu.templates.sessionrec.SessionRecEvaluation
    predictionio_tpu.templates.sessionrec.DefaultParamsList`"""

    def __init__(self, k: int = 10, output_path: str | None = "best.json"):
        super().__init__()
        self.engine_evaluator = (
            engine_factory(),
            MetricEvaluator(HitRateAtK(k=k), output_path=output_path),
        )


class DefaultParamsList(EngineParamsGenerator):
    def __init__(self, app_name: str = "SessApp", eval_k: int = 2):
        super().__init__([
            EngineParams.of(
                data_source=DataSourceParams(app_name=app_name, eval_k=eval_k),
                algorithms=[(
                    "seqrec",
                    AlgorithmParams(d_model=d, n_layers=layers, max_len=32,
                                    epochs=15, batch_size=32, lr=3e-3),
                )],
            )
            for d in (32, 64)
            for layers in (1, 2)
        ])

