"""The Engine: binds DASE component classes, runs train and eval pipelines.

Parity: core/src/main/scala/.../controller/Engine.scala:83-833 and
core/.../core/BaseEngine.scala:38-101. An ``Engine`` holds name->class
maps for DataSource/Preparator/Algorithm(s)/Serving; ``train`` runs
read -> sanity -> prepare -> sanity -> per-algorithm train -> sanity
(honoring stop-after-read/prepare, Engine.scala:643-692); ``eval`` trains
per evaluation split and aligns per-query predictions from all algorithms
before serving (Engine.scala:730-833).

The Spark driver/executor split disappears: the pipeline is one process
orchestrating host data prep and jitted mesh computation through the
EngineContext.
"""

from __future__ import annotations

import dataclasses
import logging
import sys
from typing import TYPE_CHECKING, Any, Callable, Generic, Mapping, Sequence

from predictionio_tpu.controller.base import (
    A,
    EI,
    P,
    PD,
    Q,
    TD,
    Algorithm,
    DataSource,
    Doer,
    PersistentModelManifest,
    Preparator,
    SanityCheck,
    Serving,
)
from predictionio_tpu.controller.params import EngineParams, params_from_json

if TYPE_CHECKING:
    from predictionio_tpu.workflow.context import EngineContext

logger = logging.getLogger(__name__)


class StopAfterReadInterruption(Exception):
    """Parity: WorkflowUtils.StopAfterReadInterruption (WorkflowUtils.scala:390)."""


class StopAfterPrepareInterruption(Exception):
    """Parity: StopAfterPrepareInterruption (WorkflowUtils.scala:392)."""


def _sanity_check(obj: Any, name: str, enabled: bool) -> None:
    """Parity: Engine.scala:653-664 — run sanityCheck() on data classes
    that opt in."""
    if enabled and isinstance(obj, SanityCheck):
        logger.info("%s: running sanity check", name)
        obj.sanity_check()


def _wait_for_device(model: Any) -> None:
    """Block until the device arrays a trained model holds are computed.
    JAX dispatch returns before the device finishes, so without this the
    ``train`` stage would time the enqueue and bill the device's work to
    whichever later stage first reads the arrays (``persist``). Framework
    models are dataclasses — pytree leaves to JAX — so their fields are
    unwrapped one level. A process that never imported jax has nothing
    to wait for."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    if dataclasses.is_dataclass(model) and not isinstance(model, type):
        model = [getattr(model, f.name) for f in dataclasses.fields(model)]
    jax.block_until_ready(model)


@dataclasses.dataclass
class TrainResult:
    """Models plus what the workflow should persist for each."""

    models: list[Any]
    persisted: list[Any]  # per algo: model | PersistentModelManifest | None


class Engine(Generic[TD, EI, PD, Q, P, A]):
    """Parity: Engine (Engine.scala:83-151). Component maps are
    name -> class; EngineParams name selects the class per slot."""

    def __init__(
        self,
        data_source_class_map: Mapping[str, type] | type,
        preparator_class_map: Mapping[str, type] | type,
        algorithm_class_map: Mapping[str, type] | type,
        serving_class_map: Mapping[str, type] | type,
    ):
        self.data_source_class_map = self._as_map(data_source_class_map)
        self.preparator_class_map = self._as_map(preparator_class_map)
        self.algorithm_class_map = self._as_map(algorithm_class_map)
        self.serving_class_map = self._as_map(serving_class_map)

    @staticmethod
    def _as_map(m: Mapping[str, type] | type) -> dict[str, type]:
        """Single-class sugar: Engine(MyDS, MyPrep, MyAlgo, MyServing)
        (Engine.scala:120-151 single-class constructors)."""
        if isinstance(m, Mapping):
            return dict(m)
        return {"": m}

    # -- component instantiation -------------------------------------------
    def _component(self, class_map: Mapping[str, type], slot: str, name_params: tuple[str, Any]):
        name, params = name_params
        if name not in class_map:
            raise ValueError(
                f"{slot} has no component named {name!r} "
                f"(available: {sorted(class_map)})"
            )
        return Doer.create(class_map[name], params)

    def make_components(self, engine_params: EngineParams) -> tuple[
        DataSource, Preparator, list[Algorithm], Serving
    ]:
        data_source = self._component(
            self.data_source_class_map, "datasource", engine_params.data_source_params
        )
        preparator = self._component(
            self.preparator_class_map, "preparator", engine_params.preparator_params
        )
        algo_list = list(engine_params.algorithm_params_list) or [("", None)]
        algorithms = [
            self._component(self.algorithm_class_map, "algorithms", ap)
            for ap in algo_list
        ]
        serving = self._component(
            self.serving_class_map, "serving", engine_params.serving_params
        )
        return data_source, preparator, algorithms, serving

    # -- training pipeline (object Engine.train, Engine.scala:625-728) ------
    def train(
        self,
        ctx: "EngineContext",
        engine_params: EngineParams,
        algorithms: Sequence[Any] | None = None,
    ) -> TrainResult:
        """``algorithms`` lets deploy-time retrain train the SAME
        instances that will serve (see prepare_deploy) — train hooks
        stash serve-time state on the instance just like load_model
        hooks do."""
        # per-DASE-stage spans (obs/trace.py): when the driver bound an
        # ambient trace (workflow/train.run_train always does), read /
        # prepare / train land as spans and `pio train` prints the
        # stage breakdown; with no trace active, span() is a shared
        # no-op — direct Engine.train callers pay one contextvar read
        from predictionio_tpu.obs.trace import span

        params = ctx.workflow_params
        data_source, preparator, made_algorithms, _ = \
            self.make_components(engine_params)
        if algorithms is None:
            algorithms = made_algorithms

        with span("read"):
            td = data_source.read_training(ctx)
        _sanity_check(td, "training data", not params.skip_sanity_check)
        if params.stop_after_read:
            raise StopAfterReadInterruption("stopping after read per workflow params")

        with span("prepare"):
            pd = preparator.prepare(ctx, td)
        _sanity_check(pd, "prepared data", not params.skip_sanity_check)
        if params.stop_after_prepare:
            raise StopAfterPrepareInterruption("stopping after prepare per workflow params")

        models: list[Any] = []
        for i, algo in enumerate(algorithms):
            logger.info("training algorithm %d: %s", i, type(algo).__name__)
            with span("train"):
                model = algo.train(ctx, pd)
                _sanity_check(model, f"model[{i}]",
                              not params.skip_sanity_check)
                if hasattr(algo, "gather_model"):
                    model = algo.gather_model(ctx, model)
                _wait_for_device(model)
            models.append(model)

        # checkpoints are written here (the model-store row follows in
        # run_train, under the same stage name): at catalog scale this
        # is minutes of the run, and belongs to "persist", not to no stage
        with span("persist"):
            persisted = [
                algo.make_persistent_model(
                    ctx.with_workflow_params(algorithm_slot=i), model)
                if params.save_model else None
                for i, (algo, model) in enumerate(zip(algorithms, models))
            ]
        return TrainResult(models=models, persisted=persisted)

    # -- deploy-time model restoration (Engine.prepareDeploy, :199-257) -----
    def prepare_deploy(
        self,
        ctx: "EngineContext",
        engine_params: EngineParams,
        persisted: Sequence[Any],
        algorithms: Sequence[Any] | None = None,
    ) -> list[Any]:
        """Restore deployable models. ``algorithms`` MUST be the same
        instances that will later serve the models when an algorithm
        keeps deploy-time state — ``load_model`` hooks commonly stash
        the context for serve-time live reads (e.g. the ecommerce
        template's unavailableItems/weight constraints), and loading on
        one instance while serving with another silently drops that
        state (caught by the round-3 CLI end-to-end drive)."""
        if algorithms is None:
            _, _, algorithms, _ = self.make_components(engine_params)
        models: list[Any] = []
        retrain_needed = any(p is None for p in persisted)
        retrained: TrainResult | None = None
        if retrain_needed:
            # "Unit model -> retrain on deploy" (Engine.scala:211-229).
            # save_model=False: deploy-time retrain must not redo (or
            # overwrite) persistence work.
            logger.info("some models were not persisted; retraining for deploy")
            # retrain on the SERVING instances, not throwaway ones —
            # train hooks stash serve-time state exactly like
            # load_model hooks (same bug class as the docstring above)
            retrained = self.train(
                ctx.with_workflow_params(save_model=False), engine_params,
                algorithms=algorithms,
            )
        for i, (algo, blob) in enumerate(zip(algorithms, persisted)):
            if blob is None:
                models.append(retrained.models[i])
            elif isinstance(blob, PersistentModelManifest):
                # custom-persistence reload (Engine.scala:242-251)
                models.append(algo.load_model(ctx, blob))
            else:
                models.append(blob)
        return models

    # -- evaluation pipeline (object Engine.eval, Engine.scala:730-833) -----
    def eval(
        self,
        ctx: "EngineContext",
        engine_params: EngineParams,
    ) -> list[tuple[EI, list[tuple[Q, P, A]]]]:
        data_source, preparator, algorithms, serving = self.make_components(engine_params)
        eval_splits = data_source.read_eval(ctx)
        results: list[tuple[EI, list[tuple[Q, P, A]]]] = []
        for fold, (td, ei, qa_pairs) in enumerate(eval_splits):
            logger.info("evaluating fold %d (%d queries)", fold, len(qa_pairs))
            _sanity_check(td, f"fold[{fold}] training data",
                          not ctx.workflow_params.skip_sanity_check)
            pd = preparator.prepare(ctx, td)
            models = [algo.train(ctx, pd) for algo in algorithms]

            supplemented = [
                (i, serving.supplement(q)) for i, (q, _) in enumerate(qa_pairs)
            ]
            # per-algo batch predict, aligned by dense query index — the
            # union+groupByKey of Engine.scala:783-799 becomes list indexing
            per_algo: list[dict[int, P]] = []
            for algo, model in zip(algorithms, models):
                preds = dict(algo.batch_predict(model, supplemented))
                per_algo.append(preds)
            fold_results: list[tuple[Q, P, A]] = []
            for i, (q, a) in enumerate(qa_pairs):
                predictions = [preds[i] for preds in per_algo if i in preds]
                served = serving.serve(q, predictions)
                fold_results.append((q, served, a))
            results.append((ei, fold_results))
        return results

    def batch_eval(
        self,
        ctx: "EngineContext",
        engine_params_list: Sequence[EngineParams],
    ) -> list[tuple[EngineParams, list[tuple[EI, list[tuple[Q, P, A]]]]]]:
        """Parity: BaseEngine.batchEval default (BaseEngine.scala:82-94)."""
        return [(ep, self.eval(ctx, ep)) for ep in engine_params_list]

    # -- engine.json binding (Engine.jValueToEngineParams, :357-420) --------
    def params_from_variant_json(self, variant: Mapping[str, Any]) -> EngineParams:
        def slot(key: str, class_map: Mapping[str, type]) -> tuple[str, Any]:
            spec = variant.get(key)
            if spec is None:
                # omitted slot: unambiguous only for single-component maps
                if "" in class_map:
                    name = ""
                elif len(class_map) == 1:
                    name = next(iter(class_map))
                else:
                    raise ValueError(
                        f"engine.json omits {key!r} but the engine has multiple "
                        f"{key} components {sorted(class_map)}; specify one by name"
                    )
                cls = class_map.get(name)
                default = params_from_json(cls.params_class, None) if cls else None
                return (name, default)
            name = spec.get("name", "")
            if name not in class_map:
                raise ValueError(
                    f"engine.json {key} names unknown component {name!r} "
                    f"(available: {sorted(class_map)})"
                )
            cls = class_map[name]
            return (name, params_from_json(cls.params_class, spec.get("params")))

        algorithms = []
        for spec in variant.get("algorithms", []):
            name = spec.get("name", "")
            if name not in self.algorithm_class_map:
                raise ValueError(
                    f"engine.json algorithms names unknown component {name!r} "
                    f"(available: {sorted(self.algorithm_class_map)})"
                )
            cls = self.algorithm_class_map[name]
            algorithms.append((name, params_from_json(cls.params_class, spec.get("params"))))
        if not algorithms:
            if "" in self.algorithm_class_map:
                name = ""
            elif len(self.algorithm_class_map) == 1:
                name = next(iter(self.algorithm_class_map))
            else:
                raise ValueError(
                    "engine.json omits 'algorithms' but the engine has multiple "
                    f"algorithm components {sorted(self.algorithm_class_map)}; "
                    "specify at least one by name"
                )
            cls = self.algorithm_class_map[name]
            algorithms = [(name, params_from_json(cls.params_class, None))]

        return EngineParams(
            data_source_params=slot("datasource", self.data_source_class_map),
            preparator_params=slot("preparator", self.preparator_class_map),
            algorithm_params_list=tuple(algorithms),
            serving_params=slot("serving", self.serving_class_map),
        )


    def params_from_instance_json(
        self,
        data_source_params: str,
        preparator_params: str,
        algorithms_params: str,
        serving_params: str,
    ) -> EngineParams:
        """Rebuild typed EngineParams from the JSON blobs stored on an
        EngineInstance row. Parity: Engine.engineInstanceToEngineParams
        (Engine.scala:422-514)."""
        import json

        def slot(raw: str, class_map: Mapping[str, type]) -> tuple[str, Any]:
            spec = json.loads(raw) if raw else {"name": "", "params": {}}
            name = spec.get("name", "")
            cls = class_map.get(name)
            if cls is None:
                raise ValueError(f"stored params name {name!r} not in {sorted(class_map)}")
            return (name, params_from_json(cls.params_class, spec.get("params")))

        algo_specs = json.loads(algorithms_params) if algorithms_params else []
        algorithms = []
        for spec in algo_specs:
            name = spec.get("name", "")
            cls = self.algorithm_class_map.get(name)
            if cls is None:
                raise ValueError(
                    f"stored algorithm name {name!r} not in {sorted(self.algorithm_class_map)}"
                )
            algorithms.append((name, params_from_json(cls.params_class, spec.get("params"))))
        return EngineParams(
            data_source_params=slot(data_source_params, self.data_source_class_map),
            preparator_params=slot(preparator_params, self.preparator_class_map),
            algorithm_params_list=tuple(algorithms),
            serving_params=slot(serving_params, self.serving_class_map),
        )


class EngineFactory:
    """Parity: EngineFactory (controller/EngineFactory.scala:31-40).
    Subclass and implement ``apply``; or pass any zero-arg callable
    returning an Engine."""

    def apply(self) -> Engine:
        raise NotImplementedError


def resolve_engine_factory(spec: str) -> Callable[[], Engine]:
    """Resolve an engineFactory string "pkg.module.obj" / "pkg.module:obj"
    to a zero-arg callable returning an Engine.

    Parity: WorkflowUtils.getEngine (WorkflowUtils.scala:53-90), which
    tried object-then-class reflection; here importlib + attribute lookup.
    """
    from predictionio_tpu.utils.reflection import resolve_attr

    obj = resolve_attr(spec)
    if isinstance(obj, Engine):
        return lambda: obj
    if isinstance(obj, type) and issubclass(obj, EngineFactory):
        return lambda: obj().apply()
    if callable(obj):
        return obj
    raise TypeError(f"engineFactory {spec!r} is not callable or an Engine")
