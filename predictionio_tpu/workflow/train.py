"""The training workflow driver.

Parity: core/src/main/scala/.../workflow/{CreateWorkflow.scala:136-277,
CoreWorkflow.scala:39-101}: resolve the engine factory, bind engine.json
variant params, record an INIT EngineInstance, run the train pipeline,
persist models, mark COMPLETED (or leave non-COMPLETED on failure —
SURVEY.md §5 failure-detection note).

No spark-submit process boundary exists: training runs in-process on the
JAX mesh. The CLI still offers subprocess isolation (`pio train` spawns a
worker when --isolated) without changing this driver.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import traceback
from datetime import datetime, timezone
from typing import Any, Mapping

from predictionio_tpu.controller.engine import (
    Engine,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    resolve_engine_factory,
)
from predictionio_tpu.controller.params import EngineParams, params_to_json
from predictionio_tpu.obs.trace import Trace, span, use_trace
from predictionio_tpu.storage.base import EngineInstance
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.workflow.context import EngineContext, WorkflowParams
from predictionio_tpu.workflow.persistence import save_models

logger = logging.getLogger(__name__)


def format_stage_times(stage_seconds: Mapping[str, float]) -> str:
    """One-line stage breakdown for logs and the `pio train` output,
    e.g. ``read 0.52s | prepare 0.11s | train 8.43s | persist 0.04s``."""
    return " | ".join(f"{name} {secs:.2f}s"
                      for name, secs in stage_seconds.items())


def _now() -> datetime:
    return datetime.now(timezone.utc)


def _params_json(name_params: tuple[str, Any]) -> str:
    name, params = name_params
    return json.dumps({"name": name, "params": params_to_json(params)})


def _algo_params_json(algorithm_params_list) -> str:
    return json.dumps(
        [{"name": n, "params": params_to_json(p)} for n, p in algorithm_params_list]
    )


@dataclasses.dataclass
class TrainOutcome:
    instance_id: str
    status: str
    models: list[Any]
    #: per-DASE-stage walltimes (read/prepare/train/persist seconds),
    #: collected by the training trace (docs/observability.md)
    stage_seconds: dict[str, float] = dataclasses.field(default_factory=dict)
    #: the TRAIN_REPORT document when the run was profiled
    #: (``pio train --profile``; obs/device.TrainProfiler) — per-stage
    #: wall/compile/execute split, MFU, HBM peaks, recompile table
    report: dict[str, Any] | None = None


def run_train(
    engine: Engine | None = None,
    engine_factory: str = "",
    variant: Mapping[str, Any] | None = None,
    engine_params: EngineParams | None = None,
    workflow_params: WorkflowParams = WorkflowParams(),
    storage: Storage | None = None,
    ctx: EngineContext | None = None,
    profiler: Any | None = None,
) -> TrainOutcome:
    """Train one engine variant and persist the results.

    Either pass a constructed ``engine`` (tests, programmatic use) or an
    ``engine_factory`` spec string (CLI path). ``variant`` is the parsed
    engine.json; ``engine_params`` overrides it when given.

    ``profiler`` (an :class:`~predictionio_tpu.obs.device.TrainProfiler`,
    `pio train --profile`) binds to the training trace before the run
    and its report lands on ``TrainOutcome.report``; it is always
    closed, so an interrupted or failed run cannot leak a running
    ``jax.profiler`` capture.
    """
    storage = storage or Storage.default()
    variant = dict(variant or {})
    if engine is None:
        if not engine_factory:
            engine_factory = variant.get("engineFactory", "")
        if not engine_factory:
            raise ValueError("run_train needs an engine or an engineFactory spec")
        engine = resolve_engine_factory(engine_factory)()
    if engine_params is None:
        engine_params = engine.params_from_variant_json(variant)
    ctx = ctx or EngineContext(workflow_params=workflow_params, storage=storage)

    instances = storage.get_meta_data_engine_instances()
    instance = EngineInstance(
        id="",
        status="INIT",
        start_time=_now(),
        completion_time=_now(),
        engine_id=variant.get("id", "default"),
        engine_version=variant.get("version", "1"),
        engine_variant=variant.get("variantId", variant.get("id", "default")),
        engine_factory=engine_factory or f"{type(engine).__module__}.{type(engine).__qualname__}",
        batch=workflow_params.batch,
        env={},
        mesh_conf=dict(workflow_params.mesh_conf),
        data_source_params=_params_json(engine_params.data_source_params),
        preparator_params=_params_json(engine_params.preparator_params),
        algorithms_params=_algo_params_json(engine_params.algorithm_params_list),
        serving_params=_params_json(engine_params.serving_params),
    )
    instance_id = instances.insert(instance)
    logger.info("engine instance %s: INIT", instance_id)
    ctx = ctx.with_workflow_params(engine_instance_id=instance_id)

    # the training trace is ALWAYS collected (a handful of spans per
    # run — noise next to any real train): Engine.train records the
    # read/prepare/train stages against the ambient binding, persist is
    # timed here, and `pio train` prints the breakdown
    trace = Trace("train", request_id=instance_id)
    try:
        if profiler is not None:
            # inside the try: a --profile-dir trace that cannot start
            # marks the instance FAILED and fails the command
            profiler.begin(trace)
        try:
            with use_trace(trace):
                result = engine.train(ctx, engine_params)
        except (StopAfterReadInterruption, StopAfterPrepareInterruption) as stop:
            # deliberate debug early-exit, not a failure
            # (reference: CreateWorkflow catches these cleanly)
            interrupted = dataclasses.replace(
                instances.get(instance_id), status="INTERRUPTED", completion_time=_now()
            )
            instances.update(interrupted)
            logger.info("engine instance %s: INTERRUPTED (%s)", instance_id, stop)
            report = (profiler.finish(trace, instance_id, "INTERRUPTED")
                      if profiler is not None else None)
            return TrainOutcome(instance_id, "INTERRUPTED", [],
                                trace.stage_seconds(), report=report)
        with use_trace(trace), span("persist"):
            save_models(storage, instance_id, result.persisted)
        completed = dataclasses.replace(
            instances.get(instance_id),
            status="COMPLETED",
            completion_time=_now(),
        )
        instances.update(completed)
        stage_seconds = trace.stage_seconds()
        logger.info("engine instance %s: COMPLETED (%s)", instance_id,
                    format_stage_times(stage_seconds))
        report = (profiler.finish(trace, instance_id, "COMPLETED")
                  if profiler is not None else None)
        return TrainOutcome(instance_id, "COMPLETED", result.models,
                            stage_seconds, report=report)
    except Exception:
        # training failures leave the instance non-COMPLETED
        # (CoreWorkflow.scala:68-73 only updates on success)
        failed = dataclasses.replace(
            instances.get(instance_id), status="FAILED", completion_time=_now()
        )
        instances.update(failed)
        logger.error("engine instance %s: FAILED\n%s", instance_id, traceback.format_exc())
        raise
    finally:
        if profiler is not None:
            # idempotent: stops a still-running jax.profiler capture on
            # the failure path (finish already ran on success)
            profiler.finish(trace, instance_id, "FAILED")
