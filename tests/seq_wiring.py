"""What an engine server wires when it deploys a session model, with no
port bound: its ``ServingStats``, whose ``seqKernels`` gauge it sets from
``models/seqrec.kernels``."""

from __future__ import annotations


def wired_stats(algo, model):
    from predictionio_tpu.api.engine_server import EngineService
    from predictionio_tpu.controller.base import FirstServing
    from predictionio_tpu.workflow.deploy import DeployedEngine, ServerConfig

    try:
        return EngineService(
            DeployedEngine(None, None, [algo], FirstServing(), [model]),
            ServerConfig(ip="127.0.0.1", port=0)).serving_stats
    finally:
        model.set_dispatch_observer(None)
