"""Collector pauses as spans (PR 36): a ``gc.callbacks`` hook that
records generation 1 and 2 collections on the ambient trace of the
thread they ran on, installed and removed with a tracing server."""

from __future__ import annotations

import gc
import threading

import pytest

from predictionio_tpu.api.engine_server import create_engine_server
from predictionio_tpu.obs.trace import GcPauseSpans, Trace, use_trace
from predictionio_tpu.workflow.deploy import ServerConfig

from tests.test_query_batching import _train


@pytest.fixture
def hook():
    h = GcPauseSpans()
    h.install()
    yield h
    h.remove()
    assert h not in gc.callbacks


def _names(trace: Trace) -> list:
    return [s[0] for s in trace.spans()]


@pytest.mark.parametrize("generation,want", [
    (0, []), (1, ["gc.pause.gen1"]), (2, ["gc.pause.gen2"])])
def test_a_collection_lands_on_the_ambient_trace(hook, generation, want):
    trace = Trace("queries.json")
    with use_trace(trace):
        gc.collect(generation)
    assert _names(trace) == want
    for _, _, _, start, dur in trace.spans():
        assert start >= 0 and 0 <= dur < 5.0


def test_the_thread_that_collects_is_the_one_whose_trace_gets_it(hook):
    mine, other = Trace("queries.json"), Trace("queries.json")

    def collect():
        with use_trace(other):
            gc.collect(2)

    with use_trace(mine):
        t = threading.Thread(target=collect)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert _names(other) == ["gc.pause.gen2"] and _names(mine) == []


def test_no_ambient_trace_no_span_and_no_error(hook):
    gc.collect(2)
    trace = Trace("queries.json")
    gc.collect(2)
    assert _names(trace) == []


@pytest.mark.parametrize("tracing", [False, True])
def test_installed_and_removed_with_the_server(storage, tracing):
    _train(storage, mult=2)
    before = list(gc.callbacks)
    server = create_engine_server(storage=storage, config=ServerConfig(
        ip="127.0.0.1", port=0, batching=True, tracing=tracing))
    try:
        added = [c for c in gc.callbacks if c not in before]
        assert [type(c) for c in added] == ([GcPauseSpans] if tracing else [])
        server.start()
    finally:
        server.stop()
    assert list(gc.callbacks) == before
