"""Test fixture configuration.

Multi-chip behavior is tested on a virtual 8-device CPU mesh
(SURVEY.md §4 implication; mirrors the reference's Spark local[4] test
fixture, core/src/test/scala/.../workflow/BaseTest.scala:77-90). The env
vars must be set before jax initializes its backends, hence here at
conftest import time.
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")

from predictionio_tpu.utils.testing import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import pytest  # noqa: E402


@pytest.fixture
def storage():
    """A fresh in-memory Storage (all three repositories on MEM)."""
    from predictionio_tpu.utils.testing import memory_storage

    return memory_storage()


@pytest.fixture(scope="session")
def run_mesh_child():
    """Runner for forced-multi-device subprocess children (the `mesh`
    lane): spawns a ``tests/`` script with a FRESH jax process pinned
    to ``--xla_force_host_platform_device_count=N`` — the in-process
    8-device topology is fixed at conftest import, so anything needing
    a different device count, clean env knobs (PIO_TRAIN_SHARD_FACTORS
    / PIO_SERVING_SHARD_FACTORS), or virgin jit caches goes through
    here. Returns ``(returncode, stdout, stderr)``; callers assert on
    the child's printed verdict so its traceback lands in the pytest
    failure message."""
    import subprocess
    import sys

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(tests_dir)

    def run(child: str, *, devices: int = 8, env: dict | None = None,
            timeout: float = 300):
        base = {
            k: v for k, v in os.environ.items()
            if not k.startswith(("PIO_", "XLA_", "JAX_"))
        }
        base["PYTHONPATH"] = repo
        base["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
        base["JAX_PLATFORMS"] = "cpu"
        if env:
            base.update(env)
        p = subprocess.run(
            [sys.executable, os.path.join(tests_dir, child)],
            env=base, capture_output=True, text=True, timeout=timeout)
        return p.returncode, p.stdout, p.stderr

    return run


@pytest.fixture(scope="session")
def mesh8():
    """An 8-device 2D mesh (4 data x 2 model), the standard test topology."""
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devices = np.asarray(jax.devices()).reshape(4, 2)
    with Mesh(devices, ("data", "model")) as m:
        yield m


#: lines of accepted benchmark tests (``tests/benchmarks/``, whose files
#: only a ``benchmark`` change may edit) that a later addition made
#: stale, kept as ``tests/benchmarks/conftest.py`` keeps its own: the test
#: id's tail -> (the statement, why). A failure at exactly that statement
#: is reported as expected; at any other line it is a failure. What the
#: test asserts behind the line is asserted again where the reason says.
STALE_BENCHMARK_LINES = {
    "test_nemotron_h_kind.py::test_the_manifest_gained_one_cell_and_its_"
    "metrics": (
        'assert [m["name"] for m in manifest["per_layer"] if CELL in '
        'm.get("workloads", ())] == list(NEW)',
        "moe_rows_device_ms lists the Nemotron-H cell beside the nine "
        "metrics the cell brought (tests/benchmarks/test_moe_rows_metric.py"
        "::test_what_the_stale_line_shadows asserts the rest)"),
    "test_moe_rows_metric.py::test_the_manifest_lists_both_routed_cells": (
        'assert entry == {"name": METRIC, "unit": "ms", "better": "lower", '
        '"source": "device_trace", "layer": "kernels", "moves": '
        '"query_p50_ms", "workloads": [nemotron.CELL, deepseek.CELL]}',
        "moe_rows_device_ms lists the Laguna cell after the two MoE cells "
        "(tests/benchmarks/test_laguna_kind.py::test_what_the_stale_line_"
        "shadows asserts the rest)"),
}


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if call.when != "call" or call.excinfo is None or \
            not call.excinfo.errisinstance(AssertionError):
        return
    failed_at = " ".join(str(call.excinfo.traceback[-1].statement).split())
    for tail, (statement, why) in STALE_BENCHMARK_LINES.items():
        if item.nodeid.endswith(tail) and failed_at == statement:
            report.outcome = "skipped"
            report.wasxfail = why
