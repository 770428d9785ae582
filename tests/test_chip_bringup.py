"""The chip bring-up rules (PR 21): no compute on the CPU unless asked,
a compile cache that can be placed from outside, native binaries built
from the source on disk, and a chip smoke that passes nowhere but on a
TPU. Each case is a subprocess or a pure function — none loads a model.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = (
    "import jax; from predictionio_tpu.utils import accelerator as a; "
    "print(a.configure_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir)")


def _run(argv, **env):
    base = {k: v for k, v in os.environ.items()
            if not k.startswith(("PIO_", "JAX_", "XLA_"))}
    base.update({"PYTHONPATH": REPO, **env})
    return subprocess.run(argv, cwd=REPO, env=base, text=True,
                          capture_output=True, timeout=300)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(tmp_path):
    placed = str(tmp_path / "cache")
    out = _run([sys.executable, "-c", _CACHE_PROBE], JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr
    # jax read the variable itself; the function set nothing over it
    assert out.stdout.split() == [placed, placed]
    # unset: the same fixed in-checkout path from two processes
    runs = [_run([sys.executable, "-c", _CACHE_PROBE], JAX_PLATFORMS="cpu")
            for _ in range(2)]
    want = os.path.join(REPO, ".jax_cache")
    for out in runs:
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == [want, want]


@pytest.mark.parametrize("platforms", [None, "tpu,cpu"])
def test_compute_command_refuses_the_cpu_unless_asked(platforms, tmp_path):
    """`pio train` with no accelerator and JAX_PLATFORMS unset or a chip
    host's `tpu,cpu` exits non-zero before it reads an engine or touches
    a model."""
    env = {"PIO_FS_BASEDIR": str(tmp_path)}
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    out = _run([sys.executable, "-m", "predictionio_tpu.cli.pio", "train"],
               **env)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "[ERROR]" in out.stdout and "tpu" in out.stdout.lower()
    assert "engine.json" not in out.stdout      # never got that far


@pytest.mark.parametrize("argv", [
    ["deploy", "--workers", "2"],
    ["eval", "tests.sample_engine.SampleEvaluation", "--parallel", "2"],
    ["router", "--supervise", "--replicas", "2", "--replica-port-base",
     "18900", "--replica-cmd", "pio deploy --port {port}"],
])
def test_launchers_refuse_to_share_a_chip(argv, tmp_path):
    """Several JAX processes for one accelerator fail at once, naming
    the cause — before any child starts (JAX_PLATFORMS=tpu,cpu is the
    chip host's own setting). With JAX_PLATFORMS=cpu the same launchers
    run as ever (test_serving_workers / test_experiment_grid /
    test_fleet_supervisor)."""
    out = _run([sys.executable, "-m", "predictionio_tpu.cli.pio", *argv],
               JAX_PLATFORMS="tpu,cpu", PIO_FS_BASEDIR=str(tmp_path))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "a chip belongs to one process at a time" in out.stdout


def test_admin_commands_never_open_the_accelerator(tmp_path):
    """`pio undeploy` runs while the server it stops holds the chip, so
    nothing it imports may start a JAX backend (PR 21: a module-level
    ``jnp.float32(...)`` in ops/topk did, and undeploy died on libtpu's
    lockfile). JAX_PLATFORMS=tpu with no chip: any backend start raises."""
    out = _run([sys.executable, "-m", "predictionio_tpu.cli.pio", "undeploy",
                "--ip", "127.0.0.1", "--port", "18439"],
               JAX_PLATFORMS="tpu", PIO_FS_BASEDIR=str(tmp_path))
    assert "No engine server running" in out.stdout, out.stdout + out.stderr
    assert "Unable to initialize backend" not in out.stderr


def test_chip_smoke_fails_off_chip_and_names_the_platform():
    out = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
               JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "found platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_native_build_ignores_a_stale_binary(tmp_path):
    from predictionio_tpu import native

    src = tmp_path / "probe.cc"
    src.write_text('extern "C" int probe() { return 1; }\n')
    first = native._build(str(src), "_probe")
    if first is None:
        pytest.skip("no C++ toolchain")
    # a binary left from other source — under the legacy name and under
    # another source's hash — is never picked up
    (tmp_path / "_probe.so").write_bytes(b"stale")
    src.write_text('extern "C" int probe() { return 2; }\n')
    second = native._build(str(src), "_probe")
    assert second not in (first, str(tmp_path / "_probe.so"))
    import ctypes

    assert ctypes.CDLL(second).probe() == 2
    # and the same source maps to the same binary, without a rebuild
    mtime = os.path.getmtime(second)
    assert native._build(str(src), "_probe") == second
    assert os.path.getmtime(second) == mtime
