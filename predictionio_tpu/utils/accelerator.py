"""What a compute process may run on, and where it keeps compiled code.

Three rules, all decided by the environment (no flag):

- A compute command (``pio train``/``eval``/``deploy``/``run``) runs on
  the CPU only when ``JAX_PLATFORMS`` names ``cpu`` first — which the
  tests and the docs' walk-throughs set (``JAX_PLATFORMS=cpu``). With
  anything else (unset, ``tpu``, a TPU host's ``tpu,cpu``), finding no
  accelerator is an error carrying the backend's own message: with the
  variable empty JAX falls back to the host with one warning line, and
  a model trained or served there by accident looks exactly like one
  that was not.
- A chip belongs to one process at a time. Measured on a v5e (PR 21): a
  second JAX process on a held chip fails within seconds with
  ``Unable to initialize backend 'tpu': ABORTED: Internal error when
  accessing libtpu multi-process lockfile`` — no hang, and the chip is
  free again as soon as its holder exits. So a launcher whose children
  would each open the accelerator refuses to start them
  (:func:`refuse_shared_chip`).
- XLA's persistent compilation cache lives where
  ``JAX_COMPILATION_CACHE_DIR`` says — JAX reads that itself and this
  module then sets nothing — and otherwise at ``<checkout>/.jax_cache``.
  The path is part of every cache key, so it is fixed: never a temp
  name, pid or timestamp.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache — the package's parent directory, git-ignored
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """JAX found only the host CPU and the environment did not ask for it."""


class SharedChipError(RuntimeError):
    """Several JAX processes were asked to run on one accelerator."""


def cpu_requested() -> bool:
    """Whether ``JAX_PLATFORMS`` makes the host backend the default one
    (its first entry; a TPU host's ``tpu,cpu`` does not)."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def configure_compile_cache() -> str:
    """Apply the cache rule before this process compiles anything;
    returns the directory in effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def require_devices() -> tuple[str, str, int]:
    """``(platform, device_kind, count)`` of the devices this process
    computes on. Raises :class:`NoAcceleratorError` when that is the
    host CPU without ``JAX_PLATFORMS`` having asked for it; a platform
    that *was* named and cannot start — no chip, or a chip another
    process holds — raises from ``jax.devices()`` itself."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not cpu_requested():
        try:
            jax.devices("tpu")
            why = "the TPU backend started but is not the default"
        except RuntimeError as exc:
            why = str(exc)
        raise NoAcceleratorError(
            f"no accelerator: JAX fell back to platform 'cpu' ({why}). "
            "Set JAX_PLATFORMS=cpu to compute on the host on purpose.")
    return platform, devices[0].device_kind, len(devices)


def describe_devices() -> str:
    """The one-line device report compute commands and ``pio status``
    print (``chip_smoke.py`` parses it)."""
    platform, kind, count = require_devices()
    return f"platform={platform} device_kind={kind!r} count={count}"


def start_compute() -> None:
    """Entry rule for a process that will run JAX programs: place the
    compile cache, join the multi-host job when one is configured, then
    claim the devices and say which they are."""
    from predictionio_tpu.parallel.distributed import (
        maybe_initialize_distributed,
    )

    cache = configure_compile_cache()
    # before the first backend touch: jax.distributed must initialize
    # ahead of it. Only compute commands join the coordinator barrier —
    # admin commands must not block on the other hosts.
    maybe_initialize_distributed()
    print(f"[INFO] JAX devices: {describe_devices()} | "
          f"compile cache {cache}", flush=True)


def refuse_shared_chip(what: str, processes: int) -> None:
    """Raise :class:`SharedChipError` when ``processes`` JAX processes
    would be started against the accelerator. With ``JAX_PLATFORMS=cpu``
    every process computes on the host and any number may run."""
    if processes > 1 and not cpu_requested():
        raise SharedChipError(
            f"{what} would start {processes} JAX processes on one "
            "accelerator, and a chip belongs to one process at a time: "
            "every process after the first fails at start-up (libtpu's "
            "multi-process lockfile error). Run one process per chip, "
            "or set JAX_PLATFORMS=cpu to compute on the host.")
