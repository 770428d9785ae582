"""Host-platform helpers for virtual-mesh testing: tests and dry-runs
that need an n-device mesh get it from XLA's forced host-platform
device count on the CPU backend."""

from __future__ import annotations

import os


def force_cpu_devices(n: int) -> None:
    """Make jax see ``n`` virtual CPU devices. Must run before the first
    jax computation in this process (the backend reads both settings
    once, when it initializes)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # covers a jax imported before this call, which already read the env
    jax.config.update("jax_platforms", "cpu")


def sqlite_supports_returning() -> bool:
    """Whether this interpreter's bundled SQLite understands the
    ``RETURNING`` clause (3.35.0+, 2021). The channels DAO — and the PG
    wire emulator, which is backed by the same library — issue
    ``INSERT ... RETURNING id``; containers shipping an older libsqlite
    cannot run those paths at all, so their tests capability-skip with
    this check instead of failing on a syntax error (a container
    artifact, not a regression)."""
    import sqlite3

    return sqlite3.sqlite_version_info >= (3, 35, 0)


def memory_storage():
    """A fresh all-in-memory Storage (the three repositories on the MEM
    source) — the standard test storage, analogous to the reference's
    `Storage.getLEvents(test=true)` test wiring."""
    from predictionio_tpu.storage.registry import Storage

    return Storage({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
