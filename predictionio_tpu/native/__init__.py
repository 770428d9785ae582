"""Native runtime components (C++, ctypes-bound).

``load_eventlog()`` returns the compiled event-log library (see
eventlog.cc) or None when a toolchain isn't available — callers fall
back to the pure-Python codec in storage/binevents.py, which implements
the identical byte format.

Each library is built on demand with g++ (baked into the image) next to
its source, under a name that carries a hash of that source: a binary
is reused only when it was built from exactly the source on disk, so a
stale or copied-in ``.so`` (they are git-ignored) is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_CXX = ("g++", "-O2", "-std=c++17", "-shared", "-fPIC")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False

_bucketize_lock = threading.Lock()
_bucketize_lib: ctypes.CDLL | None = None
_bucketize_failed = False


def _build(src: str, stem: str) -> str | None:
    """Path of ``<stem>-<hash>.so`` beside ``src``, compiled now unless
    that exact name already exists; the hash covers the source bytes
    and the compiler command. None when there is no toolchain."""
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(" ".join(_CXX).encode() + f.read())
    except OSError as e:
        logger.warning("native source %s unreadable (%s); using the "
                       "pure-Python path", src, e)
        return None
    so = os.path.join(os.path.dirname(src),
                      f"{stem}-{digest.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    # compile to a per-pid temp path, then atomically rename into place:
    # two processes racing on first use must never dlopen a partially
    # written .so (rename is atomic within the directory)
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            [*_CXX, "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("building %s failed (%s); using the pure-Python "
                       "path", os.path.basename(src), e)
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass
        return None


def load_eventlog() -> ctypes.CDLL | None:
    """Compile (if needed) and load the native event log; None on failure."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        so = _build(os.path.join(_DIR, "eventlog.cc"), "_eventlog")
        if so is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            _load_failed = True
            return None
        c_char_pp = ctypes.POINTER(ctypes.c_char_p)
        u8_pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))
        u64_p = ctypes.POINTER(ctypes.c_uint64)
        lib.pio_open.argtypes = [ctypes.c_char_p]
        lib.pio_open.restype = ctypes.c_void_p
        lib.pio_close.argtypes = [ctypes.c_void_p]
        lib.pio_close.restype = ctypes.c_int
        lib.pio_flush.argtypes = [ctypes.c_void_p]
        lib.pio_flush.restype = ctypes.c_int
        lib.pio_write_put.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.pio_write_put.restype = ctypes.c_int
        lib.pio_write_del.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pio_write_del.restype = ctypes.c_int
        lib.pio_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p, c_char_pp,
            ctypes.c_int32, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, u8_pp, u64_p,
        ]
        lib.pio_scan.restype = ctypes.c_int
        lib.pio_get.argtypes = [ctypes.c_char_p, ctypes.c_char_p, u8_pp, u64_p]
        lib.pio_get.restype = ctypes.c_int
        lib.pio_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.pio_free.restype = None
        _lib = lib
        return _lib


def load_bucketize() -> ctypes.CDLL | None:
    """Compile (if needed) and load the native ratings bucketizer
    (bucketize.cc); None on failure — ops/als.bucket_rows falls back to
    the NumPy implementation with identical slab layout."""
    global _bucketize_lib, _bucketize_failed
    with _bucketize_lock:
        if _bucketize_lib is not None or _bucketize_failed:
            return _bucketize_lib
        so = _build(os.path.join(_DIR, "bucketize.cc"), "_bucketize")
        if so is None:
            _bucketize_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            _bucketize_failed = True
            return None
        return _bind_bucketize(lib)


def _bind_bucketize(lib: ctypes.CDLL) -> ctypes.CDLL | None:
    global _bucketize_lib, _bucketize_failed
    try:
        _bind_bucketize_symbols(lib)
    except AttributeError:
        # a stale/prebuilt .so without the full symbol set (e.g. built
        # from an older bucketize.cc) must mean "no native path", not a
        # crash on every call — fall back to NumPy everywhere
        _bucketize_failed = True
        return None
    _bucketize_lib = lib
    return _bucketize_lib


def _bind_bucketize_symbols(lib: ctypes.CDLL) -> None:
    i32_p = ctypes.POINTER(ctypes.c_int32)
    i64_p = ctypes.POINTER(ctypes.c_int64)
    f32_p = ctypes.POINTER(ctypes.c_float)
    lib.pio_bucketize.argtypes = [
        ctypes.c_int64, i32_p, i32_p, f32_p, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
    ]
    lib.pio_bucketize.restype = ctypes.c_void_p
    lib.pio_bucketize_num_buckets.argtypes = [ctypes.c_void_p]
    lib.pio_bucketize_num_buckets.restype = ctypes.c_int32
    lib.pio_bucketize_bucket_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32_p, i64_p,
    ]
    lib.pio_bucketize_bucket_info.restype = ctypes.c_int
    lib.pio_bucketize_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32_p, i32_p, f32_p, i32_p,
    ]
    lib.pio_bucketize_fill.restype = ctypes.c_int
    lib.pio_bucketize_free.argtypes = [ctypes.c_void_p]
    lib.pio_bucketize_free.restype = None
    # ladder entry point (ops/als.ladder_rows) — shares the bucketize
    # handle/info/fill/free contract
    lib.pio_ladder.argtypes = [
        ctypes.c_int64, i32_p, i32_p, f32_p, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, i64_p, ctypes.c_int32,
    ]
    lib.pio_ladder.restype = ctypes.c_void_p
    # chunker entry points (same library; ops/als.chunk_rows)
    lib.pio_chunk.argtypes = [
        ctypes.c_int64, i32_p, i32_p, f32_p, ctypes.c_int32, i32_p,
        ctypes.c_int32,
    ]
    lib.pio_chunk.restype = ctypes.c_void_p
    lib.pio_chunk_num_slabs.argtypes = [ctypes.c_void_p]
    lib.pio_chunk_num_slabs.restype = ctypes.c_int32
    lib.pio_chunk_slab_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32_p, i64_p,
    ]
    lib.pio_chunk_slab_info.restype = ctypes.c_int
    lib.pio_chunk_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32_p, i32_p, f32_p, i32_p,
    ]
    lib.pio_chunk_fill.restype = ctypes.c_int
    lib.pio_chunk_free.argtypes = [ctypes.c_void_p]
    lib.pio_chunk_free.restype = None
