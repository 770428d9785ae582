"""Sharded model checkpointing via orbax — crash-safe.

The reference cannot auto-persist distributed models — a PAlgorithm's
RDD model forces either a custom PersistentModel or a full retrain at
deploy (reference: core/.../controller/PAlgorithm.scala:89-125,
Engine.scala:211-229). Here mesh-sharded ``jax.Array`` models save as
orbax checkpoints: each host writes only its own shards (OCDBT), and
restore places shards straight back onto the target mesh — no
gather-to-host, no retrain-on-deploy, which is the SURVEY.md §7
"better than the reference" contract for sharded model persistence.

A plain-numpy backend (``npz``) keeps the same directory API working
where orbax is not installed. Where it is installed, a failed orbax
save raises — the format never changes quietly.

Crash safety (docs/fleet.md "trustworthy generations"): a canary-vs-
stable rollout is only meaningful when each replica group really runs
the generation it claims, so a torn or bit-flipped checkpoint must
fail LOUDLY at load, never deploy garbage:

- the npz payload is written to a temp path, fsync'd, and atomically
  renamed to a CONTENT-ADDRESSED name (``arrays-<digest>.npz``); the
  atomically replaced ``checkpoint_meta.json`` then names that payload
  — the meta replace is the commit point, so a crash anywhere mid-save
  leaves the previous meta pointing at the previous (still present)
  payload, never a new payload under an old manifest;
- :func:`save_sharded` writes a manifest (inside the meta) naming
  every array with its shape, dtype and — on the npz path, where the
  bytes are host-local — a SHA-256 content checksum;
- :func:`load_sharded` verifies the manifest: missing/extra arrays,
  shape/dtype drift, or a checksum mismatch raise
  :class:`CheckpointCorruptError`. (Orbax arrays may be device-sharded
  across hosts, so their manifest carries shape/dtype only — hashing
  would force the gather-to-host this module exists to avoid; orbax's
  own OCDBT format detects truncation.)

Pre-manifest checkpoints (version 1) load without verification, so
existing artifacts keep working.

Memory-mapped loading (``pio deploy --workers N``; docs/
serving-performance.md "Multi-process serving"): ``load_sharded(...,
mmap_mode="r")`` maps each npz member's raw ``.npy`` bytes straight out
of the page cache instead of copying them onto the heap. N prefork
worker processes that load the same checkpoint then *share* one
physical copy of the factor tables — the kernel backs every worker's
mapping with the same pages — so model memory is O(1) in workers
instead of O(N). ``PIO_CHECKPOINT_MMAP=r`` turns it on fleet-wide
without a code change (read per load call, never frozen at import).

Checksum-verification story under mmap: the sha256 content check reads
every byte, which would fault the whole file in and erase the laziness
the mapping exists for. The policy is **verify-once at save, verify
eagerly on integrity-suspect paths**: a mmap load verifies the
manifest's *shape/dtype* per array (header-only, O(arrays)) but skips
the content hash — the save path already fsync'd + atomically renamed
the content-addressed payload, so a torn write cannot be named by a
committed meta. Deployments that want the full content check (e.g.
after a disk scare) load eagerly (the default), which verifies every
checksum as before. Any mmap failure — compressed member, legacy
layout, filesystem without mmap — logs a warning and falls back to the
eager verified load; the knob can degrade, never brick a deploy.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import Any, Mapping

import numpy as np

logger = logging.getLogger(__name__)

_ORBAX_SUBDIR = "orbax"
_META_FILE = "checkpoint_meta.json"
_NPZ_FILE = "arrays.npz"
_META_VERSION = 2


class CheckpointCorruptError(RuntimeError):
    """The persisted checkpoint fails integrity verification (torn
    write, bit flip, missing file). Callers must treat the checkpoint
    as unusable — the deploy path surfaces this instead of serving a
    silently wrong model."""


def _ocp():
    try:
        import orbax.checkpoint as ocp

        return ocp
    except Exception:  # pragma: no cover - orbax is baked into the image
        return None


def _array_meta(name: str, value: Any, checksum: bool) -> dict:
    meta: dict[str, Any] = {
        "shape": list(getattr(value, "shape", ())),
        "dtype": str(getattr(value, "dtype", "")),
    }
    if checksum:
        host = np.ascontiguousarray(np.asarray(value))
        meta["sha256"] = hashlib.sha256(host.tobytes()).hexdigest()
    return meta


def save_sharded(directory: str, arrays: Mapping[str, Any]) -> str:
    """Persist a flat {name: jax.Array|np.ndarray} mapping. Sharded
    arrays are written shard-locally by orbax; returns the backend used
    ("orbax" or "npz"). Crash-safe: see the module docstring."""
    os.makedirs(directory, exist_ok=True)
    ocp = _ocp()
    if ocp is not None:
        # a failed orbax save raises: quietly writing npz instead would
        # change the on-disk format (and gather sharded tables to host)
        # behind the operator's back
        path = os.path.join(os.path.abspath(directory), _ORBAX_SUBDIR)
        with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
            ckptr.save(path, dict(arrays), force=True)
        # shape/dtype manifest only: hashing a sharded array would
        # gather it to host (module docstring)
        _write_meta(directory, "orbax", {
            name: _array_meta(name, v, checksum=False)
            for name, v in arrays.items()
        })
        return "orbax"
    manifest = {
        name: _array_meta(name, v, checksum=True)
        for name, v in arrays.items()
    }
    # content-addressed payload name: the meta (written LAST, replaced
    # atomically) is the commit point. A crash between payload and meta
    # leaves the previous meta naming the previous payload — which is
    # still on disk, because a new generation never overwrites it.
    digest = hashlib.sha256(json.dumps(manifest, sort_keys=True)
                            .encode()).hexdigest()[:16]
    payload_name = f"arrays-{digest}.npz"
    final = os.path.join(directory, payload_name)
    tmp = f"{final}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **{k: np.asarray(v) for k, v in arrays.items()})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    _write_meta(directory, "npz", manifest, payload=payload_name)
    # the commit landed: previous generations' payloads are garbage now
    for stale in os.listdir(directory):
        if (stale.startswith("arrays-") and stale.endswith(".npz")
                and stale != payload_name) or stale == _NPZ_FILE:
            try:
                os.unlink(os.path.join(directory, stale))
            except OSError:
                pass
    return "npz"


def default_mmap_mode() -> str | None:
    """The fleet-wide mmap default: ``PIO_CHECKPOINT_MMAP`` set to
    ``r``/``1``/``true`` means read-only mapping, anything else (or
    unset) means eager copy-and-verify. Read at call time — the
    ServerConfig env discipline, never frozen at import."""
    raw = os.environ.get("PIO_CHECKPOINT_MMAP", "").strip().lower()
    if raw in ("r", "1", "true", "yes", "on"):
        return "r"
    return None


def _mmap_npz(path: str) -> dict[str, Any]:
    """Map every member of an uncompressed npz as a read-only
    ``np.memmap`` view into the archive file (module docstring). Raises
    on anything unexpected (compressed member, pickled object array,
    short file) — the caller falls back to the eager load."""
    import zipfile

    from numpy.lib import format as npy_format

    out: dict[str, Any] = {}
    with zipfile.ZipFile(path) as zf, open(path, "rb") as f:
        for info in zf.infolist():
            if info.compress_type != zipfile.ZIP_STORED:
                raise ValueError(
                    f"member {info.filename!r} is compressed; "
                    "mmap needs raw stored bytes")
            name = info.filename
            if name.endswith(".npy"):
                name = name[:-4]
            # the zip local file header is variable length: seek to
            # it, read the name/extra lengths, land on the .npy data
            f.seek(info.header_offset)
            local = f.read(30)
            if len(local) != 30 or local[:4] != b"PK\x03\x04":
                raise ValueError("torn local header")
            name_len = int.from_bytes(local[26:28], "little")
            extra_len = int.from_bytes(local[28:30], "little")
            f.seek(info.header_offset + 30 + name_len + extra_len)
            version = npy_format.read_magic(f)
            shape, fortran, dtype = npy_format._read_array_header(
                f, version)
            if dtype.hasobject:
                raise ValueError(
                    f"member {name!r} holds objects; not mappable")
            out[name] = np.memmap(
                path, dtype=dtype, mode="r", shape=shape,
                offset=f.tell(), order="F" if fortran else "C")
    return out


def load_sharded(
    directory: str,
    shardings: Mapping[str, Any] | None = None,
    mmap_mode: str | None = None,
) -> dict[str, Any]:
    """Restore a mapping saved by :func:`save_sharded`, verifying the
    integrity manifest when one exists (raises
    :class:`CheckpointCorruptError` on any mismatch).

    ``shardings`` optionally maps names to ``jax.sharding.Sharding``
    targets — orbax then materialises each array directly with that
    placement (shard-by-shard on multi-host meshes). Without it, arrays
    restore host-local.

    ``mmap_mode="r"`` (npz backend only) maps the arrays instead of
    copying them — the prefork-worker page-sharing path; shape/dtype
    still verify against the manifest but content checksums are skipped
    (module docstring has the verification trade-off). ``None`` defers
    to :func:`default_mmap_mode` (the ``PIO_CHECKPOINT_MMAP`` env);
    orbax checkpoints and device-sharded restores ignore it."""
    meta = _read_meta(directory)
    backend = meta.get("backend", "npz")
    manifest: Mapping[str, Any] | None = meta.get("arrays")
    if backend == "orbax":
        ocp = _ocp()
        if ocp is None:
            raise RuntimeError(
                f"checkpoint at {directory} was written by orbax, which is "
                "not importable here"
            )
        import jax

        path = os.path.join(os.path.abspath(directory), _ORBAX_SUBDIR)
        with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
            if shardings:
                targets = {}
                for name, m in ckptr.metadata(path).item_metadata.items():
                    sh = shardings.get(name)
                    if sh is not None:
                        targets[name] = jax.ShapeDtypeStruct(
                            m.shape, m.dtype, sharding=sh
                        )
                    else:
                        targets[name] = jax.ShapeDtypeStruct(m.shape, m.dtype)
                out = dict(ckptr.restore(path, targets))
            else:
                out = dict(ckptr.restore(path))
        _verify(directory, out, manifest, check_sums=False)
        return out
    payload_name = meta.get("payload", _NPZ_FILE)
    npz_path = os.path.join(directory, payload_name)
    if mmap_mode is None:
        mmap_mode = default_mmap_mode()
    if mmap_mode is not None:
        try:
            out = _mmap_npz(npz_path)
        except FileNotFoundError:
            raise CheckpointCorruptError(
                f"checkpoint at {directory} is missing {payload_name} — "
                "incomplete or deleted save") from None
        except Exception as exc:  # degrade to the eager verified load
            logger.warning(
                "mmap load of %s failed (%s); falling back to the "
                "eager copy-and-verify load", npz_path, exc)
        else:
            # header-only verification: the content hash would fault
            # the whole mapping in (module docstring)
            _verify(directory, out, manifest, check_sums=False)
            if shardings:
                import jax

                for name, sh in shardings.items():
                    if name in out:
                        out[name] = jax.device_put(out[name], sh)
            return out
    try:
        data = np.load(npz_path)
        out = {k: data[k] for k in data.files}
    except FileNotFoundError:
        raise CheckpointCorruptError(
            f"checkpoint at {directory} is missing {payload_name} — "
            "incomplete or deleted save") from None
    except Exception as exc:  # truncated/garbled zip payload
        raise CheckpointCorruptError(
            f"checkpoint at {directory} is unreadable ({exc}) — "
            "torn write or corruption") from exc
    _verify(directory, out, manifest, check_sums=True)
    if shardings:
        import jax

        for name, sh in shardings.items():
            if name in out:
                out[name] = jax.device_put(out[name], sh)
    return out


def _verify(directory: str, arrays: Mapping[str, Any],
            manifest: Mapping[str, Any] | None, check_sums: bool) -> None:
    """Arrays-vs-manifest integrity check; no-op for pre-manifest
    (version 1) checkpoints."""
    if manifest is None:
        return
    have, want = set(arrays), set(manifest)
    if have != want:
        raise CheckpointCorruptError(
            f"checkpoint at {directory} does not match its manifest: "
            f"missing {sorted(want - have)}, unexpected {sorted(have - want)}")
    for name, meta in manifest.items():
        value = arrays[name]
        if list(getattr(value, "shape", ())) != list(meta.get("shape", ())):
            raise CheckpointCorruptError(
                f"checkpoint array {name!r} at {directory} has shape "
                f"{list(value.shape)}, manifest says {meta.get('shape')}")
        if str(getattr(value, "dtype", "")) != meta.get("dtype", ""):
            raise CheckpointCorruptError(
                f"checkpoint array {name!r} at {directory} has dtype "
                f"{value.dtype}, manifest says {meta.get('dtype')}")
        expected = meta.get("sha256")
        if check_sums and expected:
            host = np.ascontiguousarray(np.asarray(value))
            actual = hashlib.sha256(host.tobytes()).hexdigest()
            if actual != expected:
                raise CheckpointCorruptError(
                    f"checkpoint array {name!r} at {directory} fails its "
                    f"content checksum — bit flip or torn write; refusing "
                    f"to load a corrupted model")


def _write_meta(directory: str, backend: str,
                arrays: Mapping[str, Any] | None = None,
                payload: str | None = None) -> None:
    # atomic + durable: a crash between the checkpoint write and the
    # meta landing must never leave a readable-but-stale meta; fsync
    # then os.replace so readers see either the old complete meta or
    # the new one
    path = os.path.join(directory, _META_FILE)
    tmp = f"{path}.tmp.{os.getpid()}"
    doc: dict[str, Any] = {"backend": backend, "version": _META_VERSION}
    if arrays is not None:
        doc["arrays"] = dict(arrays)
    if payload is not None:
        doc["payload"] = payload
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _read_meta(directory: str) -> dict:
    meta_path = os.path.join(directory, _META_FILE)
    if not os.path.exists(meta_path):
        # no meta: prefer a complete orbax checkpoint over legacy npz (a
        # crash after the orbax write but before the meta landed must not
        # silently resurrect a stale npz from an earlier save)
        if os.path.isdir(os.path.join(directory, _ORBAX_SUBDIR)):
            return {"backend": "orbax"}
        return {"backend": "npz"}
    try:
        with open(meta_path) as f:
            doc = json.load(f)
    except (json.JSONDecodeError, OSError) as exc:
        raise CheckpointCorruptError(
            f"checkpoint meta at {meta_path} is unreadable ({exc})") from exc
    if not isinstance(doc, dict):
        raise CheckpointCorruptError(
            f"checkpoint meta at {meta_path} is not a JSON object")
    return doc
