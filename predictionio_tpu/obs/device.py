"""Device memory and FLOPs accounting — the half of the device/compiler
observability layer below :mod:`~predictionio_tpu.obs.compile`
(docs/observability.md "Device and compiler observability").

Three pieces, all degrade-gracefully on backends that expose nothing
(the CPU tier-1 environment must scrape clean, just sparser):

- **HBM gauges** — ``jax.local_devices()`` ``memory_stats()`` rendered
  as ``pio_device_bytes_in_use`` / ``pio_device_peak_bytes_in_use`` /
  ``pio_device_bytes_limit`` per device. CPU devices return no stats
  and contribute no samples (absent, not zero — a dashboard must not
  read "0 bytes of HBM" on a host backend).
- **Peak-FLOPs table** — dense per-chip peaks keyed by device kind
  (bf16/matmul peaks, the number MFU is conventionally quoted
  against), overridable with ``PIO_DEVICE_PEAK_FLOPS`` for kinds the
  table has not met (including CPU, where the override is the ONLY way
  to get a non-null MFU).
- **TrainProfiler** — drives ``pio train --profile``: binds to the
  training trace, samples per-stage memory high-water via the span
  observer hook, bins the recompile sentinel's compile events into the
  DASE stages, prices executed FLOPs from the captured
  ``Compiled.cost_analysis()`` data, and emits the ``TRAIN_REPORT``
  document plus the ``pio_train_mfu`` / ``pio_train_stage_hbm_peak_bytes``
  gauges (exported by :func:`train_report_collector`, which any server
  in the same process picks up through its MetricRegistry).

MFU here is measured honestly or not at all: a null ``mfu`` with a
``mfuReason`` beats a fabricated number (reading guidance in
docs/observability.md).
"""

from __future__ import annotations

import logging
import os
import sys
import time
from typing import Any, Callable, Iterable, Mapping

from predictionio_tpu.obs.compile import CompileRecorder, recorder
from predictionio_tpu.obs.registry import Metric

logger = logging.getLogger(__name__)

#: the TRAIN_REPORT.json schema tag — bump on breaking field changes
TRAIN_REPORT_SCHEMA = "pio.train_report.v1"

#: dense matmul peak FLOPs per CHIP by device-kind substring
#: (lowercased, first match wins — more specific entries first). The
#: bf16 systolic-array peaks every public MFU figure is quoted
#: against; chips whose kind string this table has not met report a
#: null MFU with a reason instead of a guess.
PEAK_FLOPS_TABLE: tuple[tuple[str, float], ...] = (
    ("v6e", 918e12),      # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

_PEAK_FLOPS_ENV = "PIO_DEVICE_PEAK_FLOPS"


def peak_flops_for_kind(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for needle, peak in PEAK_FLOPS_TABLE:
        if needle in kind:
            return peak
    return None


def resolve_peak_flops(device_kind: str) -> tuple[float | None, str]:
    """(peak FLOPs per chip, source) for ``device_kind``. The
    ``PIO_DEVICE_PEAK_FLOPS`` override wins over the table (operators
    measuring a new chip, or assigning CPU an honest local peak);
    ``source`` is ``"env"``/``"table"`` or the reason there is none."""
    raw = os.environ.get(_PEAK_FLOPS_ENV, "").strip()
    if raw:
        try:
            value = float(raw)
            if value > 0:
                return value, "env"
            logger.warning("%s=%r is not positive; ignoring",
                           _PEAK_FLOPS_ENV, raw)
        except ValueError:
            logger.warning("%s=%r is not a number; ignoring",
                           _PEAK_FLOPS_ENV, raw)
    peak = peak_flops_for_kind(device_kind)
    if peak is not None:
        return peak, "table"
    return None, (f"no peak-FLOPs table entry for device kind "
                  f"{device_kind!r} (set {_PEAK_FLOPS_ENV})")


# ---------------------------------------------------------------------------
# device memory
# ---------------------------------------------------------------------------

#: memory_stats() keys -> exported gauge suffixes (only these three:
#: allocator-internal counters vary per backend and churn per release)
_MEM_FIELDS = (
    ("bytes_in_use", "pio_device_bytes_in_use",
     "Device memory currently allocated (memory_stats bytes_in_use)"),
    ("peak_bytes_in_use", "pio_device_peak_bytes_in_use",
     "Device memory high-water since process start"),
    ("bytes_limit", "pio_device_bytes_limit",
     "Device memory capacity visible to the allocator"),
)


def device_memory_snapshot() -> dict[str, dict[str, float]]:
    """``{device_label: {field: value}}`` for every local device that
    exposes ``memory_stats()`` — empty on host-only backends, empty on
    any jax runtime error (an obs read must never take the server
    down), and empty in processes that never imported jax: a /metrics
    scrape must not be the thing that initializes a device backend in
    a deliberately jax-free worker (the prefork echo/test engines)."""
    if "jax" not in sys.modules:
        return {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return {}
    out: dict[str, dict[str, float]] = {}
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        label = f"{dev.platform}:{dev.id}"
        fields = {}
        for field, _, _ in _MEM_FIELDS:
            value = stats.get(field)
            if value is not None:
                fields[field] = float(value)
        if fields:
            fields["device_kind"] = getattr(dev, "device_kind", dev.platform)
            out[label] = fields
    return out


def device_memory_collector() -> Callable[[], Iterable[Metric]]:
    """Scrape-time HBM gauges; contributes nothing on backends without
    ``memory_stats`` (the graceful-absence contract)."""

    def collect() -> list[Metric]:
        snapshot = device_memory_snapshot()
        if not snapshot:
            return []
        out = []
        for field, name, help_text in _MEM_FIELDS:
            samples = [
                ({"device": label, "kind": str(stats.get("device_kind", ""))},
                 stats[field])
                for label, stats in sorted(snapshot.items())
                if field in stats
            ]
            if samples:
                out.append(Metric(name=name, kind="gauge", help=help_text,
                                  samples=samples))
        return out

    return collect


def _primary_device_kind() -> str:
    try:
        import jax

        dev = jax.local_devices()[0]
        return getattr(dev, "device_kind", dev.platform)
    except Exception:
        return "unknown"


def _device_count() -> int:
    try:
        import jax

        return max(1, jax.local_device_count())
    except Exception:
        return 1


# ---------------------------------------------------------------------------
# the train profiler (`pio train --profile`)
# ---------------------------------------------------------------------------

#: the last profiled train run's report, exported by
#: :func:`train_report_collector` (per process, like the recorder)
_LAST_REPORT: dict | None = None


class TrainProfiler:
    """Per-stage wall/compile/execute split, MFU, and HBM high-water
    for one training run.

    Usage (what ``run_train(profiler=...)`` does)::

        profiler = TrainProfiler(profile_dir=args.profile_dir)
        profiler.begin(trace)          # before engine.train
        ...                            # the traced run
        report = profiler.finish(trace, outcome)

    ``begin`` flips the recompile sentinel into cost-capture mode (per
    new signature it additionally prices the program via the AOT
    ``Compiled.cost_analysis()`` — documented profile-time overhead)
    and installs a span observer on the trace that samples device
    memory as each DASE stage closes. ``finish`` is idempotent and
    always runs (the driver calls it in a ``finally``), so an aborted
    run still stops the ``jax.profiler`` trace."""

    def __init__(self, recorder_: CompileRecorder | None = None,
                 profile_dir: str | None = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.recorder = recorder_ if recorder_ is not None else recorder()
        self.profile_dir = profile_dir
        self._clock = clock
        self._stage_mem: dict[str, dict[str, float]] = {}
        self._baseline_events = 0
        self._t0: float | None = None
        self._jax_trace_on = False
        self._finished = False

    # -- lifecycle -----------------------------------------------------------
    def begin(self, trace: Any) -> None:
        self.recorder.capture_cost = True
        self._baseline_events = len(self.recorder.events())
        if trace is not None:
            trace.observer = self._on_span
        if self.profile_dir:
            # asked for by name (--profile-dir): a trace that cannot
            # start fails the run instead of leaving an empty directory
            import jax.profiler

            os.makedirs(self.profile_dir, exist_ok=True)
            jax.profiler.start_trace(self.profile_dir)
            self._jax_trace_on = True
        # the wall clock starts AFTER the capture machinery is up:
        # jax.profiler.start_trace costs seconds on a cold process, and
        # charging it to the run would deflate MFU and report an
        # execute split dominated by the profiler itself
        self._t0 = self._clock()

    def _on_span(self, name: str, start_off: float, dur: float) -> None:
        # called from Trace.add_span as each stage span closes; keep
        # the per-stage MAX so repeated spans (one per algorithm in the
        # train stage) keep the high-water
        snapshot = device_memory_snapshot()
        if not snapshot:
            return
        peak = max((s.get("peak_bytes_in_use", 0.0)
                    for s in snapshot.values()), default=0.0)
        in_use = sum(s.get("bytes_in_use", 0.0) for s in snapshot.values())
        have = self._stage_mem.get(name)
        if have is None or peak >= have.get("peak_bytes_in_use", 0.0):
            self._stage_mem[name] = {"peak_bytes_in_use": peak,
                                     "bytes_in_use": in_use}

    def finish(self, trace: Any, instance_id: str = "",
               status: str = "") -> dict:
        """Stop captures and build the TRAIN_REPORT document. Also
        publishes it for :func:`train_report_collector`."""
        global _LAST_REPORT
        if self._jax_trace_on:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover - backend drift
                logger.warning("jax.profiler stop_trace failed: %s", e)
            self._jax_trace_on = False
        if self._finished:
            return _LAST_REPORT or {}
        self._finished = True
        self.recorder.capture_cost = False
        wall = (self._clock() - self._t0) if self._t0 is not None else 0.0

        events = self.recorder.events()[self._baseline_events:]
        compile_total = sum(e[4] for e in events)
        stages = self._stage_split(trace)

        device_kind = _primary_device_kind()
        peak_flops, peak_source = resolve_peak_flops(device_kind)
        flops_total = self.recorder.executed_flops()
        mfu, mfu_reason = self._mfu(flops_total, peak_flops, peak_source,
                                    wall, device_kind)

        mem = device_memory_snapshot()
        hbm_peak = max((s.get("peak_bytes_in_use")
                        for s in mem.values()
                        if s.get("peak_bytes_in_use") is not None),
                       default=None)
        report = {
            "schema": TRAIN_REPORT_SCHEMA,
            "instanceId": instance_id,
            "status": status,
            "deviceKind": device_kind,
            "deviceCount": _device_count(),
            "wallSeconds": round(wall, 6),
            "stages": stages,
            "compile": {
                "totalSeconds": round(compile_total, 6),
                "totalCompiles": len(events),
                "table": self.recorder.recompile_table(),
            },
            "flops": {
                "executed": flops_total,
                "peakPerChip": peak_flops,
                "peakSource": peak_source if peak_flops is not None else None,
            },
            "mfu": mfu,
            "mfuReason": mfu_reason,
            "hbm": {
                "peakBytes": hbm_peak,
                # where the arrays landed: one entry per local device
                "perDevice": {
                    label: {"peakBytes": s.get("peak_bytes_in_use"),
                            "bytesInUse": s.get("bytes_in_use")}
                    for label, s in sorted(mem.items())} or None,
                "perStage": {name: dict(vals)
                             for name, vals in self._stage_mem.items()}
                            or None,
            },
            "profileDir": self.profile_dir,
        }
        _LAST_REPORT = report
        return report

    # -- pieces --------------------------------------------------------------
    def _stage_split(self, trace: Any) -> dict[str, dict]:
        """Per-stage wall/compile/execute: wall from the trace's span
        records, compile via the recorder's ONE midpoint-binning rule
        (:meth:`CompileRecorder.compile_seconds_between` — events from
        runs before this trace started cannot land in its intervals,
        the clock is monotonic), execute as the remainder (device
        execution and host work are indistinguishable without a
        profiler trace — --profile-dir is the deep-dive)."""
        stages: dict[str, dict] = {}
        if trace is None:
            return stages
        t0 = trace.start_perf
        intervals: dict[str, list[tuple[float, float]]] = {}
        for name, _parent, _sid, start_off, dur in trace.spans():
            intervals.setdefault(name, []).append(
                (t0 + start_off, t0 + start_off + dur))
        for name, spans in intervals.items():
            wall = sum(e - s for s, e in spans)
            compile_s = sum(
                self.recorder.compile_seconds_between(s, e)
                for s, e in spans)
            stages[name] = {
                "wallSeconds": round(wall, 6),
                "compileSeconds": round(compile_s, 6),
                "executeSeconds": round(max(0.0, wall - compile_s), 6),
            }
        return stages

    @staticmethod
    def _mfu(flops_total: float | None, peak_flops: float | None,
             peak_source: str, wall: float,
             device_kind: str) -> tuple[float | None, str]:
        if flops_total is None:
            return None, ("backend exposed no cost analysis for the "
                          "executed programs")
        if peak_flops is None:
            return None, peak_source  # carries the no-table-entry reason
        if wall <= 0:
            return None, "zero measured wall time"
        per_chip = flops_total / wall / _device_count()
        return per_chip / peak_flops, "ok"


def summarize_train_report(report: Mapping[str, Any]) -> str:
    """The one-line human summary `pio train --profile` prints."""
    compile_doc = report.get("compile", {})
    mfu = report.get("mfu")
    mfu_text = (f"{mfu * 100:.2f}%" if isinstance(mfu, (int, float))
                else f"n/a ({report.get('mfuReason', 'unknown')})")
    hbm = (report.get("hbm") or {}).get("peakBytes")
    hbm_text = (f"{hbm / (1 << 30):.2f} GiB" if hbm is not None else "n/a")
    wall = report.get("wallSeconds", 0.0)
    total_c = compile_doc.get("totalSeconds", 0.0)
    return (f"wall {wall:.2f}s | compile {total_c:.2f}s "
            f"({compile_doc.get('totalCompiles', 0)} compiles) | "
            f"execute {max(0.0, wall - total_c):.2f}s | "
            f"MFU {mfu_text} | HBM peak {hbm_text} | "
            f"device {report.get('deviceKind', '?')}"
            f" x{report.get('deviceCount', 1)}")


def train_report_collector() -> Callable[[], Iterable[Metric]]:
    """Gauges from the LAST profiled train run in this process —
    nothing until one ran (`pio train --profile`; the acceptance gauge
    ROADMAP item 1 measures against)."""

    def collect() -> list[Metric]:
        report = _LAST_REPORT
        if report is None:
            return []
        out = []
        mfu = report.get("mfu")
        if isinstance(mfu, (int, float)):
            out.append(Metric(
                name="pio_train_mfu", kind="gauge",
                help="Model FLOPs utilization of the last profiled "
                     "train run (executed FLOPs / wall / peak per chip)",
                samples=[({}, float(mfu))]))
        out.append(Metric(
            name="pio_train_compile_seconds", kind="gauge",
            help="XLA compile seconds inside the last profiled train",
            samples=[({},
                      float(report.get("compile", {})
                            .get("totalSeconds", 0.0)))]))
        per_stage = (report.get("hbm") or {}).get("perStage") or {}
        samples = [({"stage": stage},
                    float(vals.get("peak_bytes_in_use", 0.0)))
                   for stage, vals in sorted(per_stage.items())]
        if samples:
            out.append(Metric(
                name="pio_train_stage_hbm_peak_bytes", kind="gauge",
                help="Device memory high-water sampled as each DASE "
                     "stage of the last profiled train closed "
                     "(monotone across stages: allocator high-water)",
                samples=samples))
        return out

    return collect
