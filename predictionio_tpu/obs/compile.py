"""The recompile sentinel: XLA compilation observability for the
package's jit entry points (docs/observability.md "Device and compiler
observability").

``jax.jit`` retraces and recompiles on every new abstract input
signature. On the training path that is expected cold-start cost; on
the SERVING path a compile firing under a live request is a
multi-second latency cliff hiding inside one response — the exact
failure mode the ``ops/topk.BATCH_WIDTHS`` menu exists to prevent, and
until this module, an invisible one. :func:`instrumented_jit` wraps
``jax.jit`` so every entry point in ``ops/`` reports:

- ``pio_jit_compiles_total{fn}`` — compiles per function;
- ``pio_jit_compile_seconds_total`` — cumulative seconds spent inside
  XLA compilation (trace + lower + backend compile, attributed via
  ``jax.monitoring`` duration events, falling back to call walltime
  when the monitoring hook is unavailable);
- ``pio_serving_recompile_total`` — compiles that fired AFTER the
  serving warmup mark, each with a WARN log and an ``xla_compile``
  span on the ambient trace (a live request paying a compile is an
  incident, not a detail).

Compile DETECTION rides the jitted callable's own cache
(``_cache_size()`` before/after the call — the exact cache ``jax.jit``
consults, so the sentinel can never disagree with the compiler about
what was a miss); where that private hook is absent the wrapper falls
back to its own abstract-signature set. Calls made with tracer
arguments (jit-of-jit inlining) never bump the inner cache and are
never counted.

The recorder itself (:class:`CompileRecorder`) is plain Python with an
injectable clock — unit-testable without jax, and jax is only imported
once :func:`instrumented_jit` actually wraps something, keeping
``obs/`` importable below the compute layer.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
import zlib
from contextvars import ContextVar
from typing import Any, Callable, Iterable

from predictionio_tpu.obs.registry import Metric

logger = logging.getLogger(__name__)

#: signatures are bounded strings: a pathological arg tree (hundreds of
#: bucket slabs) must not turn the recompile table into a memory leak
_SIG_MAX_CHARS = 200

#: bounded compile-event history — enough for any real train run's
#: per-stage binning (a run with thousands of compiles has bigger
#: problems), never an unbounded list on a long-lived server
_MAX_EVENTS = 1024


def _crc(text: str) -> str:
    return f"{zlib.crc32(text.encode('utf-8', 'replace')):08x}"


def describe_abstract_signature(args: tuple, kwargs: dict) -> str:
    """A human-readable abstract signature: arrays as ``dtype[shape]``,
    static scalars by value — the key the recompile table groups by.
    Bounded length (tail replaced by a crc32 so distinct giant
    signatures stay distinct)."""

    def leaf(x: Any) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            dims = ",".join(str(d) for d in shape)
            return f"{getattr(dtype, 'name', dtype)}[{dims}]"
        if isinstance(x, (tuple, list)):
            return "(" + ",".join(leaf(e) for e in x) + ")"
        if isinstance(x, (bool, int, float, str, bytes, type(None))):
            return repr(x)
        return type(x).__name__

    parts = [leaf(a) for a in args]
    parts += [f"{k}={leaf(v)}" for k, v in sorted(kwargs.items())]
    sig = "(" + ", ".join(parts) + ")"
    if len(sig) > _SIG_MAX_CHARS:
        sig = sig[: _SIG_MAX_CHARS - 12] + "...#" + _crc(sig)
    return sig


class CompileRecorder:
    """Thread-safe ledger of jit compiles: per-function counts, the
    per-(function, signature) recompile table, cumulative compile
    seconds, and the post-warmup serving-recompile counter.

    ``clock`` is injectable (``time.perf_counter`` in production,
    a ManualClock in tests) and only stamps event times — the compile
    DURATIONS are measured by the caller and passed in."""

    def __init__(self, clock: Any = time.perf_counter):
        self._lock = threading.Lock()
        # either a bare callable (time.perf_counter) or the repo's
        # Clock protocol (utils/resilience: .monotonic()/.sleep())
        self._clock = (clock.monotonic
                       if hasattr(clock, "monotonic") and not callable(clock)
                       else clock)
        self._compiles: dict[str, int] = {}
        self._seconds: dict[str, float] = {}
        #: (fn, signature) -> compile count — the recompile table
        self._signatures: dict[tuple[str, str], int] = {}
        #: (fn, signature) -> calls (tracked only while capture_cost,
        #: for the profiler's executed-FLOPs accounting)
        self._calls: dict[tuple[str, str], int] = {}
        #: (fn, signature) -> per-call FLOPs from cost analysis
        #: (present only when the backend priced the program)
        self._flops: dict[tuple[str, str], float] = {}
        #: signatures whose pricing was ATTEMPTED (capture mode) — a
        #: backend answering "no data" must not be re-asked per call
        self._priced: set[tuple[str, str]] = set()
        #: recent compile events: (fn, sig, start, end, seconds) —
        #: ``start``/``end`` are clock values, used by the train
        #: profiler to bin compile time into DASE stages
        self._events: list[tuple[str, str, float, float, float]] = []
        self._serving_recompiles = 0
        self._warmup_done = False
        #: profile mode: track per-signature calls + capture cost
        #: analysis on compile (the instrumented_jit wrapper reads it)
        self.capture_cost = False

    # -- recording -----------------------------------------------------------
    def record_compile(self, fn: str, signature: str, seconds: float,
                       start: float | None = None,
                       end: float | None = None) -> bool:
        """Count one compile. Returns True when it fired post-warmup
        (a serving recompile) — the caller owns the WARN/span side
        effects so this stays side-effect-free for unit tests except
        for the log line, which lives in :func:`note_serving_recompile`.
        """
        if end is None:
            end = self._clock()
        if start is None:
            start = end - seconds
        with self._lock:
            self._compiles[fn] = self._compiles.get(fn, 0) + 1
            self._seconds[fn] = self._seconds.get(fn, 0.0) + seconds
            key = (fn, signature)
            self._signatures[key] = self._signatures.get(key, 0) + 1
            if len(self._events) < _MAX_EVENTS:
                self._events.append((fn, signature, start, end, seconds))
            post_warmup = self._warmup_done
            if post_warmup:
                self._serving_recompiles += 1
        return post_warmup

    def note_serving_recompile(self, fn: str, signature: str,
                               seconds: float) -> None:
        """The operator-facing side of a post-warmup compile: the WARN
        that turns a silent latency cliff into a searchable incident
        (runbook: docs/observability.md 'The recompile runbook')."""
        logger.warning(
            "serving recompile: %s compiled for new signature %s "
            "(%.3fs) AFTER warmup — a live request paid this compile. "
            "Off-menu batch or top-k width? Check ops/topk "
            "BATCH_WIDTHS/serving_batch and _K_WIDTHS/serving_k "
            "snapping (runbook: docs/observability.md).",
            fn, signature, seconds)

    def record_call(self, fn: str, signature: str) -> None:
        """Per-signature call counting — only while ``capture_cost``
        (the profiler's executed-FLOPs accounting needs calls × FLOPs
        per signature; steady-state serving skips the bookkeeping)."""
        with self._lock:
            key = (fn, signature)
            self._calls[key] = self._calls.get(key, 0) + 1

    def ensure_priced(self, fn: str, signature: str,
                      price: Callable[[], float | None]) -> None:
        """Price one signature's program at most once (capture mode):
        ``price`` runs OUTSIDE the lock (it may lower+compile) and a
        None answer ("backend has no cost data") is remembered so the
        backend is not re-asked on every call — programs compiled
        BEFORE profiling began get priced on their first profiled
        call, so a warm process still reports executed FLOPs."""
        key = (fn, signature)
        with self._lock:
            if key in self._priced:
                return
            self._priced.add(key)
        value = price()
        if value is not None:
            with self._lock:
                self._flops[key] = value

    # -- warmup --------------------------------------------------------------
    def mark_warmup_complete(self) -> None:
        with self._lock:
            self._warmup_done = True

    @property
    def warmup_complete(self) -> bool:
        with self._lock:
            return self._warmup_done

    def reset(self) -> None:
        """Back to the just-constructed state (tests; a fresh bench
        phase). The process-global recorder outlives servers, so e2e
        tests reset instead of re-importing."""
        with self._lock:
            self._compiles.clear()
            self._seconds.clear()
            self._signatures.clear()
            self._calls.clear()
            self._flops.clear()
            self._priced.clear()
            self._events.clear()
            self._serving_recompiles = 0
            self._warmup_done = False
            self.capture_cost = False

    # -- views ---------------------------------------------------------------
    def totals(self) -> tuple[int, float, int]:
        """(compiles, compile_seconds, serving_recompiles)."""
        with self._lock:
            return (sum(self._compiles.values()),
                    sum(self._seconds.values()),
                    self._serving_recompiles)

    def compiles_by_fn(self) -> dict[str, int]:
        with self._lock:
            return dict(self._compiles)

    def seconds_by_fn(self) -> dict[str, float]:
        with self._lock:
            return dict(self._seconds)

    def recompile_table(self) -> list[dict]:
        """One row per (function, signature): the TRAIN_REPORT /
        /stats.json table a menu-drift investigation starts from."""
        with self._lock:
            sig_counts = dict(self._signatures)
            flops = dict(self._flops)
            calls = dict(self._calls)
        return [
            {"fn": fn, "signature": sig, "compiles": n,
             **({"flopsPerCall": flops[(fn, sig)]}
                if (fn, sig) in flops else {}),
             **({"calls": calls[(fn, sig)]} if (fn, sig) in calls else {})}
            for (fn, sig), n in sorted(sig_counts.items())
        ]

    def events(self) -> list[tuple[str, str, float, float, float]]:
        with self._lock:
            return list(self._events)

    def compile_seconds_between(self, start: float, end: float) -> float:
        """Compile seconds whose event MIDPOINT falls in [start, end) —
        the profiler's per-stage binning (clock values from the same
        clock the recorder stamps with)."""
        total = 0.0
        for _, _, s, e, secs in self.events():
            mid = (s + e) / 2.0
            if start <= mid < end:
                total += secs
        return total

    def executed_flops(self) -> float | None:
        """Σ flops(signature) × calls(signature) over every signature
        with cost data — None when NO signature carried any (the
        backend exposed no cost analysis)."""
        with self._lock:
            flops = dict(self._flops)
            calls = dict(self._calls)
        total, have = 0.0, False
        for key, per_call in flops.items():
            if per_call is None:
                continue
            n = calls.get(key, 0)
            if n:
                have = True
                total += per_call * n
        return total if have else None

    def stats_doc(self) -> dict:
        """The /stats.json 'compile' section."""
        compiles, seconds, recompiles = self.totals()
        return {
            "compiles": compiles,
            "compileSeconds": round(seconds, 6),
            "servingRecompiles": recompiles,
            "warmupComplete": self.warmup_complete,
            "byFunction": self.compiles_by_fn(),
        }


#: the process-global recorder every instrumented entry point reports
#: to by default (per-process, like the jit caches it observes)
_GLOBAL_RECORDER = CompileRecorder()


def recorder() -> CompileRecorder:
    return _GLOBAL_RECORDER


# ---------------------------------------------------------------------------
# compile-duration attribution: jax.monitoring fires per-phase duration
# events (/jax/core/compile/...) synchronously on the compiling thread;
# a contextvar scope attributes them to the instrumented call in flight
# ---------------------------------------------------------------------------


class _CompileScope:
    __slots__ = ("seconds", "parent")

    def __init__(self, parent: "_CompileScope | None"):
        self.seconds = 0.0
        self.parent = parent


_SCOPE: ContextVar[_CompileScope | None] = ContextVar(
    "pio_compile_scope", default=None)

_LISTENER_LOCK = threading.Lock()
_LISTENER_STATE = {"registered": False, "available": False}


def _on_duration_event(name: str, seconds: float, **kwargs) -> None:
    # every phase of a compile (jaxpr trace, MLIR lowering, backend
    # compile) counts toward the call in flight; unrelated events
    # (none currently share the prefix) are ignored
    if not name.startswith("/jax/core/compile/") \
            and not name.startswith("/jax/backend_compile"):
        return
    scope = _SCOPE.get()
    if scope is not None:
        scope.seconds += seconds


def _ensure_listener() -> bool:
    """Register the jax.monitoring listener once per process. Returns
    whether duration attribution is available (False -> the wrapper
    falls back to call walltime for compile seconds)."""
    with _LISTENER_LOCK:
        if _LISTENER_STATE["registered"]:
            return _LISTENER_STATE["available"]
        _LISTENER_STATE["registered"] = True
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            _LISTENER_STATE["available"] = True
        except Exception:  # pragma: no cover - jax drift guard
            _LISTENER_STATE["available"] = False
        return _LISTENER_STATE["available"]


def _cache_size(jitted: Any) -> int | None:
    try:
        return int(jitted._cache_size())
    except Exception:
        return None


def _cost_analysis_flops(jitted: Any, args: tuple,
                         kwargs: dict) -> float | None:
    """Per-call FLOPs from ``Compiled.cost_analysis()`` via the AOT
    path — only under ``capture_cost`` (profiling): the AOT lowering
    re-traces, which is real work we must not add to steady-state
    serving."""
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
        flops = compiled.cost_analysis().get("flops")
        # XLA reports -1 for programs it cannot price — that is "no
        # data", not negative work
        return float(flops) if flops is not None and flops >= 0 else None
    except Exception:
        return None


def instrumented_jit(fn: Callable | None = None, *,
                     jit_name: str | None = None,
                     recorder: CompileRecorder | None = None,
                     **jit_kwargs) -> Callable:
    """``jax.jit`` with the recompile sentinel attached.

    Drop-in at every decoration site::

        @partial(instrumented_jit, static_argnames=("k",))
        def topk_scores(scores, k): ...

    The wrapped callable behaves like the plain jitted function (same
    cache, same donation/static semantics — everything in
    ``jit_kwargs`` passes straight through) and additionally reports
    compiles to ``recorder`` (the process-global one by default). The
    underlying jitted callable is exposed as ``__wrapped_jit__`` and
    its AOT ``lower`` is re-exported, so existing AOT callers keep
    working."""
    if fn is None:
        return functools.partial(instrumented_jit, jit_name=jit_name,
                                 recorder=recorder, **jit_kwargs)

    import jax  # deferred: obs/ stays importable without a device stack

    jitted = jax.jit(fn, **jit_kwargs)
    label = jit_name or getattr(fn, "__name__", repr(fn))
    listener_ok = _ensure_listener()
    bound_recorder = recorder
    #: signatures this wrapper has counted a compile for. With the
    #: cache hook present it guards ATTRIBUTION under concurrency: two
    #: threads in the same function can both observe a cache-size bump
    #: from ONE compile (the on-menu caller would then be blamed for
    #: the off-menu caller's compile, and the recompile counter would
    #: double) — a compile is only recorded by the caller whose OWN
    #: signature is new, checked-and-added under the lock. Without the
    #: hook (jax drift) it is the whole detection mechanism.
    seen_signatures: set[str] = set()
    seen_lock = threading.Lock()

    def _claim(sig: str) -> bool:
        with seen_lock:
            if sig in seen_signatures:
                return False
            seen_signatures.add(sig)
            return True

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        rec = bound_recorder if bound_recorder is not None \
            else _GLOBAL_RECORDER
        before = _cache_size(jitted)
        scope = _CompileScope(_SCOPE.get())
        token = _SCOPE.set(scope)
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **kwargs)
        finally:
            t1 = time.perf_counter()
            _SCOPE.reset(token)
        after = _cache_size(jitted)
        if before is not None and after is not None:
            sig = None
            compiled = after > before
            if compiled or rec.capture_cost:
                sig = describe_abstract_signature(args, kwargs)
            if compiled:
                # only the caller whose own signature is new records
                # the compile (see seen_signatures note above)
                compiled = _claim(sig)
        else:
            # cache hook unavailable (jax drift): first-seen abstract
            # signature approximates the jit cache key
            sig = describe_abstract_signature(args, kwargs)
            compiled = _claim(sig)
        if compiled:
            # real compile seconds when the monitoring hook attributed
            # them; the call's walltime (compile-dominated on a miss)
            # otherwise
            seconds = scope.seconds if (listener_ok and scope.seconds > 0) \
                else (t1 - t0)
            post_warmup = rec.record_compile(label, sig, seconds,
                                             start=t0, end=t1)
            if post_warmup:
                rec.note_serving_recompile(label, sig, seconds)
                from predictionio_tpu.obs.trace import active_trace

                trace = active_trace()
                if trace is not None:
                    trace.add_span("xla_compile", t0, t1)
        else:
            # a nested scope that did not itself compile folds its
            # attributed seconds into the enclosing call's scope (they
            # belong to the outer compile in flight)
            if scope.parent is not None and scope.seconds > 0:
                scope.parent.seconds += scope.seconds
        if rec.capture_cost and sig is not None:
            # pricing is lazy and once-per-signature: programs compiled
            # BEFORE the profile window still contribute executed FLOPs
            rec.ensure_priced(
                label, sig,
                lambda: _cost_analysis_flops(jitted, args, kwargs))
            rec.record_call(label, sig)
        return out

    wrapper.__wrapped_jit__ = jitted
    wrapper.lower = jitted.lower
    return wrapper


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def compile_metrics_collector(
        rec: CompileRecorder | None = None) -> Callable[[], Iterable[Metric]]:
    """Scrape-time collector for the sentinel's families. The
    aggregate counters are ALWAYS present (zero-valued on an idle
    server) so dashboards and the worker-merge plane see the families
    before the first compile; the per-function family appears with its
    first sample."""

    def collect() -> list[Metric]:
        r = rec if rec is not None else _GLOBAL_RECORDER
        compiles, seconds, recompiles = r.totals()
        out = [
            Metric(
                name="pio_jit_compile_seconds_total", kind="counter",
                help="Cumulative seconds spent in XLA compilation "
                     "across instrumented jit entry points",
                samples=[({}, seconds)],
            ),
            Metric(
                name="pio_serving_recompile_total", kind="counter",
                help="Jit compiles that fired AFTER serving warmup — "
                     "each one was a live request paying a compile "
                     "(runbook: docs/observability.md)",
                samples=[({}, float(recompiles))],
            ),
        ]
        by_fn = r.compiles_by_fn()
        if by_fn:
            out.append(Metric(
                name="pio_jit_compiles_total", kind="counter",
                help="XLA compiles per instrumented jit entry point",
                samples=[({"fn": fn}, float(n))
                         for fn, n in sorted(by_fn.items())],
            ))
        return out

    return collect
