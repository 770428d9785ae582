"""Observability plane (docs/observability.md): tracing, log-bucketed
histograms, the metric registry, Prometheus text rendering, `GET
/metrics` on both servers, request ids, structured access logs, the
windowed ingest rate, and — the invariant that motivates the whole
layer — torn-free concurrent scrapes under live traffic.

The Prometheus round-trip uses the small in-test parser below: the
exporter's output contract is pinned by parsing it back, not by string
golden-files.
"""

from __future__ import annotations

import contextvars
import http.client
import json
import logging
import re
import threading
import time
import urllib.request

import pytest

from predictionio_tpu.api.event_server import EventServer, EventServerConfig
from predictionio_tpu.api.stats import IngestStats
from predictionio_tpu.obs.histogram import LatencyHistogram
from predictionio_tpu.obs.registry import (
    HistogramFamily,
    Metric,
    MetricRegistry,
)
from predictionio_tpu.obs.exporter import render_prometheus
from predictionio_tpu.obs.trace import (
    Trace,
    TraceLog,
    active_trace,
    span,
    use_trace,
)
from predictionio_tpu.storage.base import AccessKey, App
from predictionio_tpu.utils.testing import memory_storage

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# a small Prometheus text parser — the round-trip half of the exporter
# contract (tests parse what the server exposes; golden strings rot)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>[^ ]+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_UNESCAPE_RE = re.compile(r"\\(.)")


def _unescape(value: str) -> str:
    """Single-pass label-value unescape (text-format 0.0.4). The old
    sequential ``str.replace`` chain re-scanned bytes produced by
    earlier passes, so a value holding a LITERAL backslash before 'n'
    (``a\\nb``) came back with a real newline — pinned by the
    round-trip test with hostile values in test_fleet_obs.py."""
    return _UNESCAPE_RE.sub(
        lambda m: {"n": "\n", '"': '"', "\\": "\\"}.get(
            m.group(1), m.group(1)), value)


def parse_prometheus(text: str) -> dict:
    """{family: {"type": ..., "help": ..., "samples":
    {(sample_name, frozen_labels): float}}} — raises on any line that
    is not HELP/TYPE/sample, which IS the validity assertion."""
    families: dict[str, dict] = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            families.setdefault(name, {"samples": {}})["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram"), line
            families.setdefault(name, {"samples": {}})["type"] = kind
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        labels = tuple(sorted(
            (k, _unescape(v))
            for k, v in _LABEL_RE.findall(m.group("labels") or "")))
        value = float(m.group("value")) if m.group("value") != "NaN" \
            else float("nan")
        sample_name = m.group("name")
        family = sample_name
        for suffix in ("_bucket", "_sum", "_count"):
            if family.endswith(suffix) and family[:-len(suffix)] in families:
                family = family[:-len(suffix)]
                break
        assert family in families, f"sample before HELP/TYPE: {line!r}"
        families[family]["samples"][(sample_name, labels)] = value
    for name, fam in families.items():
        assert "type" in fam and "help" in fam, f"{name}: missing HELP/TYPE"
        if fam["type"] == "counter":
            assert name.endswith("_total"), \
                f"counter {name} must end in _total"
    return families


def check_histogram_consistency(families: dict, name: str) -> None:
    """Per label set: buckets cumulative and monotone, +Inf == _count."""
    fam = families[name]
    assert fam["type"] == "histogram"
    by_labels: dict[tuple, dict[str, float]] = {}
    counts: dict[tuple, float] = {}
    for (sample, labels), value in fam["samples"].items():
        base = tuple(kv for kv in labels if kv[0] != "le")
        if sample == f"{name}_bucket":
            le = dict(labels)["le"]
            by_labels.setdefault(base, {})[le] = value
        elif sample == f"{name}_count":
            counts[base] = value
    assert by_labels, f"{name}: no buckets"
    for base, buckets in by_labels.items():
        assert "+Inf" in buckets, f"{name}{base}: no +Inf bucket"
        finite = sorted(
            ((float(le), v) for le, v in buckets.items() if le != "+Inf"))
        values = [v for _, v in finite] + [buckets["+Inf"]]
        assert values == sorted(values), \
            f"{name}{base}: non-monotone buckets {values}"
        assert buckets["+Inf"] == counts[base], \
            f"{name}{base}: +Inf {buckets['+Inf']} != count {counts[base]}"


# ---------------------------------------------------------------------------
# histogram + registry units
# ---------------------------------------------------------------------------

class TestLatencyHistogram:
    def test_buckets_and_overflow(self):
        h = LatencyHistogram(bounds=(0.001, 0.01, 0.1))
        h.observe(0.0005)     # <= 0.001
        h.observe(0.005)      # <= 0.01
        h.observe_many([0.05, 5.0])   # <= 0.1, overflow
        s = h.snapshot()
        assert s.cumulative == (1, 2, 3, 4)
        assert s.count == 4 and s.cumulative[-1] == 4
        assert abs(s.sum - 5.0555) < 1e-9

    def test_quantiles_saturate_at_top_bound(self):
        h = LatencyHistogram(bounds=(0.001, 0.01))
        for _ in range(99):
            h.observe(0.0005)
        h.observe(10.0)  # overflow
        s = h.snapshot()
        assert s.quantile(0.5) == 0.001
        assert s.quantile(0.999) == 0.01  # saturates, never invents
        assert s.summary_ms()["count"] == 100

    def test_rejects_unsorted_bounds(self):
        with pytest.raises(ValueError):
            LatencyHistogram(bounds=(0.1, 0.01))

    def test_concurrent_observe_loses_nothing(self):
        h = LatencyHistogram()
        n, threads = 2000, 8

        def work():
            for i in range(n):
                h.observe(0.0001 * (i % 50))

        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = h.snapshot()
        assert s.count == n * threads == s.cumulative[-1]


class TestRegistry:
    def test_merge_and_kind_conflict(self):
        reg = MetricRegistry()
        reg.register(lambda: [Metric("pio_x_total", "counter", "x",
                                     samples=[({}, 1.0)])])
        reg.register(lambda: [Metric("pio_x_total", "counter", "x",
                                     samples=[({"a": "b"}, 2.0)])])
        merged = {m.name: m for m in reg.collect()}
        assert len(merged["pio_x_total"].samples) == 2
        reg.register(lambda: [Metric("pio_x_total", "gauge", "x")])
        with pytest.raises(ValueError):
            reg.collect()

    def test_histogram_family_fallback_label(self):
        fam = HistogramFamily("pio_t_seconds", "t", "route", ("a",))
        fam.observe("a", 0.01)
        fam.observe("zzz-unknown", 0.01)   # folds into "other"
        (metric,) = fam.collect()
        labels = {dict(ls)["route"]: snap.count
                  for ls, snap in metric.histograms}
        assert labels == {"a": 1, "other": 1}

    def test_render_round_trip_with_escaping(self):
        reg = MetricRegistry()
        h = LatencyHistogram(bounds=(0.001, 1.0))
        h.observe(0.5)
        reg.register(lambda: [
            Metric("pio_demo_total", "counter", "help with \\ backslash",
                   samples=[({"k": 'va"l\nue'}, 3.0)]),
            Metric("pio_demo_seconds", "histogram", "hist",
                   histograms=[({"route": "q"}, h.snapshot())]),
        ])
        families = parse_prometheus(render_prometheus(reg))
        assert families["pio_demo_total"]["samples"][
            ("pio_demo_total", (("k", 'va"l\nue'),))] == 3.0
        check_histogram_consistency(families, "pio_demo_seconds")


# ---------------------------------------------------------------------------
# tracing units
# ---------------------------------------------------------------------------

class TestTrace:
    def test_ambient_span_noop_without_trace(self):
        assert active_trace() is None
        with span("nothing"):     # the disabled path: shared no-op
            pass

    def test_spans_and_external_intervals(self):
        t = Trace("req", request_id="r1")
        with use_trace(t):
            with span("a"):
                pass
        t.add_span("queue_wait", 1.0, 1.25)   # dispatcher-style record
        t.finish(status=200)
        doc = t.to_dict()
        names = [s["name"] for s in doc["spans"]]
        assert "a" in names and "queue_wait" in names
        qw = next(s for s in doc["spans"] if s["name"] == "queue_wait")
        assert qw["durationMs"] == 250.0
        assert doc["requestId"] == "r1" and doc["tags"] == {"status": 200}

    def test_contextvar_survives_copy_context(self):
        """The deadline-dispatch pool runs queries under
        contextvars.copy_context(); spans opened there must land on
        the caller's trace."""
        t = Trace("req")
        with use_trace(t):
            ctx = contextvars.copy_context()
        result = []

        def work():
            result.append(active_trace())
            with span("pooled"):
                pass

        th = threading.Thread(target=lambda: ctx.run(work))
        th.start()
        th.join()
        assert result == [t]
        assert [s["name"] for s in t.to_dict()["spans"]] == ["pooled"]

    def test_trace_log_is_bounded(self):
        log = TraceLog(maxlen=4)
        for i in range(10):
            tr = Trace(f"t{i}")
            tr.finish()
            log.record(tr)
        snap = log.snapshot()
        assert len(snap) == 4
        assert snap[0]["name"] == "t9"   # newest first


# ---------------------------------------------------------------------------
# ingest windowed rate (the EWMA closed-loop-bias fix)
# ---------------------------------------------------------------------------

class ManualClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestIngestWindowedRate:
    def test_windowed_rate_counts_complete_seconds(self):
        clock = ManualClock()
        stats = IngestStats(clock=clock)
        assert stats.snapshot()["eventsPerSecWindowed"] is None
        for _ in range(5):
            stats.record_batch(10)      # 50 events in second 1000
        clock.t = 1001.5
        stats.record_batch(30)          # partial second 1001 (excluded)
        snap = stats.snapshot()
        # window = [1000, 1001): 50 events over 1 complete second
        assert snap["eventsPerSecWindowed"] == 50.0
        assert snap["windowSeconds"] == 1
        clock.t = 1004.0
        snap = stats.snapshot()
        # window = [1000, 1004): 80 events over 4 seconds
        assert snap["eventsPerSecWindowed"] == 20.0

    def test_stale_buckets_age_out(self):
        clock = ManualClock()
        stats = IngestStats(clock=clock)
        stats.record_batch(1000)
        clock.t += 200.0                # far past WINDOW_SECONDS
        stats.record_batch(59)
        clock.t += 1.0
        snap = stats.snapshot()
        # only the recent second is in the window; the old burst aged out
        assert snap["eventsPerSecWindowed"] == pytest.approx(1.0)
        # ...while the EWMA still carries closed-loop history
        assert snap["events"] == 1059

    def test_windowed_rate_is_not_issue_rate_biased(self):
        """The documented EWMA caveat: a closed-loop generator that
        pauses between bursts drags the EWMA; the ring reports what
        actually landed per wall second."""
        clock = ManualClock()
        stats = IngestStats(clock=clock)
        for _ in range(10):
            stats.record_batch(100)     # burst: 1000 events in 1s
            clock.t += 0.1
        clock.t += 1.0                  # generator think-time
        ewma = stats.snapshot()["eventsPerSecEwma"]
        windowed = stats.snapshot()["eventsPerSecWindowed"]
        assert windowed == pytest.approx(500.0)   # 1000 over 2 seconds
        assert ewma == pytest.approx(1000.0, rel=0.2)


# ---------------------------------------------------------------------------
# servers end to end
# ---------------------------------------------------------------------------

EVENT = {"event": "rate", "entityType": "user", "entityId": "u1",
         "targetEntityType": "item", "targetEntityId": "i1",
         "properties": {"rating": 5}}


@pytest.fixture
def event_server():
    storage = memory_storage()
    app_id = storage.get_meta_data_apps().insert(App(0, "obsapp"))
    storage.get_meta_data_access_keys().insert(AccessKey("k", app_id, ()))
    storage.get_events().init(app_id)
    srv = EventServer(storage, EventServerConfig(
        ip="127.0.0.1", port=0, stats=True, tracing=True, access_log=True))
    srv.start()
    yield srv
    srv.stop()


def _http(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=20)
    payload = json.dumps(body) if body is not None else None
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    conn.request(method, path, body=payload, headers=hdrs)
    resp = conn.getresponse()
    raw = resp.read()
    out = (resp.status, raw, dict(resp.getheaders()))
    conn.close()
    return out


class TestEventServerObservability:
    def test_metrics_exposes_ingest_and_resilience(self, event_server):
        port = event_server.port
        assert _http(port, "POST", "/events.json?accessKey=k", EVENT)[0] == 201
        assert _http(port, "POST", "/batch/events.json?accessKey=k",
                     [EVENT, EVENT])[0] == 200
        status, raw, headers = _http(port, "GET", "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        families = parse_prometheus(raw.decode())
        samples = families["pio_ingest_events_total"]["samples"]
        assert samples[("pio_ingest_events_total", ())] == 3.0
        assert families["pio_ingest_batches_total"]["samples"][
            ("pio_ingest_batches_total", ())] == 2.0
        check_histogram_consistency(families, "pio_ingest_batch_size")
        check_histogram_consistency(families, "pio_ingest_insert_seconds")
        check_histogram_consistency(families, "pio_http_request_seconds")
        assert ("pio_server_info",
                (("server", "event"),
                 ("version", __import__("predictionio_tpu").__version__))
                ) in families["pio_server_info"]["samples"]

    def test_ingest_traces_split_validate_from_insert(self, event_server):
        port = event_server.port
        _http(port, "POST", "/batch/events.json?accessKey=k", [EVENT])
        # traces carry per-request data (unlike the aggregate-only
        # /metrics) — the accessKey auth every event route uses applies
        assert _http(port, "GET", "/traces.json")[0] == 401
        status, raw, _ = _http(port, "GET", "/traces.json?accessKey=k")
        assert status == 200
        doc = json.loads(raw)
        assert doc["tracing"] is True
        batch = next(t for t in doc["traces"]
                     if t["name"] == "batch/events.json")
        names = [s["name"] for s in batch["spans"]]
        assert names == ["parse", "validate", "insert_batch"]

    def test_request_id_echoed_and_propagated(self, event_server):
        port = event_server.port
        # inbound well-formed id is echoed verbatim
        _, _, headers = _http(port, "GET", "/",
                              headers={"X-PIO-Request-Id": "corr-42"})
        assert headers["X-PIO-Request-Id"] == "corr-42"
        # malformed id is replaced, not propagated (log injection)
        _, _, headers = _http(port, "GET", "/",
                              headers={"X-PIO-Request-Id": 'bad id "x"'})
        rid = headers["X-PIO-Request-Id"]
        assert rid != 'bad id "x"' and re.match(r"^[0-9a-f]{16}$", rid)

    def test_structured_access_log(self, event_server):
        # capture on the pio.access logger directly: the lazily
        # attached default handler may have turned propagation off, so
        # caplog's root-logger capture is not guaranteed to see it
        captured: list[logging.LogRecord] = []

        class Capture(logging.Handler):
            def emit(self, record):
                captured.append(record)

        handler = Capture(level=logging.INFO)
        access = logging.getLogger("pio.access")
        access.addHandler(handler)
        try:
            _http(event_server.port, "POST", "/events.json?accessKey=k",
                  EVENT, headers={"X-PIO-Request-Id": "log-me"})
            # the access line is emitted AFTER the response is written:
            # the client can observe the 201 before the handler thread
            # reaches the logger (reliably so on a 1-core host), so
            # poll with a deadline instead of racing the removeHandler
            deadline = time.monotonic() + 10.0
            entry = None
            while entry is None and time.monotonic() < deadline:
                records = [json.loads(r.getMessage()) for r in list(captured)]
                entry = next(
                    (r for r in records if r["request_id"] == "log-me"),
                    None)
                if entry is None:
                    time.sleep(0.02)
        finally:
            access.removeHandler(handler)
        assert entry is not None, "access-log line never emitted"
        assert entry["method"] == "POST"
        assert entry["path"] == "/events.json"
        assert entry["status"] == 201
        assert entry["latency_ms"] > 0
        assert entry["server"] == "event"

    def test_stats_json_carries_windowed_rate_fields(self, event_server):
        port = event_server.port
        _http(port, "POST", "/events.json?accessKey=k", EVENT)
        status, raw, _ = _http(port, "GET", "/stats.json?accessKey=k")
        assert status == 200
        ingest = json.loads(raw)["ingest"]
        assert "eventsPerSecWindowed" in ingest
        assert "windowSeconds" in ingest
        assert ingest["insertLatency"]["count"] == 1


@pytest.fixture
def engine_server(storage):
    from predictionio_tpu.api.engine_server import create_engine_server
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.workflow.deploy import ServerConfig
    from predictionio_tpu.workflow.train import run_train

    from tests.sample_engine import AlgoParams, DSParams

    params = EngineParams.of(
        data_source=DSParams(id=7, n_train=5),
        algorithms=[("sample", AlgoParams(id=0, mult=2))])
    run_train(engine_factory="tests.sample_engine.engine_factory",
              engine_params=params, variant={"id": "sample-engine"},
              storage=storage)
    server = create_engine_server(storage=storage, config=ServerConfig(
        ip="127.0.0.1", port=0, batching=True, batch_max=8,
        batch_wait_ms=5.0, cache_enabled=True, tracing=True))
    server.start()
    yield server
    server.stop()


def _post_query(port, payload, headers=None):
    return _http(port, "POST", "/queries.json", payload, headers)


class TestEngineServerObservability:
    def test_metrics_exposes_serving_counters_and_histograms(
            self, engine_server):
        port = engine_server.port
        for i in range(4):
            assert _post_query(port, {"x": i})[0] == 200
        status, raw, headers = _http(port, "GET", "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        families = parse_prometheus(raw.decode())
        get = lambda n: families[n]["samples"][(n, ())]  # noqa: E731
        assert get("pio_serving_dispatches_total") >= 1
        assert get("pio_serving_batched_queries_total") == 4
        for name in ("pio_serving_batch_size",
                     "pio_serving_queue_wait_seconds",
                     "pio_serving_device_dispatch_seconds",
                     "pio_http_request_seconds"):
            check_histogram_consistency(families, name)
        # queue-wait and device-dispatch were actually fed
        assert families["pio_serving_queue_wait_seconds"]["samples"][
            ("pio_serving_queue_wait_seconds_count", ())] == 4

    def test_query_trace_splits_queue_wait_from_device_time(
            self, engine_server):
        """The acceptance-criterion trace: one /queries.json trace
        carries distinct queue-wait and device-dispatch spans."""
        port = engine_server.port
        status, _, headers = _post_query(
            port, {"x": 41}, headers={"X-PIO-Request-Id": "trace-me"})
        assert status == 200
        assert headers["X-PIO-Request-Id"] == "trace-me"
        trace_id = headers["X-PIO-Trace-Id"]
        _, raw, _ = _http(port, "GET", "/traces.json")
        doc = json.loads(raw)
        trace = next(t for t in doc["traces"] if t["traceId"] == trace_id)
        assert trace["requestId"] == "trace-me"
        assert trace["tags"]["status"] == 200
        spans = {s["name"]: s for s in trace["spans"]}
        for name in ("parse", "bind", "codec_key", "cache_lookup",
                     "batcher.queue_wait", "batcher.device_dispatch",
                     "encode"):
            assert name in spans, f"missing span {name}: {sorted(spans)}"
        qw, dd = spans["batcher.queue_wait"], spans["batcher.device_dispatch"]
        # the split: wait ends where the dispatch starts, both measured
        assert qw["startMs"] < dd["startMs"]
        assert qw["startMs"] + qw["durationMs"] == pytest.approx(
            dd["startMs"], abs=0.5)
        assert trace["durationMs"] >= dd["durationMs"]

    def test_cache_hit_trace_has_no_dispatch_span(self, engine_server):
        port = engine_server.port
        assert _post_query(port, {"x": 7})[0] == 200
        status, _, headers = _post_query(port, {"x": 7})   # cache hit
        assert status == 200
        _, raw, _ = _http(port, "GET", "/traces.json")
        doc = json.loads(raw)
        hit = next(t for t in doc["traces"]
                   if t["traceId"] == headers["X-PIO-Trace-Id"])
        names = [s["name"] for s in hit["spans"]]
        assert "cache_lookup" in names
        assert "batcher.device_dispatch" not in names

    def test_tracing_disabled_emits_nothing(self, storage):
        from predictionio_tpu.api.engine_server import create_engine_server
        from predictionio_tpu.controller import EngineParams
        from predictionio_tpu.workflow.deploy import ServerConfig
        from predictionio_tpu.workflow.train import run_train

        from tests.sample_engine import AlgoParams, DSParams

        run_train(
            engine_factory="tests.sample_engine.engine_factory",
            engine_params=EngineParams.of(
                data_source=DSParams(id=7, n_train=5),
                algorithms=[("sample", AlgoParams(id=0, mult=2))]),
            variant={"id": "sample-engine"}, storage=storage)
        server = create_engine_server(storage=storage, config=ServerConfig(
            ip="127.0.0.1", port=0, tracing=False))
        server.start()
        try:
            port = server.port
            status, _, headers = _post_query(port, {"x": 1})
            assert status == 200
            assert "X-PIO-Trace-Id" not in headers
            assert "X-PIO-Request-Id" in headers
            _, raw, _ = _http(port, "GET", "/traces.json")
            doc = json.loads(raw)
            assert doc == {"tracing": False, "traces": []}
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# the dispatch split (PR 23): phases of batcher.device_dispatch recorded
# where the work happens, through the REAL recommendation template
# ---------------------------------------------------------------------------

def _end(span_doc):
    return span_doc["startMs"] + span_doc["durationMs"]


class TestDispatchSpans:
    #: /traces.json rounds to the microsecond
    TOL_MS = 0.01

    def test_traced_query_carries_the_dispatch_phases(
            self, storage, tmp_path, monkeypatch):
        from tests.rec_engine import (DISPATCH_PHASES, post_query,
                                      start_rec_server, trace_of, train_rec)

        train_rec(storage, tmp_path, monkeypatch)
        server = start_rec_server(storage, tracing=True)
        try:
            status, body, headers = post_query(
                server.port, {"user": "u1", "num": 3})
            assert status == 200 and len(body["itemScores"]) == 3
            spans = trace_of(server.port, headers["X-PIO-Trace-Id"])["spans"]
        finally:
            server.stop()
        by_name = {s["name"]: s for s in spans}
        assert len(by_name) == len(spans), "a span was recorded twice"
        dd = by_name["batcher.device_dispatch"]
        # the six phases: children of THIS request's dispatch span, in
        # the order they ran, each inside the parent's interval
        phases = [by_name[name] for name in DISPATCH_PHASES]
        assert [s["name"] for s in spans
                if s["name"].startswith("dispatch.")
                and s["name"] != "dispatch.copy_start"] == list(DISPATCH_PHASES)
        for before, after in zip(phases, phases[1:]):
            assert _end(before) <= after["startMs"] + self.TOL_MS
        # dispatch.copy_start: no seventh phase, it lies inside enqueue
        # (the results' copies to the host start right behind the launch)
        copy_start, enqueue = by_name["dispatch.copy_start"], \
            by_name["dispatch.enqueue"]
        assert copy_start["startMs"] >= enqueue["startMs"] - self.TOL_MS
        assert _end(copy_start) <= _end(enqueue) + self.TOL_MS
        for s in phases + [copy_start]:
            assert s["parentId"] == dd["spanId"]
            assert s["startMs"] >= dd["startMs"] - self.TOL_MS
            assert _end(s) <= _end(dd) + self.TOL_MS
        # batcher.hold: the tail of the queue wait, ending where it ends
        qw, hold = by_name["batcher.queue_wait"], by_name["batcher.hold"]
        assert hold["parentId"] == qw["spanId"]
        assert hold["startMs"] >= qw["startMs"] - self.TOL_MS
        assert _end(hold) == pytest.approx(_end(qw), abs=self.TOL_MS)
        assert _end(qw) == pytest.approx(dd["startMs"], abs=self.TOL_MS)
        # batcher.wake: from the dispatch's end to the handler running
        wake = by_name["batcher.wake"]
        assert wake["startMs"] == pytest.approx(_end(dd), abs=self.TOL_MS)
        assert _end(wake) <= by_name["encode"]["startMs"] + self.TOL_MS
        # respond: after encode, the last work a traced query records;
        # behind it only the flush (appended once the handler has made
        # it: tests/test_request_root_span.py holds that side)
        assert by_name["respond"]["startMs"] >= _end(by_name["encode"]) \
            - self.TOL_MS
        assert [s["name"] for s in spans
                if s["startMs"] > by_name["respond"]["startMs"]] in (
            [], ["request.flush"])

    def test_tracing_off_binds_nothing_and_adds_no_device_sync(
            self, storage, tmp_path, monkeypatch):
        """With ``ServerConfig.tracing`` false the dispatcher allocates
        no Trace, ``batch_predict`` makes no ``block_until_ready`` call
        (the path is the untraced one, to the sync), and /traces.json
        stays empty."""
        import jax

        from predictionio_tpu.serving import batcher as batcher_mod
        from tests.rec_engine import post_query, start_rec_server, train_rec

        train_rec(storage, tmp_path, monkeypatch)
        calls = []
        real_block = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (calls.append("block_until_ready"), real_block(x))[1])
        monkeypatch.setattr(
            batcher_mod, "Trace",
            lambda *a, **k: calls.append("Trace") or Trace(*a, **k))
        server = start_rec_server(storage, tracing=False)
        try:
            for u in ("u1", "u2", "nobody"):
                status, _, headers = post_query(
                    server.port, {"user": u, "num": 3})
                assert status == 200
                assert "X-PIO-Trace-Id" not in headers
            assert server.service.serving_stats.count("dispatches") >= 1
            _, raw, _ = _http(server.port, "GET", "/traces.json")
            assert json.loads(raw) == {"tracing": False, "traces": []}
        finally:
            server.stop()
        assert calls == []
        # the spies do see the traced path (the test would otherwise
        # pass on a renamed call)
        server = start_rec_server(storage, tracing=True)
        try:
            assert post_query(server.port, {"user": "u1", "num": 3})[0] == 200
        finally:
            server.stop()
        assert sorted(set(calls)) == ["Trace", "block_until_ready"]


# ---------------------------------------------------------------------------
# the concurrency hammer: scrapes must never tear under live traffic
# ---------------------------------------------------------------------------

class TestConcurrentScrapes:
    SCRAPES = 25

    def test_metrics_and_stats_under_live_traffic(self, engine_server):
        """Hammer /metrics and /stats.json from threads while query
        traffic flows: every exposition parses, histograms stay
        internally consistent, counters are monotone scrape-over-scrape."""
        port = engine_server.port
        stop = threading.Event()
        errors: list[BaseException] = []

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    st, _, _ = _post_query(port, {"x": i % 16})
                    assert st == 200
                except Exception as e:   # noqa: BLE001
                    errors.append(e)
                    return
                i += 1

        monotone_keys = [
            ("pio_serving_batched_queries_total",
             "pio_serving_batched_queries_total", ()),
            ("pio_serving_dispatches_total",
             "pio_serving_dispatches_total", ()),
        ]

        def scraper():
            last: dict = {}
            try:
                for _ in range(self.SCRAPES):
                    st, raw, _ = _http(port, "GET", "/metrics")
                    assert st == 200
                    families = parse_prometheus(raw.decode())
                    for name in ("pio_serving_queue_wait_seconds",
                                 "pio_serving_device_dispatch_seconds",
                                 "pio_serving_batch_size",
                                 "pio_http_request_seconds"):
                        check_histogram_consistency(families, name)
                    for fam, sample, labels in monotone_keys:
                        value = families[fam]["samples"][(sample, labels)]
                        key = (sample, labels)
                        assert value >= last.get(key, 0.0), \
                            f"counter {key} went backwards"
                        last[key] = value
            except BaseException as e:   # noqa: BLE001
                errors.append(e)

        def stats_reader():
            try:
                for _ in range(self.SCRAPES):
                    st, raw, _ = _http(port, "GET", "/stats.json")
                    assert st == 200
                    doc = json.loads(raw)
                    serving = doc["serving"]
                    # torn-snapshot guard: the histogram summary's
                    # count can never exceed the queries that entered
                    hist_total = sum(
                        int(v) * int(k)
                        for k, v in serving["batchSizeHistogram"].items())
                    assert hist_total <= serving["batchedQueries"] \
                        + serving["deduped"] + serving["expired"] + 1
            except BaseException as e:   # noqa: BLE001
                errors.append(e)

        workers = [threading.Thread(target=traffic) for _ in range(4)]
        readers = ([threading.Thread(target=scraper) for _ in range(2)]
                   + [threading.Thread(target=stats_reader)])
        for t in workers + readers:
            t.start()
        for t in readers:
            t.join(timeout=120)
        stop.set()
        for t in workers:
            t.join(timeout=30)
        assert not errors, errors[0]

    def test_event_server_scrapes_under_ingest(self, event_server):
        port = event_server.port
        stop = threading.Event()
        errors: list[BaseException] = []

        def traffic():
            while not stop.is_set():
                try:
                    st, _, _ = _http(
                        port, "POST", "/batch/events.json?accessKey=k",
                        [EVENT] * 5)
                    assert st == 200
                except Exception as e:   # noqa: BLE001
                    errors.append(e)
                    return

        def scraper():
            last = 0.0
            try:
                for _ in range(self.SCRAPES):
                    st, raw, _ = _http(port, "GET", "/metrics")
                    assert st == 200
                    families = parse_prometheus(raw.decode())
                    check_histogram_consistency(
                        families, "pio_ingest_batch_size")
                    check_histogram_consistency(
                        families, "pio_ingest_insert_seconds")
                    events = families["pio_ingest_events_total"]["samples"][
                        ("pio_ingest_events_total", ())]
                    assert events >= last, "events_total went backwards"
                    last = events
            except BaseException as e:   # noqa: BLE001
                errors.append(e)

        workers = [threading.Thread(target=traffic) for _ in range(3)]
        readers = [threading.Thread(target=scraper) for _ in range(2)]
        for t in workers + readers:
            t.start()
        for t in readers:
            t.join(timeout=120)
        stop.set()
        for t in workers:
            t.join(timeout=30)
        assert not errors, errors[0]


# ---------------------------------------------------------------------------
# train stage breakdown + dashboard scrape + lint scope
# ---------------------------------------------------------------------------

def test_train_outcome_carries_stage_seconds(storage):
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.workflow.train import format_stage_times, run_train

    from tests.sample_engine import AlgoParams, DSParams

    params = EngineParams.of(
        data_source=DSParams(id=7, n_train=5),
        algorithms=[("sample", AlgoParams(id=0, mult=3))])
    outcome = run_train(
        engine_factory="tests.sample_engine.engine_factory",
        engine_params=params, variant={"id": "sample-engine"},
        storage=storage)
    assert outcome.status == "COMPLETED"
    assert set(outcome.stage_seconds) == {"read", "prepare", "train",
                                          "persist"}
    assert all(v >= 0 for v in outcome.stage_seconds.values())
    line = format_stage_times(outcome.stage_seconds)
    assert "read" in line and "persist" in line and "s" in line


def test_dashboard_metrics_scrape(storage):
    from predictionio_tpu.tools.dashboard import Dashboard

    dash = Dashboard(storage, ip="127.0.0.1", port=0)
    dash.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/metrics", timeout=10) as r:
            families = parse_prometheus(r.read().decode())
        assert ("pio_server_info",
                (("server", "dashboard"),
                 ("version", __import__("predictionio_tpu").__version__))
                ) in families["pio_server_info"]["samples"]
        check_histogram_consistency(families, "pio_http_request_seconds")
    finally:
        dash.stop()


def test_obs_is_in_lint_scope():
    """Satellite contract: the new subsystem is patrolled by the
    hot-path and resilience-bypass rules (analysis/config.py)."""
    from predictionio_tpu.analysis.config import HOT_PATHS, default_config

    assert "obs/" in HOT_PATHS
    policy = default_config()
    assert "obs/" in policy.rules["resilience-bypass"].paths
