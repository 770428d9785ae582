"""Serving micro-batcher (ServerConfig.batching): concurrent queries
coalesce into one batch_predict dispatch — the TPU-first answer to
per-query dispatch RTT (QueryBatcher docstring; beyond reference)."""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.api.engine_server import create_engine_server
from predictionio_tpu.workflow.deploy import ServerConfig
from predictionio_tpu.workflow.train import run_train

from tests.sample_engine import AlgoParams, DSParams


def _train(storage, mult=2):
    from predictionio_tpu.controller import EngineParams

    params = EngineParams.of(
        data_source=DSParams(id=7, n_train=5),
        algorithms=[("sample", AlgoParams(id=0, mult=mult))],
    )
    return run_train(
        engine_factory="tests.sample_engine.engine_factory",
        engine_params=params,
        variant={"id": "sample-engine"},
        storage=storage,
    )


@pytest.fixture
def batching_server(storage):
    _train(storage, mult=2)
    server = create_engine_server(
        storage=storage,
        config=ServerConfig(ip="127.0.0.1", port=0, batching=True,
                            batch_max=32, batch_wait_ms=60.0),
    )
    server.start()
    yield server
    server.stop()


def _post(port, payload, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def _concurrent_posts(port, payloads):
    """Fire all payloads at once; returns results in payload order."""
    results = [None] * len(payloads)
    barrier = threading.Barrier(len(payloads))

    def go(i):
        barrier.wait()
        try:
            results[i] = _post(port, payloads[i])
        except urllib.error.HTTPError as e:
            results[i] = (e.code, json.loads(e.read()))

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    return results


class TestQueryBatching:
    def test_concurrent_queries_coalesce_and_answer_correctly(
            self, dedup_server):
        # fixed-window fixture: the assertion is about deterministic
        # coalescing, which the adaptive policy intentionally does not
        # guarantee (a fast dispatcher may outrun staggered arrivals
        # and serve singles at zero added latency)
        server = dedup_server
        n = 12
        results = _concurrent_posts(
            server.port, [{"x": i} for i in range(n)])
        for i, (status, body) in enumerate(results):
            assert status == 200
            assert body["value"] == 2 * i, (i, body)   # mult=2, per query
        # the status page proves coalescing happened: fewer dispatches
        # than queries
        doc = server.service.status_doc()
        b = doc["batching"]
        assert b["batchedQueries"] == n
        assert 1 <= b["batches"] < n
        assert doc["requestCount"] == n

    def test_single_query_still_served(self, batching_server):
        status, body = _post(batching_server.port, {"x": 5})
        assert status == 200 and body["value"] == 10

    def test_poisoned_query_fails_alone(self, batching_server, monkeypatch):
        """A query that raises inside predict must 500 by itself — the
        batch retries individually (QueryBatcher._finish)."""
        server = batching_server
        algo = server.service.deployed.algorithms[0]
        orig = algo.predict

        def poisoned(model, query):
            if query.x == 13:
                raise RuntimeError("poisoned query")
            return orig(model, query)

        monkeypatch.setattr(algo, "predict", poisoned)
        results = _concurrent_posts(
            server.port, [{"x": x} for x in (11, 12, 13, 14)])
        by_x = dict(zip((11, 12, 13, 14), results))
        assert by_x[13][0] == 500
        for x in (11, 12, 14):
            assert by_x[x] == (200, {"value": 2 * x,
                                     "tags": ["algo0", "served"]}), x

    def test_reload_applies_to_next_batch(self, batching_server, storage):
        server = batching_server
        _, body = _post(server.port, {"x": 3})
        assert body["value"] == 6                       # mult=2
        _train(storage, mult=10)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/reload", timeout=10):
            pass
        _, body = _post(server.port, {"x": 3})
        assert body["value"] == 30                      # mult=10

    def test_stop_closes_batcher(self, storage):
        _train(storage, mult=2)
        server = create_engine_server(
            storage=storage,
            config=ServerConfig(ip="127.0.0.1", port=0, batching=True))
        server.start()
        server.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            server.service.batcher.submit(object())


@pytest.fixture
def dedup_server(storage):
    """Fixed 100ms window so a barrier-fired burst coalesces into one
    batch deterministically — the dedup observation point."""
    _train(storage, mult=2)
    server = create_engine_server(
        storage=storage,
        config=ServerConfig(ip="127.0.0.1", port=0, batching=True,
                            batch_policy="fixed", batch_max=32,
                            batch_wait_ms=100.0))
    server.start()
    yield server
    server.stop()


@pytest.fixture
def caching_server(storage):
    _train(storage, mult=2)
    server = create_engine_server(
        storage=storage,
        config=ServerConfig(ip="127.0.0.1", port=0, batching=True,
                            batch_max=16, batch_wait_ms=40.0,
                            cache_enabled=True, cache_ttl_s=300.0))
    server.start()
    yield server
    server.stop()


class TestDedupAndStats:
    def test_identical_concurrent_queries_dedup(self, dedup_server):
        """K threads posting the SAME query produce >=1 batch where the
        dedup pass folded them into fewer device slots (ISSUE 3)."""
        server = dedup_server
        n = 8
        results = _concurrent_posts(server.port, [{"x": 5}] * n)
        for status, body in results:
            assert status == 200
            assert body["value"] == 10
        stats = _get(server.port, "/stats.json")
        serving = stats["serving"]
        assert serving["deduped"] >= 1
        # every deduped query was answered without its own device slot
        dispatched = sum(int(k) * v
                         for k, v in serving["batchSizeHistogram"].items())
        assert dispatched == serving["batchedQueries"] - serving["deduped"]
        assert serving["batchedQueries"] == n
        # deduped waiters still count as served requests (the same
        # bookkeeping invariant cache hits carry)
        assert stats["requestCount"] == n

    def test_stats_json_exposes_batcher_internals(self, batching_server):
        server = batching_server
        _concurrent_posts(server.port, [{"x": i} for i in range(6)])
        stats = _get(server.port, "/stats.json")
        assert stats["batching"]["enabled"] is True
        assert "ewmaInterarrivalMs" in stats["batching"]
        serving = stats["serving"]
        assert serving["dispatches"] >= 1
        assert serving["batchedQueries"] == 6
        assert sum(serving["batchSizeHistogram"].values()) \
            == serving["dispatches"]
        assert stats["cache"] == {"enabled": False}

    def test_status_page_carries_policy_snapshot(self, batching_server):
        doc = batching_server.service.status_doc()
        assert doc["batching"]["policy"] == "AdaptiveBatchPolicy"

    def test_chunked_request_gets_411_and_close(self, batching_server):
        """HTTP/1.1 keep-alive + an undecoded chunked body would desync
        every later request on the socket — the server must 411 and
        close instead (RFC 9112 §6.3).

        Raw socket, ONE write: http.client streams chunked bodies, and
        the server 411s + closes after the HEADERS — a mid-stream chunk
        write then races the close and intermittently dies on
        ECONNRESET before getresponse() ever runs (flaky on 1-core
        hosts, where the server wins the race reliably). Sending the
        complete request in a single send and reading to EOF removes
        the race: there is nothing left to write when the close
        lands."""
        import socket

        request = (
            b"POST /queries.json HTTP/1.1\r\n"
            b"Host: x\r\n"
            b"Content-Type: application/json\r\n"
            b"Transfer-Encoding: chunked\r\n"
            b"\r\n"
            b"8\r\n"
            b'{"x": 1}\r\n'
            b"0\r\n\r\n"
        )
        with socket.create_connection(
                ("127.0.0.1", batching_server.port), timeout=10) as s:
            s.sendall(request)
            data = b""
            try:
                while b"\r\n\r\n" not in data:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    data += chunk
            except ConnectionResetError:
                # the server closes with our (never-read) chunk bytes
                # still buffered, so its stack may RST; whatever
                # arrived before the reset IS the response — the
                # header assertions below decide
                pass
        status_line, _, rest = data.partition(b"\r\n")
        assert status_line.startswith(b"HTTP/1.1 411"), data[:80]
        headers = rest.split(b"\r\n\r\n", 1)[0].lower()
        # the desync guard: the connection must not be reused
        assert b"connection: close" in headers, headers

    def test_handler_has_idle_read_timeout(self):
        """Keep-alive without a read timeout would pin one handler
        thread per idle client connection for the process lifetime."""
        from predictionio_tpu.api.engine_server import _Handler

        assert _Handler.protocol_version == "HTTP/1.1"
        assert isinstance(_Handler.timeout, (int, float))
        assert 0 < _Handler.timeout <= 120

    def test_malformed_content_length_gets_400_and_close(
            self, batching_server):
        """int() failures and negative lengths cannot be drained — the
        server must 400 and close rather than crash the handler or
        block in read(-1) until the idle timeout."""
        import socket

        for bad in (b"abc", b"-1"):
            with socket.create_connection(
                    ("127.0.0.1", batching_server.port), timeout=10) as s:
                s.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
                          b"Content-Length: " + bad + b"\r\n\r\n")
                data = s.recv(65536)
                assert data.startswith(b"HTTP/1.1 400"), (bad, data[:40])

    def test_get_with_body_drained_on_keepalive(self, batching_server):
        """A Content-Length body on a non-POST must be drained, or the
        leftover bytes desync the next request on the same socket."""
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", batching_server.port, timeout=10)
        try:
            conn.request("GET", "/healthz", b"xxxxx")   # body on a GET
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            # next request on the SAME socket must parse cleanly
            conn.request("POST", "/queries.json",
                         json.dumps({"x": 4}).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["value"] == 8
        finally:
            conn.close()

    def test_keepalive_serves_sequential_requests(self, batching_server):
        """One connection, several requests — the HTTP/1.1 fast path."""
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", batching_server.port, timeout=10)
        try:
            for x in (1, 2, 3):
                conn.request("POST", "/queries.json",
                             json.dumps({"x": x}).encode(),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200 and body["value"] == 2 * x
        finally:
            conn.close()


class TestResultCacheHTTP:
    def test_repeat_query_hits_cache(self, caching_server):
        server = caching_server
        for _ in range(3):
            status, body = _post(server.port, {"x": 4})
            assert status == 200 and body["value"] == 8
        stats = _get(server.port, "/stats.json")
        assert stats["cache"]["enabled"] is True
        assert stats["serving"]["cacheHits"] >= 2
        assert stats["serving"]["cacheHitRatio"] > 0
        # hits still count as answered queries — a hot cache must not
        # make the server look idle on the status page
        assert stats["requestCount"] == 3

    def test_reload_invalidates_cache(self, caching_server, storage):
        """A cached prediction must die with the model that computed it
        — /reload swaps the instance AND clears the cache atomically."""
        server = caching_server
        _, body = _post(server.port, {"x": 3})
        assert body["value"] == 6                       # mult=2, now cached
        _, body = _post(server.port, {"x": 3})
        assert body["value"] == 6                       # served from cache
        _train(storage, mult=10)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/reload", timeout=10):
            pass
        _, body = _post(server.port, {"x": 3})
        assert body["value"] == 30                      # NOT the stale 6
        stats = _get(server.port, "/stats.json")
        assert stats["serving"]["cacheInvalidations"] == 1


class TestCoalescedDispatchSpans:
    def test_each_coalesced_query_gets_its_own_copy_of_the_phases(
            self, storage, tmp_path, monkeypatch):
        """Two traced queries in ONE dispatch: the dispatcher records
        the phases once, on its own per-dispatch trace, and copies them
        onto each request's trace under that request's own
        batcher.device_dispatch span (per-query weighting, the
        convention batcher.queue_wait already follows)."""
        from tests.rec_engine import (DISPATCH_PHASES, post_query,
                                      start_rec_server, trace_of, train_rec)

        train_rec(storage, tmp_path, monkeypatch)
        # a fixed 300 ms door: a barrier-fired pair always coalesces
        server = start_rec_server(storage, tracing=True, batch_policy="fixed",
                                  batch_max=8, batch_wait_ms=300.0)
        try:
            port = server.port
            assert _post(port, {"user": "u3", "num": 2})[0] == 200  # compile
            before = server.service.serving_stats.count("dispatches")
            users = ["u1", "u2"]
            trace_ids: dict[str, str] = {}
            barrier = threading.Barrier(len(users))

            def go(user):
                barrier.wait()
                status, _, headers = post_query(port,
                                                {"user": user, "num": 2})
                assert status == 200
                trace_ids[user] = headers["X-PIO-Trace-Id"]

            threads = [threading.Thread(target=go, args=(u,)) for u in users]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert server.service.serving_stats.count("dispatches") \
                == before + 1
            traces = [trace_of(port, trace_ids[u]) for u in users]
        finally:
            server.stop()
        absolute = []
        for trace in traces:
            spans = {s["name"]: s for s in trace["spans"]}
            dd = spans["batcher.device_dispatch"]
            for name in DISPATCH_PHASES:
                assert spans[name]["parentId"] == dd["spanId"]
            for name in ("batcher.hold", "batcher.wake", "respond"):
                assert name in spans
            absolute.append(
                [trace["startTime"] * 1e3 + spans[n]["startMs"]
                 for n in DISPATCH_PHASES])
        # own copies (distinct span ids) of the SAME intervals
        ids = [{s["spanId"] for s in t["spans"]} for t in traces]
        assert not ids[0] & ids[1]
        assert absolute[0] == pytest.approx(absolute[1], abs=1.0)
