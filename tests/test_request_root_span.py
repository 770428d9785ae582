"""A traced query's root span and its two edges (PR 36), over a real
socket: ``request`` from the request line in hand to the response
flushed, ``request.read`` and ``request.flush`` at its ends, the trace
in the ring when the client has its answer and completed once the
handler is back at its read. With tracing off none of it runs."""

from __future__ import annotations

import json
import socket
import sys
import time

import pytest

from predictionio_tpu.api.engine_server import _Handler, create_engine_server
from predictionio_tpu.workflow.deploy import ServerConfig

from tests.test_query_batching import _train

#: /traces.json rounds to the microsecond
TOL_MS = 0.002


@pytest.fixture
def server_of(storage):
    _train(storage, mult=2)
    started = []

    def make(tracing: bool):
        server = create_engine_server(storage=storage, config=ServerConfig(
            ip="127.0.0.1", port=0, batching=True, tracing=tracing))
        server.start()
        started.append(server)
        return server

    yield make
    for server in started:
        server.stop()


def _request(body: bytes, extra: bytes = b"") -> bytes:
    return (b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n" + extra
            + b"Content-Length: " + str(len(body)).encode()
            + b"\r\n\r\n" + body)


def _response(sock, buf: bytearray):
    """(status, headers, body) of the next response on a keep-alive
    connection."""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            break
        buf += chunk
    head, _, rest = bytes(buf).partition(b"\r\n\r\n")
    lines = head.split(b"\r\n")
    headers = {k.strip().lower(): v.strip() for k, v in
               (line.decode().split(":", 1) for line in lines[1:])}
    need = int(headers.get("content-length", 0))
    while len(rest) < need:
        chunk = sock.recv(65536)
        if not chunk:
            break
        rest += chunk
    del buf[:]
    buf += rest[need:]
    return int(lines[0].split()[1]), headers, rest[:need]


def _ring(server) -> list:
    with server.service.trace_log._lock:
        return list(server.service.trace_log._ring)


def _doc(server, trace_id: str) -> dict:
    return next(t.to_dict() for t in _ring(server) if t.trace_id == trace_id)


def test_root_span_and_its_edges_over_a_keepalive_connection(server_of):
    server = server_of(tracing=True)
    buf = bytearray()
    with socket.create_connection(("127.0.0.1", server.port), timeout=10) as s:
        s.sendall(_request(json.dumps({"x": 4}).encode()))
        status, headers, body = _response(s, buf)
        assert status == 200 and json.loads(body)["value"] == 8
        first_id = headers["x-pio-trace-id"]
        # the client has the response: the trace is in the ring, behind
        # its dispatch's record, finished, every parent link resolving
        ring = _ring(server)
        assert [t.name for t in ring] == ["dispatch", "queries.json"]
        assert ring[-1].trace_id == first_id
        early = ring[-1].to_dict()
        assert early["durationMs"] is not None
        ids = {sp["spanId"] for sp in early["spans"]}
        assert all(sp.get("parentId", "") in ids | {""}
                   for sp in early["spans"])
        assert "request.read" in {sp["name"] for sp in early["spans"]}
        time.sleep(0.02)
        # a second request on the same connection: when its answer is
        # here the handler has long been back at its read
        s.sendall(_request(json.dumps({"x": 5}).encode()))
        status, headers, body = _response(s, buf)
        assert status == 200 and json.loads(body)["value"] == 10
        second_id = headers["x-pio-trace-id"]
    first = _doc(server, first_id)
    by_name = {sp["name"]: sp for sp in first["spans"]}
    root, read, flush = (by_name[n] for n in
                         ("request", "request.read", "request.flush"))
    assert "parentId" not in root
    # it starts with request.read and ends with request.flush
    assert root["startMs"] == read["startMs"] == 0.0
    assert first["spans"][0]["name"] == "request"
    assert flush["startMs"] + flush["durationMs"] == pytest.approx(
        root["durationMs"], abs=TOL_MS)
    assert flush["startMs"] == pytest.approx(
        by_name["respond"]["startMs"] + by_name["respond"]["durationMs"],
        abs=TOL_MS)
    # the parent of every top-level span, and at least their sum
    top = [sp for sp in first["spans"] if sp.get("parentId") == root["spanId"]]
    assert {sp["name"] for sp in top} == {
        "request.read", "parse", "bind", "codec_key", "batcher.queue_wait",
        "batcher.device_dispatch", "batcher.wake", "encode", "respond",
        "request.flush"}
    assert all(sp.get("parentId") for sp in first["spans"] if sp is not root)
    assert root["durationMs"] >= sum(sp["durationMs"] for sp in top) - TOL_MS
    assert first["durationMs"] == root["durationMs"]
    assert all(sp["startMs"] >= 0 for sp in first["spans"])
    assert first["tags"]["status"] == 200 and "dispatch" in first["tags"]
    # the second request has a stamp of its own: its trace starts after
    # the first one's flush, and is not the connection's age
    traces = {t.trace_id: t for t in _ring(server)}
    t1, t2 = traces[first_id], traces[second_id]
    assert t2.start_perf >= t1.start_perf + root["durationMs"] / 1e3 + 0.015
    second_root = next(sp for sp in t2.to_dict()["spans"]
                       if sp["name"] == "request")
    assert second_root["durationMs"] < 20.0 + root["durationMs"]


def _status_traces(server) -> list:
    return [t.to_dict() for t in _ring(server) if t.name == "queries.json"]


def test_400_and_411_record_a_trace_as_before(server_of):
    server = server_of(tracing=True)
    cases = [
        (_request(b"{not json"), 400, True),
        (b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
         b"Content-Length: abc\r\n\r\n", 400, False),
        (b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
         b"Transfer-Encoding: chunked\r\n\r\n0\r\n\r\n", 411, False),
    ]
    for raw, want, _ in cases:
        with socket.create_connection(
                ("127.0.0.1", server.port), timeout=10) as s:
            s.sendall(raw)
            status, _, _ = _response(s, bytearray())
            assert status == want
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        docs = _status_traces(server)
        if len(docs) == 3 and all(
                "request" in {sp["name"] for sp in d["spans"]}
                and d["spans"][-1]["name"] == "request.flush" for d in docs):
            break
        time.sleep(0.01)
    assert [d["tags"]["status"] for d in docs] == [400, 400, 411]
    for d, (_, _, read_body) in zip(docs, cases):
        names = {sp["name"] for sp in d["spans"]}
        assert {"request", "respond", "request.flush"} <= names
        # the body was in hand only where a length let it be read
        assert ("request.read" in names) is read_body
        assert not names & {"bind", "batcher.queue_wait"}


def test_a_read_timeout_records_the_trace_it_has(server_of, monkeypatch):
    """The body never comes: the handler's read times out, the trace is
    recorded with status 0 as it always was, and its open root still
    gives every span a parent the document holds."""
    monkeypatch.setattr(_Handler, "timeout", 0.3)
    server = server_of(tracing=True)
    with socket.create_connection(("127.0.0.1", server.port), timeout=10) as s:
        s.sendall(b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Length: 10\r\n\r\n{")
        assert s.recv(65536) == b""          # hung up on, no response
    (doc,) = _status_traces(server)
    assert doc["tags"]["status"] == 0 and doc["durationMs"] >= 250
    assert [sp["name"] for sp in doc["spans"]] == ["request"]


def _count_flushes(monkeypatch) -> list:
    """Who called ``wfile.flush()``: the function names, per call."""
    callers: list = []
    real_setup = _Handler.setup

    class Counting:
        def __init__(self, wfile):
            self._wfile = wfile

        def flush(self):
            callers.append(sys._getframe(1).f_code.co_name)
            return self._wfile.flush()

        def __getattr__(self, name):
            return getattr(self._wfile, name)

    def setup(self):
        real_setup(self)
        self.wfile = Counting(self.wfile)

    monkeypatch.setattr(_Handler, "setup", setup)
    return callers


@pytest.mark.parametrize("tracing", [False, True])
def test_the_handler_flushes_itself_only_for_a_traced_query(
        server_of, monkeypatch, tracing):
    callers = _count_flushes(monkeypatch)
    server = server_of(tracing=tracing)
    buf = bytearray()
    with socket.create_connection(("127.0.0.1", server.port), timeout=10) as s:
        for x in (1, 2):
            s.sendall(_request(json.dumps({"x": x}).encode()))
            assert _response(s, buf)[0] == 200
        # another route is never traced and runs the stdlib's flush alone
        s.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n")
        assert _response(s, buf)[0] == 200
    assert callers.count("_dispatch") == (2 if tracing else 0)
    assert callers.count("handle_one_request") == 3
    if not tracing:
        # tracing off: the ring stays empty, nothing was stamped
        assert _ring(server) == []
        assert server.service.gc_pauses is None
    else:
        assert sorted(t.name for t in _ring(server)) == [
            "dispatch", "dispatch", "queries.json", "queries.json"]
