"""Lean upstream HTTP client for the router's forward path.

``http.client`` spends CPU on every request (header assembly plus the
email-parser response machinery), and the router sits on EVERY query,
so its upstream hop uses the same discipline as the engine server's
response path: pre-built single-write requests over
pooled keep-alive sockets, and a minimal Content-Length response
parser. The engine server always sends ``Content-Length``
(api/engine_server._respond), which is what makes the minimal parser
sufficient.

Resilience contract: the ONLY raw network call lives in
:meth:`BackendTransport._connect` (the lint-declared guarded site);
every routed request goes through the owning backend's
:class:`~predictionio_tpu.utils.resilience.Resilience` policy at the
router layer (``resilient(backend.resilience, ...)``), so breaker
accounting and failure classification are never bypassed. A stale
pooled socket (the peer idled us out between requests) gets ONE
in-transport refresh with a fresh connection — only when ZERO response
bytes arrived (a reused socket the peer had already closed); once any
response byte has been read the backend executed the request, so the
failure is surfaced instead of replayed (a replay would run the query
twice). The refresh keeps keep-alive reuse from burning the router's
cross-replica retry.

Every socket operation is bounded: ``timeout`` is mandatory on
:meth:`BackendTransport.request` and is a TOTAL budget for the
exchange — the remaining budget is re-armed before every read, so a
replica trickling bytes cannot hold a router thread past the deadline.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import socket
import threading
import time
from typing import Callable, Iterable, Mapping, TypeVar

from predictionio_tpu.utils.resilience import TransientError  # noqa: F401  (re-export for callers)

logger = logging.getLogger(__name__)

_T = TypeVar("_T")
_R = TypeVar("_R")


def fan_out(items: Iterable[_T],
            fn: Callable[[_T], _R]) -> list[_R | None]:
    """Run ``fn`` over ``items`` CONCURRENTLY (one thread per item, the
    probe-pass idiom from fleet/membership.py) and return results in
    item order. Scrape-time fan-outs must pay the SLOWEST target's
    timeout, not the sum — sequentially, three black-holed replicas
    turn a "bounded" 2s-per-target scrape into 6s of wall clock and
    blow the Prometheus scrape deadline. ``fn`` is expected to handle
    its own per-target failures (degrade, don't raise); an escaped
    exception is logged and yields ``None`` in that slot."""
    items = list(items)

    def run(item: _T) -> _R | None:
        try:
            return fn(item)
        except Exception:  # noqa: BLE001 — one target must not kill the fan-out
            logger.exception("fan-out target failed")
            return None

    if len(items) <= 1:
        return [run(item) for item in items]
    results: list[_R | None] = [None] * len(items)

    def runner(idx: int, item: _T) -> None:
        results[idx] = run(item)

    threads = [
        threading.Thread(target=runner, args=(i, item), daemon=True,
                         name=f"pio-fan-out-{i}")
        for i, item in enumerate(items)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results

#: response headers the router forwards / acts on; everything else an
#: upstream sends is dropped at the parse (the router is not a general
#: reverse proxy — it fronts engine servers it knows)
_MAX_HEADER_BYTES = 64 * 1024


class UpstreamProtocolError(TransientError):
    """The upstream's response could not be parsed (closed mid-message,
    no Content-Length, oversized headers) — transient: the replica is
    misbehaving and the breaker should know."""


@dataclasses.dataclass
class UpstreamResponse:
    """One parsed upstream response: status, body bytes, and the
    (lower-cased) header map."""

    status: int
    body: bytes
    headers: dict[str, str]

    def header(self, name: str, default: str | None = None) -> str | None:
        return self.headers.get(name.lower(), default)


def build_request(method: str, path: str, host: str,
                  headers: Mapping[str, str] | None = None,
                  body: bytes | None = None) -> bytes:
    """One request as a single bytes blob (one ``sendall`` syscall)."""
    lines = [f"{method} {path} HTTP/1.1", f"Host: {host}"]
    for k, v in (headers or {}).items():
        lines.append(f"{k}: {v}")
    body = body or b""
    if body or method == "POST":
        lines.append(f"Content-Length: {len(body)}")
    head = ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1")
    return head + body


def _recv_within(sock: socket.socket, deadline: float) -> bytes:
    """One ``recv`` bounded by the exchange's remaining TOTAL budget.

    ``settimeout`` is per-operation: without re-arming it from the
    deadline each read, a replica trickling one byte per almost-timeout
    holds the handler thread (and its admission slot) indefinitely."""
    remaining = deadline - time.monotonic()
    if remaining <= 0:
        raise socket.timeout("upstream exchange exceeded its deadline")
    sock.settimeout(remaining)
    return sock.recv(65536)


def _parse_response(sock: socket.socket, buf: bytearray,
                    deadline: float) -> UpstreamResponse:
    """Read one response off ``sock`` into/out of ``buf`` (which may
    hold bytes from a previous read and keeps any trailing pipelined
    bytes — there are none in practice: one request in flight per
    pooled socket). On failure ``buf`` keeps everything read so far, so
    the caller can tell whether ANY response bytes arrived."""
    while True:
        head_end = buf.find(b"\r\n\r\n")
        if head_end >= 0:
            break
        if len(buf) > _MAX_HEADER_BYTES:
            raise UpstreamProtocolError("oversized response headers")
        chunk = _recv_within(sock, deadline)
        if not chunk:
            raise UpstreamProtocolError("upstream closed mid-headers")
        buf += chunk
    head = bytes(buf[:head_end]).decode("latin-1")
    lines = head.split("\r\n")
    parts = lines[0].split(" ", 2)
    if len(parts) < 2 or not parts[1].isdigit():
        raise UpstreamProtocolError(f"bad status line {lines[0]!r}")
    status = int(parts[1])
    headers: dict[str, str] = {}
    for line in lines[1:]:
        name, sep, value = line.partition(":")
        if sep:
            headers[name.strip().lower()] = value.strip()
    length_raw = headers.get("content-length")
    if length_raw is None or not length_raw.isdigit():
        # the engine server always sends Content-Length; anything else
        # cannot be framed on a keep-alive socket
        raise UpstreamProtocolError("upstream response has no Content-Length")
    need = head_end + 4 + int(length_raw)
    while len(buf) < need:
        chunk = _recv_within(sock, deadline)
        if not chunk:
            raise UpstreamProtocolError("upstream closed mid-body")
        buf += chunk
    body = bytes(buf[head_end + 4:need])
    del buf[:need]
    return UpstreamResponse(status=status, body=body, headers=headers)


class BackendTransport:
    """Pooled keep-alive HTTP/1.1 client for ONE backend address."""

    def __init__(self, host: str, port: int, pool_size: int = 32):
        self.host = host
        self.port = port
        self._addr = f"{host}:{port}"
        #: idle keep-alive sockets; SimpleQueue-style FIFO bounded by
        #: ``pool_size`` — beyond it sockets are closed, not pooled
        self._pool: "queue.Queue[socket.socket]" = queue.Queue(
            maxsize=max(1, pool_size))

    # -- pool ---------------------------------------------------------------
    def _connect(self, timeout: float) -> socket.socket:
        # THE guarded raw-network site (lint: resilience-bypass) —
        # reachable only from request(), whose callers route through
        # resilient(backend.resilience, ...) at the router layer
        sock = socket.create_connection((self.host, self.port), timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock

    def _checkout(self) -> socket.socket | None:
        try:
            return self._pool.get_nowait()
        except queue.Empty:
            return None

    def _checkin(self, sock: socket.socket) -> None:
        try:
            self._pool.put_nowait(sock)
        except queue.Full:
            sock.close()

    def close(self) -> None:
        while True:
            sock = self._checkout()
            if sock is None:
                return
            sock.close()

    # -- requests -----------------------------------------------------------
    def request(self, method: str, path: str,
                headers: Mapping[str, str] | None = None,
                body: bytes | None = None, *,
                timeout: float) -> UpstreamResponse:
        """One request/response exchange, bounded by ``timeout`` across
        connect + send + reads. Raises ``OSError`` subclasses /
        :class:`UpstreamProtocolError` on transport failure — both
        transient to the resilience layer. HTTP status codes (any of
        them) are returned, not raised: classification is the router's
        job."""
        raw = build_request(method, path, self._addr, headers, body)
        deadline = time.monotonic() + timeout
        sock = self._checkout()
        reused = sock is not None
        if sock is None:
            sock = self._connect(timeout)
        try:
            sock.settimeout(max(0.001, deadline - time.monotonic()))
            first_buf = bytearray()
            try:
                sock.sendall(raw)
                response = _parse_response(sock, first_buf, deadline)
            except (UpstreamProtocolError, OSError):
                sock.close()
                if not reused or first_buf:
                    # fresh socket, or response bytes already arrived:
                    # the backend executed the request, so replaying
                    # would run the query twice — surface the failure
                    # and let the router retry on a DIFFERENT replica
                    raise
                # a reused socket the peer already closed (keep-alive
                # idle timeout): zero response bytes means the request
                # was never processed — one fresh-connection refresh,
                # still inside the deadline
                sock = self._connect(max(0.001, deadline - time.monotonic()))
                sock.settimeout(max(0.001, deadline - time.monotonic()))
                sock.sendall(raw)
                response = _parse_response(sock, bytearray(), deadline)
        except BaseException:
            sock.close()
            raise
        if response.headers.get("connection", "").lower() == "close":
            sock.close()
        else:
            self._checkin(sock)
        return response
