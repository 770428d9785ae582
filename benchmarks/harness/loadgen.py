"""Load-generator child: JAX-free, exec'ed (never forked) by the run.

Raw keep-alive HTTP/1.1 sockets and a Content-Length scanner
(``bench_serving._client_main``'s client, copied): a client must be
cheaper than the server it measures. It starts before the host claims
the chip, waits for ``GO <port> <t0>`` on stdin, and leaves one ``.npz``
with, per request: due, sent, done (seconds after t0 on
CLOCK_MONOTONIC, which the host shares), status, pool index and body.

Every connection is opened between GO and t0, as a pool is before its
traffic. Open loop: this child's share of the Poisson schedule; a worker thread
takes the next due request, sleeps until it is due, sends, reads.
Closed loop: each connection sends its next request when the last one
is answered, until the window ends.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import traffic as tr  # noqa: E402

TIMEOUT, MALFORMED, BROKEN = 0, -1, -2      # status codes that are not HTTP


def read_response(sock: socket.socket, buf: bytearray) -> tuple[int, bytes]:
    """Headers, then exactly Content-Length body bytes."""
    while True:
        head_end = buf.find(b"\r\n\r\n")
        if head_end >= 0:
            break
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("closed mid-headers")
        buf += chunk
    head = bytes(buf[:head_end]).lower()
    status = int(head[9:12])
    at = head.find(b"content-length:")
    if at < 0:
        raise ConnectionError("no content-length")
    line_end = head.find(b"\r\n", at)
    length = int(head[at + 15:line_end if line_end >= 0 else len(head)])
    need = head_end + 4 + length
    while len(buf) < need:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("closed mid-body")
        buf += chunk
    body = bytes(buf[head_end + 4:need])
    del buf[:need]
    return status, body


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--of", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    sys.setswitchinterval(0.0005)

    pool = tr.query_pool(config, traffic, args.seed)
    num = int(traffic["num"])
    bodies = {}

    def request(ix: int) -> bytes:
        user = int(pool[ix])
        if user not in bodies:
            body = tr.request_body(user, num)
            bodies[user] = (
                b"POST /queries.json HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                b"Content-Type: application/json\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)
        return bodies[user]

    closed = traffic["kind"] == "serve_closed"
    conns = int(traffic["connections"]) // args.of
    if closed:
        due = None
    else:
        due = tr.arrivals(traffic, args.seed, args.index, 1.0 / args.of,
                          args.seconds)
    for ix in range(len(pool)):         # build request bytes before GO
        request(ix)

    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if len(go) != 3 or go[0] != "GO":
        return 1
    port, t0 = int(go[1]), float(go[2])
    timeout_s = float(traffic.get("timeout_s", 2.0))
    records = []                         # (due, sent, done, status, ix, body)
    lock = threading.Lock()
    cursor = [0]

    def connect() -> socket.socket:
        sock = socket.create_connection(("127.0.0.1", port), timeout=timeout_s)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock

    def worker(tid: int) -> None:
        buf, mine, j = bytearray(), [], 0
        try:                # the pool is established before the window
            sock = connect()
        except OSError:
            sock = None
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        while True:
            if closed:
                # connection tid of generator index walks the pool from
                # its own offset, so no two connections send in step
                ix = ((args.index * conns + tid) * 7919 + j) % len(pool)
                j += 1
                at = time.monotonic() - t0
                if at >= args.seconds:
                    break
                when = at
            else:
                with lock:
                    n = cursor[0]
                    cursor[0] += 1
                if n >= len(due):
                    break
                # request n of generator index, over the shared pool
                ix = (n * args.of + args.index) % len(pool)
                when = float(due[n])
                wait = t0 + when - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            sent = time.monotonic() - t0
            try:
                if sock is None:
                    sock = connect()
                    buf.clear()
                sock.sendall(request(ix))
                status, body = read_response(sock, buf)
                if status == 200 and not (body.startswith(b"{")
                                          and body.endswith(b"}")):
                    status = MALFORMED
            except socket.timeout:
                status, body = TIMEOUT, b""
            except (OSError, ValueError):
                status, body = BROKEN, b""
            if status <= 0 and sock is not None:
                sock.close()
                sock = None
            mine.append((when, sent, time.monotonic() - t0, status, ix, body))
        if sock is not None:
            sock.close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(conns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records.sort(key=lambda r: r[0])
    blob = b"".join(r[5] for r in records)
    tmp = args.out + ".part.npz"
    np.savez(tmp,
             due=np.array([r[0] for r in records], dtype=np.float64),
             sent=np.array([r[1] for r in records], dtype=np.float64),
             done=np.array([r[2] for r in records], dtype=np.float64),
             status=np.array([r[3] for r in records], dtype=np.int32),
             ix=np.array([r[4] for r in records], dtype=np.int64),
             body_len=np.array([len(r[5]) for r in records], dtype=np.int64),
             bodies=np.frombuffer(blob, dtype=np.uint8))
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
