"""One REST load-generator process: raw sockets, pre-serialised requests.

Started by ``generators/rest.py`` from the process that holds the chip, so
it imports no JAX and nothing of the program (numpy and the benchmark's
own table only). The HTTP handling follows the program's
``ccfd_tpu/utils/loadgen.py::_CLIENT`` (keep-alive, ``Content-Length``
bodies, reconnect on a closed connection), mended where that client is no
yardstick: the payloads are seeded rows that all differ, a request is
timed from the instant it was *due*, and a request that fails, is refused
or is never answered stays in the sample as a miss.

Protocol with the parent, one line each way on stdin/stdout:
``READY`` after the table, the request bytes and the connections are made;
``WARM`` -> a few requests on every connection -> ``WARMED``;
``GO <t0> <seconds>`` (``t0`` on ``time.perf_counter``, which is one clock
for every process of a Linux host) -> the window, a bounded drain, the
results written to ``out`` as ``.npz`` -> ``DONE``.

Arrivals: ``poisson`` is an open loop, this worker's share of the cell's
rate as exponential gaps from the seed; a request that comes due while all
connections are busy waits for one and its wait counts, so a stalled
server raises the later requests' latency. ``closed`` keeps every
connection busy with one request at a time, and a request is due when its
connection became free.
"""

from __future__ import annotations

import collections
import json
import select
import socket
import sys
import time

import numpy as np

DRAIN_S = 5.0  # after the window: unanswered by then is a miss


def schedule(kind: str, rate_per_s: float, seconds: float, seed: int,
             worker: int) -> np.ndarray:
    """Due instants (seconds from the window's start) of this worker's
    open-loop requests: a function of the seed and the worker alone."""
    if kind != "poisson":
        raise ValueError(f"unknown open-loop arrivals {kind!r}")
    rng = np.random.default_rng([int(seed), 0x5EED, int(worker)])
    n = int(rate_per_s * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
    while due[-1] < seconds:  # the draw came up short: extend it
        more = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
        due = np.concatenate([due, due[-1] + more])
    return due[due < seconds]


class Conn:
    __slots__ = ("sock", "buf", "req")

    def __init__(self, addr):
        self.sock = socket.create_connection(addr, timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""
        self.req = -1


def take_response(buf: bytes):
    """``(status, body, rest)`` when ``buf`` holds one whole response with
    a ``Content-Length``, else None."""
    head_end = buf.find(b"\r\n\r\n")
    if head_end < 0:
        return None
    head = buf[:head_end].lower()
    at = head.find(b"content-length:")
    if at < 0:
        raise ValueError("response without Content-Length")
    length = int(head[at + 15:].split(b"\r\n", 1)[0])
    end = head_end + 4 + length
    if len(buf) < end:
        return None
    return int(buf[9:12]), buf[head_end + 4:end], buf[end:]


class Worker:
    def __init__(self, a: dict):
        sys.path.insert(0, a["root"])
        from benchmark.reference import table

        self.a = a
        self.addr = (a["host"], int(a["port"]))
        self.rows_per_request = int(a["rows_per_request"])
        lines, _, _ = table.make_table(int(a["table_rows"]), int(a["seed"]))
        k = self.rows_per_request
        n_blocks = len(lines) // k
        w, nw = int(a["worker"]), int(a["workers"])
        # this worker's blocks of the table: w, w + nw, w + 2 nw, ...
        self.blocks = list(range(w, n_blocks, nw))
        head = (f"POST {a['path']} HTTP/1.1\r\nHost: {a['host']}\r\n"
                "Content-Type: application/json\r\nContent-Length: ")
        self.requests = []
        for b in self.blocks:
            body = ('{"data": {"ndarray": [['
                    + "], [".join(lines[b * k:(b + 1) * k])
                    + "]]}}").encode()
            self.requests.append(
                head.encode() + str(len(body)).encode() + b"\r\n\r\n" + body)
        self.conns = [Conn(self.addr) for _ in range(int(a["connections"]))]

    # -- one exchange, blocking: warm-up only ------------------------------
    def warm(self, per_conn: int) -> None:
        for i, c in enumerate(self.conns):
            for j in range(per_conn):
                c.sock.sendall(self.requests[(i + j) % len(self.requests)])
                while True:
                    got = take_response(c.buf)
                    if got is not None:
                        break
                    chunk = c.sock.recv(1 << 16)
                    if not chunk:
                        raise ConnectionError("closed during warm-up")
                    c.buf += chunk
                if got[0] != 200:
                    raise RuntimeError(f"warm-up answered {got[0]}")
                c.buf = got[2]

    def _reconnect(self, c: Conn) -> None:
        try:
            c.sock.close()
        except OSError:
            pass
        fresh = Conn(self.addr)
        c.sock, c.buf, c.req = fresh.sock, b"", -1

    # -- the window ---------------------------------------------------------
    def run(self, t0: float, seconds: float) -> dict:
        a = self.a["arrivals"]
        closed = a["kind"] == "closed"
        if closed:
            cap = 1 << 18  # far over what one worker's callers finish in a run
            due = np.full(cap, np.inf)
        else:
            due = t0 + schedule(a["kind"], float(a["rate_per_s"])
                                / int(self.a["workers"]), seconds,
                                int(self.a["seed"]), int(self.a["worker"]))
            cap = len(due)
        sent = np.full(cap, np.nan)
        done = np.full(cap, np.inf)
        status = np.zeros(cap, np.int16)
        bodies: list = [None] * cap
        free = collections.deque(self.conns)
        busy: dict = {}  # socket -> Conn with a request outstanding
        t_end = t0 + seconds
        nxt = 0
        clock = time.perf_counter
        while clock() < t0:
            time.sleep(max(0.0, min(0.001, t0 - clock())))
        while True:
            now = clock()
            while free and nxt < cap and (
                    now < t_end if closed else due[nxt] <= now):
                c = free.popleft()
                if closed:
                    due[nxt] = now
                c.req = nxt
                sent[nxt] = now
                try:
                    c.sock.sendall(self.requests[nxt % len(self.requests)])
                    busy[c.sock] = c
                except OSError:  # this request failed; the slot lives on
                    status[nxt] = -1
                    self._reconnect(c)
                    free.append(c)
                nxt += 1
                now = clock()
            over = now >= t_end if closed else nxt >= cap
            if (over and not busy) or now > t_end + DRAIN_S:
                break
            if closed or not free or nxt >= cap:
                wait = 0.05
            else:
                wait = min(0.05, max(0.0, due[nxt] - now))
            # select(2): a timeout in microseconds, where epoll and poll
            # round it up to a whole millisecond
            for sock in select.select(list(busy), (), (), wait)[0]:
                c = busy[sock]
                try:
                    chunk = sock.recv(1 << 18)
                except OSError:
                    chunk = b""
                if not chunk:  # closed under a request: that one failed
                    del busy[sock]
                    status[c.req] = -1
                    self._reconnect(c)
                    free.append(c)
                    continue
                c.buf += chunk
                got = take_response(c.buf)
                if got is None:
                    continue
                i = c.req
                done[i] = clock()
                status[i], bodies[i], c.buf = got
                c.req = -1
                del busy[sock]
                free.append(c)
        n = nxt
        return self._report(t0, t_end, due[:n], sent[:n], done[:n],
                            status[:n], bodies[:n])

    def _report(self, t0, t_end, due, sent, done, status, bodies) -> dict:
        k = self.rows_per_request
        n = len(due)
        ok = np.zeros(n, bool)
        proba = np.full((n, k), np.nan)
        for i in range(n):
            if status[i] != 200 or not np.isfinite(done[i]):
                continue
            try:
                rows = json.loads(bodies[i])["data"]["ndarray"]
                p = np.asarray(rows, np.float64)
            except (ValueError, KeyError, TypeError):
                continue
            if p.shape == (k, 2):
                proba[i] = p[:, 1]
                ok[i] = True
        first_row = np.array([self.blocks[i % len(self.blocks)] * k
                              for i in range(n)], np.int64)
        np.savez(self.a["out"], t0=t0, t_end=t_end, due=due, sent=sent,
                 done=done, status=status, ok=ok, proba=proba,
                 first_row=first_row)
        return {"requests": n, "ok": int(ok.sum())}

    def close(self) -> None:
        for c in self.conns:
            try:
                c.sock.close()
            except OSError:
                pass


def main() -> int:
    worker = Worker(json.loads(sys.argv[1]))
    try:
        print("READY", flush=True)
        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            if words[0] == "WARM":
                worker.warm(int(words[1]))
                print("WARMED", flush=True)
            elif words[0] == "GO":
                rep = worker.run(float(words[1]), float(words[2]))
                print("DONE " + json.dumps(rep), flush=True)
                return 0
    finally:
        worker.close()
    return 1


if __name__ == "__main__":
    sys.exit(main())
