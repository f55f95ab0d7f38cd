"""The traffic generators: schedules from the seed alone, latency timed from
due instants, a failed request kept as a miss, bursts at the stated mean."""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from benchmark.generators import bus, rest_worker
from benchmark.harness import core
from benchmark.reference import table

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- schedules ---------------------------------------------------------------
@pytest.mark.parametrize("worker", [0, 3])
def test_open_loop_schedule_is_a_function_of_the_seed_alone(worker):
    a = rest_worker.schedule("poisson", 500.0, 4.0, 2**31 + 11, worker)
    b = rest_worker.schedule("poisson", 500.0, 4.0, 2**31 + 11, worker)
    c = rest_worker.schedule("poisson", 500.0, 4.0, 2**31 + 12, worker)
    assert np.array_equal(a, b)
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert (np.diff(a) > 0).all() and a[-1] < 4.0
    assert abs(len(a) - 2000) < 5 * np.sqrt(2000)  # Poisson count


def test_workers_draw_different_shares_of_one_rate():
    a = rest_worker.schedule("poisson", 500.0, 4.0, 7, 0)
    b = rest_worker.schedule("poisson", 500.0, 4.0, 7, 1)
    assert not np.array_equal(a[:10], b[:10])


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        rest_worker.schedule("uniform", 1.0, 1.0, 0, 0)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_bus_bursts_keep_the_stated_mean_rate(seed):
    burst = {"factor": 3, "every_s": 1.0, "for_s": 0.1}
    counts = bus.tick_counts(40_000.0, 0.005, 10.0, burst, seed)
    assert len(counts) == 2000
    assert counts.sum() == 400_000  # whole burst periods: the mean exactly
    assert np.array_equal(
        counts, bus.tick_counts(40_000.0, 0.005, 10.0, burst, seed))
    high, low = counts.max(), counts.min()
    assert abs(high / low - 3.0) < 0.05
    assert abs((counts > (high + low) / 2).mean() - 0.1) < 0.006
    flat = bus.tick_counts(40_000.0, 0.005, 10.0, None, seed)
    assert flat.sum() == 400_000 and flat.max() - flat.min() <= 1


def test_bus_burst_offset_comes_from_the_seed():
    burst = {"factor": 3, "every_s": 1.0, "for_s": 0.1}
    a = bus.tick_counts(10_000.0, 0.005, 2.0, burst, 1)
    b = bus.tick_counts(10_000.0, 0.005, 2.0, burst, 2)
    assert a.sum() == b.sum() and not np.array_equal(a, b)


# -- a fake model server on port 0 ---------------------------------------------
class FakeServer:
    """Answers the Seldon contract with one [1 - p, p] per row, p = 0.25.
    ``stall_at``: the n-th request waits ``stall_s`` before its answer
    (one thread serves all connections, so later requests wait behind it).
    ``fail_every``: every n-th request is answered 500."""

    def __init__(self, stall_at=None, stall_s=0.0, fail_every=None):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.sock.settimeout(0.001)
        self.port = self.sock.getsockname()[1]
        self.stall_at, self.stall_s, self.fail_every = (
            stall_at, stall_s, fail_every)
        self.seen = 0
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        conns = {}
        while not self.stop.is_set():
            try:
                c, _ = self.sock.accept()
                c.settimeout(0.0)
                conns[c] = b""
            except OSError:
                pass
            for c in list(conns):
                try:
                    chunk = c.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError:
                    chunk = b""
                if not chunk:
                    c.close()
                    del conns[c]
                    continue
                conns[c] += chunk
                while True:
                    buf = conns[c]
                    head = buf.find(b"\r\n\r\n")
                    if head < 0:
                        break
                    length = int(buf[:head].lower().split(
                        b"content-length:")[1].split(b"\r\n")[0])
                    if len(buf) < head + 4 + length:
                        break
                    body = json.loads(buf[head + 4:head + 4 + length])
                    conns[c] = buf[head + 4 + length:]
                    self.seen += 1
                    if self.seen == self.stall_at:
                        time.sleep(self.stall_s)
                    rows = len(body["data"]["ndarray"])
                    if self.fail_every and self.seen % self.fail_every == 0:
                        out, status = b'{"error": "x"}', b"500 Internal"
                    else:
                        out = json.dumps({"data": {"ndarray": [
                            [0.75, 0.25]] * rows}}).encode()
                        status = b"200 OK"
                    c.setblocking(True)
                    c.sendall(b"HTTP/1.1 " + status + b"\r\nContent-Length: "
                              + str(len(out)).encode() + b"\r\n\r\n" + out)
                    c.settimeout(0.0)
        for c in conns:
            c.close()
        self.sock.close()

    def close(self):
        self.stop.set()
        self.thread.join(timeout=10)
        assert not self.thread.is_alive()


def _worker(port, tmp_path, arrivals, rows=1, connections=2):
    return rest_worker.Worker({
        "root": ROOT, "host": "127.0.0.1", "port": port, "path": "/p",
        "seed": 5, "worker": 0, "workers": 1, "table_rows": 256,
        "rows_per_request": rows, "connections": connections,
        "arrivals": arrivals, "out": str(tmp_path / "out.npz")})


def test_open_loop_times_from_due_so_a_stall_raises_later_latencies(
        tmp_path):
    server = FakeServer(stall_at=10, stall_s=0.4)
    w = _worker(server.port, tmp_path,
                {"kind": "poisson", "rate_per_s": 200.0})
    try:
        w.warm(1)  # two requests: the stall comes at the 8th of the window
        w.run(time.perf_counter() + 0.05, 1.0)
    finally:
        w.close()
        server.close()
    out = np.load(tmp_path / "out.npz")
    latency = out["done"] - out["due"]
    assert out["ok"].all() and len(latency) > 100
    stalled = int(np.argmin(np.where(latency >= 0.39, out["due"], np.inf)))
    # every request that came due while the server stood still waited for
    # it: timed from its due instant, not from when it was finally sent
    behind = (out["due"] > out["due"][stalled]) & (
        out["due"] < out["due"][stalled] + 0.3)
    assert behind.sum() >= 20
    waited = latency[behind] + (out["due"][behind] - out["due"][stalled])
    assert (waited >= 0.4 - 1e-3).all()
    assert (out["sent"][behind] - out["due"][behind]).max() > 0.05


def test_a_failed_request_stays_in_the_sample_as_a_miss(tmp_path):
    server = FakeServer(fail_every=4)
    w = _worker(server.port, tmp_path, {"kind": "closed"}, rows=4)
    try:
        w.run(time.perf_counter() + 0.05, 0.5)
    finally:
        w.close()
        server.close()
    out = np.load(tmp_path / "out.npz")
    n = len(out["ok"])
    assert n >= 8 and (out["status"] == 500).sum() >= n // 4 - 1
    assert not out["ok"][out["status"] == 500].any()
    latency_ms = np.where(out["ok"], (out["done"] - out["due"]) * 1e3, np.inf)
    assert core.percentile(latency_ms, 99) == np.inf  # a miss misses a limit
    assert core.printable(core.percentile(latency_ms, 99)) == core.MISS_MS
    assert np.isfinite(core.percentile(latency_ms, 50))
    served = out["proba"][out["ok"]]
    assert served.shape[1] == 4 and (served == 0.25).all()
    # blocks of 4 rows: this worker's first request is block 0, rows 0-3
    assert out["first_row"][0] == 0 and out["first_row"][1] == 4


# -- the bus generator's accounting ----------------------------------------------
class FakeTap:
    def __init__(self):
        self.batches = []

    def add(self, when, process, seqs, proba, pids=None):
        self.batches.append((when, process, [
            {"customer_id": s, "proba": p} for s, p in zip(seqs, proba)],
            pids if pids is not None else list(seqs)))


def _generator(produced, consumed, shed=0):
    g = bus.Generator(
        {"warm_records": 0, "arrivals": {"kind": "ticks"}}, seed=1, root=ROOT,
        workdir="", handles={"consumed": lambda: consumed,
                             "shed": lambda: shed, "fraud_threshold": 0.5},
        table_rows=8)
    g.produced = produced
    return g


@pytest.mark.parametrize("case,want", [
    ("sound", {"records_lost": 0, "records_doubled": 0,
               "route_mismatches": 0}),
    ("lost", {"records_lost": 1, "records_doubled": 0}),
    ("doubled", {"records_doubled": 1}),
    ("misrouted", {"route_mismatches": 1}),
    ("shed_is_counted_not_lost", {"records_lost": 0}),
])
def test_bus_accounting_names_every_record(case, want):
    tap = FakeTap()
    seqs = list(range(10))
    proba = [0.9 if s == 3 else 0.1 for s in seqs]
    licit = [s for s in seqs if s != 3]
    shed = 0
    if case == "lost":
        licit.remove(7)
    if case == "shed_is_counted_not_lost":
        licit.remove(7)
        shed = 1
    tap.add(100.1, "standard", licit, [0.1] * len(licit))
    tap.add(100.2, "misrouted" == case and "standard" or "fraud", [3], [0.9])
    if case == "doubled":
        tap.add(100.3, "standard", [5], [0.1])
    g = _generator(produced=10, consumed=10, shed=shed)
    counts = np.array([4, 6])  # two ticks of the window, 0.005 s apart
    out = g._account(tap, t0=100.0, seconds=1.0, first_seq=0, tick_s=0.005,
                     counts=counts, late=np.zeros(2), drained=True)
    for key, value in want.items():
        assert out.extra[key] == value, (key, out.extra)
    assert out.attempted == 10
    if case == "sound":
        # timed from each record's tick, not from its produce stamp
        assert out.failed == 0
        verdict = np.where(np.arange(10) == 3, 100.2, 100.1)
        due = np.where(np.arange(10) < 4, 100.0, 100.005)
        assert np.allclose(out.latency_ms, (verdict - due) * 1e3)
        assert out.rows_in_window == 10
        assert list(out.served_rows[:3]) == [0, 1, 2]
    if case == "lost":
        assert out.failed == 1 and np.isinf(out.latency_ms[7])


# -- customer-keyed bus traffic -------------------------------------------------
KEYS = {"kind": "zipf", "customers": 1000, "exponent": 1.1}


def test_customer_keys_are_a_function_of_the_seed_and_skewed():
    a = bus.customer_keys(KEYS, 2**31 + 5)
    assert np.array_equal(a, bus.customer_keys(KEYS, 2**31 + 5))
    assert not np.array_equal(a, bus.customer_keys(KEYS, 2**31 + 6))
    assert len(a) == bus.KEY_CYCLE and a.min() >= 0 and a.max() < 1000
    share = np.sort(np.bincount(a, minlength=1000))[::-1] / len(a)
    # rank r sends in proportion to r^-1.1: a few send most
    weight = np.arange(1, 1001) ** -1.1
    assert share[0] == pytest.approx(weight[0] / weight.sum(), rel=0.05)
    assert share[:10].sum() > 0.35 and share[-100:].sum() < 0.02


@pytest.mark.parametrize("got,want", [
    ([1, 3, 3, 1, 2, 3], [1, 0, 2, 4, 3, 5]),  # every record, in order
    ([3, 3], [0, 2]),  # a customer's k-th arrival is its k-th record
    ([2, 2], [3, -1]),  # one arrival more than was sent has no record
])
def test_arrivals_are_named_customer_by_customer(got, want):
    sent = np.array([3, 1, 3, 2, 1, 3])
    assert list(bus._by_customer_order(sent, np.array(got))) == want


@pytest.mark.parametrize("case,want", [
    ("sound", {"records_lost": 0, "records_doubled": 0,
               "records_out_of_order": 0, "customers_seen": 3}),
    ("lost", {"records_lost": 1, "records_doubled": 0}),
    ("doubled", {"records_doubled": 1}),
    ("scored_out_of_order", {"records_out_of_order": 2, "records_lost": 0}),
])
def test_keyed_accounting_goes_customer_by_customer(case, want):
    g = _generator(produced=6, consumed=6)
    g.traffic["keys"] = {"kind": "zipf", "customers": 4, "exponent": 1.1}
    g.customers = np.resize(np.array([3, 1, 3, 2, 1, 3]), bus.KEY_CYCLE)
    _, rows, _ = table.make_table(8, 1)
    order = [0, 1, 2, 3, 4, 5]
    if case == "scored_out_of_order":
        order = [2, 1, 0, 3, 4, 5]  # customer 3's first two, swapped
    g.h["stream"] = lambda: {
        "customer": g.customers[order], "x": rows[order],
        "proba": np.full(6, 0.1)}
    tap = FakeTap()
    started = [3, 1, 3, 2, 1, 3]
    if case == "lost":
        started = started[:-1]
    if case == "doubled":
        started = started + [2]
    tap.add(100.1, "standard", started, [0.1] * len(started))
    out = g._account(tap, t0=100.0, seconds=1.0, first_seq=None, tick_s=None,
                     counts=None, late=np.zeros(0), drained=True)
    for key, value in want.items():
        assert out.extra[key] == value, (key, out.extra)
    # records are named by their customer's order, whatever was scored
    assert list(out.stream["row"]) == [0, 1, 2, 3, 4, 5]


def test_a_saturated_rate_is_taken_between_the_windows_first_and_last_batch():
    """Ten-row batches stamped at 99.9 (before the window), 100.1, 100.5
    and 100.9: 30 rows arrive inside the window, and the 20 of the two
    later batches took the 0.8 s between the first stamp and the last."""
    from benchmark.readers import verdicts_per_second

    g = _generator(produced=40, consumed=40)
    tap = FakeTap()
    for k, when in enumerate((99.9, 100.1, 100.5, 100.9)):
        tap.add(when, "standard", list(range(10 * k, 10 * k + 10)),
                [0.1] * 10)
    out = g._account(tap, t0=100.0, seconds=1.0, first_seq=None, tick_s=None,
                     counts=None, late=np.zeros(0), drained=True)
    assert out.rows_in_window == 30 and out.attempted == 30
    assert out.rate_span == (pytest.approx(0.8), 20)
    assert verdicts_per_second.read({"outcome": out}, {}) == pytest.approx(25)
    out.rate_span = None  # a generator without batch stamps: the window's
    assert verdicts_per_second.read({"outcome": out}, {}) == pytest.approx(30)
