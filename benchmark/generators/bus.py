"""Bus traffic: the one generator every transaction-topic mix is data for.

A traffic file gives::

    {"generator": "bus", "warm_records": 20000,
     "arrivals": {"kind": "saturated", "max_backlog": 50000,
                  "batch_records": 4096}}
     "arrivals": {"kind": "ticks", "rate_per_s": 40000, "tick_ms": 5,
                  "warm_s": 1.0,
                  "burst": {"factor": 3, "every_s": 1.0, "for_s": 0.1}}

The deployment's broker lives in the process that holds the chip, so the
producer is a thread of it, as in ``bench.py::_bench_pipeline`` (the
feeder shares the interpreter lock with the router; that cost is part of
what the cell measures and is the same for every PR). Records are the
seeded table's CSV lines; a record's key is its sequence number, so after
the window every record is accounted for by name: routed once, where,
when and with what probability (``EngineTap``), or shed or failed.

With ``"keys": {"kind": "zipf", "customers": 100000, "exponent": 1.1}``
a record's key is instead a customer drawn from the seed, a few of them
sending most of the traffic (``customer_keys``). The bus keeps a key's
records in order, so a customer's k-th verdict belongs to its k-th
record: records are accounted for customer by customer, and the
deployment's score tap (``handles["stream"]``) gives every served
verdict in the order it was consumed, which is what a reference of a
history-dependent scorer needs.

``saturated`` (copied from ``_bench_pipeline``): a feeder keeps the topic
at most ``max_backlog`` records ahead of the consumer; its rate is taken
between the first and the last batch of verdicts stamped inside the window
(``Outcome.rate_span``). ``ticks`` is an open
loop: every ``tick_ms`` the records that came due are produced, ``burst``
multiplies the rate by ``factor`` for ``for_s`` out of every ``every_s``
at an offset drawn from the seed, and the base rate is lowered so that the
mean stays ``rate_per_s``. A record is timed from its tick's *scheduled*
instant to its process start.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.harness.core import Outcome
from benchmark.reference import table

DRAIN_S = 60.0  # a full backlog at the slowest cell's rate, with room
SAME_BATCH_S = 0.005  # a micro-batch takes tens of milliseconds


def tick_counts(rate_per_s: float, tick_s: float, seconds: float,
                burst: dict | None, seed: int) -> np.ndarray:
    """Records due at each tick of a window: a function of the seed alone.
    The running total follows the target rate exactly, so the mean over
    whole burst periods is ``rate_per_s`` whatever the burst."""
    n = int(round(seconds / tick_s))
    at = np.arange(n) * tick_s
    weight = np.ones(n)
    if burst:
        every, for_s = float(burst["every_s"]), float(burst["for_s"])
        offset = np.random.default_rng([int(seed), 0xB0B5]).uniform(0, every)
        in_burst = ((at - offset) % every) < for_s
        weight = np.where(in_burst, float(burst["factor"]), 1.0)
        weight = weight / (1.0 + (float(burst["factor"]) - 1.0)
                           * for_s / every)
    target = np.cumsum(weight * rate_per_s * tick_s)
    whole = np.floor(target + 1e-9).astype(np.int64)
    return np.diff(whole, prepend=0)


KEY_CYCLE = 1 << 22  # customer draws repeat after this many records


def customer_keys(keys: dict, seed: int) -> np.ndarray:
    """The customer of every record by sequence number (cycled): rank r
    among ``customers`` is drawn with weight r^-exponent, and the ranks are
    shuffled over the ids so that the heavy senders are not ids 0, 1, 2."""
    if keys["kind"] != "zipf":
        raise ValueError(f"unknown bus keys {keys['kind']!r}")
    n = int(keys["customers"])
    rng = np.random.default_rng([int(seed), 0xC057])
    weight = np.arange(1, n + 1, dtype=np.float64) ** -float(keys["exponent"])
    rank = rng.choice(n, size=KEY_CYCLE, p=weight / weight.sum())
    return rng.permutation(n)[rank].astype(np.int64)


class Generator:
    def __init__(self, traffic: dict, seed: int, root: str, workdir: str,
                 handles: dict, table_rows: int):
        self.traffic = traffic
        self.arrivals = traffic["arrivals"]
        self.seed = int(seed)
        self.h = handles
        self.table_rows = int(table_rows)
        self.produced = 0
        self.customers: np.ndarray | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def prepare(self) -> None:
        lines, _, _ = table.make_table(self.table_rows, self.seed)
        self.records = [line.encode() for line in lines]
        if "keys" in self.traffic:
            self.customers = customer_keys(self.traffic["keys"], self.seed)
            self._keys = self.customers.tolist()

    def _produce(self, n: int) -> None:
        """The next ``n`` records of the table, keyed by sequence number
        or by the sequence number's customer."""
        lo = self.produced
        while n > 0:
            at = lo % self.table_rows
            take = min(n, self.table_rows - at)
            if self.customers is None:
                keys = range(lo, lo + take)
            else:
                take = min(take, KEY_CYCLE - lo % KEY_CYCLE)
                keys = self._keys[lo % KEY_CYCLE:lo % KEY_CYCLE + take]
            self.h["broker"].produce_batch(
                self.h["topic"], self.records[at:at + take], keys)
            lo += take
            n -= take
        self.produced = lo

    def _wait_consumed(self, target: int, timeout_s: float) -> bool:
        deadline = time.perf_counter() + timeout_s
        while self.h["consumed"]() < target:
            if time.perf_counter() > deadline:
                return False
            time.sleep(0.005)
        return True

    def _in_thread(self, body) -> None:
        def guarded() -> None:
            try:
                body()
            except BaseException as e:  # noqa: BLE001 - re-raised in run()
                self._error = e

        self._thread = threading.Thread(target=guarded, daemon=True,
                                        name="bench-producer")
        self._thread.start()

    # -- saturated -----------------------------------------------------------
    def _feed(self) -> None:
        a = self.arrivals
        backlog, batch = int(a["max_backlog"]), int(a["batch_records"])
        while not self._stop.is_set():
            if self.produced - self.h["consumed"]() > backlog - batch:
                time.sleep(0.002)
                continue
            self._produce(batch)

    # -- ticks -----------------------------------------------------------------
    def _tick(self, t_begin: float, counts: np.ndarray, tick_s: float,
              late: np.ndarray) -> None:
        for k, n in enumerate(counts):
            due = t_begin + k * tick_s
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if self._stop.is_set():
                return
            late[k] = time.perf_counter() - due
            self._produce(int(n))

    def warm(self) -> None:
        self.h["start_router"]()
        self._produce(int(self.traffic["warm_records"]))
        if not self._wait_consumed(self.produced, 60.0):
            raise RuntimeError("the router did not take the warm-up records")
        if self.arrivals["kind"] == "saturated":
            target = self.produced + int(self.arrivals["max_backlog"])
            self._in_thread(self._feed)
            self._wait_consumed(target, 60.0)  # the feeder's steady state

    def run(self, seconds: float) -> Outcome:
        a = self.arrivals
        tap = self.h["tap"]
        late = np.zeros(0)
        if a["kind"] == "saturated":
            t0 = time.perf_counter()
            time.sleep(seconds)
            self._stop.set()
            first_seq, tick_s, counts = None, None, None
        elif a["kind"] == "ticks":
            tick_s = float(a["tick_ms"]) / 1e3
            warm_ticks = int(round(float(a["warm_s"]) / tick_s))
            counts = tick_counts(float(a["rate_per_s"]), tick_s, seconds,
                                 a.get("burst"), self.seed)
            base = int(round(float(a["rate_per_s"]) * tick_s))
            all_counts = np.concatenate(
                [np.full(warm_ticks, base, np.int64), counts])
            late = np.full(len(all_counts), np.nan)
            t_begin = time.perf_counter() + 0.05
            t0 = t_begin + warm_ticks * tick_s
            first_seq = self.produced + warm_ticks * base
            self._in_thread(
                lambda: self._tick(t_begin, all_counts, tick_s, late))
            self._thread.join(timeout=seconds + float(a["warm_s"]) + 30.0)
            late = late[warm_ticks:]
        else:
            raise ValueError(f"unknown bus arrivals {a['kind']!r}")
        self._thread.join(timeout=30.0)
        if self._thread.is_alive() or self._error is not None:
            raise RuntimeError(f"the producer failed: {self._error!r}")
        drained = self._wait_consumed(self.produced, DRAIN_S)
        self.h["stop_router"]()
        return self._account(tap, t0, float(seconds), first_seq, tick_s,
                             counts, late, drained)

    def _account(self, tap, t0, seconds, first_seq, tick_s, counts, late,
                 drained) -> Outcome:
        """Which record went where, when, with what probability."""
        total = sum(len(b[2]) for b in tap.batches)
        key = np.fromiter((v["customer_id"] for b in tap.batches
                           for v in b[2]), np.int64, count=total)
        proba = np.fromiter((v["proba"] for b in tap.batches for v in b[2]),
                            np.float64, count=total)
        started = np.fromiter((p is not None for b in tap.batches
                               for p in b[3]), bool, count=total)
        sizes = [len(b[2]) for b in tap.batches]
        when = np.repeat([b[0] for b in tap.batches], sizes)
        fraud = np.repeat([b[1] == "fraud" for b in tap.batches], sizes)
        key, proba, when, fraud = (key[started], proba[started],
                                   when[started], fraud[started])
        stream = None
        if self.customers is None:
            seq = key
            seen = np.bincount(seq, minlength=self.produced)
            doubled = int((seen > 1).sum())
            unseen = int((seen[:self.produced] == 0).sum())
            by_customer = {}
        else:
            sent = self.customers[np.arange(self.produced) % KEY_CYCLE]
            seq = _by_customer_order(sent, key)
            width = int(self.traffic["keys"]["customers"])
            surplus = (np.bincount(key, minlength=width)
                       - np.bincount(sent, minlength=width))
            doubled = int(surplus[surplus > 0].sum())
            unseen = int(-surplus[surplus < 0].sum())
            stream, by_customer = self._stream(sent)
        threshold = self.h["fraud_threshold"]
        extra = {
            "produced": self.produced,
            "produced_minus_consumed": self.produced - self.h["consumed"](),
            "drained_in_time": bool(drained),
            "records_doubled": doubled,
            # routed once, or counted by the router as shed or start error
            # (the deployment's counters close that sum)
            "records_lost": int(max(0, unseen
                                    - (total - int(started.sum()))
                                    - self.h["shed"]())),
            "route_mismatches": int((fraud != (proba >= threshold)).sum()),
            "fraud_starts": int(fraud.sum()),
            "standard_starts": int((~fraud).sum()),
            **by_customer,
        }
        in_window = (when >= t0) & (when < t0 + seconds)
        rate_span = None
        if counts is None:
            latency_ms = np.zeros(0)
            attempted = int(in_window.sum())
            failed = 0
            # verdicts come in whole micro-batches, so their count over
            # the fixed window moves in steps of a batch: the rate is
            # taken from the first batch's stamp in the window to the last
            # (a micro-batch's standard and fraud starts are stamped
            # microseconds apart: they are one batch)
            stamps = when[in_window]
            if len(stamps):
                first = stamps.min()
                rate_span = (float(stamps.max() - first),
                             int((stamps > first + SAME_BATCH_S).sum()))
        else:
            n = int(counts.sum())
            due = t0 + np.repeat(np.arange(len(counts)), counts) * tick_s
            verdict = np.full(n, np.inf)
            mine = (seq >= first_seq) & (seq < first_seq + n)
            verdict[seq[mine] - first_seq] = when[mine]
            latency_ms = (verdict - due) * 1e3
            attempted = n
            failed = int(np.isinf(verdict).sum())
        return Outcome(
            t0=t0, seconds=seconds, latency_ms=latency_ms,
            attempted=attempted, failed=failed,
            rows_in_window=int(in_window.sum()),
            served_rows=seq % self.table_rows, served_proba=proba,
            late_ms=late * 1e3, extra=extra, stream=stream,
            rate_span=rate_span)

    def _stream(self, sent: np.ndarray) -> tuple[dict, dict]:
        """The score tap's record of what was consumed, each record named
        by its sequence number (a customer's k-th consumed record is its
        k-th produced one), and held to the table: a record scored out of
        its customer's order, or with other features than were sent, is
        counted."""
        tapped = self.h["stream"]()
        customer = tapped["customer"]
        seq = _by_customer_order(sent, customer)
        _, rows, _ = table.make_table(self.table_rows, self.seed)
        known = seq >= 0
        wrong = ~known
        wrong[known] = (tapped["x"][known]
                        != rows[seq[known] % self.table_rows]).any(axis=1)
        stream = {"customer": customer, "row": seq % self.table_rows,
                  "proba": tapped["proba"]}
        return stream, {"records_out_of_order": int(wrong.sum()),
                        "customers_seen": int(len(np.unique(customer)))}

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)


def _by_customer_order(sent: np.ndarray, got: np.ndarray) -> np.ndarray:
    """The sequence number of every entry of ``got`` (customers, in the
    order their records arrived somewhere), given the customer of every
    sequence number: a customer's k-th arrival is its k-th record. -1
    where a customer arrived more often than it was sent."""
    sent_order = np.argsort(sent, kind="stable")
    got_order = np.argsort(got, kind="stable")
    width = int(max(sent.max(initial=0), got.max(initial=0))) + 1
    first_sent = np.r_[0, np.cumsum(np.bincount(sent, minlength=width))]
    n_got = np.bincount(got, minlength=width)
    first_got = np.r_[0, np.cumsum(n_got)]
    c = got[got_order]
    k = np.arange(len(got)) - first_got[c]  # arrival number within customer
    ok = k < (first_sent[c + 1] - first_sent[c])
    seq = np.full(len(got), -1, np.int64)
    seq[got_order[ok]] = sent_order[first_sent[c[ok]] + k[ok]]
    return seq
