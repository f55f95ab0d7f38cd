"""Dynamic request batching: concurrent predicts coalesce into one dispatch.

SURVEY.md §7 stage 2 specifies the serving shape as "request -> micro-batch
queue -> TPU", and hard part (d) is the policy: batch enough to hit 50k tx/s
without blowing the p99 <10 ms budget. The reference has no equivalent —
its Seldon pod scores each HTTP request alone, which is exactly the
per-request dispatch overhead this framework exists to amortize.

Policy (adaptive, not a fixed delay):

- The worker blocks until at least one request is queued, then drains
  whatever else is ALREADY waiting — a lone sequential client therefore
  pays zero added latency.
- If the non-blocking drain found company (a concurrency signal), the
  worker keeps collecting up to ``deadline_ms`` or ``max_batch`` — under
  load, dispatches grow toward the efficient bucket sizes instead of
  degenerating into per-request launches.
- One ``scorer.score`` call serves the whole batch; rows route back to
  their requests' futures. A scorer failure fails exactly the requests in
  that batch, never the worker.
- ``workers`` > 1 OVERLAPS dispatches: while one batch is on the wire to
  the device, another
  worker is already collecting and launching the next. Under continuous
  load a single worker makes every request wait for the in-flight
  dispatch *plus* its own (~2x device RTT); overlapping brings the queue
  wait back down toward one RTT and multiplies throughput by the
  pipeline depth the device can absorb. XLA dispatch is thread-safe and
  releases the GIL, so workers genuinely overlap.

This composes with the Scorer's shape bucketing: the batcher decides WHEN
to dispatch, the scorer pads the result to a compiled bucket.

Overload policy (runtime/overload.py; both knobs default OFF, preserving
the historical unbounded-queue semantics):

- ``codel`` (a :class:`~ccfd_tpu.runtime.overload.DeadlinePolicy`)
  CoDel-style drops stale requests FROM THE FRONT at dispatch-assembly
  time: a request whose queue sojourn exceeds its priority class's target
  fails with :class:`~ccfd_tpu.runtime.overload.OverloadShed` (the REST
  fronts map it to 429 + retry-after) instead of reaching the device —
  serving already-blown work at saturation just blows the SLO for
  everything queued behind it.
- ``max_queue_rows`` bounds the queue with priority-aware eviction: an
  arrival past the bound evicts queued LOWER-priority work (front first)
  to make room, or — when the arrival is itself the cheapest — is refused
  synchronously with ``OverloadShed``.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Callable

import numpy as np


class DynamicBatcher:
    def __init__(
        self,
        score_fn: Callable[[np.ndarray], np.ndarray],
        max_batch: int = 16384,
        deadline_ms: float = 2.0,
        on_dispatch: Callable[[int], None] | None = None,
        workers: int = 1,
        codel: "object | None" = None,
        max_queue_rows: int = 0,
        on_shed: Callable[[int, int], None] | None = None,
        profiler: "object | None" = None,
        profile_stage: str = "rest",
    ):
        self._score = score_fn
        # stage profiler (observability/profile.py): per coalesced
        # dispatch, feed the queue-sojourn / device-dispatch split under
        # "<profile_stage>.batcher" / "<profile_stage>.dispatch" — the
        # measured layers of the REST latency-budget ledger
        self._profiler = profiler
        self._stage_queue = f"{profile_stage}.batcher"
        self._stage_dispatch = f"{profile_stage}.dispatch"
        self.max_batch = max_batch
        self.deadline_s = max(0.0, deadline_ms) / 1e3
        self._on_dispatch = on_dispatch
        # entries: (x, future, enqueue_ts, priority)
        self._queue: list[tuple[np.ndarray, Future, float, int]] = []
        self._queued_rows = 0
        self._codel = codel
        self._max_queue_rows = int(max_queue_rows)
        self._on_shed = on_shed  # (rows, priority) per shed decision
        self._cv = threading.Condition()
        self._stats_mu = threading.Lock()  # shed_rows: updated from both
        # submit (client) threads and worker threads, with/without _cv
        self._stop = False
        self.dispatches = 0  # observability: how many TPU launches happened
        self.rows = 0
        self.shed_rows = 0
        self._threads = [
            threading.Thread(target=self._run, daemon=True, name=f"ccfd-batcher-{i}")
            for i in range(max(1, workers))
        ]
        for t in self._threads:
            t.start()

    # -- client side -------------------------------------------------------
    def submit(self, x: np.ndarray, priority: int = 1) -> "Future[np.ndarray]":
        """Enqueue a (n, F) request; the future resolves to its (n,) slice.
        Raises :class:`~ccfd_tpu.runtime.overload.OverloadShed` when the
        bounded queue refuses the request (overload admission)."""
        x = np.ascontiguousarray(x, np.float32)
        f: "Future[np.ndarray]" = Future()
        n = x.shape[0]
        shed: list[tuple[np.ndarray, Future, float, int]] = []
        with self._cv:
            if self._stop:
                raise RuntimeError("batcher is stopped")
            if (self._max_queue_rows
                    and self._queued_rows + n > self._max_queue_rows):
                if self._queued_rows == 0:
                    pass  # idle-pass (the gate's rule): a lone oversize
                    # request runs alone rather than starving forever
                else:
                    # feasibility FIRST: evicting queued serviceable work
                    # is only justified when it actually makes the
                    # arrival fit — otherwise refuse the arrival and
                    # destroy nothing
                    evictable = sum(
                        e[0].shape[0] for e in self._queue
                        if e[3] < priority)
                    if (self._queued_rows - evictable + n
                            > self._max_queue_rows):
                        self._shed_arrival(n, priority)
                    shed = self._evict_locked(n, priority)
            self._queue.append((x, f, time.perf_counter(), priority))
            self._queued_rows += n
            self._cv.notify()
        self._fail_shed(shed)
        return f

    def _shed_arrival(self, n: int, priority: int):
        """Refuse the arriving request itself (counted, synchronous)."""
        with self._stats_mu:
            self.shed_rows += n
        if self._on_shed is not None:
            self._on_shed(n, priority)
        from ccfd_tpu.runtime.overload import OverloadShed

        raise OverloadShed("serving batcher queue full")

    def _evict_locked(self, need_rows: int, priority: int):
        """Caller holds ``self._cv``. Pop queued entries of LOWER priority
        (front first — the oldest, closest to going stale anyway) until
        ``need_rows`` fit; returns the evictees for the caller to fail
        outside the lock."""
        shed = []
        i = 0
        while (self._queued_rows + need_rows > self._max_queue_rows
               and i < len(self._queue)):
            if self._queue[i][3] < priority:
                entry = self._queue.pop(i)
                self._queued_rows -= entry[0].shape[0]
                shed.append(entry)
            else:
                i += 1
        return shed

    def _fail_shed(self, shed) -> None:
        if not shed:
            return
        from ccfd_tpu.runtime.overload import OverloadShed

        for x, f, _enq, pri in shed:
            # dedicated stats lock: submit threads and batcher workers
            # both shed, and a lost += here would undercount the shed
            # accounting the SLO harness gates on
            with self._stats_mu:
                self.shed_rows += x.shape[0]
            if self._on_shed is not None:
                self._on_shed(x.shape[0], pri)
            if not f.done():
                f.set_exception(OverloadShed(
                    "shed from the serving queue for higher-priority work"))

    def score(self, x: np.ndarray, priority: int = 1) -> np.ndarray:
        """Synchronous convenience: submit + wait."""
        return self.submit(x, priority=priority).result()

    def qsize(self) -> int:
        """Requests currently queued (not yet taken by a worker) — the
        public depth surface monitoring probes read."""
        with self._cv:
            return len(self._queue)

    # -- worker ------------------------------------------------------------
    def _take_first(self) -> list:
        with self._cv:
            while not self._queue and not self._stop:
                self._cv.wait()
            batch = self._queue
            self._queue = []
            self._queued_rows = 0
            return batch

    def _drain_locked(self, room: int) -> list:
        """Caller holds self._cv. Pops queued requests that fit in ``room``;
        a request bigger than the remaining room stays queued for its own
        dispatch (merging it would make the whole batch wait for a
        multi-bucket score)."""
        take: list = []
        while self._queue and room > 0:
            x = self._queue[0][0]
            if x.shape[0] > room:
                break
            take.append(self._queue.pop(0))
            self._queued_rows -= x.shape[0]
            room -= x.shape[0]
        return take

    def _shed_stale(self, batch: list) -> list:
        """CoDel-style deadline policy at dispatch assembly: entries whose
        queue sojourn exceeds their class target drop FROM THE FRONT (the
        queue is FIFO, so stale entries are the head) and fail with
        OverloadShed; fresh work behind them still makes the dispatch."""
        if self._codel is None or not batch:
            return batch
        now = time.perf_counter()
        # head-first cheap check: fresh head == fresh batch
        if now - batch[0][2] <= self._codel.target_s:
            return batch
        kept: list = []
        shed: list = []
        for entry in batch:
            if self._codel.should_drop(now - entry[2], entry[3]):
                shed.append(entry)
            else:
                kept.append(entry)
        self._fail_shed(shed)
        return kept

    def _run(self) -> None:
        while True:
            batch = self._take_first()
            if self._stop and not batch:
                return
            size = sum(x.shape[0] for x, _f, _e, _p in batch)
            # company in the queue at grab time = concurrency: keep
            # collecting toward the deadline. Lone request: dispatch now.
            if len(batch) > 1 and self.deadline_s > 0:
                deadline = time.perf_counter() + self.deadline_s
                # grace: how long to wait for the NEXT arrival before
                # giving up. Waiting out the whole deadline after arrivals
                # dry up just parks every merged request for the residual —
                # with a bounded client pool the queue drains in one sweep
                # and nothing else is coming for a full round trip.
                grace = self.deadline_s / 8.0
                with self._cv:
                    while size < self.max_batch and not self._stop:
                        more = self._drain_locked(self.max_batch - size)
                        if more:
                            batch.extend(more)
                            size += sum(x.shape[0] for x, _f, _e, _p in more)
                            continue
                        if self._queue:
                            break  # head doesn't fit: give it its own dispatch
                        remaining = deadline - time.perf_counter()
                        # wait wakes on submit's notify, else the grace
                        # lapses and the batch goes — no busy polling
                        if remaining <= 0 or not self._cv.wait(
                            timeout=min(grace, remaining)
                        ):
                            break
            batch = self._shed_stale(batch)
            if batch:
                self._dispatch(batch)

    def _dispatch(self, batch: list) -> None:
        xs = [x for x, _f, _e, _p in batch]
        n_rows = int(sum(x.shape[0] for x in xs))
        t0 = time.perf_counter()
        if self._profiler is not None:
            # queue sojourn up to dispatch assembly, row-weighted mean —
            # the "batcher_wait" layer of the REST budget ledger
            wait = sum((t0 - e) * x.shape[0]
                       for x, _f, e, _p in batch) / max(1, n_rows)
            self._profiler.observe(self._stage_queue, queue_s=wait,
                                   rows=n_rows)
        try:
            proba = self._score(np.concatenate(xs) if len(xs) > 1 else xs[0])
        except Exception as e:  # noqa: BLE001 - fail the batch, not the worker
            for _x, f, _e2, _p in batch:
                if not f.cancelled():
                    f.set_exception(e)
            return
        if self._profiler is not None:
            self._profiler.observe(
                self._stage_dispatch,
                dispatch_s=time.perf_counter() - t0,
                batch=n_rows, rows=n_rows)
        with self._cv:  # workers share the stats; += alone would race
            self.dispatches += 1
            self.rows += n_rows
        if self._on_dispatch is not None:
            self._on_dispatch(n_rows)
        off = 0
        for x, f, _e, _p in batch:
            n = x.shape[0]
            if not f.cancelled():
                f.set_result(np.asarray(proba[off : off + n]))
            off += n

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        # fail anything still queued so no caller blocks forever
        with self._cv:
            leftovers = self._queue
            self._queue = []
            self._queued_rows = 0
        for _x, f, _e, _p in leftovers:
            if not f.done():
                f.set_exception(RuntimeError("batcher stopped"))
