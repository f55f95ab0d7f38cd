"""Deadline-bounded device dispatch for the serving path.

Reference parity: the reference's only failure knob on the scoring hop is the
*client-side* HTTP timeout ``SELDON_TIMEOUT`` (`/root/reference/README.md:386-393`).
When a device wedges mid-dispatch (a device sync that never returns, so the
blocked thread never does either), a client-side timeout alone leaves the
*server* accumulating stuck taker threads and an unbounded p99.
This module is the server-side half: device work runs on a small pool of
sacrificial threads; the caller waits at most a deadline, and on expiry the
scorer falls back to its host forward (or raises :class:`ScorerTimeout`,
which the REST fronts map to 503) while a background probe watches for the
device to heal. This is safety code for a device that stops answering WHILE
serving; start-up has no such fallback (``Scorer.warmup`` raises).

A truly wedged dispatch thread cannot be cancelled (the hang is inside the
runtime, holding the GIL released); it is deliberately leaked — daemonized,
its ticket abandoned — and the pool refuses new device work once
``max_threads`` are stuck, so a flapping attachment can't leak unboundedly.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable

log = logging.getLogger("ccfd_tpu.dispatch")


class ScorerTimeout(Exception):
    """Device dispatch exceeded its deadline and no host fallback exists."""


class _Ticket:
    __slots__ = ("done", "result", "error", "abandoned")

    def __init__(self) -> None:
        self.done = threading.Event()
        self.result: Any = None
        self.error: BaseException | None = None
        self.abandoned = False  # set by the waiter on timeout


class DeviceDispatcher:
    """Run callables on worker threads with a per-call deadline.

    Workers are spawned lazily up to ``max_threads``; above the cap, calls
    queue and the deadline covers queue wait + execution, so healthy
    concurrency beyond the cap degrades to waiting — it is never mistaken
    for a wedge (only a genuine deadline expiry is). A worker that picks up
    a ticket whose waiter already gave up skips it (the work would be stale
    device churn executed after the attachment heals).
    """

    def __init__(self, max_threads: int = 4, name: str = "ccfd-dispatch"):
        self.max_threads = int(max_threads)
        self._name = name
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._n_threads = 0
        self._n_idle = 0
        self._seq = 0

    def _spawn_locked(self) -> None:
        self._seq += 1
        t = threading.Thread(
            target=self._worker, name=f"{self._name}-{self._seq}", daemon=True
        )
        self._n_threads += 1
        self._n_idle += 1
        t.start()

    def _worker(self) -> None:
        while True:
            ticket, fn = self._q.get()
            with self._lock:
                self._n_idle -= 1
            if ticket.abandoned:
                with self._lock:
                    self._n_idle += 1
                continue
            try:
                ticket.result = fn()
            # ccfd-lint: disable=counted-drops -- not a drop: ticket.error re-raises at the waiter in call()
            except BaseException as e:  # noqa: BLE001 - delivered to waiter
                ticket.error = e
            ticket.done.set()
            with self._lock:
                self._n_idle += 1

    def call(self, fn: Callable[[], Any], deadline_s: float) -> Any:
        """Run ``fn`` with a deadline covering queue wait + execution.
        Raises :class:`ScorerTimeout` on expiry."""
        with self._lock:
            if self._n_idle == 0 and self._n_threads < self.max_threads:
                self._spawn_locked()
        ticket = _Ticket()
        self._q.put((ticket, fn))
        if ticket.done.wait(timeout=deadline_s):
            if ticket.error is not None:
                raise ticket.error
            return ticket.result
        ticket.abandoned = True
        raise ScorerTimeout(f"device dispatch exceeded {deadline_s:.3f}s")


class WedgeMonitor:
    """Tracks whether the device attachment is believed wedged and probes for
    recovery so serving can return to the device path without manual action.

    ``probe_fn`` must be a cheap device round trip (a tiny dispatch). It runs
    through the same :class:`DeviceDispatcher` so a still-wedged attachment
    costs one sacrificial thread per probe interval at worst — and the
    dispatcher's thread cap bounds even that.
    """

    def __init__(
        self,
        dispatcher: DeviceDispatcher,
        probe_fn: Callable[[], Any],
        deadline_s: float,
        probe_interval_s: float = 10.0,
    ):
        self._dispatcher = dispatcher
        self._probe_fn = probe_fn
        self._deadline_s = float(deadline_s)
        self._probe_interval_s = float(probe_interval_s)
        self._lock = threading.Lock()
        self._wedged_since: float | None = None
        self._prober: threading.Thread | None = None
        self.on_change: Callable[[bool], None] | None = None

    @property
    def wedged(self) -> bool:
        with self._lock:
            return self._wedged_since is not None

    @property
    def wedged_for_s(self) -> float:
        with self._lock:
            if self._wedged_since is None:
                return 0.0
            return time.monotonic() - self._wedged_since

    def mark_wedged(self) -> None:
        with self._lock:
            first = self._wedged_since is None
            if first:
                self._wedged_since = time.monotonic()
            # _prober is None exactly when no prober loop will make another
            # pass: the loop only exits under this lock after nulling it
            # (an is_alive() check would race with a prober between its
            # final wedged-check and thread exit)
            start_prober = first and self._prober is None
            if start_prober:
                self._prober = threading.Thread(
                    target=self._probe_loop, name="ccfd-wedge-probe", daemon=True
                )
                self._prober.start()
        if first and self.on_change is not None:
            try:
                self.on_change(True)
            except Exception:  # noqa: BLE001 - observer must not break serving
                log.warning("wedge observer raised on mark_wedged",
                            exc_info=True)

    def _clear(self) -> None:
        with self._lock:
            was = self._wedged_since is not None
            self._wedged_since = None
        if was and self.on_change is not None:
            try:
                self.on_change(False)
            except Exception:  # noqa: BLE001 - observer must not break serving
                log.warning("wedge observer raised on clear", exc_info=True)

    def _probe_loop(self) -> None:
        while True:
            with self._lock:
                if self._wedged_since is None:
                    # exit is atomic with nulling the handle: a concurrent
                    # mark_wedged either sees _prober set (and this loop's
                    # next pass picks the new wedge up) or spawns a fresh one
                    self._prober = None
                    return
            try:
                self._dispatcher.call(self._probe_fn, self._deadline_s)
            except ScorerTimeout:
                time.sleep(self._probe_interval_s)
                continue
            # ccfd-lint: disable=counted-drops -- a failing probe is the wedged steady state, already exported via the wedge gauge; per-interval logs would spam
            except Exception:  # noqa: BLE001 - a failing probe is not recovery
                time.sleep(self._probe_interval_s)
                continue
            self._clear()
            # loop: the exit decision happens under the lock above
