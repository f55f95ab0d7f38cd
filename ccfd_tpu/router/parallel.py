"""Partition-parallel router fan-out with shared coalesced device dispatch.

One :class:`~ccfd_tpu.router.router.Router` thread consumes every bus
partition and serializes decode + engine hand-off even in the pipelined
loop, so a row model's pipeline sustains a fraction of what the same
scorer does alone. The reference scales this exact hop by Kafka
partitions × router replicas (reference deploy/frauddetection_cr.yaml
partitions, router.yaml replicas); the TPU-native analog is many consumer
workers feeding ONE accelerator through a coalescing batcher — the
"300M predictions/sec" pattern (arXiv:2109.09541), with the batch/deadline
budget SLO-bounded rather than fixed (InferLine, arXiv:1812.01776).

:class:`ParallelRouter` runs N worker loops (default = the transaction
topic's partition count; ``CCFD_ROUTER_WORKERS`` overrides under the
operator/CLI roles). Each worker is a full Router running the existing
pipelined poll→decode→dispatch→route stages and owning a disjoint
partition subset via ordinary consumer-group assignment — per-partition
ordering is therefore preserved by construction: a partition has exactly
one consuming worker, and that worker routes its batches in poll order.

What the workers SHARE is the control plane:

- **One device scorer behind a coalescing batcher** (serving/batcher.py
  DynamicBatcher): concurrent workers' sub-batches merge into one bucketed
  device dispatch — the same amortization the REST path gets — with the
  batcher's deadline bounding how long a lone worker's batch can wait for
  stragglers. ``router_coalesced_dispatches_total`` /
  ``router_coalesced_rows_total`` against ``router_worker_batches_total``
  show the fan-in. History-aware scorers (``score_with_ids``) bypass
  coalescing: their per-customer state keys on the decoded records, which
  a row-concatenating batcher cannot carry.
- **One in-flight budget** (router.InflightBudget): the bounded-in-flight
  shedding bound holds across ALL workers — N workers cannot hold N× the
  configured budget.
- **One circuit breaker** on the scorer edge (when the degradation ladder
  is on): the edge is shared, so its health accounting must be too.
- **One engine**: hand-off stays race-free because the Engine serializes
  every public entry point under its own RLock (process/engine.py) — the
  documented locked path; per-partition sharding is unnecessary because
  batched starts already amortize the lock per micro-batch, not per
  transaction.
- **A group-wide pause barrier**: ``pause()`` requests every worker's
  hold FIRST, then awaits all acks, so the checkpoint coordinator
  (runtime/recovery.py) sees the same guarantee as with one router —
  every consumed record fully routed, nothing in flight anywhere — before
  it reads an aligned cut.

Per-worker observability: each worker's batches are labelled
``router_worker_batches_total{worker=i}`` and its ``router.batch`` spans
carry a ``worker`` attr, so the PR-2 per-stage trace attribution survives
the fan-out.

The facade mirrors the Router surface the rest of the runtime touches
(pause/resume/recycle_consumers/swap_engine/engine/run/start/stop/close/
step and the ``_stop`` liveness flag), so the CheckpointCoordinator, the
Supervisor, the ChaosMonkey and the soak tools drive it unchanged.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Mapping

import numpy as np

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.router.router import EngineClient, InflightBudget, Router
from ccfd_tpu.router.rules import RuleSet


class ParallelRouter:
    def __init__(
        self,
        cfg: Config,
        broker: Broker,
        score_fn: Callable[[np.ndarray], np.ndarray],
        engine: EngineClient,
        registry: Registry | None = None,
        workers: int = 0,
        max_batch: int = 4096,
        rules: RuleSet | None = None,
        host_score_fn: Callable[[np.ndarray], np.ndarray] | None = None,
        breaker: "Any | None" = None,
        degrade: bool | None = None,
        max_inflight: int | None = None,
        tracer: "Any | None" = None,
        coalesce: bool = True,
        coalesce_max_batch: int | None = None,
        coalesce_deadline_ms: float | None = None,
        coalesce_workers: int = 2,
        overload: "Any | None" = None,
        profiler: "Any | None" = None,
        heal_gate: "Any | None" = None,
        audit: "Any | None" = None,
        commit_after_route: bool = False,
        decision_fn: "Any | None" = None,
    ):
        self.cfg = cfg
        self.broker = broker
        self.registry = registry or Registry()
        self.max_batch = max_batch
        if workers <= 0:
            workers = max(1, len(broker.end_offsets(cfg.kafka_topic)))
        self.n_workers = workers

        # -- shared in-flight budget (the global bound, not per worker) ----
        # An EXPLICIT max_inflight is a global statement: N workers share
        # it and cannot hold N× it. The default scales with the pool —
        # each worker's pipelined steady state legitimately holds up to
        # 2×max_batch (one batch in flight + one fresh poll), so the
        # pool-wide default is 2×max_batch×workers: healthy operation
        # never sheds, exactly like the single-router default.
        #
        # With an OverloadControl (runtime/overload.py) the pool shares
        # ITS adaptive AIMD budget instead: one limit, moved by every
        # worker's scorer-latency observations, bounding the whole pool —
        # the same global-across-workers semantics, made dynamic.
        self._overload = overload
        if overload is not None:
            self._budget = overload.budget
            self.max_inflight = self._budget.limit
        else:
            self.max_inflight = (int(max_inflight)
                                 if max_inflight is not None
                                 else 2 * max_batch * workers)
            self._budget = InflightBudget(self.max_inflight,
                                          registry=self.registry)

        # -- shared scorer edge: one breaker, one coalescing batcher -------
        self._degrade = (degrade if degrade is not None
                         else (host_score_fn is not None
                               or breaker is not None))
        if self._degrade and breaker is None:
            from ccfd_tpu.router.router import default_scorer_breaker

            breaker = default_scorer_breaker(self.registry)
        self._breaker = breaker

        self.batcher = None
        worker_score: Any = score_fn
        # The fused decision plane bypasses the coalescing batcher the
        # same way history-aware scorers do: its decide() IS the device
        # dispatch (score + rules in one executable) and chunks on the
        # scorer's own bucket ladder — a row-concatenating batcher in
        # front would only re-split what decide re-buckets anyway, and
        # its proba-only wire cannot carry the fired-index column back.
        if (coalesce and workers > 1 and decision_fn is None
                and not callable(getattr(score_fn, "score_with_ids", None))):
            from ccfd_tpu.serving.batcher import DynamicBatcher

            c_disp = self.registry.counter(
                "router_coalesced_dispatches_total",
                "device dispatches made on behalf of the worker pool — "
                "fewer than router_worker_batches_total means concurrent "
                "workers' sub-batches coalesced",
            )
            c_rows = self.registry.counter(
                "router_coalesced_rows_total",
                "transaction rows scored through the coalescing batcher",
            )

            def on_dispatch(n_rows: int) -> None:
                c_disp.inc()
                c_rows.inc(n_rows)

            self.batcher = DynamicBatcher(
                score_fn,
                # one dispatch can absorb every worker's full poll; the
                # scorer's own shape bucketing pads it to a compiled size
                max_batch=(coalesce_max_batch
                           or max_batch * workers),
                deadline_ms=(cfg.batch_deadline_ms
                             if coalesce_deadline_ms is None
                             else coalesce_deadline_ms),
                on_dispatch=on_dispatch,
                workers=max(1, coalesce_workers),
            )
            worker_score = self.batcher.score

        self.workers = [
            Router(
                cfg, broker, worker_score, engine, self.registry,
                max_batch=max_batch, rules=rules,
                host_score_fn=host_score_fn, breaker=self._breaker,
                degrade=degrade, max_inflight=self.max_inflight,
                tracer=tracer, inflight_budget=self._budget, worker_id=i,
                overload=overload, profiler=profiler, heal_gate=heal_gate,
                # ONE shared decision-provenance log: every worker stamps
                # into the same ring/segments, so conservation (routed ==
                # recorded) holds across the pool, like the budget bound
                audit=audit,
                commit_after_route=commit_after_route,
                decision_fn=decision_fn,
            )
            for i in range(workers)
        ]
        self._c_in = self.registry.counter(
            "transaction_incoming_total", "transactions consumed")
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    # -- facade ------------------------------------------------------------
    @property
    def engine(self) -> EngineClient:
        return self.workers[0].engine

    def step(self, poll_timeout_s: float = 0.0) -> int:
        """One synchronous cycle across every worker (tests/tools). Workers
        step sequentially on the calling thread; with the batcher on, each
        lone submit dispatches immediately (the batcher's lone-request
        fast path), so step() stays deterministic."""
        return sum(w.step(poll_timeout_s) for w in self.workers)

    # -- group-wide checkpoint barrier -------------------------------------
    def pause(self, timeout_s: float = 10.0) -> bool:
        """Group-wide batch-boundary hold: EVERY worker parked with its
        in-flight batch fully routed. Holds are requested on all workers
        up front, then acks awaited against one shared deadline — on True
        nothing consumed-but-unrouted exists anywhere in the pool (the
        shared batcher is necessarily idle: each worker waits out its own
        submission before acking), which is exactly the cut-consistency
        the checkpoint coordinator needs."""
        import time

        deadline = time.monotonic() + timeout_s
        for w in self.workers:
            w.request_pause()
        ok = True
        for w in self.workers:
            ok = w.await_pause(max(0.0, deadline - time.monotonic())) and ok
        return ok

    def resume(self) -> None:
        for w in self.workers:
            w.resume()

    def recycle_consumers(self) -> None:
        """Close and recreate every worker's consumers (crash recovery,
        with the group barrier held). Each recycle is a group rebalance;
        after the last one the pool holds a fresh disjoint assignment."""
        for w in self.workers:
            w.recycle_consumers()

    def set_heal_gate(self, gate: "Any | None") -> None:
        """Point every worker's degradation ladder at the device heal
        gate (runtime/heal.py) — the pool shares ONE DeviceSupervisor,
        like it shares one breaker and one budget."""
        for w in self.workers:
            w.set_heal_gate(gate)

    def swap_engine(self, engine: EngineClient) -> None:
        for w in self.workers:
            w.swap_engine(engine)

    # -- daemon loop (Supervisor-shaped: run blocks, stop unblocks) --------
    def reset(self) -> None:
        self._stop.clear()
        for w in self.workers:
            w.reset()

    def run(self, poll_timeout_s: float = 0.05, pipeline: bool = True) -> None:
        """Spawn one driver thread per worker and block until stop(). The
        supervisor treats this exactly like Router.run: the service body
        blocks, stop() unblocks it, reset() re-arms for the respawn.

        Crash visibility: a worker loop crash must not strand its
        partition subset behind a run() that still looks healthy — the
        first crash stops the WHOLE pool and re-raises out of run(), so
        the supervisor sees the failure and respawns the service exactly
        as it would for a crashed single Router."""
        crashes: list[BaseException] = []

        def worker_main(w: Router) -> None:
            try:
                # keyed on the POOL's stop flag: a driver that unwedges
                # long after a previous shutdown (its own Router._stop was
                # set back then) re-enters the loop instead of exiting,
                # so a reused zombie driver can never strand its worker
                while not self._stop.is_set():
                    w.reset()
                    w.run(poll_timeout_s, pipeline)
            # ccfd-lint: disable=counted-drops -- not a drop: the crash is collected and re-raised out of run() for the supervisor
            except BaseException as e:  # noqa: BLE001 - propagate via run()
                crashes.append(e)
                self.stop()

        # reuse still-alive drivers from a previous incarnation (a worker
        # wedged in a device score can outlive the last shutdown's bounded
        # join): spawning a SECOND driver for the same Router would race
        # its consumers and corrupt the shared budget accounting once the
        # zombie unwedges — the zombie itself resumes as the driver
        threads: list[threading.Thread] = []
        for i, w in enumerate(self.workers):
            old = self._threads[i] if i < len(self._threads) else None
            if old is not None and old.is_alive():
                threads.append(old)
                continue
            t = threading.Thread(
                target=worker_main, args=(w,),
                daemon=True, name=f"ccfd-router-w{i}",
            )
            threads.append(t)
            t.start()
        self._threads = threads
        self._stop.wait()
        for w in self.workers:
            w.stop()
        for t in threads:
            t.join(timeout=30)
        if crashes:
            raise crashes[0]

    def start(
        self, poll_timeout_s: float = 0.05, pipeline: bool = True
    ) -> threading.Thread:
        self.reset()
        t = threading.Thread(
            target=self.run, args=(poll_timeout_s, pipeline),
            daemon=True, name="ccfd-router",
        )
        t.start()
        return t

    def stop(self) -> None:
        self._stop.set()
        for w in self.workers:
            w.stop()

    def close(self) -> None:
        self.stop()
        for w in self.workers:
            w.close()
        if self.batcher is not None:
            self.batcher.stop()
