"""The history pipeline, wired as ``bench.py::_bench_seq_pipeline`` wires
it: customer-keyed records on the transaction topic -> ``Router``
micro-batch -> ``SeqScorer`` (``HistoryStore`` assembly on the host, one
(B, L, 30) dispatch of the ``seq`` transformer per chunk) -> the threshold
rule -> standard or fraud process start on ``build_engine``'s engine; an
in-process ``Broker``, one router.

The program has no committed checkpoint of the family, so the weights are
the benchmark's, one draw served in every run
(``reference/seq_f32.make_params``), handed to the program as its own
tree; the control quantises the same tree with the program's
``ops/seq_quant`` (its int8 path).

Two taps, both delegating wrappers that keep references and read a clock
once a batch: ``EngineTap`` at the engine boundary (when, where, with what
probability) and ``ScoreTap`` around ``score_with_ids`` (which customer's
record was scored in which order, with what probability: a verdict here
depends on what the store held, so the reference needs the order).
Guarantees held: ``kafka_pipeline``'s (every record routed exactly once or
counted; none lost, none doubled; no degraded tier), a customer's records
scored in the order they were produced, nothing evicted from the store,
no commit dropped or skipped, no record scored without its history.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.deployments.kafka_pipeline import EngineTap
from benchmark.reference import seq_f32, table


class ScoreTap:
    """What the router hands the scorer and what comes back, per call."""

    def __init__(self, scorer):
        self._scorer = scorer
        self.score = scorer.score_with_ids
        self.calls: list[tuple[list, np.ndarray, np.ndarray]] = []

    def score_with_ids(self, txs, x):
        proba = self.score(txs, x)
        self.calls.append((txs, x, proba))
        return proba

    def __call__(self, x):
        raise RuntimeError("the history scorer was called without records")

    def stream(self) -> dict:
        """Every scored record in the order it was consumed."""
        n = sum(len(c[0]) for c in self.calls)
        return {
            "customer": np.fromiter((t["id"] for c in self.calls
                                     for t in c[0]), np.int64, count=n),
            "x": (np.concatenate([c[1] for c in self.calls])
                  if self.calls else np.zeros((0, table.NUM_FEATURES))),
            "proba": (np.concatenate([np.asarray(c[2], np.float64)
                                      for c in self.calls])
                      if self.calls else np.zeros(0)),
        }


class Deployment:
    def __init__(self, config: dict, *, root: str, control: bool,
                 traced: bool, seed: int = 0):
        self.config = config
        self.root = root
        self.control = control
        self.traced = traced
        self.router = None
        self.thread = None
        self.profiler = None

    def start(self) -> dict:
        import jax

        from ccfd_tpu.bus.broker import Broker
        from ccfd_tpu.config import Config
        from ccfd_tpu.metrics.prom import Registry
        from ccfd_tpu.process.fraud import build_engine
        from ccfd_tpu.serving.history import SeqScorer
        from ccfd_tpu.utils.gctune import tune_for_service

        s, r = self.config["serving"], self.config["router"]
        params = jax.device_put(seq_f32.make_params(self.config["model"]))
        if self.control:
            from ccfd_tpu.ops.seq_quant import quantize_seq

            params = quantize_seq(params)
        self.cfg = dataclasses.replace(
            Config(), kafka_topic=r["topic"],
            fraud_threshold=float(r["fraud_threshold"]))
        self.broker = Broker()
        self.registry = Registry()
        self.tap = EngineTap(
            build_engine(self.cfg, self.broker, self.registry, None))
        self.scorer = SeqScorer(
            params, length=int(s["length"]),
            batch_sizes=tuple(s["batch_sizes"]),
            compute_dtype=s["compute_dtype"],
            max_customers=int(s["max_customers"]),
            inflight=int(s["inflight"]), registry=self.registry)
        self.scorer.warmup()
        tune_for_service()
        self.score_tap = ScoreTap(self.scorer)
        if self.traced:  # the program's own stage timings, traced run only
            from ccfd_tpu.observability.profile import StageProfiler

            self.profiler = StageProfiler()
        return {"broker": self.broker, "topic": r["topic"], "tap": self.tap,
                "start_router": self.start_router,
                "consumed": self.consumed, "stop_router": self.stop_router,
                "shed": lambda: int(self.registry.counter(
                    "router_shed_total").total()),
                "fraud_threshold": float(r["fraud_threshold"]),
                "stream": self.score_tap.stream}

    def wrap_score(self, wrap) -> None:
        self.score_tap.score = wrap(self.score_tap.score)

    def start_router(self) -> None:
        """Called by the generator once the score callable is final."""
        from ccfd_tpu.router.router import Router

        r = self.config["router"]
        self.router = Router(
            self.cfg, self.broker, self.score_tap, self.tap, self.registry,
            max_batch=int(r["max_batch"]), profiler=self.profiler)
        self.thread = self.router.start(
            poll_timeout_s=float(r["poll_timeout_s"]), pipeline=True)

    def consumed(self) -> int:
        return int(self.registry.counter(
            "transaction_incoming_total").value())

    def stop_router(self) -> None:
        if self.router is not None:
            self.router.stop()
            self.thread.join(timeout=60)
            if self.thread.is_alive():
                raise RuntimeError("the router did not stop")
            self.router = None

    def counters(self) -> dict:
        reg = self.registry
        grid = self.scorer.executable_grid()
        dispatches: dict[int, int] = {}
        for g in grid["grid"]:
            b = int(g["b_bucket"])
            dispatches[b] = dispatches.get(b, 0) + int(g["dispatches"])
        assembly = reg.get("seq_assembly_seconds")
        wait = reg.get("seq_dispatch_seconds")
        return {
            "model": grid["model"],
            "dispatches": dispatches,
            "assembly_seconds_sum": float(assembly.sum()),
            "assembly_seconds_count": int(assembly.count()),
            "dispatch_wait_seconds_sum": float(wait.sum()),
            "dispatch_wait_seconds_count": int(wait.count()),
            "anonymous_rows": int(reg.counter(
                "seq_anonymous_rows_total").total()),
            "stale_commits": int(reg.counter(
                "seq_stale_commits_total").total()),
            "contended_skips": int(self.scorer.store.contended_skips),
            "customers_in_store": len(self.scorer.store),
            "consumed": self.consumed(),
            "routed": int(reg.counter("transaction_outgoing_total").total()),
            "shed": int(reg.counter("router_shed_total").total()),
            "start_errors": int(reg.counter(
                "router_process_start_errors_total").total()),
            "degraded": int(reg.counter("router_degraded_total").total()),
        }

    def check_guarantees(self, checks, before, after, outcome) -> None:
        s = self.config["serving"]
        top = max(int(b) for b in s["batch_sizes"])
        dispatched = sum(after["dispatches"].values()) - sum(
            before["dispatches"].values())
        checks.at_least("device_dispatches", dispatched, 1)
        # the largest program is what fills the device's memory: a window
        # that never ran it would report a peak that only warm-up reached
        checks.at_least("top_bucket_dispatches", after["dispatches"].get(
            top, 0) - before["dispatches"].get(top, 0), 1)
        checks.exactly("served_model", after["model"],
                       "seq_q8" if self.control else "seq")
        checks.exactly("router_degraded_total", after["degraded"], 0)
        checks.exactly("consumed_minus_routed_shed_errors",
                       after["consumed"] - after["routed"] - after["shed"]
                       - after["start_errors"], 0)
        checks.exactly("records_missed", outcome.failed, 0)
        for key in ("produced_minus_consumed", "records_lost",
                    "records_doubled", "route_mismatches",
                    "records_out_of_order"):
            checks.exactly(key, outcome.extra[key], 0)
        for key in ("anonymous_rows", "stale_commits", "contended_skips"):
            checks.exactly(key, after[key], 0)
        checks.exactly("customers_in_store_minus_seen",
                       after["customers_in_store"]
                       - outcome.extra["customers_seen"], 0)

    def stop(self) -> None:
        self.stop_router()
        broker = getattr(self, "broker", None)
        if broker is not None:
            broker.close()
