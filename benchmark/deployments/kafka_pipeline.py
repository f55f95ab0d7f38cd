"""The streaming path, wired as ``bench.py::_bench_pipeline`` wires it:
records on the transaction topic -> ``Router`` micro-batch -> ``Scorer`` ->
the threshold rule -> standard or fraud process start on ``build_engine``'s
engine, an in-process ``Broker``, one router.

The benchmark's one tap is ``EngineTap``, a delegating wrapper at the
engine boundary: per batch of process starts it keeps the instant, the
process and the list of variables the router handed over (a reference and
a clock read; nothing per record inside the window). After the window the
generator reads from it which record went where, when, and with what
probability. Guarantees held: every produced record is routed exactly
once or counted as shed or start error; none lost, none doubled; no
degraded tier; every row scored on the device. The cells' traffic is
chosen so that nothing is shed, so a record that was due in the window
and got no process start also makes the run not correct.
"""

from __future__ import annotations

import dataclasses
import time

from benchmark.deployments.seldon_rest import (
    check_device_path, restore_params, scorer_counters, serving_section)


class EngineTap:
    """The engine as the router sees it, with every batch of starts
    stamped on ``time.perf_counter`` once the engine has taken it."""

    start_batch_nocopy = True

    def __init__(self, engine):
        self._engine = engine
        self.batches: list[tuple[float, str, list, list]] = []

    def start_process_batch(self, def_id, variables_list, copy_vars=True):
        pids = self._engine.start_process_batch(
            def_id, variables_list, copy_vars=copy_vars)
        self.batches.append(
            (time.perf_counter(), def_id, variables_list, pids))
        return pids

    def start_process(self, def_id, variables):
        pid = self._engine.start_process(def_id, variables)
        self.batches.append((time.perf_counter(), def_id, [variables], [pid]))
        return pid

    def __getattr__(self, name):
        return getattr(self._engine, name)


class Deployment:
    def __init__(self, config: dict, *, root: str, control: bool,
                 traced: bool, seed: int = 0):
        self.config = config
        self.root = root
        self.serving = serving_section(config, control)
        self.traced = traced
        self.router = None
        self.thread = None
        self.profiler = None

    def start(self) -> dict:
        from ccfd_tpu.bus.broker import Broker
        from ccfd_tpu.config import Config
        from ccfd_tpu.metrics.prom import Registry
        from ccfd_tpu.process.fraud import build_engine
        from ccfd_tpu.serving.scorer import Scorer
        from ccfd_tpu.utils.gctune import tune_for_service

        s = self.serving
        r = self.config["router"]
        self.cfg = dataclasses.replace(
            Config(), kafka_topic=r["topic"],
            fraud_threshold=float(r["fraud_threshold"]))
        self.broker = Broker()
        self.registry = Registry()
        self.tap = EngineTap(
            build_engine(self.cfg, self.broker, self.registry, None))
        self.scorer = Scorer(
            model_name=s["model_name"], params=restore_params(s, self.root),
            compute_dtype=s["compute_dtype"],
            batch_sizes=tuple(s["batch_sizes"]),
            host_tier_rows=int(s["host_tier_rows"]))
        self.scorer.warmup()
        tune_for_service()
        self._score = self.scorer.score
        if self.traced:  # the program's own stage timings, traced run only
            from ccfd_tpu.observability.profile import StageProfiler

            self.profiler = StageProfiler()
        return {"broker": self.broker, "topic": r["topic"], "tap": self.tap,
                "start_router": self.start_router,
                "consumed": self.consumed, "stop_router": self.stop_router,
                "shed": lambda: int(self.registry.counter(
                    "router_shed_total").total()),
                "fraud_threshold": float(r["fraud_threshold"])}

    def wrap_score(self, wrap) -> None:
        self._score = wrap(self._score)

    def start_router(self) -> None:
        """Called by the generator once the score callable is final."""
        from ccfd_tpu.router.router import Router

        r = self.config["router"]
        self.router = Router(
            self.cfg, self.broker, self._score, self.tap, self.registry,
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
        out = scorer_counters(self.scorer)
        routed = reg.counter("transaction_outgoing_total")
        out.update({
            "consumed": self.consumed(),
            "routed": int(routed.total()),
            "shed": int(reg.counter("router_shed_total").total()),
            "start_errors": int(reg.counter(
                "router_process_start_errors_total").total()),
            "degraded": int(reg.counter("router_degraded_total").total()),
        })
        return out

    def check_guarantees(self, checks, before, after, outcome) -> None:
        check_device_path(checks, before, after)
        checks.exactly("router_degraded_total", after["degraded"], 0)
        # the generator drained the topic before it stopped the router, so
        # every record it produced has one terminal disposition
        checks.exactly("consumed_minus_routed_shed_errors",
                       after["consumed"] - after["routed"] - after["shed"]
                       - after["start_errors"], 0)
        # below the knee nothing is shed: a record due in the window that
        # got no process start is a miss, and a run with one is not correct
        checks.exactly("records_missed", outcome.failed, 0)
        for key in ("produced_minus_consumed", "records_lost",
                    "records_doubled", "route_mismatches"):
            checks.exactly(key, outcome.extra[key], 0)

    def stop(self) -> None:
        self.stop_router()
        broker = getattr(self, "broker", None)
        if broker is not None:
            broker.close()
