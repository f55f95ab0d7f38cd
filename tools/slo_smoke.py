"""SLO smoke: prove the burn-rate plane fires on the right SLO, and only it.

Exit-code-gated drill for ``tools/verify_tier1.sh --slo-smoke`` (ISSUE 9
acceptance): against a LIVE in-process pipeline with the stage profiler and
SLO engine armed —

1. SLO specs load from the platform CR's ``slo:`` block (the declarative
   contract, not hard-coded harness objectives); only the burn windows are
   shrunk to seconds so a CI run can cross them.
2. A baseline phase drives both the pipeline (producer-shaped feeder →
   bus → router → engine) and the REST serving lane (DynamicBatcher in
   front of a second scorer) and must stay green on every SLO.
3. A fault phase injects a 200 ms scorer-latency step on the REST lane
   ONLY (runtime/faults.py — the same injection surface the breaker and
   overload drills use). Required outcome:
   - the REST-p99 SLO's fast-window burn rate crosses the alert
     threshold within the run and ``ccfd_slo_breach_total{slo=rest-p99}``
     increments, while e2e-p99 and error-rate stay green (0 breaches);
   - the per-layer budget ledger attributes >= 80% of the ADDED REST
     latency to the scorer-dispatch layer (phase-delta means over the
     ledger's count/sum bookkeeping);
   - the ledger's measured layers sum to the measured REST e2e latency
     within tolerance (the decomposition is complete, not just ordered).
4. The burn-rate gauges are scraped over REAL HTTP from the live
   exporter, and the StageProfile JSON artifact round-trips through the
   ``/profile`` endpoint: fetched bytes validate against the schema and
   match a locally-taken snapshot stage for stage.

    JAX_PLATFORMS=cpu python tools/slo_smoke.py
    tools/verify_tier1.sh --slo-smoke

Prints one JSON line on stdout; exit 0 only when every check holds.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.bus.broker import Broker  # noqa: E402
from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.data.ccfd import synthetic_dataset  # noqa: E402
from ccfd_tpu.metrics.exporter import MetricsExporter  # noqa: E402
from ccfd_tpu.metrics.prom import Registry  # noqa: E402
from ccfd_tpu.observability.profile import (  # noqa: E402
    StageProfiler,
    validate_profile,
)
from ccfd_tpu.observability.slo import SLOEngine  # noqa: E402
from ccfd_tpu.platform.operator import PlatformSpec  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.runtime.faults import FaultPlan, FaultSpec  # noqa: E402
from ccfd_tpu.serving.batcher import DynamicBatcher  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Harness:
    def __init__(self, cr_path: str, windows: str, fault_ms: float,
                 e2e_target_ms: float | None = None,
                 device: bool = False,
                 incident_dir: str | None = None):
        """``device=True`` arms the DeviceTelemetry plane (both scorers
        stage through it, the ledger's h2d layer reads measured values);
        ``incident_dir`` (may be "") additionally wires a FlightRecorder
        to the engine's breach edge and the exporter's /incidents —
        the incident smoke (tools/incident_smoke.py) reuses this harness
        with both armed."""
        self.cfg = Config(slo_windows=windows)
        # the declarative SLO contract comes from the CR, not this harness
        spec = PlatformSpec.from_yaml(cr_path, cfg=self.cfg)
        self.slo_options = dict(spec.component("slo").options)
        self.slo_options["windows"] = windows  # CI-scale burn windows
        if e2e_target_ms and self.slo_options.get("specs"):
            # CI-box margin (the load_shape --slo-ms precedent): the CR's
            # production e2e target sits inside this container's scheduler
            # noise (1-3% of rows stall past 50 ms on a busy 1-core box),
            # and the smoke's claim is "the FAULTED SLO breaches, the
            # others don't" — not "this box meets production latency".
            # Only the target widens; the spec structure stays the CR's.
            self.slo_options["specs"] = [
                ({**s, "target_ms": float(e2e_target_ms)}
                 if s.get("name") == "e2e-p99" else s)
                for s in self.slo_options["specs"]
            ]

        self.regs = {name: Registry()
                     for name in ("router", "kie", "seldon", "slo")}
        self.profiler = StageProfiler(registry=self.regs["slo"],
                                      overload_registry=self.regs["router"])
        self.profiler.arm_compile_listener()
        self.telemetry = None
        if device:
            from ccfd_tpu.observability.device import DeviceTelemetry

            self.telemetry = DeviceTelemetry(registry=self.regs["slo"])
        self.engine = SLOEngine.from_config(
            self.cfg, self.regs, self.regs["slo"],
            profiler=self.profiler, options=self.slo_options,
            telemetry=self.telemetry,
        )
        self.recorder = None
        if incident_dir is not None:
            from ccfd_tpu.observability.incident import FlightRecorder

            self.regs["incident"] = Registry()
            self.recorder = FlightRecorder(
                self.regs, registry=self.regs["incident"],
                profiler=self.profiler, telemetry=self.telemetry,
                ring=16, out_dir=incident_dir or None)
            self.engine.add_breach_listener(self.recorder.on_breach)

        # -- pipeline lane (e2e-p99 + error-rate evidence; NO faults) -----
        self.broker = Broker(default_partitions=2)
        self.kie = build_engine(self.cfg, self.broker, self.regs["kie"], None)
        scorer = Scorer(model_name="mlp", batch_sizes=(128, 1024, 4096),
                        telemetry=self.telemetry)
        scorer.warmup()
        self.router = Router(self.cfg, self.broker, scorer.score, self.kie,
                             self.regs["router"], max_batch=1024,
                             profiler=self.profiler)

        # -- REST serving lane (rest-p99 evidence; fault target) ----------
        rest_scorer = Scorer(model_name="mlp", batch_sizes=(16, 128, 1024),
                             telemetry=self.telemetry)
        rest_scorer.warmup()
        self.fault_plan = FaultPlan(
            {"scorer_rest": FaultSpec(latency_ms=fault_ms)}, active=False)
        score_rest = self.fault_plan.injector(
            "scorer_rest", self.regs["seldon"]).wrap_fn(rest_scorer.score)
        self.batcher = DynamicBatcher(score_rest, max_batch=1024,
                                      deadline_ms=1.0, workers=2,
                                      profiler=self.profiler)
        self.h_rest = self.regs["seldon"].histogram(
            "seldon_api_executor_client_requests_seconds",
            "request latency by endpoint",
        )

        ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=3)
        self.X = np.asarray(ds.X, np.float32)
        self._rows = [
            ",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(512)
        ]
        self.produced = 0
        self.exporter = MetricsExporter(self.regs, profiler=self.profiler,
                                        sink=None,
                                        telemetry=self.telemetry,
                                        recorder=self.recorder).start()

    # -- drivers -----------------------------------------------------------
    def pump_pipeline(self, rows: int = 200) -> None:
        base = self.produced
        idx = [(base + i) % len(self._rows) for i in range(rows)]
        self.broker.produce_batch(
            self.cfg.kafka_topic, [self._rows[i] for i in idx],
            [(base + i) % 97 for i in range(rows)])
        self.produced = base + rows
        while self.router.step() > 0:
            pass

    def rest_request(self, rows: int = 16) -> None:
        lo = self.produced % (len(self.X) - rows)
        t0 = time.perf_counter()
        self.batcher.score(self.X[lo:lo + rows])
        self.h_rest.observe(time.perf_counter() - t0)

    def drive(self, seconds: float, tick_s: float = 0.4) -> None:
        end = time.monotonic() + seconds
        next_tick = 0.0
        while time.monotonic() < end:
            self.pump_pipeline()
            self.rest_request()
            now = time.monotonic()
            if now >= next_tick:
                self.engine.tick()
                next_tick = now + tick_s
            time.sleep(0.02)
        self.engine.tick()

    def phase_stats(self) -> dict:
        """Cumulative per-layer + e2e counters (diffed across phases)."""
        ledger = self.engine.ledger.evaluate()
        return {
            "layers": {
                name: {"count": e["count"], "sum_s": e["sum_s"]}
                for name, e in ledger["layers"].items()
            },
            "rest_count": self.h_rest.count(),
            "rest_sum_s": self.h_rest.sum(),
        }

    def close(self) -> None:
        self.batcher.stop()
        self.router.close()
        self.exporter.stop()
        self.broker.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cr", default=os.path.join(
        REPO, "deploy", "platform_cr.yaml"))
    ap.add_argument("--baseline-s", type=float, default=5.0)
    ap.add_argument("--fault-s", type=float, default=8.0)
    ap.add_argument("--fault-ms", type=float, default=200.0)
    ap.add_argument("--windows", default="3,6,20",
                    help="CI-scale burn windows in seconds "
                    "(fast, fast-confirm, slow)")
    ap.add_argument("--e2e-target-ms", type=float, default=250.0,
                    help="CI-box margin for the e2e SLO target (0 keeps "
                    "the CR's production value; see Harness)")
    args = ap.parse_args()

    h = Harness(args.cr, args.windows, args.fault_ms,
                e2e_target_ms=args.e2e_target_ms)
    checks: dict[str, bool] = {}
    detail: dict = {}

    # CR really is the spec source
    spec_names = [s.name for s in h.engine.specs]
    checks["specs_from_cr"] = (
        bool(h.slo_options.get("specs"))
        and spec_names == [s["name"] for s in h.slo_options["specs"]]
    )
    detail["specs"] = spec_names

    # -- baseline: everything green ---------------------------------------
    h.drive(args.baseline_s)
    base_status = h.engine.tick()
    base_stats = h.phase_stats()
    checks["baseline_green"] = not any(
        s["breaching"] or s["breaches"] for s in base_status["slos"].values())

    # -- fault phase: 200 ms latency step on the REST scorer edge only ----
    h.fault_plan.activate()
    h.drive(args.fault_s)
    h.fault_plan.deactivate()
    status = h.engine.tick()
    fault_stats = h.phase_stats()

    rest = status["slos"]["rest-p99"]
    fast_names = [w["window"] for w in status["windows"][:-1]]
    fast_thr = status["windows"][0]["threshold"]
    detail["rest_burn"] = rest["burn_rate"]
    checks["rest_burn_crossed"] = all(
        rest["burn_rate"].get(w, 0.0) >= fast_thr for w in fast_names)
    checks["rest_breached"] = h.engine.breaches("rest-p99") >= 1
    checks["others_stayed_green"] = all(
        h.engine.breaches(name) == 0
        for name in spec_names if name != "rest-p99")

    # -- ledger attribution of the ADDED latency --------------------------
    base_e2e = (1e3 * (base_stats["rest_sum_s"])
                / max(1, base_stats["rest_count"]))
    fault_n = fault_stats["rest_count"] - base_stats["rest_count"]
    fault_e2e = (1e3 * (fault_stats["rest_sum_s"] - base_stats["rest_sum_s"])
                 / max(1, fault_n))
    added_e2e = fault_e2e - base_e2e

    # per-layer phase means: fault-phase mean minus baseline-phase mean
    def layer_added(layer: str) -> float:
        a, b = fault_stats["layers"][layer], base_stats["layers"][layer]
        n = a["count"] - b["count"]
        fault_mean = (1e3 * (a["sum_s"] - b["sum_s"]) / n) if n > 0 else 0.0
        base_mean = (1e3 * b["sum_s"] / b["count"]) if b["count"] else 0.0
        return fault_mean - base_mean

    added = {layer: layer_added(layer)
             for layer in ("batcher_wait", "dispatch")}
    added_sum = sum(v for v in added.values() if v > 0)
    dispatch_share = (added["dispatch"] / added_sum) if added_sum > 0 else 0.0
    detail["added_ms"] = {k: round(v, 2) for k, v in added.items()}
    detail["added_e2e_ms"] = round(added_e2e, 2)
    detail["dispatch_share"] = round(dispatch_share, 3)
    checks["dispatch_owns_added_latency"] = (
        dispatch_share >= 0.8
        and added["dispatch"] >= 0.8 * max(added_e2e, 1e-9))

    # measured ledger layers sum to the measured e2e within tolerance
    # (fault-phase means; transport floor + h2d are static/zero and tiny)
    def phase_mean(layer: str) -> float:
        a, b = fault_stats["layers"][layer], base_stats["layers"][layer]
        n = a["count"] - b["count"]
        return (1e3 * (a["sum_s"] - b["sum_s"]) / n) if n > 0 else 0.0

    ledger_sum = (phase_mean("batcher_wait") + phase_mean("dispatch")
                  + h.cfg.slo_transport_floor_ms)
    detail["ledger_sum_ms"] = round(ledger_sum, 2)
    detail["fault_e2e_ms"] = round(fault_e2e, 2)
    tol = 0.25 * fault_e2e + 2.0
    checks["ledger_sums_to_e2e"] = abs(ledger_sum - fault_e2e) <= tol

    # -- burn gauges over real HTTP ---------------------------------------
    with urllib.request.urlopen(
            h.exporter.endpoint + "/prometheus", timeout=10) as resp:
        scrape = resp.read().decode()
    pat = re.compile(
        r'ccfd_slo_burn_rate\{slo="rest-p99",window="%s"\} ([0-9.e+-]+)'
        % re.escape(fast_names[0]))
    m = pat.search(scrape)
    checks["burn_gauge_scraped_http"] = (
        m is not None and float(m.group(1)) >= fast_thr)
    checks["breach_counter_scraped"] = (
        'ccfd_slo_breach_total{slo="rest-p99"}' in scrape)

    # -- StageProfile artifact round-trips through /profile ---------------
    local = h.profiler.snapshot()
    with urllib.request.urlopen(
            h.exporter.endpoint + "/profile", timeout=10) as resp:
        remote = json.loads(resp.read().decode())
    errs = validate_profile(remote)
    checks["profile_schema_valid"] = not errs
    same_stages = set(remote["stages"]) == set(local["stages"]) and all(
        remote["stages"][s]["rows"] == local["stages"][s]["rows"]
        for s in local["stages"]
    )
    checks["profile_roundtrip"] = same_stages
    detail["profile_stages"] = sorted(remote.get("stages", {}))
    if errs:
        detail["profile_errors"] = errs[:5]

    h.close()
    ok = all(checks.values())
    print(json.dumps({
        "harness": "slo_smoke",
        "ok": ok,
        "checks": checks,
        "detail": detail,
    }))
    print(f"SLOSMOKE verdict={'PASS' if ok else 'FAIL'}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
