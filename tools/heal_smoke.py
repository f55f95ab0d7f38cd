"""Heal smoke: prove the device self-healing loop end to end (ISSUE 11).

Exit-code-gated drill for ``tools/verify_tier1.sh --heal-smoke``: against a
LIVE in-process pipeline (producer-shaped feeder → bus → router → engine)
with the degradation ladder, overload watchdog, device telemetry, flight
recorder and DeviceSupervisor all armed —

1. A baseline phase serves through the device path and must sit HEALTHY.
2. A ``device_hang`` device fault (runtime/faults.py) is injected at the
   scorer dispatch seam. Required outcome: the supervisor's canary (and
   the serving watchdog's breaker trips) drive the state machine
   HEALTHY → SUSPECT → QUARANTINED; while quarantined, every transaction
   still gets a decision through the HOST tier with accounting conserved
   (incoming == outgoing, zero sheds) and zero rows touching the device.
3. The fault deactivates; the heal ladder walks (canary retry → reinit →
   respawn as needed) into PROBATION and re-promotes WARM: after the
   flip, a traffic phase must produce ZERO XLA compile events attributed
   to serving stages (everything compiled under ``heal.warm`` /
   warmup labels), and the device tier serves again (the degraded-host
   counter stops moving).
4. One schema-valid FlightRecorder bundle exists per transition edge
   (exactly one ``device_quarantine`` and one ``device_repromote``),
   round-tripped over REAL HTTP via ``/incidents/<id>``, and the
   ``ccfd_device_health`` gauges are scraped over the live exporter.

    JAX_PLATFORMS=cpu python tools/heal_smoke.py
    tools/verify_tier1.sh --heal-smoke

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
from ccfd_tpu.observability.device import DeviceTelemetry  # noqa: E402
from ccfd_tpu.observability.incident import (  # noqa: E402
    FlightRecorder,
    validate_incident,
)
from ccfd_tpu.observability.profile import StageProfiler  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.router.router import Router, default_scorer_breaker  # noqa: E402
from ccfd_tpu.runtime import faults  # noqa: E402
from ccfd_tpu.runtime.heal import (  # noqa: E402
    NON_SERVING_COMPILE_STAGES,
    DeviceSupervisor,
)
from ccfd_tpu.runtime.overload import OverloadControl  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402


def serving_compiles(prof: StageProfiler) -> int:
    return sum(v for s, v in prof.compile_counts().items()
               if s not in NON_SERVING_COMPILE_STAGES)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hang-ms", type=float, default=400.0)
    ap.add_argument("--canary-deadline-ms", type=float, default=150.0)
    ap.add_argument("--rows-per-pump", type=int, default=256)
    ap.add_argument("--quarantine-wait-s", type=float, default=20.0)
    ap.add_argument("--heal-wait-s", type=float, default=30.0)
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    cfg = Config(confidence_threshold=1.0)
    regs = {"router": Registry(), "kie": Registry(), "heal": Registry()}
    reg = regs["router"]
    tele = DeviceTelemetry(registry=regs["heal"], sample_every=1)
    prof = StageProfiler(registry=regs["heal"],
                         overload_registry=reg)
    prof.arm_compile_listener()
    recorder = FlightRecorder(regs, registry=regs["heal"],
                              profiler=prof, telemetry=tele, ring=16)
    broker = Broker(default_partitions=2)
    engine = build_engine(cfg, broker, regs["kie"], None)
    scorer = Scorer(model_name="mlp", batch_sizes=(16, 128, 1024),
                    host_tier_rows=0, telemetry=tele)
    scorer.warmup()
    overload = OverloadControl.from_config(cfg, reg, max_batch=1024,
                                           workers=1)
    # serving watchdog: a hung dispatch is killed (breaker trip), never
    # stalls a pump — the same bound the soak runs with
    overload.dispatch_deadline_s = 0.2
    breaker = default_scorer_breaker(reg)
    sup = DeviceSupervisor(
        scorer, registry=regs["heal"], breaker=breaker, telemetry=tele,
        profiler=prof, recorder=recorder, overload=overload,
        canary_deadline_ms=args.canary_deadline_ms,
        suspect_strikes=2, probation_canaries=3,
        backoff_base_s=0.05, backoff_cap_s=0.5,
    )
    router = Router(cfg, broker, scorer.score, engine, reg,
                    max_batch=1024, host_score_fn=scorer.host_score,
                    breaker=breaker, degrade=True, overload=overload,
                    profiler=prof, heal_gate=sup)
    exporter = MetricsExporter(regs, profiler=prof, telemetry=tele,
                               recorder=recorder).start()

    ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=7)
    rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(512)]
    produced = [0]

    def pump(n=args.rows_per_pump) -> None:
        base = produced[0]
        idx = [(base + i) % len(rows) for i in range(n)]
        broker.produce_batch(cfg.kafka_topic, [rows[i] for i in idx],
                             [(base + i) % 97 for i in range(n)])
        produced[0] = base + n
        while router.step() > 0:
            pass

    c_in = reg.counter("transaction_incoming_total")
    c_out = reg.counter("transaction_outgoing_total")
    c_deg = reg.counter("router_degraded_total")
    c_shed = reg.counter("router_shed_total")
    c_err = reg.counter("router_process_start_errors_total")

    try:
        # -- 1. baseline: device serving, supervisor healthy --------------
        pump()
        pump()
        checks["baseline_healthy"] = sup.tick() == "healthy"
        checks["baseline_device_serving"] = c_deg.total() == 0

        # -- 2. inject device_hang -> quarantine with host-tier serving ---
        plan = faults.DeviceFaultPlan.from_string(
            f"device_hang:ms={args.hang_ms}", active=True)
        faults.install_device_faults(plan)
        deadline = time.monotonic() + args.quarantine_wait_s
        state = sup.state
        while state != "quarantined" and time.monotonic() < deadline:
            state = sup.tick()
        checks["reached_quarantined"] = state == "quarantined"
        detail["quarantine_status"] = sup.status()
        host_before = c_deg.value({"tier": "host"})
        in_before = c_in.total()
        pump()
        pump()
        host_served = c_deg.value({"tier": "host"}) - host_before
        detail["host_rows_while_quarantined"] = int(host_served)
        checks["host_tier_served_quarantined_traffic"] = (
            host_served == c_in.total() - in_before > 0)

        # -- 3. heal -> warm re-promotion ----------------------------------
        faults.install_device_faults(None)
        deadline = time.monotonic() + args.heal_wait_s
        while state != "healthy" and time.monotonic() < deadline:
            state = sup.tick()
            time.sleep(0.02)
        checks["healed_to_healthy"] = state == "healthy"
        checks["repromoted_once"] = sup.repromotions == 1
        compiles_at_flip = serving_compiles(prof)
        deg_at_flip = c_deg.total()
        pump()
        pump()
        checks["warm_no_serving_compiles_after_flip"] = (
            serving_compiles(prof) == compiles_at_flip)
        detail["serving_compiles_after_flip"] = (
            serving_compiles(prof) - compiles_at_flip)
        checks["device_serving_after_flip"] = c_deg.total() == deg_at_flip

        # -- accounting: every consumed row decided, nothing shed ----------
        conserved = (c_in.total()
                     == c_out.total() + c_shed.total() + c_err.total())
        checks["accounting_conserved"] = bool(conserved)
        detail["accounting"] = {
            "incoming": c_in.total(), "outgoing": c_out.total(),
            "shed": c_shed.total(), "start_errors": c_err.total(),
        }

        # -- 4. one schema-valid bundle per transition edge ----------------
        bundles = recorder.incidents()
        kinds = [b["trigger"].get("type") for b in bundles]
        checks["one_bundle_per_edge"] = sorted(kinds) == [
            "device_quarantine", "device_repromote"]
        valid = True
        for b in bundles:
            doc = recorder.incident_doc(b["id"])
            errs = validate_incident(doc)
            if errs or doc.get("validation_errors"):
                valid = False
                detail.setdefault("bundle_errors", []).extend(errs[:5])
        checks["bundles_schema_valid"] = valid and bool(bundles)

        # -- over REAL HTTP: gauges + bundle round trip --------------------
        with urllib.request.urlopen(exporter.endpoint + "/prometheus",
                                    timeout=10) as resp:
            scrape = resp.read().decode()
        m = re.search(r'ccfd_device_health\{[^}]*state="healthy"[^}]*\} '
                      r'([0-9.e+-]+)', scrape)
        checks["health_gauge_scraped_http"] = (
            m is not None and float(m.group(1)) == 1.0)
        checks["heal_counters_scraped"] = (
            "ccfd_heal_transitions_total" in scrape
            and "ccfd_heal_canary_total" in scrape)
        with urllib.request.urlopen(exporter.endpoint + "/incidents",
                                    timeout=10) as resp:
            listing = json.loads(resp.read().decode())["incidents"]
        q_id = next((b["id"] for b in listing
                     if b["trigger"].get("type") == "device_quarantine"),
                    None)
        fetched_ok = False
        if q_id:
            with urllib.request.urlopen(
                    exporter.endpoint + f"/incidents/{q_id}",
                    timeout=10) as resp:
                fetched = json.loads(resp.read().decode())
            fetched_ok = not validate_incident(fetched)
        checks["bundle_round_trips_http"] = fetched_ok
    finally:
        faults.install_device_faults(None)
        router.close()
        exporter.stop()
        broker.close()

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks, "detail": detail,
                      "supervisor": sup.status()}))
    print(f"HEALSMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
