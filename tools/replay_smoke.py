"""Replay smoke: prove the bulk replay plane holds the parity law live.

Exit-code-gated drill for ``tools/verify_tier1.sh --replay-smoke``
(ISSUE 17 acceptance), against a LIVE in-process pipeline — bus →
router → scorer → KIE — with the decision-provenance plane, the
overload plane AND the SLO burn-rate engine all armed:

1. **Record** a transaction window through the live stack with feature
   capture armed (``AuditLog.capture_rows``): every routed tx stamps a
   re-scorable DecisionRecord into on-disk segments.
2. **Replay** the recorded window through the SAME path at ``bulk``
   priority while live traffic keeps flowing: byte-stable parity is
   required — every recorded verdict re-produced exactly (``match ==
   total``, zero divergence/drop/ghost), with the route-seam tap
   diverting replay verdicts so the provenance log is NOT re-stamped
   (routed grows, recorded doesn't: conservation of the live log).
3. **Inject** one divergence — a recorded row doctored to carry a
   different champion hash and score (the swapped-champion shape) —
   and require the re-drive to detect it AND classify it
   ``champion_hash`` (never ``nondeterminism``).
4. **Zero live-SLO impact**: the burn-rate gauges scraped from the live
   exporter over real HTTP must show zero fast-window breaches across
   every declared SLO while replay ran at full bulk admission, and the
   bulk ceiling must have been actuated (gauge exported) and restored.

    JAX_PLATFORMS=cpu python tools/replay_smoke.py
    tools/verify_tier1.sh --replay-smoke

Prints one JSON line on stdout; exit 0 only when every check holds.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import threading
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
from ccfd_tpu.observability.audit import AuditLog  # noqa: E402
from ccfd_tpu.observability.slo import SLOEngine  # noqa: E402
from ccfd_tpu.parallel.partition import params_fingerprint  # noqa: E402
from ccfd_tpu.platform.operator import PlatformSpec  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.replay.service import (  # noqa: E402
    ReplayService,
    ReplayVerdictTap,
)
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.runtime.overload import OverloadControl  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=512,
                    help="size of the recorded window")
    ap.add_argument("--cr", default=os.path.join(
        REPO, "deploy", "platform_cr.yaml"))
    ap.add_argument("--windows", default="2,4,12",
                    help="CI-scale burn windows in seconds")
    ap.add_argument("--e2e-target-ms", type=float, default=250.0,
                    help="CI-box margin for the e2e SLO target (the "
                    "slo_smoke precedent: this box's scheduler noise, "
                    "not production latency, is what it absorbs)")
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    state = tempfile.mkdtemp(prefix="ccfd_replay_smoke_")
    audit_dir = os.path.join(state, "audit")

    cfg = Config(confidence_threshold=1.0, slo_windows=args.windows)
    spec = PlatformSpec.from_yaml(args.cr, cfg=cfg)
    slo_options = dict(spec.component("slo").options)
    slo_options["windows"] = args.windows
    if args.e2e_target_ms and slo_options.get("specs"):
        slo_options["specs"] = [
            ({**s, "target_ms": float(args.e2e_target_ms)}
             if s.get("name") == "e2e-p99" else s)
            for s in slo_options["specs"]
        ]

    regs = {name: Registry()
            for name in ("router", "kie", "seldon", "slo", "replay")}
    slo_engine = SLOEngine.from_config(cfg, regs, regs["slo"],
                                       options=slo_options)

    # -- the live stack: bus -> router -> scorer -> KIE, fully armed ------
    broker = Broker(default_partitions=2)
    kie = build_engine(cfg, broker, regs["kie"], None)
    scorer = Scorer(model_name="mlp", batch_sizes=(128, 1024, 4096),
                    host_tier_rows=0)
    scorer.warmup()
    fp = params_fingerprint(jax.tree.map(np.asarray, scorer.params))

    def lineage():
        return ("v1", fp)

    overload = OverloadControl.from_config(cfg, regs["router"],
                                           max_batch=1024, workers=1)
    audit = AuditLog(dir=audit_dir, registry=regs["router"])
    audit.lineage_fn = lineage
    tap = ReplayVerdictTap(inner=audit, registry=regs["replay"])
    router = Router(cfg, broker, scorer.score, kie, regs["router"],
                    max_batch=1024, overload=overload, audit=tap)
    svc = ReplayService(cfg, broker, audit, tap=tap,
                        registry=regs["replay"],
                        state_dir=os.path.join(state, "replay"),
                        overload=overload, lineage_fn=lineage)
    checks["capture_armed_by_service"] = audit.capture_rows is True
    exporter = MetricsExporter(regs).start()

    # -- 1. record the window ---------------------------------------------
    ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=17)
    rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(args.rows)]
    broker.produce_batch(cfg.kafka_topic, rows,
                         [f"tx-{i:05d}" for i in range(args.rows)])
    while router.step() > 0:
        pass
    audit.flush()
    recs = audit.scan_window()
    checks["window_recorded_rescorable"] = (
        len(recs) == args.rows
        and all(r.get("row") is not None for r in recs)
        and all(r.get("hash") == fp for r in recs))
    since = int(recs[0]["seq"]) if recs else 0
    until = int(recs[-1]["seq"]) if recs else 0
    recorded_before = int(regs["router"].counter(
        "ccfd_audit_records_total").value())

    # -- 2. replay through the live stack, live traffic still flowing -----
    stop = threading.Event()
    live_extra = [0]

    def drive() -> None:
        # the live lane replay must not starve: a trickle of live
        # (normal-priority) traffic interleaves with the bulk re-drive,
        # and the burn engine ticks throughout
        i = 0
        next_tick = 0.0
        while not stop.is_set():
            if i < 40:
                broker.produce_batch(
                    cfg.kafka_topic, rows[:16],
                    [f"live-{i}-{j}" for j in range(16)])
                live_extra[0] += 16
                i += 1
            router.step()
            now = time.monotonic()
            if now >= next_tick:
                slo_engine.tick()
                next_tick = now + 0.3
            time.sleep(0.005)

    driver = threading.Thread(target=drive, daemon=True,
                              name="replay-smoke-drive")
    driver.start()
    report = svc.run_window(since, until, window_id="smoke")

    # -- 3. one injected divergence: the swapped-champion shape -----------
    # (the driver is still pumping: the re-drive needs the live router)
    inj = [dict(r) for r in recs[:64]]
    inj[7] = dict(inj[7])
    inj[7]["proba"] = 1.0 - float(inj[7]["proba"])  # the old champion's
    inj[7]["hash"] = "0" * len(fp)                  # score, its hash
    rep2 = svc.run_window(window=inj, window_id="smoke-inject",
                          resume=False)
    # keep the live lane going long enough to cross the fast burn windows
    time.sleep(max(1.0, 1.5 * float(args.windows.split(",")[0])))
    stop.set()
    driver.join(timeout=10)
    audit.flush()

    detail["report"] = {k: report[k] for k in
                        ("window_id", "total", "replayed", "match",
                         "divergence", "drop", "ghost", "dup", "causes",
                         "rows_per_s", "parity")}
    checks["byte_stable_parity"] = (
        report["parity"] and report["match"] == report["total"] == args.rows
        and report["divergence"] == 0 and report["drop"] == 0
        and report["ghost"] == 0)
    # conservation of the live log: the re-drive routed through the same
    # stack but the tap diverted every replay verdict — recorded grew
    # only by the live trickle, never by the replay
    recorded_after = int(regs["router"].counter(
        "ccfd_audit_records_total").value())
    routed_total = int(regs["router"].counter(
        "transaction_outgoing_total").total())
    checks["replay_never_restamps_the_log"] = (
        recorded_after == recorded_before + live_extra[0]
        and routed_total >= args.rows * 2)
    detail["conservation"] = {
        "recorded_before": recorded_before,
        "recorded_after": recorded_after,
        "live_extra": live_extra[0], "routed_total": routed_total,
    }
    joined = int(regs["replay"].counter(
        "ccfd_replay_verdicts_total").value({"fate": "joined"}))
    checks["verdicts_joined_via_tap"] = joined >= args.rows
    checks["bulk_ceiling_restored"] = (
        overload is None or overload.bulk_ceiling == 1.0)

    checks["injected_divergence_detected"] = rep2["divergence"] == 1
    checks["injected_divergence_classified"] = (
        rep2["causes"] == {"champion_hash": 1}
        and rep2["match"] == len(inj) - 1
        and not any(f.get("cause") == "nondeterminism"
                    for f in rep2["findings"]))
    detail["injected"] = {"causes": rep2["causes"],
                          "findings": rep2["findings"][:2]}

    # -- 4. zero live-SLO breaches, from the scraped burn gauges ----------
    status = slo_engine.tick()
    checks["slo_engine_green"] = not any(
        s["breaching"] or s["breaches"] for s in status["slos"].values())
    with urllib.request.urlopen(exporter.endpoint + "/prometheus",
                                timeout=10) as resp:
        scrape = resp.read().decode()
    burns = re.findall(r'ccfd_slo_burn_rate\{[^}]*\} ([0-9.e+-]+)', scrape)
    breaches = re.findall(r'ccfd_slo_breach_total\{[^}]*\} ([0-9.e+-]+)',
                          scrape)
    checks["burn_gauges_scraped"] = len(burns) > 0
    checks["zero_breaches_scraped"] = all(float(b) == 0.0 for b in breaches)
    checks["bulk_ceiling_gauge_scraped"] = "ccfd_bulk_ceiling" in scrape
    checks["replay_counters_scraped"] = (
        'ccfd_replay_rows_total{outcome="match"}' in scrape
        and "ccfd_replay_rows_per_s" in scrape)
    detail["slo"] = {
        "burn_samples": len(burns),
        "max_burn": max((float(b) for b in burns), default=0.0),
        "breach_counters": [float(b) for b in breaches],
    }
    detail["throughput_rows_per_s"] = round(report["rows_per_s"], 1)

    svc.stop()
    exporter.stop()
    router.close()
    broker.close()

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks, "detail": detail}))
    print(f"REPLAYSMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
