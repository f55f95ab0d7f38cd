"""Audit smoke: prove the decision-provenance plane reconstructs after a
crash-restore (ISSUE 14).

Exit-code-gated drill for ``tools/verify_tier1.sh --audit-smoke``:

1. **Seed** a lifecycle lineage (genesis champion, checkpointed + hashed)
   and arm the full provenance plane — AuditLog with a durable segment
   dir, a keep-everything trace sink, a FlightRecorder with a bundle dir
   and the audit embed, the lineage sample and an OPEN incident — then
   route live traffic through a real Router.
2. **Conservation**: every routed tx has exactly one record (the
   ``ccfd_audit_records_total`` counter equals the summed
   ``transaction_outgoing_total``), zero duplicates.
3. **Overhead**: the same traffic through an armed vs a disarmed router —
   the armed pipeline must stay within run-to-run noise (gated at a
   generous CI-box margin; both numbers reported).
4. **Crash**: a partial frame is torn onto the newest segment (the bytes
   a crash mid-append leaves) and every live object is abandoned.
5. **Restore + reconstruct**: a fresh AuditLog truncates the torn tail
   (counted), rebuilds the ring, and ``ccfd_tpu audit <tx_id>``
   reconstructs a specific pre-crash FRAUD decision end-to-end — record
   intact, checkpoint hash EQUAL to the lineage champion's hash (which
   equals the serving params' fingerprint), device tier recorded, the
   open incident id resolving to the on-disk bundle.
6. **HTTP**: ``/decisions`` + ``/decisions/<tx_id>`` round-trip over real
   HTTP (strict JSON, unknown id 404s), the ``ccfd_audit_*`` counters
   scrape, and the ``--url`` form of the CLI joins the kept trace.

    JAX_PLATFORMS=cpu python tools/audit_smoke.py
    tools/verify_tier1.sh --audit-smoke

Prints one JSON line on stdout; exit 0 only when every check holds.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.bus.broker import Broker  # noqa: E402
from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.data.ccfd import synthetic_dataset  # noqa: E402
from ccfd_tpu.lifecycle.controller import (  # noqa: E402
    Guardrails,
    LifecycleController,
)
from ccfd_tpu.lifecycle.evaluator import ShadowEvaluator  # noqa: E402
from ccfd_tpu.lifecycle.shadow import ShadowTap  # noqa: E402
from ccfd_tpu.lifecycle.versions import VersionStore  # noqa: E402
from ccfd_tpu.metrics.exporter import MetricsExporter  # noqa: E402
from ccfd_tpu.metrics.prom import Registry  # noqa: E402
from ccfd_tpu.observability.audit import AuditLog  # noqa: E402
from ccfd_tpu.observability.incident import FlightRecorder  # noqa: E402
from ccfd_tpu.observability.trace import SpanSink, Tracer  # noqa: E402
from ccfd_tpu.parallel.checkpoint import CheckpointManager  # noqa: E402
from ccfd_tpu.parallel.partition import params_fingerprint  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pump(router, broker, cfg, rows, keys) -> None:
    broker.produce_batch(cfg.kafka_topic, rows, keys)
    while router.step() > 0:
        pass


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--bench-rows", type=int, default=8192,
                    help="rows per overhead-measurement round")
    ap.add_argument("--overhead-max-x", type=float, default=1.5,
                    help="armed/disarmed wall-clock ratio gate (CI-box "
                    "margin; the claim is 'within run-to-run noise', "
                    "measured as min-of-3 rounds)")
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    state = tempfile.mkdtemp(prefix="ccfd_audit_smoke_")
    audit_dir = os.path.join(state, "audit")
    inc_dir = os.path.join(state, "incidents")
    lineage_path = os.path.join(state, "versions.json")
    os.makedirs(inc_dir, exist_ok=True)

    cfg = Config(confidence_threshold=1.0)
    reg = Registry()

    # -- 1. seed: genesis champion with a recorded checkpoint hash ---------
    scorer = Scorer(model_name="mlp", batch_sizes=(16, 128, 1024, 4096),
                    host_tier_rows=0)
    scorer.warmup()
    store = VersionStore(lineage_path)
    ckpts = CheckpointManager(os.path.join(state, "checkpoints"), keep=8,
                              use_orbax=False)
    lc_broker = Broker(default_partitions=1)
    lc = LifecycleController(
        cfg, scorer, store=store, checkpoints=ckpts,
        shadow=ShadowTap(scorer, lc_broker, cfg.shadow_topic, Registry()),
        evaluator=ShadowEvaluator(cfg, lc_broker, scorer, Registry()),
        guardrails=Guardrails(), registry=Registry())
    champ = store.champion()
    serving_fp = params_fingerprint(jax.tree.map(np.asarray, scorer.params))
    checks["champion_seeded_with_hash"] = (
        champ is not None and champ.checkpoint_hash == serving_fp)
    detail["champion"] = {"version": champ.version if champ else None,
                          "hash": (champ.checkpoint_hash or "")[:12]}

    # -- the provenance plane, fully armed ---------------------------------
    sink = SpanSink(sample=1.0, max_retained=256, registry=reg)
    tracer = Tracer(reg, component="router", sink=sink)
    audit = AuditLog(dir=audit_dir, registry=reg)
    recorder = FlightRecorder({"router": reg}, registry=reg, ring=8,
                              out_dir=inc_dir, audit=audit)
    audit.lineage_fn = lambda: ((champ.version, champ.checkpoint_hash)
                                if champ else (None, None))
    # an incident is OPEN for the whole traffic window: the drill bundle
    # below stands in for a breaching SLO (the operator gates the same
    # join on SLOEngine.any_breaching; tests/test_audit.py pins that)
    open_incident: dict = {"id": None}
    audit.incident_fn = lambda: open_incident["id"]
    bundle = recorder.incident({"type": "audit_drill"})
    open_incident["id"] = bundle["id"]
    checks["drill_bundle_on_disk"] = os.path.exists(
        os.path.join(inc_dir, bundle["id"] + ".json"))

    broker = Broker(default_partitions=2)
    engine = build_engine(cfg, broker, Registry(), None)
    router = Router(cfg, broker, scorer.score, engine, reg, max_batch=1024,
                    tracer=tracer, audit=audit)

    ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=11)
    rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(args.rows)]
    keys = [f"tx-{i:05d}" for i in range(args.rows)]
    _pump(router, broker, cfg, rows, keys)
    flushed = audit.flush()
    checks["flushed_to_segments"] = flushed > 0 and bool(
        os.listdir(audit_dir))

    # -- 2. conservation: routed == recorded, zero duplicates --------------
    routed = int(reg.counter("transaction_outgoing_total").total())
    recorded = int(reg.counter("ccfd_audit_records_total").value())
    c = audit.counts()
    checks["conservation_routed_eq_recorded"] = (
        routed == recorded == args.rows)
    checks["zero_duplicates"] = (c["restamped"] == 0
                                 and c["ring"] == args.rows)
    detail["conservation"] = {"routed": routed, "recorded": recorded,
                              "restamped": c["restamped"]}

    # the target: a specific FRAUD decision stamped during the open
    # incident, with the full join set
    target = None
    for s in audit.list(limit=args.rows):
        if "fraud" in str(s.get("branch", "")) and s.get("incident"):
            target = audit.get(s["tx"])
            break
    checks["fraud_decision_found"] = target is not None
    if target is None:
        print(json.dumps({"ok": False, "checks": checks, "detail": detail}))
        print("AUDITSMOKE verdict=FAIL", flush=True)
        return 3
    tx_id = str(target["tx"])
    detail["target"] = {"tx": tx_id, "uid": target["uid"],
                        "proba": target["proba"]}

    # -- 3. overhead: armed vs disarmed within CI noise --------------------
    bench_rows = [",".join(f"{v:.6g}" for v in ds.X[i % len(ds.X)]).encode()
                  for i in range(args.bench_rows)]
    bench_keys = list(range(args.bench_rows))

    def one_round(arm: bool) -> float:
        b = Broker(default_partitions=2)
        e = build_engine(cfg, b, Registry(), None)
        r = Router(cfg, b, scorer.score, e, Registry(), max_batch=4096,
                   audit=(AuditLog(dir=None, registry=None)
                          if arm else None))
        t0 = time.perf_counter()
        _pump(r, b, cfg, bench_rows, bench_keys)
        dt = time.perf_counter() - t0
        r.close()
        b.close()
        return dt

    one_round(False)  # warm both paths once (compiles, allocator)
    disarmed = min(one_round(False) for _ in range(3))
    armed = min(one_round(True) for _ in range(3))
    ratio = armed / max(disarmed, 1e-9)
    detail["overhead"] = {"disarmed_s": round(disarmed, 4),
                          "armed_s": round(armed, 4),
                          "ratio": round(ratio, 3)}
    checks["overhead_within_noise"] = ratio <= args.overhead_max_x

    # -- 4. crash: torn frame on the newest segment, objects abandoned ----
    segs = sorted(os.listdir(audit_dir))
    newest = os.path.join(audit_dir, segs[-1])
    with open(newest, "ab") as f:
        # a crash mid-append: the frame header landed, the payload didn't
        f.write(b"CCFDSUM1 " + b"ab" * 32 + b" 4096\ntorn-payload")
    router.close()
    broker.close()
    lc.close()
    lc_broker.close()

    # -- 5. restore: truncation counted, ring rebuilt, CLI reconstructs ---
    reg2 = Registry()
    audit2 = AuditLog(dir=audit_dir, registry=reg2)
    c2 = audit2.counts()
    checks["torn_tail_truncated_and_counted"] = (
        c2["truncated_frames"] >= 1
        and int(reg2.counter("ccfd_audit_dropped_total").value(
            {"reason": "torn_tail"})) >= 1)
    checks["ring_rebuilt_after_crash"] = c2["ring"] >= args.rows
    pre_crash = dict(target)
    post = audit2.get(tx_id)
    checks["record_survives_crash"] = post == pre_crash

    from ccfd_tpu.cli import main as cli_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli_main(["audit", tx_id, "--dir", audit_dir,
                       "--lifecycle-dir", state, "--incident-dir", inc_dir,
                       "--json"])
    checks["cli_reconstructs"] = rc == 0
    doc = json.loads(out.getvalue() or "{}")
    rec = doc.get("record", {})
    lin = doc.get("lineage", {})
    inc = doc.get("incident", {})
    checks["hash_equals_lineage_champion"] = (
        rec.get("hash") == champ.checkpoint_hash == serving_fp
        and lin.get("hash_parity") is True)
    checks["tier_intact"] = rec.get("tier") == "device"
    checks["incident_linkage_intact"] = (
        rec.get("incident") == bundle["id"] and inc.get("found") is True)
    checks["lineage_events_joined"] = len(lin.get("events") or []) > 0
    detail["reconstruction"] = {
        "hash": (rec.get("hash") or "")[:12],
        "tier": rec.get("tier"),
        "incident": rec.get("incident"),
        "trace": (rec.get("trace") or "")[:16],
    }

    # -- 6. the same reconstruction over real HTTP -------------------------
    exporter = MetricsExporter({"audit": reg2}, sink=sink,
                               audit=audit2).start()
    try:
        base = exporter.endpoint
        with urllib.request.urlopen(base + f"/decisions/{tx_id}",
                                    timeout=10) as resp:
            http_rec = json.loads(resp.read().decode())
            ctype = resp.headers.get("Content-Type", "")
        checks["decision_over_http"] = (http_rec == post
                                        and "application/json" in ctype)
        with urllib.request.urlopen(base + "/decisions?limit=8",
                                    timeout=10) as resp:
            listing = json.loads(resp.read().decode())
        checks["listing_over_http"] = (
            0 < len(listing.get("decisions", [])) <= 8)
        try:
            urllib.request.urlopen(base + "/decisions/tx-nope", timeout=10)
            checks["unknown_tx_404"] = False
        except urllib.error.HTTPError as e:
            checks["unknown_tx_404"] = e.code == 404
        with urllib.request.urlopen(base + "/prometheus",
                                    timeout=10) as resp:
            scrape = resp.read().decode()
        checks["counters_scraped_http"] = (
            "ccfd_audit_records_total" in scrape
            and 'ccfd_audit_dropped_total{reason="torn_tail"}' in scrape
            and "ccfd_audit_ring_records" in scrape
            and "ccfd_audit_log_bytes" in scrape)
        # --url mode: the kept trace joins over the live sink
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main(["audit", tx_id, "--url", base,
                           "--lifecycle-dir", state,
                           "--incident-dir", inc_dir, "--json"])
        doc2 = json.loads(out.getvalue() or "{}")
        checks["cli_url_mode"] = rc == 0 and doc2.get("record") == post
        checks["kept_trace_joined"] = (
            doc2.get("trace", {}).get("kept") is True)
    finally:
        exporter.stop()

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks, "detail": detail}))
    print(f"AUDITSMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
