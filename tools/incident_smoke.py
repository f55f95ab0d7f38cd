"""Incident smoke: prove the SLO-breach flight recorder closes the loop.

Exit-code-gated drill for ``tools/verify_tier1.sh --incident-smoke``
(ISSUE 10 acceptance). Reuses the slo_smoke harness — live pipeline +
REST serving lanes, CR-loaded SLO specs, CI-scale burn windows — with the
DEVICE TELEMETRY plane and the FLIGHT RECORDER armed:

1. Baseline phase: every SLO green, ZERO incident bundles.
2. A fault-injected 200 ms scorer-latency step on the REST lane breaches
   the rest SLO. Required outcome:
   - EXACTLY ONE incident bundle (edge-triggered with the breach
     counter), schema-valid (``ccfd.incident.v3``), round-tripped over
     REAL HTTP via ``/incidents`` + ``/incidents/<id>`` (and an unknown
     id 404s);
   - the bundle's stage profile + budget ledger attribute the damage to
     the DISPATCH layer (>= 80% of the added REST latency);
   - with telemetry armed the ledger's ``h2d`` layer reports MEASURED
     (non-placeholder) values — per-put samples from the scorer's
     instrumented staging path — and the measured layers still sum to
     the measured REST e2e within tolerance;
   - the bundle carries flight data: a non-empty snapshot ring.
3. ``tools/incident_report.py`` renders the bundle (the human summary
   must build from the same bytes the exporter served).

    JAX_PLATFORMS=cpu python tools/incident_smoke.py
    tools/verify_tier1.sh --incident-smoke

Prints one JSON line on stdout; exit 0 only when every check holds.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        f"ccfd_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    from ccfd_tpu.observability.incident import validate_incident

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cr", default=os.path.join(
        REPO, "deploy", "platform_cr.yaml"))
    ap.add_argument("--baseline-s", type=float, default=5.0)
    ap.add_argument("--fault-s", type=float, default=8.0)
    ap.add_argument("--fault-ms", type=float, default=200.0)
    ap.add_argument("--windows", default="3,6,20")
    ap.add_argument("--e2e-target-ms", type=float, default=250.0)
    args = ap.parse_args()

    slo_smoke = _load_tool("slo_smoke")
    inc_dir = tempfile.mkdtemp(prefix="ccfd_incident_smoke_")
    h = slo_smoke.Harness(args.cr, args.windows, args.fault_ms,
                          e2e_target_ms=args.e2e_target_ms,
                          device=True, incident_dir=inc_dir)
    checks: dict[str, bool] = {}
    detail: dict = {}

    # -- baseline: green, no bundles --------------------------------------
    h.drive(args.baseline_s)
    base_status = h.engine.tick()
    base_stats = h.phase_stats()
    checks["baseline_green"] = not any(
        s["breaching"] or s["breaches"] for s in base_status["slos"].values())
    checks["baseline_no_bundles"] = len(h.recorder.incidents()) == 0

    # -- fault phase: the breach must dump exactly one bundle -------------
    h.fault_plan.activate()
    h.drive(args.fault_s)
    h.fault_plan.deactivate()
    h.engine.tick()
    fault_stats = h.phase_stats()

    checks["rest_breached"] = h.engine.breaches("rest-p99") >= 1
    incidents = h.recorder.incidents()
    checks["exactly_one_bundle"] = len(incidents) == 1
    detail["incidents"] = [i["id"] for i in incidents]

    # -- round trip over real HTTP ----------------------------------------
    with urllib.request.urlopen(
            h.exporter.endpoint + "/incidents", timeout=10) as resp:
        listing = json.loads(resp.read().decode())
    ids = [i["id"] for i in listing.get("incidents", [])]
    checks["listing_over_http"] = ids == [i["id"] for i in incidents]
    bundle = None
    if ids:
        with urllib.request.urlopen(
                h.exporter.endpoint + f"/incidents/{ids[0]}",
                timeout=10) as resp:
            bundle = json.loads(resp.read().decode())
    errs = validate_incident(bundle) if bundle else ["no bundle fetched"]
    checks["bundle_schema_valid"] = not errs
    if errs:
        detail["bundle_errors"] = errs[:5]
    try:
        urllib.request.urlopen(
            h.exporter.endpoint + "/incidents/inc-nope", timeout=10)
        checks["unknown_id_404"] = False
    except urllib.error.HTTPError as e:
        checks["unknown_id_404"] = e.code == 404

    # -- the bundle names the guilty layer --------------------------------
    # phase-delta attribution (the slo_smoke construction): the fault
    # phase's ADDED latency must land on the dispatch layer
    def layer_added(layer: str) -> float:
        a, b = fault_stats["layers"][layer], base_stats["layers"][layer]
        n = a["count"] - b["count"]
        fault_mean = (1e3 * (a["sum_s"] - b["sum_s"]) / n) if n > 0 else 0.0
        base_mean = (1e3 * b["sum_s"] / b["count"]) if b["count"] else 0.0
        return fault_mean - base_mean

    added = {layer: layer_added(layer)
             for layer in ("batcher_wait", "dispatch", "h2d")}
    added_sum = sum(v for v in added.values() if v > 0)
    dispatch_share = (added["dispatch"] / added_sum) if added_sum > 0 else 0.0
    detail["added_ms"] = {k: round(v, 3) for k, v in added.items()}
    detail["dispatch_share"] = round(dispatch_share, 3)
    checks["bundle_blames_dispatch"] = dispatch_share >= 0.8
    # and the bundle's own stage profile shows the step on rest.dispatch
    if bundle and isinstance(bundle.get("stage_profile"), dict):
        sp = bundle["stage_profile"]["stages"].get("rest.dispatch", {})
        p99 = sp.get("dispatch", {}).get("p99_ms", 0.0)
        checks["bundle_profile_shows_step"] = p99 >= 0.8 * args.fault_ms
        detail["bundle_rest_dispatch_p99_ms"] = p99
    else:
        checks["bundle_profile_shows_step"] = False

    # -- h2d layer: measured, and the decomposition stays complete --------
    ledger = (bundle or {}).get("slo_status", {}).get("budget_ledger") or \
        h.engine.tick().get("budget_ledger")
    h2d = ledger["layers"]["h2d"]
    checks["h2d_measured"] = (not h2d.get("static")
                              and h2d.get("count", 0) > 0)
    detail["h2d_layer"] = {k: h2d.get(k)
                           for k in ("count", "spent_p99_ms",
                                     "spent_mean_ms")}

    def phase_mean(layer: str) -> float:
        a, b = fault_stats["layers"][layer], base_stats["layers"][layer]
        n = a["count"] - b["count"]
        return (1e3 * (a["sum_s"] - b["sum_s"]) / n) if n > 0 else 0.0

    fault_n = fault_stats["rest_count"] - base_stats["rest_count"]
    fault_e2e = (1e3 * (fault_stats["rest_sum_s"]
                        - base_stats["rest_sum_s"]) / max(1, fault_n))
    # NOTE: h2d rides INSIDE the dispatch layer's wall (the scorer stages
    # within the timed score call), so the completeness check adds the
    # measured h2d mean on top and the tolerance must absorb it — on this
    # CPU harness it is microseconds against a 200 ms step
    ledger_sum = (phase_mean("batcher_wait") + phase_mean("dispatch")
                  + phase_mean("h2d") + h.cfg.slo_transport_floor_ms)
    detail["ledger_sum_ms"] = round(ledger_sum, 2)
    detail["fault_e2e_ms"] = round(fault_e2e, 2)
    tol = 0.25 * fault_e2e + 2.0
    checks["ledger_sums_to_e2e"] = abs(ledger_sum - fault_e2e) <= tol

    # -- flight data + crash-safe persistence ------------------------------
    checks["bundle_has_ring"] = bool(bundle and len(bundle["ring"]) > 0)
    on_disk = [f for f in os.listdir(inc_dir) if f.endswith(".json")]
    torn = [f for f in os.listdir(inc_dir) if f.endswith(".tmp")]
    checks["bundle_on_disk_no_tmp"] = len(on_disk) == 1 and not torn

    # -- the human report renders from the served bytes --------------------
    report = _load_tool("incident_report")
    bundle_path = os.path.join(inc_dir, on_disk[0]) if on_disk else "/nope"
    checks["report_renders"] = report.main([bundle_path]) == 0

    h.close()
    ok = all(checks.values())
    print(json.dumps({
        "harness": "incident_smoke",
        "ok": ok,
        "checks": checks,
        "detail": detail,
    }))
    print(f"INCIDENTSMOKE verdict={'PASS' if ok else 'FAIL'}",
          file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
