#!/usr/bin/env python
"""Model-lifecycle drill: reject a degraded challenger, promote a good one,
then force a mid-canary guardrail breach and assert auto-rollback.

The governed-rollout acceptance run (lifecycle/):

1. **Degraded challenger** — trained on label-flipped data (the bad-batch
   failure mode the lifecycle exists to catch: one poisoned label window
   must not reach production). Asserts it is REJECTED at the SHADOW gate
   and serving never changed.
2. **Improved challenger** — trained longer on the true labels. Asserts it
   passes SHADOW, serves a canary slice (both arms observed), and is
   PROMOTED to champion with serving actually swapped.
3. **Canary breach** — a third candidate reaches CANARY, then the
   scorer-edge circuit breaker is driven open (the degraded-edge signal
   the router's ladder also watches). Asserts auto-ROLLBACK to the
   champion checkpoint, serving restored bit-for-bit to the promoted
   champion.

Every transition is checked against the persisted audit trail, and the
``ccfd_lifecycle_stage`` / ``ccfd_lifecycle_promotions_total`` /
``ccfd_lifecycle_rollbacks_total`` series are asserted observable through
a live MetricsExporter scrape. Writes its artifact (lineage + audit +
metrics) under the system's temporary directory and exits 0 on success.

Usage:  python tools/lifecycle_drill.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        tempfile.gettempdir(), "ccfd_lifecycle_drill.json"))
    ap.add_argument("--state-dir", default="",
                    help="lifecycle state dir (default: a temp dir)")
    args = ap.parse_args()

    t_start = time.time()
    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import FEATURE_NAMES, synthetic_dataset
    from ccfd_tpu.lifecycle.controller import (
        STAGE_CANARY,
        STAGE_IDLE,
        Guardrails,
        LifecycleController,
    )
    from ccfd_tpu.lifecycle.evaluator import ShadowEvaluator
    from ccfd_tpu.lifecycle.shadow import ShadowTap
    from ccfd_tpu.lifecycle.versions import VersionStore
    from ccfd_tpu.metrics.exporter import MetricsExporter
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.parallel.checkpoint import CheckpointManager
    from ccfd_tpu.parallel.train import TrainConfig, fit_mlp
    from ccfd_tpu.router.router import default_scorer_breaker
    from ccfd_tpu.serving.scorer import Scorer

    cfg = Config()
    broker = Broker()
    reg = Registry()
    state_dir = args.state_dir or tempfile.mkdtemp(prefix="ccfd_lifecycle_drill_")

    ds = synthetic_dataset(n=4096, fraud_rate=0.05, seed=0)
    tc = TrainConfig(compute_dtype="float32")
    print("[drill] training champion (true labels, 150 steps)...")
    champion = fit_mlp(ds.X, ds.y, steps=150, seed=0, tc=tc)
    scorer = Scorer(model_name="mlp", params=champion,
                    batch_sizes=(16, 128, 1024, 4096),
                    compute_dtype="float32")

    store = VersionStore(os.path.join(state_dir, "versions.json"))
    ckpt = CheckpointManager(os.path.join(state_dir, "checkpoints"), keep=8)
    shadow = ShadowTap(scorer, broker, cfg.shadow_topic, reg)
    evaluator = ShadowEvaluator(cfg, broker, scorer, reg)
    breaker = default_scorer_breaker(reg)
    guardrails = Guardrails(
        min_labels=64, min_shadow_rows=512, canary_min_labels=32,
        # AUC + alert-rate carry the degraded-challenger verdict here; the
        # PSI ceiling stays wide because the drill's two champions are
        # trained from different seeds (their absolute score scales differ
        # more than a production parent->child retrain's would)
        max_score_psi=10.0, canary_weight=0.2,
    )
    ctl = LifecycleController(
        cfg, scorer, store=store, checkpoints=ckpt, shadow=shadow,
        evaluator=evaluator, guardrails=guardrails, registry=reg,
        breaker=breaker)
    served = ctl.wrap_score(scorer.score)
    exporter = MetricsExporter({"lifecycle": reg}, port=0).start()

    probe = ds.X[:128]
    baseline = scorer.score(probe).copy()
    rng = np.random.default_rng(0)

    def pump(with_labels: bool = True, until=None, max_iters: int = 64) -> None:
        """Feed live batches through the serving lane + labels, stepping
        the shadow worker and controller, until ``until()`` or budget."""
        for _ in range(max_iters):
            idx = rng.integers(0, len(ds.X), size=512)
            served(ds.X[idx])
            shadow.step()
            if with_labels:
                for j in rng.integers(0, len(ds.X), size=24):
                    broker.produce(cfg.labels_topic, {
                        "transaction": dict(
                            zip(FEATURE_NAMES, map(float, ds.X[j]))),
                        "label": int(ds.y[j]),
                    })
            ctl.step()
            if until is not None and until():
                return
        raise AssertionError("drill pump exhausted its budget before the "
                             "expected transition")

    checks: dict = {}

    # -- phase 1: degraded challenger must die in SHADOW -------------------
    print("[drill] phase 1: label-flipped challenger (degraded)...")
    degraded = fit_mlp(ds.X, 1.0 - ds.y, steps=150, seed=1, tc=tc)
    v_bad = ctl.submit_candidate(degraded, label_watermark=0)
    pump(until=lambda: store.get(v_bad).stage != "SHADOW")
    bad = store.get(v_bad)
    assert bad.stage == "REJECTED", f"degraded candidate ended {bad.stage}"
    assert np.allclose(scorer.score(probe), baseline, atol=1e-5), \
        "serving changed while rejecting the degraded challenger"
    assert scorer.challenger_version is None and not ctl.gate.active
    checks["degraded_rejected_in_shadow"] = True
    checks["degraded_reject_metrics"] = bad.metrics
    print(f"[drill]   v{v_bad} REJECTED: "
          f"auc_challenger={bad.metrics.get('auc_challenger'):.3f} vs "
          f"champion={bad.metrics.get('auc_champion'):.3f}")

    # -- phase 2: improved challenger promotes through CANARY --------------
    print("[drill] phase 2: improved challenger (600 steps)...")
    improved = fit_mlp(ds.X, ds.y, steps=600, seed=2, tc=tc)
    v_good = ctl.submit_candidate(improved, label_watermark=int(
        reg.counter("retrain_labels_total").value() or 0))
    saw_canary = [False]

    def good_resolved():
        if ctl.stage == STAGE_CANARY:
            saw_canary[0] = True
        return store.get(v_good).stage in ("CHAMPION", "REJECTED",
                                           "ROLLED_BACK")

    pump(until=good_resolved)
    good = store.get(v_good)
    assert good.stage == "CHAMPION", f"improved candidate ended {good.stage}"
    assert saw_canary[0], "promotion skipped the canary phase"
    c_rows = reg.counter("ccfd_lifecycle_canary_rows_total")
    assert c_rows.value(labels={"arm": "champion"}) > 0
    assert c_rows.value(labels={"arm": "challenger"}) > 0
    promoted = scorer.score(probe).copy()
    assert not np.allclose(promoted, baseline, atol=1e-5), \
        "promotion did not change serving"
    assert ctl.champion == v_good and store.champion().version == v_good
    checks["promoted_through_canary"] = True
    checks["canary_rows"] = {
        "champion": int(c_rows.value(labels={"arm": "champion"})),
        "challenger": int(c_rows.value(labels={"arm": "challenger"})),
    }
    print(f"[drill]   v{v_good} PROMOTED (canary rows: "
          f"{checks['canary_rows']})")

    # -- phase 3: canary guardrail breach auto-rolls back ------------------
    print("[drill] phase 3: third candidate + forced breaker-open breach...")
    third = fit_mlp(ds.X, ds.y, steps=650, seed=3, tc=tc)
    v_third = ctl.submit_candidate(third, label_watermark=0)
    pump(until=lambda: ctl.stage == STAGE_CANARY)
    assert store.get(v_third).stage == "CANARY"
    # degraded scorer edge mid-canary: drive the breaker open exactly as
    # the router's ladder would under a blackholed device
    for _ in range(8):
        breaker.record_failure(0.1)
    assert breaker.state == "open"
    pump(with_labels=False, until=lambda: ctl.stage == STAGE_IDLE,
         max_iters=4)
    rolled = store.get(v_third)
    assert rolled.stage == "ROLLED_BACK", f"breach ended {rolled.stage}"
    assert np.allclose(scorer.score(probe), promoted, atol=1e-5), \
        "rollback did not restore the champion checkpoint"
    assert ctl.serving_consistent()
    checks["canary_breach_rolled_back"] = True
    reasons = [e["detail"].get("reason", "")
               for e in store.audit_trail(v_third) if e["event"] == "stage"]
    assert any("breaker" in r for r in reasons), reasons
    print(f"[drill]   v{v_third} ROLLED_BACK: {reasons[-1]}")

    # -- observability: the acceptance metrics through a live scrape -------
    with urllib.request.urlopen(f"{exporter.endpoint}/metrics") as resp:
        body = resp.read().decode()
    for metric, want in (
        ("ccfd_lifecycle_stage", None),
        ("ccfd_lifecycle_promotions_total", 1.0),
        ("ccfd_lifecycle_rollbacks_total", 1.0),
        ("ccfd_lifecycle_rejections_total", 1.0),
    ):
        line = next((ln for ln in body.splitlines()
                     if ln.startswith(metric + " ")), None)
        assert line is not None, f"{metric} not exported"
        if want is not None:
            assert float(line.split()[-1]) == want, line
    checks["metrics_scraped_via_exporter"] = True

    audit = store.audit_trail()
    artifact = {
        "seconds": round(time.time() - t_start, 1),
        "state_dir": state_dir,
        "checks": checks,
        "versions": [v.to_dict() for v in store.versions()],
        "audit_trail": audit,
        "metrics": {
            "promotions": reg.counter(
                "ccfd_lifecycle_promotions_total").value(),
            "rollbacks": reg.counter(
                "ccfd_lifecycle_rollbacks_total").value(),
            "rejections": reg.counter(
                "ccfd_lifecycle_rejections_total").value(),
            "candidates": reg.counter(
                "ccfd_lifecycle_candidates_total").value(),
            "shadow_rows": reg.counter(
                "ccfd_lifecycle_shadow_rows_total").value(),
            "stage": reg.gauge("ccfd_lifecycle_stage").value(),
            "champion_version": reg.gauge(
                "ccfd_lifecycle_champion_version").value(),
        },
        "ok": True,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    exporter.stop()
    ctl.close()
    broker.close()
    print(f"[drill] OK: {len(audit)} audit events; artifact -> {args.out}")
    print(json.dumps({k: artifact[k] for k in ("seconds", "checks",
                                               "metrics")}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
