"""Storage smoke: prove the durable-state integrity plane end to end
(ISSUE 13).

Exit-code-gated drill for ``tools/verify_tier1.sh --storage-smoke``:

1. **Seed** a lifecycle state dir: a controller bootstraps a genesis
   champion (v1, checkpointed + hashed in the lineage), then a second
   champion era (v2) is stamped — two checkpoint steps on disk, v2 the
   recorded champion.
2. **Corrupt champion + torn lineage**: bitrot flips bytes in v2's
   ``params.npz`` and the live ``versions.json`` is truncated mid-frame
   (a torn write that survived a crash). A restarted controller must
   (a) QUARANTINE the torn lineage and recover the FULL lineage from the
   last-good retained generation (champion still v2, counter intact),
   (b) QUARANTINE the corrupt champion checkpoint and restore the newest
   VERIFIABLE step (v1's — the parent), with the re-stamp alarm firing so
   serving-params fingerprint == lineage ``checkpoint_hash``, and (c)
   keep the device path serving (no storage pin) with accounting exactly
   conserved through a live router.
3. **All generations corrupted**: every remaining checkpoint step gets
   bitrot. The next restart must find NOTHING verifiable and pin serving
   to the RULES tier through the heal-gate seam (``StoragePinGate``):
   every transaction still gets a decision, all of them from the rules
   floor, zero from the device or host tiers, accounting conserved.
4. **Faults + sweep + HTTP**: an injected ``torn_write`` storm makes a
   lineage save fail loudly (write_errors counted, orphan tmp left); the
   next VersionStore bring-up SWEEPS the debris
   (``ccfd_storage_tmp_swept_total``); and the ``ccfd_storage_*``
   counters plus the pin gauge are scraped over REAL HTTP.

    JAX_PLATFORMS=cpu python tools/storage_smoke.py
    tools/verify_tier1.sh --storage-smoke

Prints one JSON line on stdout; exit 0 only when every check holds.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
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
from ccfd_tpu.models import mlp  # noqa: E402
from ccfd_tpu.parallel.checkpoint import CheckpointManager  # noqa: E402
from ccfd_tpu.parallel.partition import params_fingerprint  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.runtime import durability, faults  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402


def _perturb(params, delta: float):
    """Same tree, shifted last-layer bias — a distinct champion era."""
    p = {"norm": params["norm"], "layers": [dict(l) for l in params["layers"]]}
    last = dict(p["layers"][-1])
    last["b"] = np.asarray(last["b"]) + np.float32(delta)
    p["layers"][-1] = last
    return p


def _controller(cfg, scorer, store, ckpts, reg, gate=None):
    broker = Broker(default_partitions=1)
    shadow = ShadowTap(scorer, broker, cfg.shadow_topic, reg)
    evaluator = ShadowEvaluator(cfg, broker, scorer, reg)
    lc = LifecycleController(
        cfg, scorer, store=store, checkpoints=ckpts, shadow=shadow,
        evaluator=evaluator, guardrails=Guardrails(), registry=reg,
        storage_pin=(gate.pin if gate is not None else None),
        storage_unpin=(gate.unpin if gate is not None else None),
    )
    return lc, broker


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=256)
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    state = tempfile.mkdtemp(prefix="ccfd_storage_smoke_")
    lineage_path = os.path.join(state, "versions.json")
    ckpt_dir = os.path.join(state, "checkpoints")

    reg_storage = Registry()
    reg_router = Registry()
    durability.bind_registry(reg_storage)
    cfg = Config(confidence_threshold=1.0)

    params_a = _perturb(mlp.init(jax.random.PRNGKey(0)), -1.0)
    params_b = _perturb(mlp.init(jax.random.PRNGKey(0)), +2.0)

    # -- 1. seed: two champion eras on disk --------------------------------
    reg_lc = Registry()
    scorer_a = Scorer(model_name="mlp", params=params_a,
                      batch_sizes=(16, 128, 1024), host_tier_rows=0)
    store = VersionStore(lineage_path)
    # npz path: deterministic single-file artifact the drill can bitrot
    ckpts = CheckpointManager(ckpt_dir, keep=8, use_orbax=False)
    lc_a, broker_a = _controller(cfg, scorer_a, store, ckpts, reg_lc)
    checks["seed_champion_v1"] = (store.champion() is not None
                                  and store.champion().version == 1)
    # second era, stamped the way a promotion stamps it: v2 becomes the
    # recorded champion with its own checkpoint + hash (the full gated
    # promotion is lifecycle_drill's claim, not this one's)
    store.set_stage(1, "RETIRED", reason="storage-smoke era 2")
    v2 = store.create(parent=1, stage="TRAIN")
    ckpts.pinned = {v2.version}
    ckpts.save(v2.version, params_b)
    store.set_checkpoint(v2.version, v2.version,
                         checkpoint_hash=params_fingerprint(params_b))
    store.set_stage(v2.version, "CHAMPION", reason="storage-smoke era 2")
    lc_a.close()
    broker_a.close()
    hash_b = params_fingerprint(params_b)
    detail["recorded_champion_hash"] = hash_b[:12]

    # -- 2. bitrot the champion checkpoint + tear the lineage --------------
    durability.flip_bytes(os.path.join(ckpt_dir, "step_2", "params.npz"))
    with open(lineage_path, "rb") as f:
        raw = f.read()
    with open(lineage_path, "wb") as f:
        f.write(raw[: len(raw) // 2])  # torn mid-frame

    c0 = durability.counts()
    reg_lc2 = Registry()
    scorer_b = Scorer(model_name="mlp", batch_sizes=(16, 128, 1024),
                      host_tier_rows=0)  # fresh boot params
    gate = durability.StoragePinGate(registry=reg_storage)
    store2 = VersionStore(lineage_path)
    # the torn lineage quarantined; the last-good generation recovered
    # the FULL lineage — champion v2, both eras, counter intact
    checks["lineage_quarantined"] = os.path.exists(lineage_path + ".corrupt")
    champ2 = store2.champion()
    checks["lineage_recovered_last_good"] = (
        champ2 is not None and champ2.version == 2
        and champ2.checkpoint_hash == hash_b)
    ckpts2 = CheckpointManager(ckpt_dir, keep=8, use_orbax=False)
    ckpts2.pinned = {2}
    lc_b, broker_b = _controller(cfg, scorer_b, store2, ckpts2, reg_lc2,
                                 gate=gate)
    # corrupt champion checkpoint quarantined; the newest VERIFIABLE step
    # (the parent era's) restored, and the re-stamp alarm re-recorded its
    # hash — serving params fingerprint == lineage checkpoint_hash
    checks["champion_ckpt_quarantined"] = os.path.exists(
        os.path.join(ckpt_dir, "step_2.corrupt"))
    served_fp = params_fingerprint(
        jax.tree.map(np.asarray, scorer_b.params))
    checks["last_good_restored"] = served_fp == params_fingerprint(params_a)
    checks["hash_parity_with_lineage"] = (
        store2.get(2).checkpoint_hash == served_fp)
    checks["no_pin_while_verifiable"] = not gate.pinned
    events = [e["event"] for e in store2.audit_trail()]
    checks["fallback_audited"] = "storage_fallback_restore" in events

    # device path still serves through a live router, gate composed in
    engine_b = build_engine(cfg, broker_b, Registry(), None)
    router_b = Router(cfg, broker_b, scorer_b.score, engine_b, reg_router,
                      max_batch=1024, host_score_fn=scorer_b.host_score,
                      degrade=True, heal_gate=gate)
    ds = synthetic_dataset(n=2048, fraud_rate=0.01, seed=7)
    rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(args.rows)]

    def pump(router, broker):
        broker.produce_batch(cfg.kafka_topic, rows,
                             list(range(len(rows))))
        while router.step() > 0:
            pass

    c_in = reg_router.counter("transaction_incoming_total")
    c_out = reg_router.counter("transaction_outgoing_total")
    c_deg = reg_router.counter("router_degraded_total")
    c_shed = reg_router.counter("router_shed_total")
    c_err = reg_router.counter("router_process_start_errors_total")
    pump(router_b, broker_b)
    checks["device_serving_after_restore"] = (
        c_in.total() == len(rows) and c_deg.total() == 0)
    lc_b.close()
    router_b.close()
    broker_b.close()

    # -- 3. ALL generations corrupted -> rules-tier pin --------------------
    for name in os.listdir(ckpt_dir):
        npz = os.path.join(ckpt_dir, name, "params.npz")
        if name.startswith("step_") and not name.endswith(".corrupt") \
                and os.path.exists(npz):
            durability.flip_bytes(npz)
    reg_lc3 = Registry()
    reg_router3 = Registry()
    scorer_c = Scorer(model_name="mlp", batch_sizes=(16, 128, 1024),
                      host_tier_rows=0)
    gate3 = durability.StoragePinGate(registry=reg_storage)
    store3 = VersionStore(lineage_path)
    ckpts3 = CheckpointManager(ckpt_dir, keep=8, use_orbax=False)
    lc_c, broker_c = _controller(cfg, scorer_c, store3, ckpts3, reg_lc3,
                                 gate=gate3)
    checks["pinned_when_nothing_verifies"] = (gate3.pinned
                                              and lc_c.storage_pinned)
    detail["pin_reason"] = gate3.reason
    engine_c = build_engine(cfg, broker_c, Registry(), None)
    router_c = Router(cfg, broker_c, scorer_c.score, engine_c, reg_router3,
                      max_batch=1024, host_score_fn=scorer_c.host_score,
                      degrade=True, heal_gate=gate3)
    c_in3 = reg_router3.counter("transaction_incoming_total")
    c_out3 = reg_router3.counter("transaction_outgoing_total")
    c_deg3 = reg_router3.counter("router_degraded_total")
    c_shed3 = reg_router3.counter("router_shed_total")
    c_err3 = reg_router3.counter("router_process_start_errors_total")
    pump(router_c, broker_c)
    rules_rows = c_deg3.value({"tier": "rules"})
    host_rows = c_deg3.value({"tier": "host"})
    checks["rules_tier_served_everything"] = (
        c_in3.total() == len(rows) and rules_rows == len(rows)
        and host_rows == 0)
    checks["accounting_conserved"] = (
        c_in.total() == c_out.total() + c_shed.total() + c_err.total()
        and c_in3.total()
        == c_out3.total() + c_shed3.total() + c_err3.total())
    detail["accounting"] = {
        "phase2": {"in": c_in.total(), "out": c_out.total()},
        "phase3": {"in": c_in3.total(), "out": c_out3.total(),
                   "rules": int(rules_rows), "host": int(host_rows)},
    }
    lc_c.close()
    router_c.close()
    broker_c.close()

    # corruption was detected + quarantined, last-good served — counted
    c1 = durability.counts()

    def delta(metric):
        a = sum(c0.get(metric, {}).values())
        b = sum(c1.get(metric, {}).values())
        return b - a

    checks["corruption_counted"] = delta("corrupt") >= 3
    checks["fallback_counted"] = delta("fallback") >= 1
    detail["storage_counts"] = {k: sum(v.values()) for k, v in c1.items()}

    # -- 4. injected write fault -> loud error + orphan tmp -> swept -------
    plan = faults.StorageFaultPlan.from_string("torn_write", active=True)
    faults.install_storage_faults(plan)
    store3.record_event(None, "storage-smoke", {"under": "torn_write"})
    faults.install_storage_faults(None)
    orphans = [n for n in os.listdir(state) if n.endswith(".tmp")]
    checks["torn_write_left_tmp"] = bool(orphans)
    VersionStore(lineage_path)  # bring-up sweeps the debris
    c1 = durability.counts()  # re-snapshot: phase 4 moved the counters
    checks["write_error_counted"] = delta("write_errors") >= 1
    checks["tmp_swept"] = (
        not [n for n in os.listdir(state) if n.endswith(".tmp")]
        and delta("tmp_swept") >= len(orphans))
    detail["storage_counts"] = {k: sum(v.values()) for k, v in c1.items()}

    # -- gauges + counters over REAL HTTP ----------------------------------
    exporter = MetricsExporter({"storage": reg_storage,
                                "router": reg_router}).start()
    try:
        with urllib.request.urlopen(exporter.endpoint + "/prometheus",
                                    timeout=10) as resp:
            scrape = resp.read().decode()
    finally:
        exporter.stop()
    checks["corrupt_counter_scraped_http"] = bool(re.search(
        r"ccfd_storage_corrupt_total\{[^}]*\} [1-9]", scrape))
    m = re.search(r"ccfd_storage_pinned(?:\{[^}]*\})? ([0-9.e+-]+)", scrape)
    checks["pin_gauge_scraped_http"] = (m is not None
                                        and float(m.group(1)) == 1.0)
    checks["fallback_counter_scraped"] = (
        "ccfd_storage_fallback_total" in scrape
        and "ccfd_storage_tmp_swept_total" in scrape)

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks, "detail": detail}))
    print(f"STORAGESMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
