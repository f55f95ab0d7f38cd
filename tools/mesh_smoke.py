"""Mesh smoke: sharded serving through the LIVE operator platform (ISSUE 12).

Exit-code-gated drill for ``tools/verify_tier1.sh --mesh-smoke``: on a
forced 8-device virtual CPU mesh (the same CI substrate as the multichip
dryrun), the platform operator brings up the full pipeline with the
``mesh:`` component armed — named (data, fsdp, tp) mesh, partitioner-
sharded Scorer behind the router pool, publish gate through the pool's
pause barrier — and must prove:

1. **Sharded serving end to end**: the producer's transactions flow
   bus -> ParallelRouter workers -> the SPMD scorer, with accounting
   exactly conserved (incoming == outgoing + shed + start_errors) and
   every produced row consumed.
2. **Score parity**: the mesh scorer's probabilities match a fresh
   single-device scorer holding the same params.
3. **One lifecycle swap under load**: with traffic in flight, the
   lifecycle controller re-asserts the champion checkpoint
   (``restore_champion`` — the same publish surface promotions and
   rollbacks use). The swap must ride the partitioner's publish gate
   (pause acknowledged by every worker, zero timeouts), record a
   checkpoint hash in the audit trail, and leave scores unchanged.
4. **Mesh telemetry over real HTTP**: ``ccfd_mesh_devices`` /
   ``ccfd_mesh_axis_size`` / ``ccfd_mesh_publishes_total`` scrape live
   (the Device board's Mesh row).

    JAX_PLATFORMS=cpu python tools/mesh_smoke.py
    tools/verify_tier1.sh --mesh-smoke

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

# the forced mesh must exist BEFORE jax initializes (same as tests/conftest)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.platform.operator import Platform, PlatformSpec  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--transactions", type=int, default=1500)
    ap.add_argument("--drain-s", type=float, default=45.0)
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    cr = {"spec": {
        "mesh": {"enabled": True, "devices": args.devices},
        "scorer": {"enabled": True, "model": "mlp"},
        "bus": {"partitions": 4},
        "router": {"workers": 2},
        "engine": {"enabled": True},
        "retrain": {"enabled": True, "interval_s": 0.2},
        "lifecycle": {"enabled": True},
        "producer": {"enabled": True,
                     "transactions": args.transactions},
        "monitoring": {"enabled": True, "port": 0},
        "health": {"enabled": False},
        "notify": {"enabled": False},
        "investigator": {"enabled": False},
        "analytics": {"enabled": False},
        "chaos": {"enabled": False},
    }}
    p = Platform(PlatformSpec.from_cr(cr, cfg=Config())).up()
    try:
        # -- 1. the live platform serves SHARDED -------------------------
        mesh_st = p.status().get("mesh") or {}
        detail["mesh"] = mesh_st
        checks["mesh_armed"] = mesh_st.get("devices") == args.devices
        checks["scorer_sharded"] = (
            p.scorer.mesh is p.mesh and p.partitioner is not None)
        checks["publish_gate_armed"] = (
            p.partitioner is not None
            and p.partitioner.gate is not None
            and p.partitioner.gate.barrier is p.router)

        checks["producer_done"] = p.wait_producer(timeout_s=120.0)
        reg = p.registries["router"]
        c_in = reg.counter("transaction_incoming_total")
        c_out = reg.counter("transaction_outgoing_total")
        c_shed = reg.counter("router_shed_total")
        c_err = reg.counter("router_process_start_errors_total")
        deadline = time.monotonic() + args.drain_s
        while (c_in.total() < args.transactions
               and time.monotonic() < deadline):
            time.sleep(0.1)
        checks["all_rows_consumed"] = c_in.total() == args.transactions

        # -- 2. single-device vs mesh score parity -----------------------
        host_params = jax.tree.map(np.asarray, p.scorer.params)
        single = Scorer(model_name="mlp", params=host_params,
                        compute_dtype=p.cfg.compute_dtype,
                        batch_sizes=(512,), host_tier_rows=0,
                        use_fused=False)
        rng = np.random.default_rng(12)
        probe = rng.standard_normal((512, 30)).astype(np.float32)
        ref = single.score(probe)
        got = p.scorer.score_pipelined(probe, depth=1)
        delta = float(np.max(np.abs(ref - got)))
        detail["parity_max_delta"] = delta
        checks["score_parity_vs_single_device"] = delta < 2e-2

        # -- 3. one lifecycle swap UNDER LOAD through the publish gate ---
        gate = p.partitioner.gate
        pubs_before = gate.publishes
        # fresh traffic in flight while the swap publishes
        feed = [",".join("0.1" for _ in range(30)).encode()] * 256
        p.broker.produce_batch(p.cfg.kafka_topic, feed, list(range(256)))
        p.lifecycle.restore_champion()
        checks["swap_rode_publish_gate"] = gate.publishes > pubs_before
        checks["swap_pause_acked_by_pool"] = gate.pause_timeouts == 0
        events = [e for e in p.lifecycle.store.audit_trail()
                  if e["event"] == "heal_respawn_restore"]
        checks["swap_recorded_checkpoint_hash"] = bool(
            events and events[-1]["detail"].get("checkpoint_hash"))
        total = args.transactions + len(feed)
        deadline = time.monotonic() + args.drain_s
        while c_in.total() < total and time.monotonic() < deadline:
            time.sleep(0.1)
        got2 = p.scorer.score_pipelined(probe, depth=1)
        delta2 = float(np.max(np.abs(ref - got2)))
        detail["parity_after_swap_max_delta"] = delta2
        checks["scores_unchanged_after_swap"] = delta2 < 2e-2

        # -- accounting conserved through the whole drill ----------------
        detail["accounting"] = {
            "incoming": c_in.total(), "outgoing": c_out.total(),
            "shed": c_shed.total(), "start_errors": c_err.total(),
        }
        checks["accounting_conserved"] = (
            c_in.total()
            == c_out.total() + c_shed.total() + c_err.total()
            and c_in.total() == total)

        # -- 4. mesh telemetry over real HTTP ----------------------------
        with urllib.request.urlopen(p.exporter.endpoint + "/prometheus",
                                    timeout=10) as resp:
            scrape = resp.read().decode()
        m = re.search(r"ccfd_mesh_devices ([0-9.e+-]+)", scrape)
        checks["mesh_gauge_scraped_http"] = (
            m is not None and float(m.group(1)) == float(args.devices))
        checks["mesh_axis_and_publish_counters_scraped"] = (
            "ccfd_mesh_axis_size" in scrape
            and "ccfd_mesh_publishes_total" in scrape)
    finally:
        p.down()

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks, "detail": detail}))
    print(f"MESHSMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
