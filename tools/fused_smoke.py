"""Fused-decision smoke: the live operator routes through ONE executable
(ISSUE 19).

Exit-code-gated drill for ``tools/verify_tier1.sh --fused-smoke``:

1. **Arm**: a CR with ``scorer.fused_decision: true`` (and the lifecycle
   lane off — the canary gate would override scores after the fused
   verdict fires, so the operator refuses the combination) brings up the
   full platform with the fused plane armed and precompiled.
2. **Route**: 512 produced transactions flow bus -> router -> fused
   decision executable -> engine. Accounting must conserve exactly:
   incoming == outgoing == 512, every row through the fused grid
   (``staged_fallbacks == 0``), per-bucket dispatch counters > 0.
3. **Parity**: the SAME records re-scored through the staged seam
   (``score`` + host ``RuleSet.evaluate``) must match the fused verdicts
   with ZERO delta — bit-equal probabilities, identical fired indices.
4. **HTTP**: the fused executable grid (model, buckets, per-bucket
   dispatch counts) scrapes from the exporter's ``/debug/device``
   inventory over real HTTP, and the ``fused_decision_*`` counters
   appear on ``/prometheus/router``.
5. **Warm**: zero serving-stage compiles after warmup — every compile
   the routing window triggered sits in a NON_SERVING stage
   (``fused.warm`` included), none on the serving path.

    JAX_PLATFORMS=cpu python tools/fused_smoke.py
    tools/verify_tier1.sh --fused-smoke

Prints one JSON line plus ``FUSEDSMOKE verdict=PASS|FAIL``; exit 0 only
when every check holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.data.ccfd import synthetic_dataset  # noqa: E402
from ccfd_tpu.platform.operator import Platform, PlatformSpec  # noqa: E402
from ccfd_tpu.runtime.heal import NON_SERVING_COMPILE_STAGES  # noqa: E402


def _cr() -> dict:
    return {
        "apiVersion": "ccfd.tpu/v1",
        "kind": "FraudDetectionPlatform",
        "spec": {
            "store": {"enabled": False},
            "bus": {"partitions": 2},
            "scorer": {"enabled": True, "model": "mlp", "train_steps": 0,
                       "fused_decision": True},
            # the fused plane refuses to arm next to the canary gate —
            # scores would be overridden AFTER the fused verdict fired
            "lifecycle": {"enabled": False},
            "engine": {"enabled": True},
            "notify": {"enabled": True, "seed": 0},
            "router": {"enabled": True},
            "producer": {"enabled": False},
            "monitoring": {"enabled": True},
            "health": {"enabled": False},
        },
    }


def _serving_compiles(prof) -> int:
    return sum(v for stage, v in prof.compile_counts().items()
               if stage not in NON_SERVING_COMPILE_STAGES)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--timeout-s", type=float, default=60.0)
    args = ap.parse_args()

    checks: dict[str, bool] = {}
    detail: dict = {}

    cfg = Config(customer_reply_timeout_s=0.2)
    p = Platform(PlatformSpec.from_cr(_cr(), cfg=cfg)).up(wait_ready_s=30.0)
    try:
        fds = p.fused_decision
        checks["fused_plane_armed"] = fds is not None and fds.enabled
        if fds is None:
            raise RuntimeError("fused decision plane did not arm")

        # warmup precompiled the grid during up(); everything after this
        # point is the serving window and must not compile
        warm_serving = _serving_compiles(p.profiler) if p.profiler else 0

        ds = synthetic_dataset(n=max(args.rows, 1024), fraud_rate=0.02,
                               seed=7)
        rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
                for i in range(args.rows)]
        keys = [f"tx-{i:05d}" for i in range(args.rows)]
        p.broker.produce_batch(cfg.kafka_topic, rows, keys)

        reg = p.registries["router"]
        out = reg.counter("transaction_outgoing_total")

        def routed() -> int:
            return int(out.value(labels={"type": "standard"})
                       + out.value(labels={"type": "fraud"}))

        deadline = time.monotonic() + args.timeout_s
        while time.monotonic() < deadline and routed() < args.rows:
            time.sleep(0.05)

        # -- 2. conservation + every row through the fused grid ------------
        n_in = int(reg.counter("transaction_incoming_total").value())
        n_out = routed()
        dispatches = sum(fds._dispatch_counts.values())
        checks["accounting_conserved"] = (
            n_in == n_out == args.rows)
        checks["all_rows_fused"] = (
            dispatches >= 1 and fds.staged_fallbacks == 0)
        detail["accounting"] = {
            "incoming": n_in, "outgoing": n_out,
            "fused_dispatches": dispatches,
            "staged_fallbacks": fds.staged_fallbacks,
        }

        # -- 4. the grid + per-bucket counters over real HTTP --------------
        # (scraped BEFORE the parity re-decide below so the HTTP counts
        # compare against the routing window's dispatch count exactly)
        metrics = p.status()["endpoints"]["metrics"]
        with urllib.request.urlopen(metrics + "/debug/device",
                                    timeout=10) as resp:
            dev = json.loads(resp.read())
        grid = (dev.get("executables") or {}).get("fused_decision") or {}
        http_counts = {int(k): int(v)
                       for k, v in (grid.get("dispatches") or {}).items()}
        checks["grid_scraped_http"] = (
            grid.get("enabled") is True
            and grid.get("model") == "mlp"
            and sum(http_counts.values()) == dispatches
            and all(v >= 1 for v in http_counts.values()))
        detail["grid"] = {k: grid.get(k) for k in (
            "model", "forward", "rules", "batch_sizes", "dispatches")}
        with urllib.request.urlopen(metrics + "/prometheus/router",
                                    timeout=10) as resp:
            scrape = resp.read().decode()
        checks["counters_scraped_http"] = (
            "fused_decision_dispatches_total" in scrape)

        # -- 3. parity: the same records through the staged seam -----------
        x = np.asarray(
            [[float(t) for t in r.decode().split(",")] for r in rows],
            np.float32)
        p_fused, f_fused = fds.decide(x)
        p_staged = np.asarray(p.scorer.score(x), np.float32)
        f_staged = fds.rules.evaluate(x, p_staged)
        checks["parity_zero_delta"] = bool(
            f_fused is not None
            and np.array_equal(p_fused, p_staged)
            and np.array_equal(f_fused, f_staged))
        detail["parity"] = {
            "rows": int(x.shape[0]),
            "proba_max_delta": float(np.abs(p_fused - p_staged).max()),
            "fired_mismatches": (int((f_fused != f_staged).sum())
                                 if f_fused is not None else -1),
        }

        # -- 5. zero serving-stage compiles after warmup -------------------
        if p.profiler is not None:
            now_serving = _serving_compiles(p.profiler)
            checks["zero_serving_compiles_after_warmup"] = (
                now_serving == warm_serving)
            detail["compiles"] = {
                "serving_during_window": now_serving - warm_serving,
                "stages": p.profiler.compile_counts(),
            }
    finally:
        p.down()

    ok = all(checks.values())
    print(json.dumps({"checks": checks, "detail": detail}, sort_keys=True))
    print(f"FUSEDSMOKE verdict={'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
