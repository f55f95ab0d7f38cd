#!/usr/bin/env python
"""Fleet kill drill: hard-kill one member of a live fleet, prove survival.

The scenario the fleet plane (ccfd_tpu/fleet/) exists for:

  1. one shared networked bus (bus/server.py over real HTTP) + N member
     processes (``python -m ccfd_tpu fleet member``), partitions split
     across members via the bus's ``router`` consumer group;
  2. traffic flows; one member is SIGKILLed MID-TRAFFIC (no atexit, no
     commit, no socket close), then the supervisor fences its idle
     consumers so the group rebalances under a bumped epoch;
  3. survivors re-adopt the dead member's partitions (disjointly — no
     partition double-owned, none orphaned), the victim respawns and the
     fleet rebalances again;
  4. the per-transaction conservation law is checked against the durable
     fleet ledger (fleet/ledger.py): every produced tx disposed, no
     ghost, no same-epoch double-route — cross-epoch redeliveries are
     counted at-least-once deliveries, not violations;
  5. champion fingerprint parity holds across survivors (nobody
     quarantined), per-member counter accounting balances, the elected
     aggregator dumped EXACTLY ONE member-kill incident bundle, and the
     survivor's exporter serves green ccfd_fleet_* gauges over HTTP;
  6. a fleet-scaling bench row (members, tx/s) is recorded.

Exit 0 iff every check passes. tools/fleet_smoke.py runs a small/fast
parameterization of this drill for `tools/verify_tier1.sh --fleet-smoke`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from urllib.request import urlopen

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _member_env() -> dict[str, str]:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",           # members are routing drills,
        "CCFD_BATCH_SIZES": "16,128,1024",  # not accelerator benches
        "CCFD_NATIVE_FRONT": "0",
    })
    return env


def _scrape(port: int) -> str:
    with urlopen(f"http://127.0.0.1:{port}/metrics", timeout=3.0) as r:
        return r.read().decode()


def _gauge(text: str, name: str) -> float | None:
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                return None
    return None


def run_drill(
    members: int = 2,
    partitions: int = 4,
    txs_before: int = 300,
    txs_after: int = 300,
    ttl_s: float = 2.0,
    state_dir: str | None = None,
    drain_timeout_s: float = 90.0,
    ready_timeout_s: float = 120.0,
) -> dict:
    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.bus.client import RemoteBroker
    from ccfd_tpu.bus.server import BrokerServer
    from ccfd_tpu.config import Config
    from ccfd_tpu.fleet.ledger import LEDGER_TOPIC, flatten_ledger
    from ccfd_tpu.fleet.protocol import (
        check_disjoint_ownership,
        check_fingerprint_parity,
        check_ledger_conservation,
        check_member_accounting,
    )
    from ccfd_tpu.fleet.supervisor import (
        FleetSupervisor,
        _free_port,
        build_member_cr,
    )

    cfg = Config.from_env()
    out: dict = {"ok": False, "checks": {}, "members": members,
                 "partitions": partitions}
    checks = out["checks"]
    state_dir = state_dir or tempfile.mkdtemp(prefix="fleet-drill-")
    out["state_dir"] = state_dir

    # the ONE shared component: a real networked bus over real HTTP
    broker = Broker(default_partitions=partitions)
    srv = BrokerServer(broker)
    bus_port = srv.start("127.0.0.1", 0)
    bus_url = f"http://127.0.0.1:{bus_port}"
    out["bus_url"] = bus_url

    names = [f"m{i:02d}" for i in range(members)]
    hb = {n: _free_port() for n in names}
    mon = {n: _free_port() for n in names}
    eps = {n: f"http://127.0.0.1:{hb[n]}" for n in names}
    sup = FleetSupervisor(bus_url, state_dir, env=_member_env())
    for n in names:
        sup.add_member(n, build_member_cr(
            n, bus_url, hb[n], [eps[o] for o in names if o != n],
            state_dir, ttl_s=ttl_s, gossip_interval_s=0.25,
            monitoring_port=mon[n],
        ))
        sup.spawn(n)

    client = RemoteBroker(bus_url)
    led = None
    produced: list[str] = []
    seq = 0

    def produce(count: int) -> None:
        nonlocal seq
        vals, keys = [], []
        for _ in range(count):
            tx = f"tx-{seq:06d}"
            seq += 1
            produced.append(tx)
            vals.append({"id": tx, "Amount": 50.0 + (seq % 400)})
            keys.append(tx)
        client.produce_batch(cfg.kafka_topic, vals, keys=keys)

    def routed_total() -> int:
        total = 0
        for n in names:
            h = sup.health(n)
            if h is not None:
                total += int(h.get("counters", {}).get("routed", 0))
        return total

    def wait_disjoint(expect_members: int, timeout_s: float = 45.0) -> list:
        deadline = time.monotonic() + timeout_s
        violations = ["never checked"]
        while time.monotonic() < deadline:
            owners = sup.ownership()
            if len(owners) == expect_members:
                violations = check_disjoint_ownership(owners, partitions)
                if not violations:
                    return []
            time.sleep(0.3)
        return violations

    try:
        sup.wait_ready(timeout_s=ready_timeout_s)
        checks["initial_ownership_disjoint"] = (
            wait_disjoint(members) == [])

        # phase 1: traffic across the whole fleet; the kill lands
        # MID-TRAFFIC (victim demonstrably routing when it dies)
        t_bench = time.monotonic()
        produce(txs_before)
        victim = names[-1]
        deadline = time.monotonic() + 60.0
        victim_routing = False
        while time.monotonic() < deadline:
            h = sup.health(victim)
            if h is not None and int(
                    h.get("counters", {}).get("routed", 0)) > 0:
                victim_routing = True
                break
            time.sleep(0.1)
        checks["victim_was_routing"] = victim_routing

        # phase 2: HARD kill + fence; survivors must re-adopt ALL
        # partitions disjointly while traffic keeps flowing
        sup.kill(victim, fence_idle_s=0.5, settle_s=1.0)
        produce(txs_after)
        survivors = [n for n in names if n != victim]
        checks["survivors_adopted_all_partitions"] = (
            wait_disjoint(len(survivors)) == [])

        # phase 3: respawn — the fleet heals back to N members
        sup.respawn(victim, timeout_s=ready_timeout_s)
        checks["rebalanced_after_respawn"] = wait_disjoint(members) == []

        # phase 4: drain the ledger until every produced tx is disposed
        led = client.consumer("fleet-drill-ledger", (LEDGER_TOPIC,))
        entries: list[dict] = []
        disposed: set[str] = set()
        deadline = time.monotonic() + drain_timeout_s
        while time.monotonic() < deadline:
            recs = led.poll(max_records=2048, timeout_s=0.5)
            if recs:
                fresh = flatten_ledger(recs)
                entries.extend(fresh)
                disposed.update(str(e["tx"]) for e in fresh)
            if set(produced) <= disposed:
                break
        bench_wall_s = time.monotonic() - t_bench

        conservation = check_ledger_conservation(produced, entries)
        out["conservation"] = {
            k: (v if not isinstance(v, list) else v[:5])
            for k, v in conservation.items()
        }
        checks["ledger_conserved"] = bool(conservation["conserved"])
        checks["ledger_covers_all_produced"] = (
            conservation["disposed"] == conservation["produced"])

        # phase 5: parity + accounting + gauges + incident evidence
        health = {n: sup.health(n) for n in names}
        live = {n: h for n, h in health.items() if h is not None}
        checks["all_members_answer_health"] = len(live) == members
        parity = check_fingerprint_parity(
            {h["member"]: h.get("fingerprint") for h in live.values()})
        out["parity"] = parity
        checks["champion_parity"] = bool(
            parity["parity"] and parity["majority"] is not None)
        checks["nobody_quarantined"] = not any(
            h.get("quarantined") for h in live.values())
        acct_violations = check_member_accounting(
            {h["member"]: h.get("counters", {}) for h in live.values()})
        out["accounting_violations"] = acct_violations
        checks["member_accounting_balances"] = not acct_violations

        # the survivor's exporter, over real HTTP: parity green, the full
        # membership back, nobody quarantined. Polled — the survivor's
        # gossip redial to the respawned victim rides a jittered backoff,
        # so its membership view converges within ~ttl, not instantly.
        gauges_green = False
        deadline = time.monotonic() + 6.0 * ttl_s
        while not gauges_green and time.monotonic() < deadline:
            try:
                text = _scrape(mon[survivors[0]])
                gauges_green = (
                    _gauge(text, "ccfd_fleet_parity") == 1.0
                    and _gauge(text, "ccfd_fleet_members") == float(members)
                    and _gauge(text, "ccfd_fleet_quarantined") == 0.0
                )
            except OSError:
                pass
            if not gauges_green:
                time.sleep(0.3)
        checks["fleet_gauges_green"] = gauges_green

        bundles = sorted(glob.glob(os.path.join(
            state_dir, "incidents-*", "inc-*-fleet_member_kill.json")))
        out["kill_bundles"] = bundles
        checks["exactly_one_kill_bundle"] = len(bundles) == 1

        # fleet-scaling row (the HOST dimension): routed throughput across
        # the whole drill window, kill and rebalance included — the
        # survivable number
        bench = {
            "mode": "fleet_scaling",
            "members": members,
            "partitions": partitions,
            "transactions": len(produced),
            "wall_s": round(bench_wall_s, 3),
            "tx_s": round(len(produced) / max(bench_wall_s, 1e-9), 1),
            "kill_and_rejoin_included": True,
        }
        out["bench"] = bench
        with open(os.path.join(state_dir, "fleet_bench.json"), "w") as f:
            json.dump(bench, f, indent=2)
        checks["bench_row_recorded"] = True

        out["ok"] = all(checks.values())
    finally:
        if led is not None:
            led.close()
        client.close()
        sup.stop_all()
        srv.stop()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--members", type=int, default=2)
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--txs-before", type=int, default=300)
    ap.add_argument("--txs-after", type=int, default=300)
    ap.add_argument("--ttl-s", type=float, default=2.0)
    ap.add_argument("--state-dir", default=None,
                    help="keep artifacts here (default: fresh tempdir)")
    args = ap.parse_args()
    out = run_drill(
        members=args.members,
        partitions=args.partitions,
        txs_before=args.txs_before,
        txs_after=args.txs_after,
        ttl_s=args.ttl_s,
        state_dir=args.state_dir,
    )
    print(json.dumps(out, indent=2))
    print(f"FLEETDRILL verdict={'PASS' if out['ok'] else 'FAIL'}",
          file=sys.stderr)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
