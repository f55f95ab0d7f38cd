"""Chaos soak: the full pipeline under STATEFUL failures, with accounting.

Round 2 soaked router kills (the one component with no state); round 3
added a mid-soak device wedge; round 4 killed the ENGINE — the stateful
tier — with every kill a real crash-recovery (runtime/recovery.py:
aligned checkpoint restore + bus-offset rewind through the SAME live
router). Round 5 closes the last gap (VERDICT r4 items 2/weak-8): the
DURABLE BUS itself is now a ChaosMonkey target — ``Broker.crash_restart``
drops all in-memory state and replays the segment log in place with
every consumer attached mid-stream — and the bus runs with RETENTION
(segment rotation + delete-before-committed-offset), so memory stays
flat over arbitrarily long soaks. The accounting walk is therefore LIVE:
a consumer group walks the audit ledger as it flows (its committed
position is what retention trims behind), with bitmap pid-ledgers so the
walker itself is flat-memory; RSS is sampled through the run and its
drift reported. The midpoint device wedge and the crash-reopen
copy-drill (a second Broker replayed from a copied log dir must agree
on every offset) remain from earlier rounds.

At the end, the audit stream (per-partition offset order, with the
coordinator's per-partition ``engine_restored`` markers) is walked for the
accounting invariant: within each engine epoch every started instance
reaches a terminal state exactly once or is still active in the final
engine; work a dead epoch did past its last checkpoint is counted as
rolled back (at-least-once redelivery, like Kafka into a restarted KIE
pod — reference deploy/ccd-service.yaml); nothing else may be lost or
double-completed.

Round 6 adds ``--net-faults``: beyond kills, the ChaosMonkey schedules
NETWORK fault storms — by default a blackholed scorer edge
(runtime/faults.py) — and the router runs its degradation ladder
(runtime/breaker.py + router tiers). The exit criteria then also require
that storms fired, the ladder absorbed them (``router_degraded_total``),
the breaker-state gauge is exported, and the accounting walk stayed
violation-free while degraded — a sick edge must cost scoring QUALITY,
never progress or correctness.

    JAX_PLATFORMS=cpu python tools/chaos_soak.py --seconds 240
    JAX_PLATFORMS=cpu python tools/chaos_soak.py --seconds 240 --net-faults

Prints one JSON line (counts and verdicts; its rates are a CPU host's and
are no record of speed).  Exit 0 only when the pipeline drained, the
device path recovered, engine kills happened and every accounting check
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.bus.broker import Broker  # noqa: E402
from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.data.ccfd import FEATURE_NAMES, synthetic_dataset  # noqa: E402
from ccfd_tpu.metrics.prom import Registry  # noqa: E402
from ccfd_tpu.models import mlp  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.runtime.chaos import ChaosMonkey  # noqa: E402
from ccfd_tpu.runtime.recovery import (  # noqa: E402
    CheckpointCoordinator,
    attach_engine_service,
)
from ccfd_tpu.runtime.supervisor import Supervisor  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402


def audit_accounting(broker: Broker, topic: str) -> dict:
    """Walk the audit stream for the at-least-once accounting invariant.

    Pids are partition-sticky (events keyed by pid) and the restore marker
    reaches every partition, so each partition's offset order is ground
    truth — the walk keeps PER-PARTITION state (a marker repeats once per
    partition and must only affect that partition's pids).  At an
    ``engine_restored`` marker (runtime/recovery.py) everything the dead
    epoch did past its last checkpoint rolls back: starts/completions of
    pids >= next_pid (instances born after the cut) and completions of
    pids in ``active_pids`` (instances restored as live again, whose
    post-cut terminal events are undone and may legitimately recur).
    Anything else lost or double-completed is a violation."""
    w = AccountingWalker()
    c = broker.consumer("soak-audit-check", (topic,))
    while True:
        recs = c.poll(50_000, timeout_s=0.2)
        if not recs:
            break
        for r in recs:
            w.feed(r)
    c.close()
    return w.result()


class _PidBits:
    """Membership over monotonically-assigned pids as a bitmap.

    The walker's seen/done ledgers hold one entry per process instance —
    at soak rates that is ~every transaction, and Python int-sets cost
    ~60 B/pid (a 20-minute soak would leak ~600 MB of *ledger*, defeating
    the flat-RSS claim the soak exists to prove). Engine pids are dense
    monotone ints, so a bytearray bit per pid is exact at 1/500th the
    memory and O(range/8) for the rollback sweeps markers need."""

    __slots__ = ("bits", "count")

    def __init__(self) -> None:
        self.bits = bytearray()
        self.count = 0

    def add(self, pid: int) -> None:
        byte, bit = pid >> 3, 1 << (pid & 7)
        if byte >= len(self.bits):
            self.bits.extend(b"\0" * (byte + 1 - len(self.bits)))
        if not self.bits[byte] & bit:
            self.bits[byte] |= bit
            self.count += 1

    def discard(self, pid: int) -> None:
        byte, bit = pid >> 3, 1 << (pid & 7)
        if byte < len(self.bits) and self.bits[byte] & bit:
            self.bits[byte] &= ~bit
            self.count -= 1

    def __contains__(self, pid: int) -> bool:
        byte = pid >> 3
        return byte < len(self.bits) and bool(self.bits[byte] & (1 << (pid & 7)))

    def clear_from(self, pid: int) -> int:
        """Clear every member >= pid; returns how many were cleared."""
        cleared = 0
        first = pid >> 3
        if first < len(self.bits):
            keep = (1 << (pid & 7)) - 1
            high = self.bits[first] & ~keep
            cleared += bin(high).count("1")
            self.bits[first] &= keep
            for i in range(first + 1, len(self.bits)):
                if self.bits[i]:
                    cleared += bin(self.bits[i]).count("1")
                    self.bits[i] = 0
        self.count -= cleared
        return cleared


class AccountingWalker:
    """Incremental form of :func:`audit_accounting` (round 5): the soak's
    bus now has RETENTION, so the ledger cannot be replayed whole at the
    end — a live consumer walks the stream as it flows, and the broker's
    delete-before-committed-offset retention protects every unwalked
    record by construction (the walker's committed position IS the trim
    floor for the audit topic). Same per-partition state machine, fed one
    record at a time in partition-offset order; seen/done ledgers are
    bitmaps (:class:`_PidBits`) so the walker itself stays flat-memory."""

    def __init__(self) -> None:
        self.starts = self.completes = self.rolled_back = self.markers = 0
        self.violations: list[str] = []
        self._parts: dict[int, dict] = {}

    def feed(self, rec) -> None:
        st = self._parts.setdefault(
            rec.partition,
            {"open": set(), "done": _PidBits(), "seen": _PidBits()},
        )
        open_p: set = st["open"]
        done_b: _PidBits = st["done"]
        seen_b: _PidBits = st["seen"]
        ev = rec.value
        kind = ev.get("event")
        if kind == "engine_restored":
            self.markers += 1
            # active-at-cut pids all precede next_pid, so the clear_from
            # below cannot touch them; & seen keeps partition-stickiness
            # (the marker lists every partition's actives)
            restored = {x for x in ev.get("active_pids", ()) if x in seen_b}
            void_open = {x for x in open_p if x >= ev["next_pid"]}
            n_void_done = done_b.clear_from(ev["next_pid"])
            undone = {x for x in restored if x in done_b}
            for x in undone:
                done_b.discard(x)
            self.rolled_back += len(void_open) + n_void_done + len(undone)
            st["open"] = restored
        elif kind == "process_started":
            self.starts += 1
            pid = ev["pid"]
            seen_b.add(pid)
            if pid in open_p:
                self.violations.append(f"double start pid={pid}")
            open_p.add(pid)
        elif kind == "process_completed":
            self.completes += 1
            pid = ev["pid"]
            if pid in done_b:
                self.violations.append(f"double complete pid={pid}")
            elif pid not in open_p:
                self.violations.append(f"complete without start pid={pid}")
            else:
                open_p.discard(pid)
                done_b.add(pid)

    @property
    def open_at_end(self) -> set[int]:
        out: set[int] = set()
        for st in self._parts.values():
            out |= st["open"]
        return out

    def result(self) -> dict:
        return {
            "starts": self.starts,
            "completes": self.completes,
            "rolled_back": self.rolled_back,
            "restore_markers": self.markers,
            "open_at_end": self.open_at_end,
            "violations": self.violations[:20],
            "violation_count": len(self.violations),
        }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=240.0)
    ap.add_argument("--wedge-s", type=float, default=20.0,
                    help="device-wedge duration at the soak midpoint")

    def _positive_ms(v: str) -> float:
        f = float(v)
        if f <= 0:
            raise argparse.ArgumentTypeError(
                "the soak exercises the dispatch deadline; it must be > 0"
            )
        return f

    ap.add_argument("--deadline-ms", type=_positive_ms, default=250.0)
    ap.add_argument("--feed-batch", type=int, default=2000)
    ap.add_argument("--checkpoint-s", type=float, default=3.0)
    ap.add_argument("--chaos-interval-s", type=float, default=15.0)
    ap.add_argument("--targets", default="router,engine,bus",
                    help="comma list for the ChaosMonkey")
    ap.add_argument("--retention-records", type=int, default=50_000,
                    help="per-partition bus retention cap (0 = retain "
                    "everything, the pre-round-5 behavior). With the cap "
                    "on, memory stays flat over arbitrarily long soaks "
                    "and the live accounting walker's committed position "
                    "is what keeps every unwalked ledger record safe")
    ap.add_argument("--segment-bytes", type=int, default=4 * 1024 * 1024,
                    help="on-disk segment size. Sized to the retention "
                    "window, NOT the 64 MiB production default: disk "
                    "trims whole segments, so segment size bounds how "
                    "much history a bus crash_restart must replay — the "
                    "first 20-min run with 64 MiB segments spent a 38 s "
                    "stall JSON-decoding a ~1M-record replay per kill")
    ap.add_argument("--bus-log", default="",
                    help="durable bus log dir (default: fresh tempdir)")
    ap.add_argument("--bus-drill-tx", type=int, default=40_000,
                    help="run the bus crash-reopen drill once this many "
                    "transactions have flowed (early: replaying the log is "
                    "O(records), so the drill must run on a bounded log, "
                    "not the multi-million-record end state)")
    ap.add_argument("--net-faults", action="store_true",
                    help="round 6: drill DEGRADED edges, not just kills — "
                    "the ChaosMonkey schedules fault storms on the scorer "
                    "edge (runtime/faults.py) and the router must keep "
                    "deciding every transaction through its degradation "
                    "ladder (host tier / rules-only) with zero accounting "
                    "violations")
    ap.add_argument("--fault-spec", default="scorer:blackhole,stall=300",
                    help="CCFD_FAULTS-syntax plan the storms activate "
                    "(default: a blackholed scorer edge)")
    ap.add_argument("--fault-interval-s", type=float, default=10.0)
    ap.add_argument("--fault-duration-s", type=float, default=3.0)
    ap.add_argument("--workers", type=int, default=2,
                    help="router worker loops (router/parallel.py): >1 "
                    "drills the partition-parallel fan-out — group-wide "
                    "pause barrier, shared in-flight budget, coalesced "
                    "dispatch — under the same kills; 1 = the historical "
                    "single router")
    ap.add_argument("--device-faults", action="store_true",
                    help="ISSUE 11: drill the DEVICE as the fault target "
                    "— a DeviceSupervisor (runtime/heal.py) supervises "
                    "the scorer while device-fault storms "
                    "(runtime/faults.py device_hang et al.) wedge it; "
                    "each storm must reach QUARANTINED with the host "
                    "tier serving (zero accounting violations), heal "
                    "through the ladder, and re-promote WARM (no "
                    "serving-stage XLA compiles after the flip)")
    ap.add_argument("--device-fault-spec", default="device_hang:ms=400",
                    help="CCFD_DEVICE_FAULTS-syntax plan the device "
                    "storms activate")
    ap.add_argument("--device-fault-interval-s", type=float, default=20.0,
                    help="seconds between device-fault storm windows")
    ap.add_argument("--storage-faults", action="store_true",
                    help="ISSUE 13: drill the DISK as the fault target — "
                    "storage-fault storms (runtime/faults.py torn_write/"
                    "rename_lost/bitrot/enospc/fsync_fail/slow_disk) "
                    "degrade every durable write at the durability seam "
                    "while the ChaosMonkey kills services mid-write; the "
                    "run must end with accounting exactly conserved, "
                    "serving-params fingerprint == the lineage champion's "
                    "checkpoint_hash, every detected corruption "
                    "quarantined (never served), and zero unswept tmp "
                    "debris. Implies --lifecycle (the hash-parity claim "
                    "needs the lineage) and a durable on-disk cut.")
    ap.add_argument("--storage-fault-spec",
                    default="bitrot:rate=0.25;torn_write:rate=0.25;"
                            "rename_lost:rate=0.15;fsync_fail:rate=0.1;"
                            "slow_disk:ms=2,rate=0.5",
                    help="CCFD_STORAGE_FAULTS-syntax plan the storage "
                    "storms activate")
    ap.add_argument("--lifecycle", action="store_true",
                    help="run the model-lifecycle controller (lifecycle/) "
                    "under the storm: candidates cycle through shadow/"
                    "canary/promotion while services are killed; asserts "
                    "the pool ends on a single consistent model version")
    ap.add_argument("--lifecycle-submit-s", type=float, default=15.0,
                    help="seconds between candidate submissions")
    ap.add_argument("--audit", action="store_true",
                    help="arm the decision-provenance plane "
                    "(observability/audit.py): every routed tx stamps a "
                    "DecisionRecord through kill-storms; the ok-gate "
                    "requires exact conservation (routed == recorded) "
                    "and that re-stamps only appear with crash restores")
    ap.add_argument("--replay", action="store_true",
                    help="ISSUE 17: fold verdict-parity into the ok-gate "
                    "— after the storm settles, a window recorded DURING "
                    "the storm is re-scored through the same live stack "
                    "(ccfd_tpu/replay/) at bulk priority; any drop, "
                    "ghost or unexplained divergence fails the exit "
                    "gate (champion_hash divergences are tolerated only "
                    "when --lifecycle actually promoted). Implies "
                    "--audit (the window source is the decision log).")
    ap.add_argument("--replay-rows", type=int, default=512,
                    help="size of the storm-recorded window the replay "
                    "drill re-scores")
    ap.add_argument("--lockcheck", action="store_true",
                    help="arm the runtime lock-order sanitizer (analysis/"
                    "lockcheck.py; CCFD_LOCKCHECK=1 implies it): every "
                    "lock ccfd_tpu constructs records its acquisition "
                    "order through the kill-storm, and ANY recorded "
                    "inversion fails the soak — the ccfd-lint lock-order "
                    "rule's dynamic half, under real chaos")
    args = ap.parse_args()
    lock_graph = None
    if args.lockcheck or os.environ.get("CCFD_LOCKCHECK"):
        from ccfd_tpu.analysis import lockcheck as _lockcheck

        # record-don't-raise: a soak must run to its accounting walk and
        # report, not die mid-storm — the ok-gate below fails on any
        # recorded inversion
        lock_graph = _lockcheck.install(raise_on_cycle=False)
    if args.storage_faults:
        # the end-of-run hash-parity claim (serving fingerprint ==
        # lineage champion checkpoint_hash) needs the lineage running
        args.lifecycle = True
    if args.replay:
        # the replay drill's window source is the decision log
        args.audit = True

    bus_dir = args.bus_log or tempfile.mkdtemp(prefix="ccfd_soak_bus_")
    # audit ON: it is the accounting ledger this soak asserts over
    cfg = Config(confidence_threshold=1.0, audit_topic="ccd-audit")
    broker = Broker(log_dir=bus_dir,
                    retention_records=args.retention_records or None,
                    segment_bytes=args.segment_bytes)
    reg_r, reg_k, reg_c = Registry(), Registry(), Registry()

    # live accounting walker: consumes the ledger AS IT FLOWS (retention
    # trims behind its committed position; the end-of-run walk of rounds
    # 2-4 would find the ledger's head already deleted)
    walker = AccountingWalker()
    walker_stop = threading.Event()
    audit_consumer = broker.consumer("soak-audit-check", (cfg.audit_topic,))

    def walk() -> None:
        while True:
            recs = audit_consumer.poll(50_000, timeout_s=0.2)
            for r in recs:
                walker.feed(r)
            if not recs and walker_stop.is_set():
                return

    walk_thread = threading.Thread(target=walk, daemon=True,
                                   name="soak-acct-walker")
    walk_thread.start()

    def engine_factory():
        return build_engine(cfg, broker, reg_k, None)

    engine = engine_factory()

    ds = synthetic_dataset(n=4096, fraud_rate=0.002, seed=0)
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    # push probabilities to a trained-model-like range: an untrained MLP
    # fires ~half of all traffic into the fraud process, which floods the
    # engine with open investigations at a rate no investigator pool could
    # match and turns the soak into a snapshot-size stress test instead of
    # a failure drill
    import jax.numpy as jnp

    params = dict(params)
    params["layers"] = [dict(l) for l in params["layers"]]
    params["layers"][-1]["b"] = jnp.asarray([-4.0], jnp.float32)
    scorer = Scorer(model_name="mlp", params=params,
                    batch_sizes=(128, 1024, 4096), host_tier_rows=64,
                    dispatch_deadline_ms=args.deadline_ms)
    wedged, release = threading.Event(), threading.Event()
    orig_apply = scorer._apply

    def gated(p, xx):
        if wedged.is_set():
            release.wait(timeout=120.0)
        return orig_apply(p, xx)

    scorer._apply = gated
    scorer.warmup()
    from ccfd_tpu.utils.gctune import tune_for_service

    tune_for_service()  # match the gc config services run with
    scorer._wedge._probe_interval_s = 2.0  # tight recovery for the soak

    # net-fault mode: the scorer edge gets a storm-scheduled fault plan
    # (blackhole by default) and the router gets the full degradation
    # ladder — breaker-gated device tier, host numpy tier, rules-only
    # floor — so a partitioned scorer degrades quality, never progress
    fault_plan = None
    score_fn = scorer.score
    host_fn = None
    if args.net_faults:
        from ccfd_tpu.runtime.faults import FaultPlan  # noqa: E402

        fault_plan = FaultPlan.from_string(args.fault_spec, seed=13,
                                           active=False)
        net_injector = fault_plan.injector("scorer", reg_r)
        if net_injector is not None:
            score_fn = net_injector.wrap_fn(scorer.score)
    if (args.net_faults or args.device_faults) and scorer.has_host_forward:
        # both degraded-edge and sick-device drills need the ladder's
        # host tier armed: quality degrades, progress never stops
        host_fn = scorer.host_score
    # -- model lifecycle under the storm (--lifecycle) ---------------------
    # The governed-rollout machinery (lifecycle/) runs THROUGH the kills:
    # a submitter cycles perturbed candidates through shadow -> canary ->
    # promotion while the router/engine/bus die and recover around it. The
    # end-of-run assertion is the one that matters operationally: after
    # recovery the pool serves a SINGLE consistent version (serving params
    # == the champion's checkpoint; no challenger slot or canary gate left
    # dangling by a mid-canary kill).
    lifecycle = None
    lifecycle_breaker = None
    lifecycle_tap = None
    lifecycle_stats = {"canary_seen": 0}
    if args.lifecycle:
        from ccfd_tpu.lifecycle.controller import (  # noqa: E402
            Guardrails,
            LifecycleController,
        )
        from ccfd_tpu.lifecycle.evaluator import ShadowEvaluator  # noqa: E402
        from ccfd_tpu.lifecycle.shadow import ShadowTap  # noqa: E402
        from ccfd_tpu.lifecycle.versions import VersionStore  # noqa: E402
        from ccfd_tpu.parallel.checkpoint import CheckpointManager  # noqa: E402
        from ccfd_tpu.router.router import default_scorer_breaker  # noqa: E402

        lc_dir = tempfile.mkdtemp(prefix="ccfd_soak_lifecycle_")
        lifecycle_tap = ShadowTap(scorer, broker, cfg.shadow_topic, reg_r)
        lifecycle_breaker = default_scorer_breaker(reg_r)
        lifecycle = LifecycleController(
            cfg, scorer,
            store=VersionStore(os.path.join(lc_dir, "versions.json")),
            # keep enough steps that the champion's checkpoint survives a
            # storm's worth of rejected/superseded candidates saved after
            # it (the end-of-run consistency check restores it)
            checkpoints=CheckpointManager(
                os.path.join(lc_dir, "checkpoints"), keep=64),
            shadow=lifecycle_tap,
            evaluator=ShadowEvaluator(cfg, broker, scorer, reg_r),
            # labels come from the engine's investigation resolutions, a
            # trickle relative to traffic: small gates so cycles complete
            # within storm windows. Perturbed candidates rank identically,
            # so the quality gates pass and the drill exercises the
            # TRANSITIONS under kills, not the verdicts.
            # min_submit_interval_s=0: the soak WANTS supersession in the
            # mix (a mid-flight candidate replaced during a storm is one
            # of the transitions under drill)
            guardrails=Guardrails(
                min_labels=16, min_shadow_rows=256, canary_min_labels=8,
                max_score_psi=10.0, min_submit_interval_s=0.0),
            registry=reg_r, breaker=lifecycle_breaker)
        score_fn = lifecycle.wrap_score(score_fn)
    # -- incident flight recorder + dispatch watchdog (ISSUE 10) -----------
    # The router-side watchdog (runtime/overload.py bounded_dispatch) gets
    # a deadline BELOW the scorer's own, so the midpoint wedge trips
    # ccfd_dispatch_timeout_total — and every trip snapshots the system
    # state into the FlightRecorder ring: watchdog kills leave post-mortem
    # flight data, not only SLO breaches.
    from ccfd_tpu.observability.incident import FlightRecorder
    from ccfd_tpu.runtime.overload import OverloadControl

    recorder = FlightRecorder({"router": reg_r, "kie": reg_k},
                              registry=reg_r, ring=32)
    overload = OverloadControl.from_config(
        cfg, reg_r, max_batch=4096, workers=max(1, args.workers))
    if overload is not None:
        overload.dispatch_deadline_s = max(0.05,
                                           args.deadline_ms * 0.8 / 1e3)
        overload.recorder = recorder
    degrade = True if (args.net_faults or args.device_faults) else None
    # -- decision-provenance plane (--audit, ISSUE 14) ---------------------
    # One shared AuditLog across the whole pool: the ok-gate folds the
    # conservation claim (every routed tx stamped exactly once — counter
    # equality survives kill-storms because the stamp happens at the same
    # seam as transaction_outgoing_total) into the soak's accounting.
    decision_audit = None
    audit_flusher = None
    router_audit = None
    replay_tap = None
    replay_lineage = None
    if args.audit:
        from ccfd_tpu.observability.audit import AuditLog  # noqa: E402

        decision_audit = AuditLog(
            dir=tempfile.mkdtemp(prefix="ccfd_soak_audit_"),
            registry=reg_r)
        router_audit = decision_audit
        if args.replay:
            # ISSUE 17: the replay drill below re-scores a storm-recorded
            # window through THIS stack. Feature capture must be armed for
            # the whole storm (windows are only re-scorable if the route
            # seam embedded the decoded rows), and the route seam's audit
            # sink becomes the tap that diverts replay-marked verdicts to
            # the join instead of re-stamping the provenance log
            from ccfd_tpu.replay.service import (  # noqa: E402
                ReplayVerdictTap,
            )

            decision_audit.capture_rows = True
            if lifecycle is not None:
                # stamp the champion lineage on every record so a promote
                # that lands mid-storm classifies as champion_hash (an
                # explained finding), never as nondeterminism
                def replay_lineage():
                    try:
                        ch = lifecycle.store.champion()
                        return ((ch.version, ch.checkpoint_hash)
                                if ch else (None, None))
                    except Exception:  # noqa: BLE001 - probe races kills
                        return (None, None)

                decision_audit.lineage_fn = replay_lineage
            replay_tap = ReplayVerdictTap(inner=decision_audit,
                                          registry=reg_r)
            router_audit = replay_tap
        # the flusher runs for the WHOLE soak (the production shape: the
        # operator supervises it) — pending records drain to segments
        # every tick instead of accumulating in memory for the run, so
        # segment rotation and the failed-append accounting are actually
        # drilled under the storm
        audit_flusher = threading.Thread(
            target=lambda: decision_audit.run(interval_s=0.25),
            daemon=True, name="soak-audit-flush")
        audit_flusher.start()
    if args.workers > 1:
        # partition-parallel fan-out: the workers split the topic's
        # partitions, share ONE in-flight budget + breaker + coalescing
        # batcher, and the pause barrier the checkpoint coordinator takes
        # below is group-wide — the soak asserts the same 0-violation
        # accounting through kills with the whole pool in play
        from ccfd_tpu.router.parallel import ParallelRouter

        router = ParallelRouter(
            cfg, broker, score_fn, engine, reg_r, workers=args.workers,
            max_batch=4096, host_score_fn=host_fn,
            breaker=lifecycle_breaker,
            degrade=degrade,
            overload=overload, audit=router_audit)
    else:
        router = Router(cfg, broker, score_fn, engine, reg_r, max_batch=4096,
                        host_score_fn=host_fn,
                        breaker=lifecycle_breaker,
                        degrade=degrade,
                        overload=overload, audit=router_audit)
    # -- device self-healing under storms (--device-faults, ISSUE 11) ------
    # The DeviceSupervisor owns the soak's scorer: device-fault storms
    # (scheduled below, interleaved with the service kills) must drive the
    # full ladder — wedge injected -> QUARANTINED (router pinned to the
    # host tier, accounting still conserving) -> heal -> WARM re-promotion
    # (no serving-stage compiles after the flip) -> device serving again.
    healer = None
    device_plan = None
    heal_prof = None
    device_cycles: list[dict] = []
    if args.device_faults:
        from ccfd_tpu.observability.profile import StageProfiler  # noqa: E402
        from ccfd_tpu.runtime.faults import (  # noqa: E402
            DeviceFaultPlan,
            install_device_faults,
        )
        from ccfd_tpu.runtime.heal import DeviceSupervisor  # noqa: E402

        heal_prof = StageProfiler(registry=reg_r)
        heal_prof.arm_compile_listener()
        device_plan = DeviceFaultPlan.from_string(args.device_fault_spec,
                                                  seed=17, active=False)
        install_device_faults(device_plan)
        healer = DeviceSupervisor(
            scorer, registry=reg_r,
            breaker=getattr(router, "_breaker", None),
            profiler=heal_prof, recorder=recorder, overload=overload,
            canary_deadline_ms=min(150.0, args.deadline_ms * 0.6),
            suspect_strikes=2, probation_canaries=2,
            backoff_base_s=0.1, backoff_cap_s=1.0,
        )
        router.set_heal_gate(healer)
    # -- storage-fault storms (--storage-faults, ISSUE 13) ------------------
    # The durability seam (runtime/durability.py) is the fault target:
    # every lineage save, candidate checkpoint and recovery-cut write runs
    # degraded during storm windows (torn/lost/bit-flipped/failed writes)
    # while the ChaosMonkey kills services mid-write. Recovery must come
    # from quarantine + last-good generations — never from serving a
    # corrupt artifact.
    storage_plan = None
    cut_path = None
    if args.storage_faults:
        from ccfd_tpu.runtime import durability  # noqa: E402
        from ccfd_tpu.runtime.faults import (  # noqa: E402
            StorageFaultPlan,
            install_storage_faults,
        )

        durability.bind_registry(reg_r)
        storage_plan = StorageFaultPlan.from_string(args.storage_fault_spec,
                                                    seed=29, active=False)
        install_storage_faults(storage_plan)
        # a durable on-disk cut: full-process crash recovery writes ride
        # the same degraded seam (torn cuts must fall back to last-good)
        cut_path = os.path.join(tempfile.mkdtemp(prefix="ccfd_soak_cut_"),
                                "cut.json")
    coord = CheckpointCoordinator(router, broker, engine_factory,
                                  interval_s=args.checkpoint_s,
                                  path=cut_path)
    sup = Supervisor(backoff_initial_s=0.05, backoff_cap_s=0.5)
    sup.add_thread_service(
        "router", lambda: router.run(poll_timeout_s=0.02), router.stop,
        reset=router.reset,
    )
    # the durable bus as a killable service: ChaosMonkey's injection stops
    # the placeholder loop, and the supervisor's reset hook performs the
    # actual crash — Broker.crash_restart drops ALL in-memory state and
    # replays the segment log in place, with every consumer (router,
    # engine audit sink, the accounting walker) attached mid-stream
    bus_stop = threading.Event()
    bus_booted = [False]

    def bus_run() -> None:
        while not bus_stop.wait(0.5):
            pass

    def bus_reset() -> None:
        bus_stop.clear()
        if bus_booted[0]:  # first start is bring-up, not a crash
            broker.crash_restart()
        bus_booted[0] = True

    sup.add_thread_service("bus", bus_run, bus_stop.set, reset=bus_reset)
    if healer is not None:
        sup.add_thread_service(
            "heal", lambda: healer.run(interval_s=0.3), healer.stop,
            reset=healer.reset)
    if lifecycle is not None:
        sup.add_thread_service(
            "lifecycle", lambda: lifecycle.run(interval_s=0.25),
            lifecycle.stop, reset=lifecycle.reset)
        sup.add_thread_service(
            "lifecycle-shadow", lambda: lifecycle_tap.run(interval_s=0.05),
            lifecycle_tap.stop, reset=lifecycle_tap.reset)
    attach_engine_service(sup, coord)
    sup.start()
    coord.start()

    # candidate submitter: perturbed copies of the live champion cycle
    # through the lifecycle while the storm rages
    submit_stop = threading.Event()

    def submit_loop() -> None:
        rng_lc = np.random.default_rng(23)
        fraud_rows = np.flatnonzero(ds.y == 1)
        legit_rows = np.flatnonzero(ds.y == 0)
        tick = max(0.5, args.lifecycle_submit_s / 8.0)
        next_submit = time.time()
        while not submit_stop.wait(tick):
            try:
                # label trickle: the evaluator's evidence stream. In the
                # platform the fraud process emits these on resolution; the
                # soak (whose engine bias routes almost nothing to fraud in
                # short runs) feeds ground truth directly, both classes
                # represented so the AUC gate gets a verdict
                picks = np.concatenate([
                    rng_lc.choice(legit_rows, size=6),
                    rng_lc.choice(fraud_rows, size=2),
                ])
                for j in picks:
                    broker.produce(cfg.labels_topic, {
                        "transaction": dict(
                            zip(FEATURE_NAMES, map(float, ds.X[j]))),
                        "label": int(ds.y[j]),
                    })
                if time.time() < next_submit:
                    continue
                next_submit = time.time() + args.lifecycle_submit_s
                base = jax.tree.map(np.asarray,
                                    lifecycle._champion_params)
                cand = {"norm": base["norm"],
                        "layers": [dict(l) for l in base["layers"]]}
                last = dict(cand["layers"][-1])
                last["b"] = last["b"] + np.float32(
                    rng_lc.normal(0.0, 0.01))
                cand["layers"][-1] = last
                lifecycle.submit_candidate(cand, label_watermark=0)
            except Exception:  # noqa: BLE001 - submit races teardown
                pass

    submitter = None
    if lifecycle is not None:
        submitter = threading.Thread(target=submit_loop, daemon=True,
                                     name="soak-lifecycle-submit")
        submitter.start()

    # feeder: keep the topic loaded without unbounded backlog; the gate
    # lets the bus drill quiesce production without killing the thread.
    # CSV byte rows with the customer id as the record KEY — the produce
    # wire the reference producer uses:
    # ~6x smaller retained records than feature dicts, GC-untracked
    # (bus/broker.py Record note), and crash_restart replays them without
    # a JSON decode per record — the soak's flat-RSS claim is about the
    # bus, not about feeding it the fattest possible payload
    rows = [
        ",".join(f"{v:.6g}" for v in ds.X[i]).encode()
        for i in range(args.feed_batch)
    ]
    row_keys = list(range(args.feed_batch))
    stop_feed = threading.Event()
    feed_gate = threading.Event()
    feed_gate.set()
    produced = [0]

    def feed() -> None:
        while not stop_feed.is_set():
            feed_gate.wait(timeout=1.0)
            if not feed_gate.is_set():
                continue
            done = router._c_in.value()
            if produced[0] - done < 200_000:
                broker.produce_batch(cfg.kafka_topic, rows, row_keys)
                produced[0] += len(rows)
            else:
                time.sleep(0.01)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()

    # -- investigators: the PRODUCT service working the task queue ---------
    # Without them every flagged transaction parks an instance forever and
    # the aligned-checkpoint cost grows without bound — unrealistic (the
    # reference demo has humans working the KIE console queue) and it
    # turns the soak into a snapshot-size benchmark. The engine reference
    # follows crash-recovery swaps via the indirection below, and
    # individual completion failures (task rolled back mid-restore, dead
    # engine) are the service's normal skip path.
    from ccfd_tpu.process.investigator import InvestigatorService

    class CurrentEngine:
        """Resolve the live engine per call (restores swap it)."""

        def tasks(self, status="open"):
            return router.engine.tasks(status)

        def complete_task(self, task_id, outcome):
            return router.engine.complete_task(task_id, outcome)

    investigator = InvestigatorService(
        CurrentEngine(), Registry(), rate_per_s=0.0,  # unthrottled: soak
        trust_threshold=0.9, base_fraud_rate=0.05, seed=7,
    )
    invest_thread = threading.Thread(target=investigator.run, daemon=True)
    invest_thread.start()

    # -- bus crash-reopen drill (bounded log, under way) -------------------
    bus_check: dict = {}
    drill_deadline = time.time() + 60
    while (router._c_in.value() < args.bus_drill_tx
           and time.time() < drill_deadline):
        time.sleep(0.25)
    feed_gate.clear()
    acked = router.pause(10.0)
    try:
        live_before = {t: broker.end_offsets(t)
                       for t in (cfg.kafka_topic, cfg.audit_topic)}
        committed_before = broker.committed_offsets("router", cfg.kafka_topic)
        # Replay a COPY of the log dir, never the live one: opening a
        # Broker replays in place — offsets.log compaction would
        # os.replace() the file out from under the live broker's append
        # fd (silently killing offset durability for the rest of the
        # run), and torn-tail truncation would mutate live segments. The
        # copy is also the honest model: a crashed process's disk as the
        # restarting process finds it.
        import shutil

        copy_dir = tempfile.mkdtemp(prefix="ccfd_soak_busdrill_")
        shutil.rmtree(copy_dir)
        shutil.copytree(bus_dir, copy_dir)
        replayed = Broker(log_dir=copy_dir)
        rep_ends = {t: replayed.end_offsets(t) for t in live_before}
        rep_committed = replayed.committed_offsets("router", cfg.kafka_topic)
        replayed.close()
        shutil.rmtree(copy_dir, ignore_errors=True)
        live_after = {t: broker.end_offsets(t) for t in live_before}
        # prefix-consistency: background timers may append between the
        # live read and the copy, so the replayed view must sit between
        # the two live reads
        ends_ok = all(
            live_before[t][p] <= rep_ends[t][p] <= live_after[t][p]
            for t in live_before for p in range(len(live_before[t]))
        )
        bus_check = {
            "at_tx": int(router._c_in.value()),
            "barrier_acked": acked,
            "end_offsets_equal": ends_ok,
            "group_offsets_equal": rep_committed == committed_before,
        }
    finally:
        router.resume()
        feed_gate.set()

    targets = [t for t in args.targets.split(",") if t]
    monkey = ChaosMonkey(sup, seed=11, targets=targets,
                         registry=reg_c, interval_s=args.chaos_interval_s,
                         fault_plan=fault_plan,
                         storage_fault_plan=storage_plan,
                         fault_interval_s=(args.fault_interval_s
                                           if (args.net_faults
                                               or args.storage_faults)
                                           else None),
                         fault_duration_s=args.fault_duration_s)
    monkey.start()

    # -- device-fault storm windows (--device-faults) ----------------------
    # Interleaved with the service kills above: each window activates the
    # device plan, requires the healer to QUARANTINE, deactivates, then
    # requires a heal to HEALTHY followed by a 2 s serving window with
    # ZERO serving-stage compiles (the warm-re-promotion proof).
    df_stop = threading.Event()
    df_thread = None
    if healer is not None:
        from ccfd_tpu.runtime.heal import (  # noqa: E402
            NON_SERVING_COMPILE_STAGES,
        )

        def serving_compiles() -> int:
            return sum(v for s, v in heal_prof.compile_counts().items()
                       if s not in NON_SERVING_COMPILE_STAGES)

        def device_storm_loop() -> None:
            while not df_stop.wait(args.device_fault_interval_s):
                if wedged.is_set():
                    continue  # the midpoint wedge is its own drill
                cycle = {"at_tx": int(router._c_in.value())}
                device_plan.activate()
                t_q = time.time()
                while (healer.state != "quarantined"
                       and time.time() - t_q < 20
                       and not df_stop.is_set()):
                    time.sleep(0.1)
                cycle["quarantined"] = healer.state == "quarantined"
                device_plan.deactivate()
                t_h = time.time()
                while (healer.state != "healthy"
                       and time.time() - t_h < 60
                       and not df_stop.is_set()):
                    time.sleep(0.1)
                cycle["healed"] = healer.state == "healthy"
                base = serving_compiles()
                t_w = time.time()
                while time.time() - t_w < 2.0 and not df_stop.is_set():
                    time.sleep(0.1)
                cycle["warm"] = bool(cycle["healed"]
                                     and serving_compiles() == base)
                cycle["healed_at_tx"] = int(router._c_in.value())
                if df_stop.is_set() and not (
                        cycle["quarantined"] and cycle["healed"]):
                    # shutdown truncated this window mid-wait: the cycle
                    # never got its 20/60 s budget, so recording it would
                    # fail the exit gate on timing, not on behavior
                    break
                device_cycles.append(cycle)

        df_thread = threading.Thread(target=device_storm_loop, daemon=True,
                                     name="soak-device-storms")
        df_thread.start()

    def rss_mb() -> float:
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return round(int(line.split()[1]) / 1024.0, 1)
        except (OSError, ValueError, IndexError):
            pass
        return 0.0

    t0 = time.time()
    t_wedge = t0 + args.seconds / 2
    wedge_done = False
    wedge_info: dict = {}
    last_progress, last_in = time.time(), 0
    max_stall_s = 0.0
    rss_samples: list[list[float]] = [[0.0, rss_mb()]]
    last_rss = t0
    while time.time() - t0 < args.seconds:
        time.sleep(1.0)
        if time.time() - last_rss >= 10.0:
            last_rss = time.time()
            rss_samples.append([round(last_rss - t0, 0), rss_mb()])
        cur = router._c_in.value()
        if cur > last_in:
            last_in, last_progress = cur, time.time()
        max_stall_s = max(max_stall_s, time.time() - last_progress)
        if lifecycle is not None and lifecycle.stage == 2:
            lifecycle_stats["canary_seen"] += 1
        if not wedge_done and time.time() >= t_wedge:
            wedge_info["wedged_at_tx"] = cur
            wedged.set()
            time.sleep(args.wedge_s)
            wedged.clear()
            release.set()
            wedge_done = True
            wedge_info["healed_at_tx"] = router._c_in.value()
            # recovery: the probe should clear the wedge promptly
            t_rec = time.time()
            while scorer._wedge.wedged and time.time() - t_rec < 60:
                time.sleep(0.5)
            wedge_info["recovered_s_after_heal"] = round(time.time() - t_rec, 1)
            wedge_info["device_path_recovered"] = not scorer._wedge.wedged

    df_stop.set()
    if df_thread is not None:
        df_thread.join(timeout=10)
    if device_plan is not None:
        device_plan.deactivate()
    if storage_plan is not None:
        storage_plan.deactivate()
    stop_feed.set()
    investigator.stop()
    invest_thread.join(timeout=10)
    monkey.stop()
    coord.stop()
    elapsed = time.time() - t0
    # drain the backlog so the accounting walk sees a settled stream, then
    # park the router for the final engine-state comparison
    settle = time.time() + 20
    prev = -1
    while time.time() < settle:
        cur = router._c_in.value()
        if cur == prev:
            break
        prev = cur
        time.sleep(1.0)
    router.pause(10.0)

    # -- lifecycle consistency after recovery ------------------------------
    lifecycle_res: dict = {}
    if lifecycle is not None:
        submit_stop.set()
        if submitter is not None:
            submitter.join(timeout=5)
        # deterministic quiesce: a candidate still mid-flight (e.g. the
        # last kill landed mid-canary) rolls back, then serving must equal
        # the champion's checkpoint — ONE consistent version in the pool
        lifecycle.resolve_for_shutdown()
        champ = lifecycle.store.champion()
        served = jax.tree.map(np.asarray, scorer.params)
        from ccfd_tpu.runtime.durability import CorruptArtifactError
        try:
            restored = lifecycle.checkpoints.restore(
                served, step=champ.checkpoint_step)
        except FileNotFoundError:
            restored = None  # champion ckpt GC'd (very long soak): fail
        except CorruptArtifactError:
            # storm bitrot landed on the champion's on-disk bytes AFTER
            # the stamp: quarantined, never served — the hash-parity
            # check below (recorded fingerprint vs the tree actually
            # serving) is the integrity claim that still must hold
            restored = None
        params_match = restored is not None and all(
            np.allclose(a, b, atol=1e-6)
            for a, b in zip(jax.tree.leaves(served),
                            jax.tree.leaves(restored[0]))
        )
        stages = [v.stage for v in lifecycle.store.versions()]
        lifecycle_res = {
            "enabled": True,
            "champion_version": champ.version,
            "versions": len(stages),
            "promotions": int(reg_r.counter(
                "ccfd_lifecycle_promotions_total").value()),
            "rollbacks": int(reg_r.counter(
                "ccfd_lifecycle_rollbacks_total").value()),
            "rejections": int(reg_r.counter(
                "ccfd_lifecycle_rejections_total").value()),
            "canary_ticks_observed": lifecycle_stats["canary_seen"],
            "serving_matches_champion_checkpoint": bool(params_match),
            "serving_consistent": lifecycle.serving_consistent(),
            # a dangling challenger slot or canary gate after quiesce
            # would be the mid-canary-kill inconsistency this drill exists
            # to rule out
            "challenger_cleared": scorer.challenger_version is None,
            "gate_inactive": not lifecycle.gate.active,
        }
        if args.storage_faults:
            from ccfd_tpu.parallel.partition import params_fingerprint
            from ccfd_tpu.runtime import durability as _dur

            serving_fp = params_fingerprint(served)
            lc_events = [e["event"] for e in lifecycle.store.audit_trail()]
            lifecycle_res["storage"] = {
                "storm_windows": storage_plan.activations,
                "injected": dict(storage_plan.injected),
                "counts": {k: sum(v.values())
                           for k, v in _dur.counts().items()},
                # the integrity claim: what serves is what the lineage
                # recorded — byte-corruption on disk was quarantined (and
                # possibly recovered from a generation), never published
                "serving_fp_matches_lineage": bool(
                    champ.checkpoint_hash is not None
                    and serving_fp == champ.checkpoint_hash),
                # divergence is only legal when the audit trail explains
                # it: a fallback restore (verified older generation
                # served, re-stamped) or a rules pin (nothing verified)
                "fallback_restores": lc_events.count(
                    "storage_fallback_restore"),
                "storage_pins": lc_events.count("storage_pin"),
                "pinned_at_end": lifecycle.storage_pinned,
            }

    total = router._c_in.value()
    final_engine = router.engine
    # finalize the live walk: the thread drains whatever the ledger still
    # holds past the walker's committed position, then exits
    walker_stop.set()
    walk_thread.join(timeout=60)
    audit_consumer.close()
    acct = walker.result()
    with final_engine.state_lock:
        active_now = {i.pid for i in final_engine.instances("active")}
    # every audit-open pid must be live in the final engine and vice versa;
    # a pid open in the walked stream but terminal in the engine is just a
    # timer completion whose audit event landed after the walk (tail), not
    # a loss — verify instead of excusing blindly
    ghost = acct["open_at_end"] - active_now
    tail_completed = set()
    for pid in list(ghost):
        try:
            if final_engine.instance(pid).status != "active":
                tail_completed.add(pid)
        except KeyError:
            # audit-coupled eviction (round 8): a tail-completed instance
            # leaves the runtime store as soon as its terminal event is
            # durably produced — the bounded post-mortem ring is the
            # queryable record. A pid in NEITHER store is a real ghost.
            info = final_engine.completed_info(pid)
            if info is not None and info["status"] != "active":
                tail_completed.add(pid)
    ghost -= tail_completed
    unaudited = active_now - acct["open_at_end"]
    acct_ok = not acct["violation_count"] and not ghost and not unaudited

    # decision-record conservation (--audit): every routed tx stamped
    # exactly ONCE — the recorded counter must equal the outgoing counter
    # through every kill/restore, and duplicates (re-stamps of the same
    # bus coordinate) are only legal when a crash restore re-drove records
    audit_res: dict = {}
    if decision_audit is not None:
        decision_audit.stop()
        if audit_flusher is not None:
            audit_flusher.join(timeout=10)
        decision_audit.flush()
        routed_total = int(reg_r.counter(
            "transaction_outgoing_total").total())
        recorded_total = int(reg_r.counter(
            "ccfd_audit_records_total").value())
        a_counts = decision_audit.counts()
        audit_res = {
            "routed": routed_total,
            "recorded": recorded_total,
            "conserved": routed_total == recorded_total,
            "restamped": a_counts["restamped"],
            "ring": a_counts["ring"],
            "truncated_frames": a_counts["truncated_frames"],
            "dropped_log_write": int(reg_r.counter(
                "ccfd_audit_dropped_total").value({"reason": "log_write"})),
        }

    # -- verdict-parity replay drill (--replay, ISSUE 17) -------------------
    # Runs strictly AFTER the conservation numbers above are frozen: the
    # re-drive routes through the same stack (incrementing the routed
    # counters) but the tap diverts every replay-marked verdict away from
    # the provenance log, so routed == recorded stays exactly what the
    # storm produced. The router must be live again for the drive.
    replay_res: dict = {}
    if args.replay and decision_audit is not None:
        from ccfd_tpu.replay.service import ReplayService  # noqa: E402

        router.resume()
        recs = decision_audit.scan_window()
        # a storm-recorded window: re-scorable rows stamped on the device
        # tier (host-tier rows — small trailing poll batches — replay on
        # device and may differ in the last ulp; the drill's claim is
        # byte-parity through the SAME serving tier)
        window = [r for r in recs
                  if r.get("row") is not None
                  and r.get("tier", "device") == "device"]
        window = window[-max(1, args.replay_rows):]
        svc = ReplayService(
            cfg, broker, decision_audit, tap=replay_tap, registry=reg_r,
            state_dir=tempfile.mkdtemp(prefix="ccfd_soak_replay_"),
            overload=overload, lineage_fn=replay_lineage)
        rep = svc.run_window(window=window, window_id="soak-storm")
        svc.stop()
        promotions = int(reg_r.counter(
            "ccfd_lifecycle_promotions_total").value()) if lifecycle else 0
        # champion_hash is the one EXPLAINED cause a storm can legally
        # produce (a promote landed between the stamp and the re-drive);
        # everything else — and any drop or ghost — fails the gate
        explained = (promotions > 0
                     and set(rep["causes"]) <= {"champion_hash"})
        replay_res = {
            "window": len(window),
            "recorded_total": len(recs),
            "replayed": rep["replayed"],
            "match": rep["match"],
            "divergence": rep["divergence"],
            "drop": rep["drop"],
            "ghost": rep["ghost"],
            "dup": rep["dup"],
            "causes": rep["causes"],
            "rows_per_s": round(rep["rows_per_s"], 1),
            "parity": rep["parity"],
            "ok": bool(len(window) > 0 and not rep["stopped"]
                       and rep["drop"] == 0 and rep["ghost"] == 0
                       and (rep["parity"] or explained)),
        }

    kills: dict[str, int] = {}
    for _ts, name in monkey.history:
        kills[name] = kills.get(name, 0) + 1
    status = sup.status()
    # RSS drift: least-squares slope over the samples past the warmup
    # quartile — the flat-memory evidence VERDICT r4 item 2 asks for
    tail = rss_samples[len(rss_samples) // 4:]
    drift_mb_per_min = 0.0
    if len(tail) >= 2:
        xs = [s[0] for s in tail]
        ys = [s[1] for s in tail]
        mx, my = sum(xs) / len(xs), sum(ys) / len(ys)
        var = sum((x - mx) ** 2 for x in xs)
        if var > 0:
            drift_mb_per_min = round(
                sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var * 60,
                3,
            )
    # memory-drift evidence (observability/memory.py): per-component
    # object counts alongside the RSS slope, so a drifting soak NAMES the
    # growing container instead of just measuring the growth
    from ccfd_tpu.observability.memory import memory_report

    mem = memory_report({
        "engine": lambda: sum(final_engine.object_counts().values()),
        "bus_retained_records": lambda: sum(
            e - b
            for t in (cfg.kafka_topic, cfg.audit_topic)
            for e, b in zip(broker.end_offsets(t),
                            broker.beginning_offsets(t))
        ),
        "walker_ledger_bytes": lambda: sum(
            len(st["done"].bits) + len(st["seen"].bits)
            for st in walker._parts.values()
        ),
    })

    result = {
        "seconds": round(elapsed, 1),
        "tx_total": int(total),
        "tx_s": round(total / elapsed, 1),
        "router_workers": args.workers,
        "coalesced": {
            "worker_batches": int(reg_r.counter(
                "router_worker_batches_total").total()),
            "dispatches": int(reg_r.counter(
                "router_coalesced_dispatches_total").value()),
        },
        # the RSS slope, top-level: THE memory-drift acceptance number
        "rss_slope_mb_per_min": drift_mb_per_min,
        "targets": targets,
        "kills": kills,
        "engine_kills": kills.get("engine", 0),
        "router_kills": kills.get("router", 0),
        "bus_kills": kills.get("bus", 0),
        "bus_crash_restarts": broker.crash_restarts,
        "retention": {
            "records_per_partition_cap": args.retention_records,
            "records_trimmed": broker.records_trimmed,
            "beginning_offsets": {
                t: broker.beginning_offsets(t)
                for t in (cfg.kafka_topic, cfg.audit_topic)
            },
            "oor_resets": broker.oor_resets,
            # who holds the trim floor per topic (diagnosis surface: a
            # group parked at a low offset is what stops trimming)
            "group_positions": {
                g: {f"{t}/{p}": off for (t, p), off in tps.items()}
                for g, tps in broker.health_snapshot()["groups"].items()
            },
        },
        "rss": {
            "start_mb": rss_samples[0][1],
            "end_mb": rss_samples[-1][1],
            "max_mb": max(s[1] for s in rss_samples),
            "drift_mb_per_min": drift_mb_per_min,
            "samples": rss_samples,
        },
        "memory": mem,
        "supervisor_restarts": {n: s["restarts"] for n, s in status.items()},
        "checkpoints": coord.checkpoints,
        "checkpoint_skips": coord.skipped,
        "restores": coord.restores,
        "max_progress_stall_s": round(max_stall_s, 1),
        "wedge": wedge_info,
        "bus_reopen_check": bus_check,
        "dispatch_timeouts": scorer.dispatch_timeouts,
        "host_fallback_scores": scorer.host_fallback_scores,
        # flight-recorder evidence (observability/incident.py): every
        # router-watchdog kill must have snapshotted into the ring
        "flight_recorder": {
            "watchdog_timeouts": int(reg_r.counter(
                "ccfd_dispatch_timeout_total").value()),
            "ring_snapshots": len(recorder.ring),
            "dispatch_timeout_snapshots": sum(
                1 for s in recorder.ring
                if s.get("reason") == "dispatch_timeout"),
        },
        "lifecycle": lifecycle_res,
        "audit": audit_res,
        "replay": replay_res,
        # device heal evidence (runtime/heal.py): each storm cycle must
        # have quarantined, healed and re-promoted WARM
        "device_heal": {
            "enabled": bool(args.device_faults),
            "spec": args.device_fault_spec if args.device_faults else "",
            "cycles": device_cycles,
            "quarantines": healer.quarantines if healer else 0,
            "repromotions": healer.repromotions if healer else 0,
            "canary_failures": healer.canary_failures if healer else 0,
            "final_state": healer.state if healer else "",
            "health_gauge_exported": (
                "ccfd_device_health" in reg_r.render()
                if healer else False),
        },
        "tasks_completed_by_investigators": investigator.completed,
        "net_faults": {
            "enabled": bool(args.net_faults),
            "spec": args.fault_spec if args.net_faults else "",
            "windows": len(monkey.fault_windows),
            "degraded_host": reg_r.counter(
                "router_degraded_total").value({"tier": "host"}),
            "degraded_rules": reg_r.counter(
                "router_degraded_total").value({"tier": "rules"}),
            "shed": reg_r.counter("router_shed_total").value(),
            "scorer_edge_failures": reg_r.counter(
                "router_score_errors_total").value(),
            "breaker_opens": (router._breaker.opens
                              if router._breaker is not None else 0),
            # the acceptance surface: breaker-state gauges reach /metrics
            # through the same registry the exporter scrapes
            "breaker_gauge_exported": "ccfd_breaker_state" in reg_r.render(),
        },
        "lockcheck": {
            "enabled": lock_graph is not None,
            "violations": (len(lock_graph.violations)
                           if lock_graph is not None else 0),
            "cycles": ([v["cycle"] for v in lock_graph.violations]
                       if lock_graph is not None else []),
        },
        "accounting": {
            "starts": acct["starts"],
            "completes": acct["completes"],
            "rolled_back": acct["rolled_back"],
            "restore_markers": acct["restore_markers"],
            "still_active": len(active_now),
            "ghost_open": len(ghost),
            "tail_completions": len(tail_completed),
            "unaudited_active": len(unaudited),
            "violations": acct["violations"],
            "violation_count": acct["violation_count"],
            "ok": acct_ok,
        },
    }
    router.resume()
    sup.stop()
    broker.close()
    print(json.dumps(result))
    fr = result["flight_recorder"]
    ok = (
        total > 0
        and (lock_graph is None or not lock_graph.violations)
        and wedge_info.get("device_path_recovered", False)
        # a watchdog kill without a ring snapshot would be exactly the
        # un-post-mortem-able kill ISSUE 10 closes
        and (fr["watchdog_timeouts"] == 0
             or fr["dispatch_timeout_snapshots"] > 0)
        and wedge_info.get("healed_at_tx", 0) > wedge_info.get("wedged_at_tx", 0)
        and result["engine_kills"] > 0
        and coord.restores > 0
        and bus_check.get("end_offsets_equal", False)
        and bus_check.get("group_offsets_equal", False)
        and ("bus" not in targets
             or (result["bus_kills"] > 0 and broker.crash_restarts > 0))
        and acct_ok
        and (
            not args.audit
            or (
                # decision-record conservation through the storm: routed
                # == recorded exactly, nothing silently lost to the audit
                # disk, and re-stamped coordinates only where a crash
                # restore legitimately re-drove the stream
                audit_res.get("conserved", False)
                and audit_res.get("dropped_log_write", 0) == 0
                and (audit_res.get("restamped", 0) == 0
                     or coord.restores > 0)
            )
        )
        # verdict-parity conservation (--replay): the storm-recorded
        # window re-scored through the same stack with zero drops, zero
        # ghosts and no divergence a lifecycle promote doesn't explain
        and (not args.replay or replay_res.get("ok", False))
        and (
            not args.lifecycle
            or (
                # the pool ends on ONE consistent model version: serving
                # params equal the champion checkpoint, no challenger slot
                # or canary gate dangling, and transitions actually cycled
                # under the storm. Under --storage-faults the on-disk
                # champion bytes may be storm-corrupt (quarantined, never
                # served) — the recorded-fingerprint parity or an audited
                # fallback/pin then carries the consistency claim.
                (lifecycle_res.get("serving_matches_champion_checkpoint")
                 or (args.storage_faults and (
                     lifecycle_res["storage"]["serving_fp_matches_lineage"]
                     or lifecycle_res["storage"]["fallback_restores"] > 0
                     or lifecycle_res["storage"]["storage_pins"] > 0)))
                and lifecycle_res.get("serving_consistent")
                and lifecycle_res.get("challenger_cleared")
                and lifecycle_res.get("gate_inactive")
                and lifecycle_res.get("versions", 0) > 1
            )
        )
        and (
            not args.storage_faults
            or (
                # storage storms actually fired and injected, writes
                # failed LOUDLY (counted) or corruption was quarantined —
                # and the run survived them all with the accounting claim
                # (acct_ok above) intact: zero corrupt artifacts served
                lifecycle_res["storage"]["storm_windows"] > 0
                and sum(lifecycle_res["storage"]["injected"].values()) > 0
            )
        )
        and (
            not args.device_faults
            or (
                # the full heal ladder, end to end, every storm window:
                # wedge injected -> QUARANTINED (host tier serving, the
                # acct_ok above proving zero violations) -> healed ->
                # WARM re-promotion (no serving-stage compiles after the
                # flip) -> device serving again at the end
                len(device_cycles) > 0
                and all(c["quarantined"] and c["healed"] and c["warm"]
                        for c in device_cycles)
                and result["device_heal"]["final_state"] == "healthy"
                and result["device_heal"]["health_gauge_exported"]
            )
        )
        and (
            not args.net_faults
            or (
                # degraded edges drilled AND absorbed: storms fired, the
                # ladder scored through them (host tier and/or rules
                # floor), the breaker surface is on /metrics, and — via
                # acct_ok above — accounting stayed violation-free while
                # degraded
                result["net_faults"]["windows"] > 0
                and (result["net_faults"]["degraded_host"]
                     + result["net_faults"]["degraded_rules"]) > 0
                and result["net_faults"]["breaker_gauge_exported"]
            )
        )
    )
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
