"""Crash recovery: offset admin, the checkpoint barrier, and engine
snapshot + bus-rewind restore as one consistent cut (runtime/recovery.py).

The reference gets this tier from Kafka redelivery + the KIE server's
persistent process store (reference deploy/ccd-service.yaml); here the
semantics are at-least-once snapshot/replay, and these tests pin the three
properties the chaos soak (tools/chaos_soak.py) then exercises under load:
live-consumer rewind, barrier alignment, and void-start accounting via the
``engine_restored`` audit marker.
"""

import threading
import time

import numpy as np

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.process.fraud import build_engine
from ccfd_tpu.router.router import Router
from ccfd_tpu.runtime.recovery import CheckpointCoordinator
from ccfd_tpu.runtime.supervisor import Supervisor


CFG = Config(fraud_threshold=0.5, audit_topic="ccd-audit")


def amount_score(x: np.ndarray) -> np.ndarray:
    return (x[:, FEATURE_NAMES.index("Amount")] > 100.0).astype(np.float32)


def tx(i: int, amount: float) -> dict:
    return {"id": i, "Amount": amount}


# -- Broker offset admin ----------------------------------------------------

def test_reset_offsets_rewinds_live_consumer():
    b = Broker(default_partitions=1)
    for i in range(10):
        b.produce("t", {"i": i})
    c = b.consumer("g", ("t",))
    got = c.poll(100)
    assert len(got) == 10
    assert b.committed_offsets("g", "t") == [10]
    b.reset_offsets("g", "t", [4])
    # the SAME consumer re-reads from the reset point: consumers hold no
    # position of their own
    again = c.poll(100)
    assert [r.value["i"] for r in again] == [4, 5, 6, 7, 8, 9]


def test_reset_offsets_clamps_and_validates():
    b = Broker(default_partitions=2)
    b.create_topic("t", 2)
    b.produce("t", {"x": 1}, key="k")
    b.reset_offsets("g", "t", [99, 99])  # clamps to log end
    ends = b.end_offsets("t")
    assert b.committed_offsets("g", "t") == ends
    try:
        b.reset_offsets("g", "t", [0])
        raise AssertionError("partition-count mismatch must raise")
    except ValueError:
        pass


def test_reset_offsets_survives_broker_crash(tmp_path):
    d = str(tmp_path / "log")
    b = Broker(default_partitions=1, log_dir=d)
    for i in range(8):
        b.produce("t", {"i": i})
    c = b.consumer("g", ("t",))
    c.poll(100)  # commit to 8
    b.reset_offsets("g", "t", [3])
    b.close()
    # replay must honor the rewind (last-wins), not resurrect max=8
    b2 = Broker(default_partitions=1, log_dir=d)
    assert b2.committed_offsets("g", "t") == [3]
    b2.close()


# -- Router checkpoint barrier ---------------------------------------------

def test_pause_parks_loop_at_batch_boundary():
    broker = Broker()
    reg = Registry()
    engine = build_engine(CFG, broker, reg)
    router = Router(CFG, broker, amount_score, engine, Registry())
    t = router.start(poll_timeout_s=0.01)
    try:
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(50)])
        assert router.pause(5.0), "barrier not acked"
        # while parked: records produced now must NOT be consumed
        consumed_at_pause = router._c_in.value()
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(50, 60)])
        time.sleep(0.1)
        assert router._c_in.value() == consumed_at_pause
        router.resume()
        deadline = time.time() + 5
        while router._c_in.value() < 60 and time.time() < deadline:
            time.sleep(0.01)
        assert router._c_in.value() == 60
    finally:
        router.stop()
        t.join(timeout=5)


def test_pause_is_reference_counted():
    """Two concurrent holders (the periodic checkpointer + an operator
    drill): one holder's resume must not release the other's barrier."""
    broker = Broker()
    engine = build_engine(CFG, broker, Registry())
    router = Router(CFG, broker, amount_score, engine, Registry())
    t = router.start(poll_timeout_s=0.01)
    try:
        assert router.pause(5.0)      # holder A
        assert router.pause(5.0)      # holder B (already parked: instant)
        router.resume()               # A releases
        consumed = router._c_in.value()
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(5)])
        time.sleep(0.15)
        assert router._c_in.value() == consumed, "B's hold was broken"
        router.resume()               # B releases
        deadline = time.time() + 5
        while router._c_in.value() < consumed + 5 and time.time() < deadline:
            time.sleep(0.01)
        assert router._c_in.value() == consumed + 5
    finally:
        router.stop()
        t.join(timeout=5)


def test_pause_returns_false_with_no_loop():
    broker = Broker()
    engine = build_engine(CFG, broker, Registry())
    router = Router(CFG, broker, amount_score, engine, Registry())
    assert router.pause(0.2) is False
    router.resume()


def test_swap_engine_validates_definitions():
    broker = Broker()
    engine = build_engine(CFG, broker, Registry())
    router = Router(CFG, broker, amount_score, engine, Registry())

    class Empty:
        def definitions(self):
            return ()

        def start_process(self, *a):  # pragma: no cover
            raise AssertionError

    try:
        router.swap_engine(Empty())
        raise AssertionError("must reject an engine missing rule targets")
    except ValueError:
        pass
    replacement = build_engine(CFG, broker, Registry())
    router.swap_engine(replacement)
    assert router.engine is replacement


# -- CheckpointCoordinator --------------------------------------------------

def _pipeline(tmp_path=None):
    broker = Broker(
        default_partitions=1,
        log_dir=None if tmp_path is None else str(tmp_path / "buslog"),
    )
    reg_engine = Registry()
    factory = lambda: build_engine(CFG, broker, reg_engine)  # noqa: E731
    engine = factory()
    router = Router(CFG, broker, amount_score, engine, Registry())
    coord = CheckpointCoordinator(router, broker, factory, interval_s=999.0)
    return broker, router, coord


def _reaches(read, n, timeout_s=20.0):  # generous: the 1-core CI host
    # runs the whole suite concurrently with background watchers
    """``read()`` once it has reached ``n``, or as the deadline left it."""
    deadline = time.time() + timeout_s
    while read() < n and time.time() < deadline:
        time.sleep(0.01)
    return read()


def _drain(router, n):
    assert _reaches(router._c_in.value, n) >= n


def _wait_started(registry, n):
    """The engine's STARTED counter for the standard process, once it has
    reached ``n`` (or the deadline passed). ``_drain`` returns when the
    decode-side counter does: the pipelined loop counts incoming at decode
    time, so the batch may still be in flight and a route-side counter
    read right after it reads short (flaky under load)."""
    started_c = registry.counter("process_instances_started_total")
    return _reaches(
        lambda: started_c.value(labels={"process": "standard"}), n)


def test_checkpoint_restore_replays_post_cut_records():
    broker, router, coord = _pipeline()
    t = router.start(poll_timeout_s=0.01)
    try:
        # standard (amount<=100) transactions complete straight through
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(20)])
        _drain(router, 20)
        cut = coord.checkpoint()
        assert cut is not None and coord.checkpoints == 1
        # post-cut work: the doomed engine processes 10 more. Wait on the
        # engine's STARTED counter, not _c_in: started_before would read
        # short and restore's barrier-drained batch would inflate the delta
        broker.produce_batch(CFG.kafka_topic,
                             [tx(i, 10.0) for i in range(20, 30)])
        _drain(router, 30)
        started_before = _wait_started(router.engine.registry, 30)
        assert started_before == 30
        # crash + restore: the 10 post-cut records must re-deliver into the
        # restored engine (at-least-once), through the SAME live router
        new_engine = coord.restore(reason="test")
        assert router.engine is new_engine
        _drain(router, 40)  # 30 + 10 replayed
        started_after = _wait_started(new_engine.registry,
                                      started_before + 10)
        assert started_after - started_before == 10
    finally:
        router.stop()
        t.join(timeout=5)


def test_restore_marker_enables_void_start_accounting():
    broker, router, coord = _pipeline()
    t = router.start(poll_timeout_s=0.01)
    try:
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(5)])
        _drain(router, 5)
        cut = coord.checkpoint()
        next_pid = cut["snap"]["next_pid"]
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(5, 8)])
        _drain(router, 8)
        coord.restore(reason="test")
        _drain(router, 11)  # 3 replayed
        router.pause(5.0)
        # Audit events are keyed by pid (partition-sticky) and the restore
        # marker is produced into EVERY partition, so each partition's
        # offset order is a complete, correctly-ordered account of its
        # pids. Marker semantics (runtime/recovery.py): roll back
        # starts/completes of pids >= next_pid and completes of restored
        # ``active_pids`` — the same walk tools/chaos_soak.py runs at scale
        n_parts = len(broker.end_offsets(CFG.audit_topic))
        c = broker.consumer("chk", (CFG.audit_topic,))
        by_part: dict[int, list] = {p: [] for p in range(n_parts)}
        for r in c.poll(100_000):
            by_part[r.partition].append(r.value)
        c.close()
        voided = 0
        open_at_end: set[int] = set()
        for events in by_part.values():
            open_p: set[int] = set()
            done_p: set[int] = set()
            seen_p: set[int] = set()
            for ev in events:
                if ev["event"] == "engine_restored":
                    restored = set(ev.get("active_pids", ())) & seen_p
                    void_open = {x for x in open_p if x >= ev["next_pid"]}
                    void_done = {x for x in done_p if x >= ev["next_pid"]}
                    undone = done_p & restored
                    voided += len(void_open) + len(void_done) + len(undone)
                    open_p = restored
                    done_p -= void_done | undone
                elif ev["event"] == "process_started":
                    seen_p.add(ev["pid"])
                    assert ev["pid"] not in open_p, "double start in epoch"
                    open_p.add(ev["pid"])
                elif ev["event"] == "process_completed":
                    assert ev["pid"] not in done_p, "double complete in epoch"
                    if ev["pid"] in open_p:
                        open_p.discard(ev["pid"])
                        done_p.add(ev["pid"])
            open_at_end |= open_p
        assert voided == 3, f"expected 3 rolled-back events, got {voided}"
        assert not open_at_end, f"unterminated instances: {open_at_end}"
        assert next_pid not in (None, 0)
    finally:
        router.resume()
        router.stop()
        t.join(timeout=5)


def test_engine_service_chaos_kill_recovers(tmp_path):
    """The supervised-engine wiring end to end: ChaosMonkey-style
    inject_failure on the engine service triggers restore-on-respawn."""
    from ccfd_tpu.runtime.recovery import attach_engine_service

    broker, router, coord = _pipeline(tmp_path)
    sup = Supervisor(backoff_initial_s=0.02, backoff_cap_s=0.1)
    sup.add_thread_service(
        "router", lambda: router.run(poll_timeout_s=0.01), router.stop,
        reset=router.reset,
    )
    attach_engine_service(sup, coord)
    sup.start()
    try:
        assert sup.wait_ready(5.0)
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(10)])
        _drain(router, 10)
        assert coord.checkpoint() is not None
        restores_before = coord.restores
        assert sup.inject_failure("engine", "chaos")
        deadline = time.time() + 10
        while coord.restores == restores_before and time.time() < deadline:
            time.sleep(0.02)
        assert coord.restores == restores_before + 1
        # pipeline still flows after recovery
        broker.produce_batch(CFG.kafka_topic,
                             [tx(i, 10.0) for i in range(10, 15)])
        _drain(router, 15)
    finally:
        sup.stop()


def test_shutdown_engine_refuses_mutation():
    """A decommissioned engine must reject late in-flight work (a scoring
    batch that raced the crash-recovery swap past the pause timeout) so
    the rewound bus re-drives it into the live engine instead of it
    silently mutating dead state and arming rogue timers."""
    broker = Broker()
    engine = build_engine(CFG, broker, Registry())
    pid = engine.start_process(
        "fraud", {"transaction": {"Amount": 500.0}, "proba": 0.99,
                  "customer_id": 7},
    )
    engine.shutdown()
    for call in (
        lambda: engine.start_process("standard", {"transaction": {}}),
        lambda: engine.start_process_batch("standard", [{}]),
        lambda: engine.signal(pid, "customer-response", {}),
        lambda: engine.complete_task(1, "approved"),
    ):
        try:
            call()
            raise AssertionError("shut-down engine accepted mutation")
        except RuntimeError as e:
            assert "shut down" in str(e)


def test_restore_without_checkpoint_is_genesis_replay():
    broker, router, coord = _pipeline()
    t = router.start(poll_timeout_s=0.01)
    try:
        broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(6)])
        _drain(router, 6)
        engine = coord.restore(reason="no-checkpoint")
        _drain(router, 12)  # full replay from offset 0
        assert _wait_started(engine.registry, 6) >= 6
    finally:
        router.stop()
        t.join(timeout=5)


def test_full_process_crash_recovery_from_disk(tmp_path):
    """The complete crash story: cut persisted to disk + durable bus.
    'Process 1' checkpoints mid-stream and dies with post-cut work done;
    'process 2' (new broker replayed from the log, new engine, new
    router) restores the cut from disk before its loop starts and the
    rewound bus re-drives exactly the post-cut gap."""
    bus_dir = str(tmp_path / "buslog")
    cut_file = str(tmp_path / "cut.json")

    # ---- process 1 ----
    b1 = Broker(default_partitions=1, log_dir=bus_dir)
    reg1 = Registry()
    f1 = lambda: build_engine(CFG, b1, reg1)  # noqa: E731
    r1 = Router(CFG, b1, amount_score, f1(), Registry())
    c1 = CheckpointCoordinator(r1, b1, f1, interval_s=999.0, path=cut_file)
    t1 = r1.start(poll_timeout_s=0.01)
    try:
        b1.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(15)])
        _drain(r1, 15)
        assert c1.checkpoint() is not None
        b1.produce_batch(CFG.kafka_topic,
                         [tx(i, 10.0) for i in range(15, 25)])
        _drain(r1, 25)
    finally:
        r1.stop()
        t1.join(timeout=5)
    b1.close()  # process 1 dies

    # ---- process 2 ----
    b2 = Broker(default_partitions=1, log_dir=bus_dir)
    reg2 = Registry()
    f2 = lambda: build_engine(CFG, b2, reg2)  # noqa: E731
    r2 = Router(CFG, b2, amount_score, f2(), Registry())
    c2 = CheckpointCoordinator(r2, b2, f2, interval_s=999.0, path=cut_file)
    restored = c2.restore_from_disk()
    assert restored is not None and c2.restores == 1
    assert r2.engine is restored
    t2 = r2.start(poll_timeout_s=0.01)
    try:
        _drain(r2, 10)  # exactly the post-cut gap re-drives
        assert _wait_started(reg2, 10) == 10
    finally:
        r2.stop()
        t2.join(timeout=5)
    b2.close()


def test_restore_from_disk_tolerates_missing_and_corrupt(tmp_path):
    broker, router, coord = _pipeline()
    coord.path = str(tmp_path / "none.json")
    assert coord.restore_from_disk() is None  # missing: cold start
    (tmp_path / "bad.json").write_text("{torn")
    coord.path = str(tmp_path / "bad.json")
    assert coord.restore_from_disk() is None  # corrupt: cold start
    assert coord.restores == 0


def test_restore_from_disk_tolerates_wrong_shapes(tmp_path):
    """Valid JSON that is not a valid cut must read as a cold start."""
    broker, router, coord = _pipeline()
    for content in ("null", "[]", '"x"', "7",
                    '{"version": 1}',
                    '{"version": 1, "snap": [], "offsets": {}}',
                    '{"version": 2, "snap": {}, "offsets": {}}'):
        f = tmp_path / "cut.json"
        f.write_text(content)
        coord.path = str(f)
        assert coord.restore_from_disk() is None, content
    assert coord.restores == 0


def test_retention_pin_seeded_at_coordinator_start():
    """The FIRST checkpoint has no prior pin: between its barrier release
    and its own pin write, the consuming groups advance and retention
    could trim the new cut's replay window (ADVICE r5 medium). The
    coordinator must therefore seed RETENTION_PIN_GROUP at construction,
    at the groups' then-current committed positions."""
    from ccfd_tpu.bus.broker import RETENTION_PIN_GROUP

    broker = Broker(default_partitions=1, retention_records=64)
    reg_engine = Registry()
    factory = lambda: build_engine(CFG, broker, reg_engine)  # noqa: E731
    router = Router(CFG, broker, amount_score, factory(), Registry(),
                    max_batch=4096)
    broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(256)])
    assert router.step() == 256  # commits the router group at 256

    coord = CheckpointCoordinator(router, broker, factory, interval_s=999.0)
    # the seed pin exists BEFORE any checkpoint ran...
    assert coord.checkpoints == 0
    assert broker.committed_offsets(RETENTION_PIN_GROUP,
                                    CFG.kafka_topic) == [256]
    # ...and it holds the trim floor through the first-checkpoint window:
    # the router races ahead of the (still-unwritten) first cut, retention
    # runs, and the records a restore-from-256 would replay must survive
    broker.produce_batch(CFG.kafka_topic,
                         [tx(i, 10.0) for i in range(1024)])
    while router.step():
        pass
    assert broker.committed_offsets("router", CFG.kafka_topic) == [1280]
    broker.enforce_retention()
    assert broker.beginning_offsets(CFG.kafka_topic) == [256], (
        "retention trimmed into the pre-first-checkpoint replay window")
    # the first real checkpoint then advances the pin to its own cut
    # (router marked stopped: no loop exists to ack the barrier)
    router.stop()
    assert coord.checkpoint() is not None
    assert broker.committed_offsets(RETENTION_PIN_GROUP,
                                    CFG.kafka_topic) == [1280]
    broker.enforce_retention()
    assert broker.beginning_offsets(CFG.kafka_topic) == [1280 - 64]


def test_seed_pin_respects_on_disk_cut_at_crash_bringup(tmp_path):
    """Crash bring-up (code-review r6): the groups' replayed committed
    positions sit PAST the persisted cut that restore_from_disk() will
    rewind to. The constructor's pin seed must fold the disk cut in
    (element-wise min), not overwrite the surviving pin forward — or
    retention could trim the very window the restore replays."""
    from ccfd_tpu.bus.broker import RETENTION_PIN_GROUP

    broker = Broker(default_partitions=1, retention_records=64)
    reg_engine = Registry()
    factory = lambda: build_engine(CFG, broker, reg_engine)  # noqa: E731
    router = Router(CFG, broker, amount_score, factory(), Registry(),
                    max_batch=4096)
    path = str(tmp_path / "cut.json")
    broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(100)])
    router.step()
    router.stop()  # parked: checkpoints don't need a live loop to ack
    coord1 = CheckpointCoordinator(router, broker, factory,
                                   interval_s=999.0, path=path)
    assert coord1.checkpoint() is not None  # disk cut at offset 100
    # post-cut traffic consumed before the "crash": groups now at 400
    broker.produce_batch(CFG.kafka_topic, [tx(i, 10.0) for i in range(300)])
    router.reset()
    while router.step():
        pass
    router.stop()
    assert broker.committed_offsets("router", CFG.kafka_topic) == [400]
    # process restart: a FRESH coordinator on the same path + broker.
    # Its seed must keep the pin at the disk cut (100), not jump to 400.
    coord2 = CheckpointCoordinator(router, broker, factory,
                                   interval_s=999.0, path=path)
    assert broker.committed_offsets(RETENTION_PIN_GROUP,
                                    CFG.kafka_topic) == [100]
    broker.enforce_retention()
    assert broker.beginning_offsets(CFG.kafka_topic) == [100], (
        "retention trimmed the on-disk cut's replay window before "
        "restore_from_disk could rewind to it")
    assert coord2.restore_from_disk() is not None  # replay window intact
