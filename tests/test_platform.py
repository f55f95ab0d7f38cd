"""Platform operator tests: CR parsing, topological bring-up, end-to-end flow.

The reference's deployment contract — an operator CR with component toggles
(deploy/frauddetection_cr.yaml) applied through an ordered run-book with
readiness gates (README.md:44-537) — exercised in-process.
"""

from __future__ import annotations

import json
import time
import urllib.request

import pytest

from ccfd_tpu.config import Config
from ccfd_tpu.platform.operator import Platform, PlatformSpec


def minimal_cr(**overrides) -> dict:
    spec = {
        "store": {"enabled": False},
        "bus": {"partitions": 2},
        "scorer": {"enabled": True, "model": "logreg", "train_steps": 0},
        "engine": {"enabled": True},
        "notify": {"enabled": True, "seed": 0},
        "router": {"enabled": True},
        "retrain": {"enabled": False},
        "producer": {"enabled": False},
        "monitoring": {"enabled": True},
        "health": {"enabled": True},
    }
    spec.update(overrides)
    return {"apiVersion": "ccfd.tpu/v1", "kind": "FraudDetectionPlatform",
            "spec": spec}


class TestSpecParsing:
    def test_defaults_without_blocks(self):
        spec = PlatformSpec.from_cr({"spec": {}}, cfg=Config())
        assert spec.component("router").enabled
        assert spec.component("scorer").enabled
        assert not spec.component("producer").enabled  # job: explicit opt-in
        assert not spec.component("store").enabled

    def test_bool_shorthand(self):
        spec = PlatformSpec.from_cr(
            {"spec": {"notify": False, "store": True}}, cfg=Config()
        )
        assert not spec.component("notify").enabled
        assert spec.component("store").enabled

    def test_options_surface(self):
        spec = PlatformSpec.from_cr(minimal_cr(), cfg=Config())
        assert spec.component("bus").opt("partitions") == 2
        assert spec.component("scorer").opt("model") == "logreg"

    def test_yaml_roundtrip(self, tmp_path):
        import yaml

        p = tmp_path / "cr.yaml"
        p.write_text(yaml.safe_dump(minimal_cr()))
        spec = PlatformSpec.from_yaml(str(p), cfg=Config())
        assert spec.component("scorer").opt("model") == "logreg"


class TestBringUp:
    def test_up_ready_down(self):
        spec = PlatformSpec.from_cr(minimal_cr(), cfg=Config())
        platform = Platform(spec).up(wait_ready_s=20.0)
        try:
            st = platform.status()
            assert st["services"]["router"]["state"] == "Running"
            assert st["services"]["notify"]["state"] == "Running"
            assert "metrics" in st["endpoints"]
            assert "health" in st["endpoints"]
        finally:
            platform.down()
        assert platform.status()["services"]["router"]["state"] == "Stopped"

    def test_probes_and_metrics_endpoints_live(self):
        spec = PlatformSpec.from_cr(minimal_cr(), cfg=Config())
        platform = Platform(spec).up(wait_ready_s=20.0)
        try:
            health = platform.status()["endpoints"]["health"]
            with urllib.request.urlopen(health + "/readyz") as r:
                assert json.loads(r.read())["ready"] is True
            metrics = platform.status()["endpoints"]["metrics"]
            with urllib.request.urlopen(metrics + "/prometheus/router") as r:
                body = r.read().decode()
            assert "transaction_incoming_total" in body
            # KIE registry on the reference's scrape path
            with urllib.request.urlopen(metrics + "/rest/metrics") as r:
                assert "fraud_investigation_amount" in r.read().decode()
        finally:
            platform.down()

    def test_full_pipeline_with_producer_and_store(self):
        """CR-driven end-to-end: store-seeded dataset -> producer -> router ->
        scorer -> engine; transactions land as process starts."""
        cfg = Config(customer_reply_timeout_s=0.5)
        cr = minimal_cr(
            store={"enabled": True, "seed_dataset": True},
            producer={"enabled": True, "transactions": 300},
        )
        spec = PlatformSpec.from_cr(cr, cfg=cfg)
        platform = Platform(spec).up(wait_ready_s=20.0)
        try:
            assert platform.wait_producer(timeout_s=30.0)
            router_reg = platform.registries["router"]
            deadline = time.monotonic() + 60.0
            c_in = router_reg.counter("transaction_incoming_total")
            out = router_reg.counter("transaction_outgoing_total")

            def started() -> float:
                return out.value(labels={"type": "standard"}) + out.value(
                    labels={"type": "fraud"}
                )

            # wait on the OUTGOING counter: incoming increments before the
            # scoring dispatch and the 300 engine starts, so sampling right
            # after c_in reaches 300 can observe a mid-batch router
            while time.monotonic() < deadline and started() < 300:
                time.sleep(0.05)
            assert c_in.value() == 300
            assert started() == 300  # every transaction routed to a process
        finally:
            platform.down()

    def test_operator_wired_tracing_reaches_scrape_and_traces_endpoint(self):
        """Satellite regression for the unscraped-tracer bug: the operator
        wires component tracers into the SCRAPED registries, so span
        histograms appear on /prometheus, the tail sampler's metrics live
        in the scraped 'tracing' registry, and a retained end-to-end trace
        resolves via the exporter's /traces/<id>."""
        cfg = Config(customer_reply_timeout_s=0.2)
        cr = minimal_cr(
            producer={"enabled": True, "transactions": 200},
            tracing={"enabled": True, "sample": 1.0},
        )
        platform = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(
            wait_ready_s=20.0)
        try:
            assert platform.trace_sink is not None
            assert platform.wait_producer(timeout_s=20.0)
            reg = platform.registries["router"]
            deadline = time.monotonic() + 30.0
            while (time.monotonic() < deadline and
                   reg.counter("transaction_incoming_total").value() < 200):
                time.sleep(0.05)
            platform.trace_sink.flush(0.0)
            metrics = platform.status()["endpoints"]["metrics"]
            with urllib.request.urlopen(metrics + "/prometheus/router") as r:
                body = r.read().decode()
            assert "trace_span_seconds" in body  # scraped, not private
            with urllib.request.urlopen(metrics + "/prometheus/tracing") as r:
                assert "ccfd_traces_kept_total" in r.read().decode()
            with urllib.request.urlopen(metrics + "/traces") as r:
                traces = json.loads(r.read())["traces"]
            e2e = [t for t in traces
                   if {"producer", "router"} <= set(t["components"])]
            assert e2e, traces[:3]
            # the newest trace may be a batch still in flight: its
            # router.batch span closes last, after the route
            deadline = time.monotonic() + 10.0
            while True:
                with urllib.request.urlopen(
                    metrics + f"/traces/{e2e[0]['trace_id']}"
                ) as r:
                    names = {s["name"] for s in json.loads(r.read())["spans"]}
                if "router.batch" in names or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            assert {"producer.batch", "router.batch"} <= names
        finally:
            platform.down()

    def test_producer_registry_reaches_exporter_and_readyz_stays_up(self):
        """Registries created after exporter start must still be scraped, and
        a finished one-shot producer must not degrade readiness."""
        cfg = Config(customer_reply_timeout_s=0.2)
        cr = minimal_cr(producer={"enabled": True, "transactions": 50})
        platform = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(wait_ready_s=20.0)
        try:
            assert platform.wait_producer(timeout_s=20.0)
            deadline = time.monotonic() + 10.0
            while (time.monotonic() < deadline and
                   platform.status()["services"]["producer"]["state"] != "Succeeded"):
                time.sleep(0.05)
            metrics = platform.status()["endpoints"]["metrics"]
            with urllib.request.urlopen(metrics + "/prometheus/producer") as r:
                assert "producer_rows_total" in r.read().decode()
            health = platform.status()["endpoints"]["health"]
            with urllib.request.urlopen(health + "/readyz") as r:
                assert r.status == 200
        finally:
            platform.down()

    def test_healthz_degrades_after_supervisor_stop(self):
        from ccfd_tpu.runtime.health import HealthServer
        from ccfd_tpu.runtime.supervisor import Supervisor

        sup = Supervisor().start()
        hs = HealthServer(sup).start()
        try:
            with urllib.request.urlopen(hs.endpoint + "/healthz") as r:
                assert r.status == 200
            sup.stop()
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(hs.endpoint + "/healthz")
            assert exc.value.code == 503
        finally:
            hs.stop()

    def test_bus_disabled_with_dependents_errors(self):
        cr = minimal_cr(bus={"enabled": False})
        with pytest.raises(ValueError, match="bus disabled"):
            Platform(PlatformSpec.from_cr(cr, cfg=Config())).up()

    def test_bus_disabled_with_only_analytics_errors(self):
        cr = minimal_cr(bus={"enabled": False}, scorer={"enabled": False},
                        engine={"enabled": False}, notify={"enabled": False},
                        router={"enabled": False})
        with pytest.raises(ValueError, match="analytics"):
            Platform(PlatformSpec.from_cr(cr, cfg=Config())).up()

    def test_missing_engine_block_disables_engine(self):
        cr = minimal_cr(engine={"enabled": False}, router={"enabled": False},
                        retrain={"enabled": False})
        spec = PlatformSpec.from_cr(cr, cfg=Config())
        platform = Platform(spec).up(wait_ready_s=10.0)
        try:
            assert platform.engine is None
            assert "router" not in platform.status()["services"]
        finally:
            platform.down()


class TestCrashRecovery:
    def test_engine_crash_recovery_through_operator(self):
        """The CR opt `engine.crash_recovery` wires the aligned-checkpoint
        coordinator into the run-book bring-up: a chaos kill of the engine
        service restores the last cut, re-points every engine referent
        (platform + KIE REST server), and the pipeline keeps flowing."""
        cr = minimal_cr(
            engine={"enabled": True, "crash_recovery": True, "rest": True,
                    "checkpoint_interval_s": 0.5},
        )
        cfg = Config(fraud_threshold=2.0)  # all standard: deterministic
        platform = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(
            wait_ready_s=20.0
        )
        try:
            assert platform.recovery is not None
            assert "engine" in platform.supervisor.status()
            from ccfd_tpu.data.ccfd import FEATURE_NAMES

            rows = [{FEATURE_NAMES[j]: float(j) for j in range(30)}
                    | {"id": i} for i in range(40)]
            platform.broker.produce_batch(cfg.kafka_topic, rows)
            deadline = time.time() + 20
            while (platform.router._c_in.value() < 40
                   and time.time() < deadline):
                time.sleep(0.05)
            assert platform.router._c_in.value() >= 40
            # wait for a checkpoint, then kill the engine service
            deadline = time.time() + 10
            while platform.recovery.checkpoints == 0 and time.time() < deadline:
                time.sleep(0.05)
            assert platform.recovery.checkpoints > 0
            old_engine = platform.engine
            assert platform.supervisor.inject_failure("engine", "test")
            deadline = time.time() + 15
            while platform.recovery.restores == 0 and time.time() < deadline:
                time.sleep(0.05)
            assert platform.recovery.restores == 1
            # give the swap a moment to land, then check the re-pointing
            deadline = time.time() + 5
            while platform.engine is old_engine and time.time() < deadline:
                time.sleep(0.05)
            assert platform.engine is not old_engine
            assert platform.engine_server.engine is platform.engine
            assert platform.router.engine is platform.engine
            # pipeline still flows through the restored engine
            platform.broker.produce_batch(
                cfg.kafka_topic, [dict(r, id=100 + i)
                                  for i, r in enumerate(rows[:10])]
            )
            deadline = time.time() + 20
            while (platform.router._c_in.value() < 50
                   and time.time() < deadline):
                time.sleep(0.05)
            assert platform.router._c_in.value() >= 50
        finally:
            platform.down()

    def test_platform_bounce_restores_cut_from_disk(self, tmp_path):
        """Full-process crash story through the run-book: platform 1
        checkpoints to disk over a durable bus and dies; platform 2's
        bring-up restores the cut BEFORE its services start and the
        rewound bus re-drives the post-cut gap."""
        cr = minimal_cr(
            bus={"partitions": 2, "log_dir": str(tmp_path / "buslog")},
            engine={"enabled": True, "crash_recovery": True,
                    "checkpoint_interval_s": 0.5,
                    "checkpoint_file": str(tmp_path / "cut.json")},
        )
        cfg = Config(fraud_threshold=2.0)
        from ccfd_tpu.data.ccfd import FEATURE_NAMES

        rows = [{FEATURE_NAMES[j]: float(j) for j in range(30)} | {"id": i}
                for i in range(30)]
        p1 = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(wait_ready_s=20.0)
        try:
            p1.broker.produce_batch(cfg.kafka_topic, rows[:20])
            deadline = time.time() + 20
            while (p1.router._c_in.value() < 20 and time.time() < deadline):
                time.sleep(0.05)
            deadline = time.time() + 10
            while p1.recovery.checkpoints == 0 and time.time() < deadline:
                time.sleep(0.05)
            assert p1.recovery.checkpoints > 0
            # post-cut gap that platform 2 must re-drive
            p1.broker.produce_batch(cfg.kafka_topic, rows[20:])
            deadline = time.time() + 20
            while (p1.router._c_in.value() < 30 and time.time() < deadline):
                time.sleep(0.05)
        finally:
            p1.down()
        # the authoritative cut is whatever actually landed on disk
        # (sha256-framed by the durability plane)
        from ccfd_tpu.runtime.durability import read_json_artifact

        cut = read_json_artifact(str(tmp_path / "cut.json"),
                                 artifact="recovery_cut", quarantine=False)
        cut_consumed = sum(cut["offsets"][f"router\x00{cfg.kafka_topic}"])
        p2 = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(wait_ready_s=20.0)
        try:
            assert p2.recovery.restores == 1  # restore_from_disk at boot
            gap = 30 - cut_consumed
            deadline = time.time() + 20
            while (p2.router._c_in.value() < gap and time.time() < deadline):
                time.sleep(0.05)
            assert p2.router._c_in.value() >= gap
        finally:
            p2.down()


class TestInvestigator:
    def test_operator_wires_investigator_and_queue_drains(self):
        """The demo loop closes: flagged transactions become tasks, the
        investigator component works them, instances reach terminal."""
        cr = minimal_cr(
            investigator={"enabled": True, "rate_per_s": 0.0,
                          "base_fraud_rate": 0.0, "seed": 1},
            # no customer simulation: every fraud instance must time out
            # into the investigation queue, not resolve via a reply
            notify={"enabled": False},
        )
        # every record flags as fraud; instant reply-timeout sends each
        # instance to the investigation queue; confidence threshold is
        # unreachable so the prediction service NEVER auto-closes (every
        # task waits for the investigator)
        cfg = Config(fraud_threshold=0.0, customer_reply_timeout_s=0.05,
                     confidence_threshold=2.0)
        from ccfd_tpu.data.ccfd import FEATURE_NAMES

        p = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(wait_ready_s=20.0)
        try:
            assert p.investigator is not None
            assert "investigator" in p.supervisor.status()
            rows = [{FEATURE_NAMES[j]: float(j) for j in range(30)}
                    | {"id": i, "Amount": 500.0} for i in range(12)]
            p.broker.produce_batch(cfg.kafka_topic, rows)
            deadline = time.time() + 25
            while time.time() < deadline:
                if p.investigator.completed >= 12:
                    break
                time.sleep(0.1)
            assert p.investigator.completed >= 12
            with p.engine.state_lock:
                active = p.engine.instances("active")
            assert active == []
        finally:
            p.down()

    def test_investigator_defaults_off(self):
        spec = PlatformSpec.from_cr({"spec": {}}, cfg=Config())
        assert not spec.component("investigator").enabled


class TestSeqServing:
    def test_operator_serves_seq_model_with_recovery_state(self):
        """CCFD_MODEL=seq through the CR: the router streams through the
        history-aware scorer, and crash recovery carries the histories."""
        cr = minimal_cr(
            scorer={"enabled": True, "model": "seq", "history_length": 8,
                    "dtype": "float32"},
            engine={"enabled": True, "crash_recovery": True,
                    "checkpoint_interval_s": 0.5},
            notify={"enabled": False},
        )
        cfg = Config(fraud_threshold=2.0)
        from ccfd_tpu.data.ccfd import FEATURE_NAMES
        from ccfd_tpu.serving.history import SeqScorer

        p = Platform(PlatformSpec.from_cr(cr, cfg=cfg)).up(wait_ready_s=30.0)
        try:
            assert isinstance(p.scorer, SeqScorer)
            assert "history" in p.recovery._extra_state
            rows = [{FEATURE_NAMES[j]: float(j) for j in range(30)}
                    | {"id": i % 3, "customer_id": i % 3}
                    for i in range(12)]
            p.broker.produce_batch(cfg.kafka_topic, rows,
                                   keys=[i % 3 for i in range(12)])
            deadline = time.time() + 25
            # wait on the STORE, not the incoming counter: the pipelined
            # loop counts records at decode time, so _c_in can reach 12
            # while the scoring batch (and its history commit) is still
            # in flight — under CI load that window spans seconds
            while (len(p.scorer.store) < 3 and time.time() < deadline):
                time.sleep(0.05)
            assert p.router._c_in.value() >= 12
            assert len(p.scorer.store) == 3  # per-customer histories live
            # a checkpoint carries the history state
            deadline = time.time() + 10
            while p.recovery.checkpoints == 0 and time.time() < deadline:
                time.sleep(0.05)
            assert p.recovery.checkpoints > 0
            cut = p.recovery._last
            assert cut and "history" in cut.get("extra", {})
            assert len(cut["extra"]["history"]["customers"]) == 3
        finally:
            p.down()
