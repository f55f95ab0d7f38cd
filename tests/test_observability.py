"""Dashboards generator, tracer spans, dashboard-metric contract, CLI demo."""

import json
import re

from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.observability.dashboards import build_all_dashboards, write_dashboards
from ccfd_tpu.observability.trace import SpanSink, Tracer


# The reference's full metrics contract (SURVEY.md §5): router business
# counters (reference README.md:522-530, Router.json:88-326), KIE amount
# histograms (README.md:532-537, KIE.json:91-657), model prediction gauges
# (ModelPrediction.json:96-322), Seldon serving SLO series
# (SeldonCore.json:119-531), plus this framework's bus-health and retrain
# surfaces (Kafka.json analog / new capability).
REFERENCE_CONTRACT_METRICS = [
    "transaction_incoming_total",
    "transaction_outgoing_total",
    "notifications_outgoing_total",
    "notifications_incoming_total",
    "fraud_investigation_amount",
    "fraud_approved_low_amount",
    "fraud_approved_amount",
    "fraud_rejected_amount",
    "proba_1", "Amount", "V17", "V10",
    "seldon_api_executor_client_requests_seconds",
    "seldon_api_executor_server_requests_total",
    "bus_topic_records_in_total",
    "bus_topic_end_offset",
    "bus_topic_backlog",
    "bus_topic_retained_records",
    "bus_topic_log_start_offset",
    "bus_records_trimmed_total",
    "bus_consumers",
    "retrain_param_swaps_total",
    "retrain_labels_total",
    "analytics_drift_psi",
    # round 6: fault-injection / breaker / degradation-ladder surface
    # (runtime/faults.py, runtime/breaker.py, router ladder)
    "ccfd_breaker_state",
    "ccfd_breaker_transitions_total",
    "router_degraded_total",
    "router_shed_total",
    "faults_injected_total",
    # round 7: distributed tracing + tail sampler + cardinality guard
    # (observability/trace.py, metrics/prom.py)
    "trace_span_seconds",
    "ccfd_trace_spans_total",
    "ccfd_traces_kept_total",
    "ccfd_traces_dropped_total",
    "ccfd_traces_retained",
    "ccfd_metric_labelsets_dropped_total",
    # round 8: partition-parallel router fan-out + coalesced dispatch
    # (router/parallel.py) and the memory-drift surface
    # (observability/memory.py, metrics/exporter.py)
    "router_worker_batches_total",
    "router_coalesced_dispatches_total",
    "router_coalesced_rows_total",
    "ccfd_process_rss_bytes",
    "ccfd_component_objects",
    # round 9: model lifecycle — shadow/canary/promotion surface
    # (lifecycle/controller.py, lifecycle/shadow.py, lifecycle/evaluator.py)
    "ccfd_lifecycle_stage",
    "ccfd_lifecycle_promotions_total",
    "ccfd_lifecycle_rollbacks_total",
    "ccfd_lifecycle_rejections_total",
    "ccfd_lifecycle_candidates_total",
    "ccfd_lifecycle_shadow_rows_total",
    "ccfd_lifecycle_shadow_dropped_total",
    "ccfd_lifecycle_auc",
    "ccfd_lifecycle_score_psi",
    "ccfd_lifecycle_alert_rate_delta",
    "ccfd_lifecycle_canary_rows_total",
    # round 10: overload control — adaptive admission, priority shedding,
    # dispatch watchdog (runtime/overload.py)
    "ccfd_inflight_limit",
    "ccfd_inflight_used",
    "ccfd_admission_total",
    "ccfd_shed_total",
    "ccfd_priority_inversions_total",
    "ccfd_dispatch_timeout_total",
    # round 12: SLO burn-rate monitoring + stage profiles
    # (observability/slo.py, observability/profile.py)
    "ccfd_slo_burn_rate",
    "ccfd_slo_error_budget_remaining",
    "ccfd_slo_breach_total",
    "ccfd_slo_breaching",
    "ccfd_slo_budget_spent_ratio",
    "ccfd_stage_latency_ms",
    "ccfd_xla_compile_events_total",
    "ccfd_xla_compile_seconds_total",
    # round 13: device & transfer telemetry + incident flight recorder
    # (observability/device.py, observability/incident.py)
    "ccfd_device_memory_bytes",
    "ccfd_h2d_bytes_total",
    "ccfd_h2d_seconds",
    "ccfd_compile_stage_seconds_total",
    "ccfd_incident_snapshots_total",
    "ccfd_incidents_total",
    "ccfd_incident_ring_size",
    # round 14: device self-healing — health state machine, canary, heal
    # ladder, warm re-promotion (runtime/heal.py)
    "ccfd_device_health",
    "ccfd_heal_transitions_total",
    "ccfd_heal_attempts_total",
    "ccfd_heal_canary_total",
    "ccfd_h2d_put_failures_total",
    # round 16: durable-state integrity plane (runtime/durability.py) —
    # corruption quarantines, last-good fallbacks, write errors, the
    # orphan-tmp sweep, mid-file bus-log truncation and the rules-tier
    # storage pin
    "ccfd_storage_corrupt_total",
    "ccfd_storage_fallback_total",
    "ccfd_storage_write_errors_total",
    "ccfd_storage_verified_reads_total",
    "ccfd_storage_unverified_reads_total",
    "ccfd_storage_tmp_swept_total",
    "ccfd_storage_log_truncated_records_total",
    "ccfd_storage_pinned",
    # round 17: decision provenance plane (observability/audit.py) —
    # per-transaction records stamped at the route seam, drop accounting,
    # the segmented log footprint and the bounded query ring
    "ccfd_audit_records_total",
    "ccfd_audit_dropped_total",
    "ccfd_audit_log_bytes",
    "ccfd_audit_ring_records",
    # round 18: multi-host fleet plane (ccfd_tpu/fleet/) — membership vs
    # lease TTL, disjoint partition ownership, champion parity +
    # self-quarantine, epoch-fenced commits, fleet-ledger health
    "ccfd_fleet_members",
    "ccfd_fleet_epoch",
    "ccfd_fleet_partition_owner",
    "ccfd_fleet_parity",
    "ccfd_fleet_quarantined",
    "ccfd_fleet_admission_ceiling",
    "router_fenced_commits_total",
    "fleet_ledger_entries_total",
    "fleet_member_kill_bundles_total",
    # round 19: capacity observatory (observability/capacity.py) — the
    # queueing-model plane's trust SLI, bottleneck one-hot, per-stage
    # headroom/utilization, predicted p99, regression-sentinel fires
    "ccfd_capacity_model_error_ratio",
    "ccfd_capacity_bottleneck",
    "ccfd_capacity_headroom_ratio",
    "ccfd_capacity_utilization",
    "ccfd_capacity_predicted_p99_ms",
    "ccfd_capacity_regression_total",
]


def _all_exprs(boards):
    return [
        t["expr"]
        for b in boards.values()
        for panel in b["panels"]
        for t in panel["targets"]
    ]


def test_dashboards_cover_contract_metrics():
    boards = build_all_dashboards()
    assert set(boards) == {
        "Router", "KIE", "ModelPrediction", "SeldonCore", "Bus",
        "KafkaCluster", "Analytics", "Retrain", "Resilience", "Tracing",
        "ModelLifecycle", "Overload", "SeqServing", "SLO", "Device",
        "Heal", "Storage", "Audit", "Fleet", "Replay", "Capacity",
    }
    exprs = _all_exprs(boards)
    for metric in REFERENCE_CONTRACT_METRICS:
        assert any(metric in e for e in exprs), (
            f"no generated panel expr queries contract metric {metric}"
        )


def test_seldon_board_has_reference_latency_quantiles():
    # reference SeldonCore.json:499-531 charts p50/p75/p90/p95/p99
    exprs = _all_exprs({"s": build_all_dashboards()["SeldonCore"]})
    for q in ("0.5", "0.75", "0.9", "0.95", "0.99"):
        assert any(f"histogram_quantile({q}," in e for e in exprs), q


def test_checked_in_dashboards_match_generator(tmp_path):
    """deploy/grafana/ is generated output; drift from the generator means
    someone hand-edited it or forgot to regenerate (VERDICT r1 weak #4)."""
    import os

    repo_dir = os.path.join(os.path.dirname(__file__), "..", "deploy", "grafana")
    fresh = {name: board for name, board in build_all_dashboards().items()}
    checked_in = sorted(os.listdir(repo_dir))
    assert checked_in == sorted(f"{n}.json" for n in fresh), (
        "deploy/grafana/ file set drifted from the generator"
    )
    for name, board in fresh.items():
        with open(os.path.join(repo_dir, f"{name}.json")) as f:
            assert json.load(f) == json.loads(json.dumps(board)), (
                f"deploy/grafana/{name}.json is stale — regenerate with "
                "python -m ccfd_tpu.observability.dashboards deploy/grafana"
            )


def _stat_panels(board: dict) -> dict[str, dict]:
    return {p["title"]: p for p in board["panels"] if p["type"] == "stat"}


def test_kafka_cluster_board_matches_reference_health_stats():
    """The real-Kafka deployment mode's board carries the reference Kafka
    board's operational stat panels — same titles, same JMX metrics, with
    alert thresholds (reference deploy/grafana/Kafka.json stat panels;
    VERDICT r2 missing #3)."""
    board = build_all_dashboards()["KafkaCluster"]
    stats = _stat_panels(board)
    want = {
        "Brokers Online": "kafka_server_replicamanager_leadercount",
        "Online Partitions": "kafka_server_replicamanager_partitioncount",
        "Under Replicated Partitions":
            "kafka_server_replicamanager_underreplicatedpartitions",
        "Offline Partitions Count":
            "kafka_controller_kafkacontroller_offlinepartitionscount",
    }
    for title, metric in want.items():
        assert title in stats, title
        panel = stats[title]
        assert any(metric in t["expr"] for t in panel["targets"]), title
        steps = panel["fieldConfig"]["defaults"]["thresholds"]["steps"]
        assert {s["color"] for s in steps} == {"green", "red"}, title


def test_bus_board_has_alert_threshold_stats():
    stats = _stat_panels(build_all_dashboards()["Bus"])
    for title in ("Live consumers", "Max consumer lag", "Scorer device wedged"):
        assert title in stats, title
        assert "thresholds" in stats[title]["fieldConfig"]["defaults"], title


def test_seq_serving_board_covers_the_dataflow_metrics():
    """The Sequence Serving panel group (round 11): every metric the
    overlapped seq dataflow exports must be charted — the split that
    motivated the rework (assembly vs dispatch), the L/B bucket mix, the
    async depth, the anonymous fast path and the crash-replay stale-commit
    tripwire (which must be an alert-colored stat, like the other
    must-stay-zero signals)."""
    board = build_all_dashboards()["SeqServing"]
    exprs = _all_exprs({"s": board})
    for metric in (
        "seq_assembly_seconds", "seq_dispatch_seconds",
        "seq_bucket_dispatch_total", "seq_bucket_rows_total",
        "seq_inflight_dispatches", "seq_anonymous_rows_total",
        "seq_history_customers", "seq_stale_commits_total",
    ):
        assert any(metric in e for e in exprs), metric
    stale = [p for p in board["panels"]
             if any("seq_stale_commits_total" in t["expr"]
                    for t in p["targets"])]
    assert stale and stale[0]["type"] == "stat"
    assert "thresholds" in stale[0]["fieldConfig"]["defaults"]


def test_seldon_board_carries_dispatch_health():
    exprs = _all_exprs({"s": build_all_dashboards()["SeldonCore"]})
    for metric in ("ccfd_device_wedged", "ccfd_dispatch_timeouts_total",
                   "ccfd_host_fallback_scores_total"):
        assert any(metric in e for e in exprs), metric


def test_write_dashboards_roundtrip(tmp_path):
    paths = write_dashboards(str(tmp_path))
    assert len(paths) == len(build_all_dashboards())
    for p in paths:
        board = json.load(open(p))
        assert board["panels"] and board["uid"].startswith("ccfd-")


def test_docs_state_generated_board_count_once():
    """README's layer map drifted to "6 Grafana boards" while the
    generator emitted 13 (ISSUE 9 satellite). The count now lives in ONE
    doc sentence ("N generated Grafana boards", README layer map) and
    this test pins it to both the generator and the checked-in file set,
    so it can't drift again."""
    import os

    root = os.path.join(os.path.dirname(__file__), "..")
    pattern = re.compile(r"(\d+) generated Grafana boards")
    counts: list[tuple[str, int]] = []
    for doc in ("README.md", "ARCHITECTURE.md"):
        with open(os.path.join(root, doc)) as f:
            counts.extend((doc, int(m)) for m in pattern.findall(f.read()))
    assert len(counts) == 1, (
        f"the generated-board count must be stated exactly once across "
        f"README/ARCHITECTURE, found {counts}"
    )
    documented = counts[0][1]
    assert documented == len(build_all_dashboards())
    checked_in = [f for f in os.listdir(os.path.join(root, "deploy", "grafana"))
                  if f.endswith(".json")]
    assert documented == len(checked_in)


def test_tracer_spans_land_in_histogram():
    reg = Registry()
    sink = SpanSink(sample=1.0)
    tr = Tracer(reg, sink=sink)
    with tr.span("score") as first:
        pass
    with tr.span("score") as second:
        pass
    assert reg.histogram("trace_span_seconds").count({"span": "score"}) == 2
    assert [len(sink.trace(sp.trace_id)) for sp in (first, second)] == [1, 1]


# -- dashboard ↔ exported-metric contract (round 7 CI guard) -----------------
# PromQL pieces that are NOT metric names: functions, keywords, label names
# and label values that the bare-identifier scan below would otherwise pick
# up once the {label="value"} matchers are stripped.
_PROMQL_NOISE = {
    "rate", "irate", "sum", "max", "min", "avg", "count",
    "histogram_quantile", "by", "on", "ignoring", "group_left",
    "group_right", "le", "m", "s",
}
# Metrics a dashboard may reference that this codebase does NOT export:
# the KafkaCluster board reads the Kafka JMX exporter of a REAL Strimzi
# cluster (deploy mode where the in-proc bus is swapped out entirely).
_EXTERNAL_METRICS = re.compile(
    r"^(kafka_server_|kafka_controller_|kafka_consumergroup_)"
)


def _registered_metric_kinds() -> dict[str, set[str]]:
    """Metric name -> registered kind(s), by static scan: the registry
    factory calls plus direct metric constructions."""
    import os

    pkg = os.path.join(os.path.dirname(__file__), "..", "ccfd_tpu")
    pat = re.compile(
        r"(?:\.(counter|gauge|histogram)|\b(Counter|Gauge|Histogram))\(\s*"
        r"['\"]([A-Za-z_][A-Za-z0-9_]*)['\"]"
    )
    kinds: dict[str, set[str]] = {}
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    for method, cls, name in pat.findall(f.read()):
                        kinds.setdefault(name, set()).add(
                            (method or cls).lower())
    # registered through a named constant, not a literal, so the literal
    # scan can't see it — import the authoritative name instead
    from ccfd_tpu.metrics.prom import LABELSETS_DROPPED

    kinds.setdefault(LABELSETS_DROPPED, set()).add("counter")
    # native-code observers fold into histograms registered in Python, so
    # the scan above is the full set
    return kinds


def _registered_metric_names() -> set[str]:
    return set(_registered_metric_kinds())


def test_every_dashboard_expr_metric_is_exported():
    """The CI guard the unscraped-tracer bug motivated: every metric name
    a generated board queries must be one some component actually
    registers (or a documented external exporter's). Catches silent
    metric-name drift between dashboards and code."""
    registered = _registered_metric_names()
    assert "transaction_incoming_total" in registered  # scan sanity
    ident = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
    unknown = []
    for name, board in build_all_dashboards().items():
        for expr in _all_exprs({name: board}):
            bare = re.sub(r"\{[^}]*\}", "", expr)  # drop label matchers
            # drop grouping clauses: their identifiers are LABEL names
            bare = re.sub(
                r"\b(?:by|on|without|ignoring|group_left|group_right)\s*"
                r"\([^)]*\)", " ", bare)
            for tok in ident.findall(bare):
                if tok in _PROMQL_NOISE or _EXTERNAL_METRICS.match(tok):
                    continue
                base = re.sub(r"_(bucket|sum|count)$", "", tok)
                if tok not in registered and base not in registered:
                    unknown.append((name, tok, expr))
    assert not unknown, (
        "dashboard exprs reference metrics nothing exports: "
        f"{unknown[:10]}"
    )


def test_contract_metrics_obey_naming_conventions():
    """ccfd-lint rule 4 folded into the contract test: every metric the
    dashboard contract names must satisfy the naming conventions the
    linter enforces — counters end _total, histograms carry a unit
    suffix, gauges never claim _total — under the kind(s) the codebase
    ACTUALLY registers it as (scanned from the registration sites, never
    inferred from the name: suffix-derived kinds would make the counter
    check circular). One shared validator (analysis/rules.metric_name_ok)
    so the test suite and the lint gate cannot drift apart."""
    from ccfd_tpu.analysis.rules import (
        GRANDFATHERED_NAMES,
        REFERENCE_BOARD_NAMES,
        metric_name_ok,
    )

    kinds = _registered_metric_kinds()
    bad = []
    for name in REFERENCE_CONTRACT_METRICS:
        registered_kinds = kinds.get(name)
        assert registered_kinds, f"contract metric {name} never registered"
        for kind in sorted(registered_kinds):
            err = metric_name_ok(kind, name)
            if err:
                bad.append(err)
    assert not bad, bad
    # the exemption lists must name (kind, metric) pairs the codebase
    # actually registers — a dead grandfather entry would silently
    # re-admit a future misnamed metric under a stale name
    stale = {(k, n) for k, n in GRANDFATHERED_NAMES
             if k not in kinds.get(n, set())}
    stale |= {("gauge", n) for n in REFERENCE_BOARD_NAMES
              if "gauge" not in kinds.get(n, set())}
    assert not stale, f"exemption entries nothing registers: {stale}"


def test_cli_demo_smoke(capsys):
    from ccfd_tpu.cli import main

    rc = main([
        "demo", "--transactions", "60", "--train-steps", "5",
        "--reply-timeout", "0.2", "--drain-s", "5",
    ])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["transactions"] == 60
    assert summary["fraud_routed"] + summary["standard_routed"] == 60
