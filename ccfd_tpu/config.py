"""12-factor env-var configuration surface.

Keeps the reference's environment-variable contract verbatim so a user of the
reference can drop in this framework with the same manifests:

- router vars: reference deploy/router.yaml:54-70 (BROKER_URL, KAFKA_TOPIC,
  CUSTOMER_NOTIFICATION_TOPIC, CUSTOMER_RESPONSE_TOPIC, KIE_SERVER_URL,
  SELDON_URL, SELDON_ENDPOINT, FRAUD_THRESHOLD) plus optional SELDON_TOKEN
  (reference README.md:447-451).
- KIE-server vars: reference deploy/ccd-service.yaml:54-66 and
  README.md:370-402 (SELDON_TIMEOUT, SELDON_POOL_SIZE, CONFIDENCE_THRESHOLD).
- producer vars: reference deploy/kafka/ProducerDeployment.yaml:77-97
  (topic, s3endpoint, s3bucket, filename, bootstrap).
- notification var: reference deploy/notification-service.yaml:50-52
  (BROKER_URL).

TPU-side knobs (CCFD_*) are new: they configure micro-batching, model choice
and compute dtype for the XLA scorer.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Sequence


@dataclasses.dataclass(frozen=True)
class Config:
    # --- bus / topics (reference router.yaml:54-62) ---
    broker_url: str = "inproc://local"
    bus_log_dir: str = ""  # durable segment-log dir (CCFD_BUS_DIR); "" = memory
    bus_fsync: bool = False  # fsync per append (CCFD_BUS_FSYNC=1)
    # per-partition retained-record cap (CCFD_BUS_RETENTION_RECORDS;
    # 0 = retain everything, the pre-round-5 behavior). The broker only
    # deletes records that are BOTH past this cap and below every
    # consumer group's committed offset — the Kafka retention analog of
    # frauddetection_cr.yaml's topic config, strengthened so rewind-based
    # crash recovery can never lose its cut (bus/broker.py).
    bus_retention_records: int = 0
    # per-topic overrides, "topic:cap,topic2:0" (0 = retain everything for
    # that topic) — Kafka's per-topic retention config analog
    # (CCFD_BUS_RETENTION_OVERRIDES)
    bus_retention_overrides: str = ""
    kafka_topic: str = "odh-demo"
    customer_notification_topic: str = "ccd-customer-outgoing"
    customer_response_topic: str = "ccd-customer-response"

    # --- service endpoints (reference router.yaml:63-68) ---
    kie_server_url: str = "inproc://engine"
    seldon_url: str = "inproc://scorer"
    # URL path suffix, as in the reference manifests (router.yaml:65-68) —
    # NOT a model name; model selection is CCFD_MODEL / model_name below.
    seldon_endpoint: str = "api/v0.1/predictions"
    seldon_token: str = ""

    # --- decision thresholds (reference router.yaml:69-70, README.md:395-402) ---
    fraud_threshold: float = 0.5
    rules_file: str = ""  # JSON rule base (CCFD_RULES) -> router/rules.py
    confidence_threshold: float = 1.0

    # --- HTTP client knobs (reference README.md:386-393) ---
    seldon_timeout_ms: int = 5000
    seldon_pool_size: int = 5
    # new: bounded retries on transport failure (reference's only failure
    # knob is the timeout; retries keep the pipeline up across scorer
    # restarts under the supervisor)
    client_retries: int = 2
    # standing network fault plan (CCFD_FAULTS,
    # "edge:latency=50,jitter=20,error=0.1;edge2:blackhole" —
    # runtime/faults.py): degraded-edge injection on the named client
    # edges (scorer/engine/bus/store). "" = no faults. The chaos CR
    # block's `faults` option is the storm-scheduled form of the same
    # syntax.
    faults_spec: str = ""

    # --- producer (reference ProducerDeployment.yaml:88-97) ---
    producer_topic: str = "odh-demo"
    s3_endpoint: str = ""
    s3_bucket: str = "ccdata"
    filename: str = "creditcard.csv"
    bootstrap: str = "odh-message-bus-kafka-brokers:9092"
    # secret-ref pair from the reference's `keysecret`
    # (ProducerDeployment.yaml:78-87, deploy/ceph/s3-secretceph.yaml:4-7)
    access_key_id: str = ""
    secret_access_key: str = ""

    # --- process engine (reference README.md:554-605 semantics) ---
    customer_reply_timeout_s: float = 30.0
    low_amount_threshold: float = 200.0
    low_proba_threshold: float = 0.75

    # --- online retrain (new; BASELINE.json configs[4]) ---
    labels_topic: str = "ccd-labels"
    audit_topic: str = ""  # "" = audit stream off; a topic name enables the
    # engine's jBPM-AuditService-analog lifecycle event stream onto the bus
    retrain_batch: int = 1024
    retrain_min_labels: int = 256

    # --- model lifecycle (lifecycle/; governed rollout of retrained
    # models: shadow -> canary -> gated promotion with auto-rollback) ---
    # paired champion/challenger shadow scores ride this topic
    shadow_topic: str = "ccd-shadow-scores"  # CCFD_LIFECYCLE_SHADOW_TOPIC
    # lineage/audit + candidate checkpoints persistence root; "" keeps the
    # version store in memory (lineage does NOT survive restarts then)
    lifecycle_dir: str = ""  # CCFD_LIFECYCLE_DIR
    # guardrails (lifecycle/controller.py Guardrails; see ARCHITECTURE.md)
    lifecycle_min_labels: int = 128          # CCFD_LIFECYCLE_MIN_LABELS
    lifecycle_min_shadow_rows: int = 1024    # CCFD_LIFECYCLE_MIN_SHADOW_ROWS
    lifecycle_auc_margin: float = 0.01       # CCFD_LIFECYCLE_AUC_MARGIN
    lifecycle_max_alert_delta: float = 0.10  # CCFD_LIFECYCLE_MAX_ALERT_DELTA
    lifecycle_max_psi: float = 0.25          # CCFD_LIFECYCLE_MAX_PSI
    lifecycle_canary_weight: float = 0.10    # CCFD_LIFECYCLE_CANARY_WEIGHT
    lifecycle_canary_min_labels: int = 64    # CCFD_LIFECYCLE_CANARY_MIN_LABELS
    # submissions inside this interval of the last accepted candidate
    # coalesce into it instead of superseding it (anti-livelock pacing
    # for fast retrain loops); 0 accepts every submission
    lifecycle_min_submit_interval_s: float = 30.0  # CCFD_LIFECYCLE_MIN_SUBMIT_INTERVAL_S

    # --- distributed tracing (observability/trace.py) ---
    # tail sampler: probabilistic keep-rate for BORING traces
    # (slow/errored/fraud/degraded traces are always kept). 1.0 keeps
    # everything (tools/trace_report.py), 0.0 keeps only forced traces.
    trace_sample: float = 0.02  # CCFD_TRACE_SAMPLE
    # a trace with any span at/above this duration is always kept
    trace_slow_ms: float = 100.0  # CCFD_TRACE_SLOW_MS

    # --- router fan-out (router/parallel.py) ---
    # worker loops consuming the transaction topic: 1 = the historical
    # single Router; 0 = auto (one worker per bus partition); >1 explicit.
    # Workers split partitions via consumer-group assignment and share one
    # device scorer through a coalescing batcher (CCFD_ROUTER_WORKERS).
    router_workers: int = 1
    # coalesce concurrent workers' sub-batches into one device dispatch
    # (CCFD_ROUTER_COALESCE; on by default — off means each worker
    # dispatches its own batches, which only makes sense for measuring)
    router_coalesce: bool = True

    # --- overload control (runtime/overload.py) ---
    # master switch for the adaptive-admission plane: AIMD in-flight
    # budget + priority-aware shedding on the router, priority-tiered
    # 429 admission on the REST fronts (CCFD_OVERLOAD; 0 disables and
    # restores the static-budget / unbounded-queue semantics everywhere)
    overload_enabled: bool = True
    # scorer-stage latency budget the router's AIMD limit is derived
    # from: observed dispatch latency above it shrinks the in-flight
    # limit multiplicatively, a window below it grows it additively
    overload_target_ms: float = 50.0       # CCFD_OVERLOAD_TARGET_MS
    # serving-stage (REST) latency budget for the admission gate's AIMD
    overload_serve_target_ms: float = 25.0  # CCFD_OVERLOAD_SERVE_TARGET_MS
    # adaptive limit bounds in rows; 0 = auto (min: one router max_batch,
    # max: 4x the initial limit)
    overload_min_inflight: int = 0         # CCFD_OVERLOAD_MIN_INFLIGHT
    overload_max_inflight: int = 0         # CCFD_OVERLOAD_MAX_INFLIGHT
    # CoDel-style bus sojourn target: records older than this (scaled 1x/
    # 2x/4x for bulk/normal/critical priority) drop from the front at
    # poll time. DEFAULT OFF (0): crash recovery legitimately re-drives
    # minutes-old records, and a standing deadline would shed the replay —
    # arm it explicitly for live traffic (CCFD_OVERLOAD_CODEL_TARGET_MS)
    overload_codel_target_ms: float = 0.0
    # serving DynamicBatcher queue sojourn target (same CoDel policy,
    # perf_counter-based so replay-safe); 0 = off
    overload_serve_codel_target_ms: float = 0.0  # CCFD_OVERLOAD_SERVE_CODEL_TARGET_MS
    # serving DynamicBatcher queue bound in rows with priority-aware
    # eviction (arrivals past it 429); 0 = unbounded (historical)
    overload_rest_queue_rows: int = 0      # CCFD_OVERLOAD_REST_QUEUE_ROWS
    # router dispatch watchdog: a scorer dispatch past this deadline is
    # killed and trips the scorer-edge breaker instead of stalling the
    # worker. -1 = auto (SELDON_TIMEOUT on accelerator backends, off on
    # cpu — same resolution as the server-side dispatch deadline); 0 = off
    overload_dispatch_deadline_ms: float = -1.0  # CCFD_OVERLOAD_DISPATCH_DEADLINE_MS

    # --- SLO monitoring (observability/slo.py; CR block `slo:`) ---
    # master switch for the stage profiler + SLO engine (CCFD_SLO; 0
    # disables the profile/burn-rate plane entirely — like CCFD_OVERLOAD
    # it is the emergency kill switch a CR cannot override)
    slo_enabled: bool = True
    # evaluation tick for the supervised SLO service
    slo_interval_s: float = 5.0            # CCFD_SLO_INTERVAL_S
    # latency objectives: "objective fraction of events at/under target"
    slo_e2e_target_ms: float = 50.0        # CCFD_SLO_E2E_TARGET_MS
    slo_rest_target_ms: float = 25.0       # CCFD_SLO_REST_TARGET_MS
    slo_objective: float = 0.99            # CCFD_SLO_OBJECTIVE
    # error-rate objective: counted process-start failures over incoming
    slo_max_error_rate: float = 0.01       # CCFD_SLO_MAX_ERROR_RATE
    # burn-rate windows in seconds: every entry but the last is a FAST
    # window alerting at slo_fast_burn (short confirms long); the last is
    # the slow budget window at burn 1.0 (CCFD_SLO_WINDOWS)
    slo_windows: str = "300,3600,21600"
    slo_fast_burn: float = 14.4            # CCFD_SLO_FAST_BURN
    # REST transport floor for the budget ledger: a NativeFront 1x1-row
    # RTT p99 taken on a CPU host before the chip. No cell of the
    # benchmark measures the REST path yet (ROADMAP A7): re-measure on
    # the deployment's own host and set CCFD_SLO_TRANSPORT_FLOOR_MS
    slo_transport_floor_ms: float = 0.072

    # --- device telemetry (observability/device.py; CR block `device:`) ---
    # master switch for the device & transfer telemetry plane: per-device
    # memory gauges, measured H2D accounting on the scorer staging path,
    # the executable inventory and the /debug/profile capture endpoint
    # (CCFD_DEVICE; 0 is the emergency kill switch — the BudgetLedger's
    # h2d layer then falls back to the fixed reservation)
    device_enabled: bool = True

    # --- incident flight recorder (observability/incident.py; CR block
    # `incident:`) ---
    # master switch for the FlightRecorder + SLO-breach incident bundles
    # (CCFD_INCIDENT; 0 kills the plane — breaches still page, they just
    # stop dumping post-mortem bundles)
    incident_enabled: bool = True
    # periodic ring-snapshot cadence for the supervised recorder service
    incident_interval_s: float = 5.0       # CCFD_INCIDENT_INTERVAL_S
    # bounded snapshot ring depth
    incident_ring: int = 64                # CCFD_INCIDENT_RING
    # bundle persistence dir ("" = bundles held in memory only — still
    # served at /incidents, lost on restart); writes are crash-safe
    # (tmp+rename)
    incident_dir: str = ""                 # CCFD_INCIDENT_DIR

    # --- capacity observatory (observability/capacity.py; CR block
    # `capacity:`) ---
    # master switch for the queueing-model plane: per-stage utilization/
    # headroom/bottleneck fitting, predicted-p99 vs observed, /capacity +
    # /capacity/whatif, and the service-curve regression sentinel
    # (CCFD_CAPACITY; 0 is the emergency kill switch — both endpoints 404
    # and no capacity gauges export)
    capacity_enabled: bool = True
    # fit-window tick for the supervised refresh service
    capacity_interval_s: float = 2.0       # CCFD_CAPACITY_INTERVAL_S
    # persisted service-curve baseline file ("" = in-memory baseline only:
    # the sentinel re-arms from live traffic after a restart); writes ride
    # the PR 13 durability seam (tmp+rename+sha256 sidecar)
    capacity_baseline_file: str = ""       # CCFD_CAPACITY_BASELINE
    # sentinel tolerance as a fractional departure from baseline: 1.0
    # fires past 2x (or under 0.5x) the baseline fitted mean
    capacity_regression_tolerance: float = 1.0  # CCFD_CAPACITY_REGRESSION_TOL
    # samples a stage needs before its baseline is captured
    capacity_min_samples: int = 50         # CCFD_CAPACITY_MIN_SAMPLES

    # --- decision provenance audit (observability/audit.py; CR block
    # `audit:`) ---
    # master switch for the per-transaction DecisionRecord plane: the
    # router stamps one compact record per routed transaction at the
    # route seam, queryable at /decisions/<tx_id> and reconstructable
    # after a crash-restore (CCFD_AUDIT; 0 is the emergency kill switch —
    # no records stamped, both exporter endpoints 404)
    audit_enabled: bool = True
    # segmented append-only log dir ("" = ring only: decisions queryable
    # live but NOT reconstructable across a restart)
    audit_dir: str = ""                    # CCFD_AUDIT_DIR
    # bounded query-ring depth (records; oldest evicted, counted)
    audit_ring: int = 65536                # CCFD_AUDIT_RING
    # log segment rotation size and retained-segment count (the PR 13
    # generation-retention idea applied to an append-only log)
    audit_segment_bytes: int = 4 * 1024 * 1024  # CCFD_AUDIT_SEGMENT_BYTES
    audit_segments: int = 8                # CCFD_AUDIT_SEGMENTS
    # supervised flusher cadence: pending records land as one framed
    # block per tick (a crash loses at most one tick of records — the
    # torn tail truncates and counts at the next bring-up)
    audit_flush_interval_s: float = 0.25   # CCFD_AUDIT_FLUSH_INTERVAL_S

    # --- bulk replay & backtest (replay/; CR block `replay:`) ---
    # master switch: arms feature-row capture at the route seam, the
    # verdict tap and the supervised replay worker (CCFD_REPLAY; off by
    # default — capture grows audit records by ~30 floats each)
    replay_enabled: bool = False
    # rows re-produced per replay batch (one cursor commit per batch —
    # the crash-resume granularity) (CCFD_REPLAY_BATCH)
    replay_batch: int = 256
    # verdict-join wait per production attempt before re-producing the
    # unanswered remainder (CCFD_REPLAY_TIMEOUT_S)
    replay_timeout_s: float = 10.0
    # re-production attempts per batch beyond the first; bulk rows shed
    # under live load come back on the next attempt
    # (CCFD_REPLAY_RETRIES)
    replay_retries: int = 3
    # fraction of the adaptive admission budget bulk/replay work may
    # occupy while a window runs — the zero-live-SLO-impact guarantee
    # (CCFD_REPLAY_BULK_CEILING)
    replay_bulk_ceiling: float = 0.5
    # pacing in rows/second; 0 saturates the bulk share
    # (CCFD_REPLAY_PACING)
    replay_pacing_rows_s: float = 0.0
    # durable-cursor directory ("" = resume disabled: a killed window
    # restarts from its first row) (CCFD_REPLAY_DIR)
    replay_dir: str = ""

    # --- durable-state integrity (runtime/durability.py; CR block
    # `durability:`) ---
    # generations retained per single-file artifact (lineage, recovery
    # cuts, engine snapshots, usertask/drift npz): a corrupt live file
    # quarantines to *.corrupt and the newest verifiable generation
    # serves instead (CCFD_STORAGE_RETAIN; 0 disables retention — reads
    # then fail hard to cold-start on corruption)
    storage_retain: int = 3
    # fsync before every atomic rename (CCFD_STORAGE_FSYNC; 0 trades
    # host-crash durability for write latency — process-crash safety is
    # kept either way)
    storage_fsync: bool = True
    # startup sweep of orphaned *.tmp files a crash mid-write leaves
    # behind (CCFD_STORAGE_SWEEP; counted ccfd_storage_tmp_swept_total)
    storage_sweep: bool = True
    # standing storage-fault plan (CCFD_STORAGE_FAULTS,
    # "bitrot;torn_write:rate=0.5;slow_disk:ms=10" — runtime/faults.py
    # storage faults, injected at the durability seam every persistent
    # writer/reader shares). "" = none. The chaos CR block's
    # `storage_faults` option is the storm-scheduled form.
    storage_faults_spec: str = ""

    # --- device self-healing (runtime/heal.py; CR block `heal:`) ---
    # master switch for the DeviceSupervisor: per-device health state
    # machine (HEALTHY -> SUSPECT -> QUARANTINED -> PROBATION), canary
    # dispatches, the heal ladder and warm re-promotion (CCFD_HEAL; 0 is
    # the emergency kill switch — the router ladder then falls back to
    # breaker-only device gating)
    heal_enabled: bool = True
    # supervision tick (canary cadence while healthy; heal-ladder poll
    # while quarantined)
    heal_interval_s: float = 5.0           # CCFD_HEAL_INTERVAL_S
    # hard deadline for one canary dispatch (rides the PR 6
    # bounded_dispatch watchdog; a hung canary is killed, counted, and
    # counts as a strike)
    heal_canary_deadline_ms: float = 250.0  # CCFD_HEAL_CANARY_DEADLINE_MS
    # consecutive strike-bearing ticks before SUSPECT escalates to
    # QUARANTINED (1 = quarantine on the first bad tick)
    heal_suspect_strikes: int = 2          # CCFD_HEAL_SUSPECT_STRIKES
    # consecutive canary+parity passes PROBATION requires before the warm
    # re-promotion flip returns serving to the device
    heal_probation_canaries: int = 3       # CCFD_HEAL_PROBATION_CANARIES
    # host-vs-device score-parity tolerance for the re-promotion gate
    # (max abs probability delta; bf16-vs-f32 sits well under 0.05)
    heal_parity_tol: float = 0.05          # CCFD_HEAL_PARITY_TOL
    # allocator pressure ratio (bytes_in_use / bytes_limit) treated as
    # OOM-pressure evidence
    heal_oom_ratio: float = 0.92           # CCFD_HEAL_OOM_RATIO
    # serving-stage XLA compiles per second treated as a compile storm
    heal_compile_storm_per_s: float = 2.0  # CCFD_HEAL_COMPILE_STORM_PER_S
    # heal-ladder backoff: jittered exponential from base to cap between
    # attempts (canary retry -> backend reinit -> scorer respawn)
    heal_backoff_base_s: float = 0.5       # CCFD_HEAL_BACKOFF_BASE_S
    heal_backoff_cap_s: float = 30.0       # CCFD_HEAL_BACKOFF_CAP_S
    # flap hysteresis: a re-quarantine inside this window of the last
    # re-promotion starts the backoff ladder deeper each round
    heal_flap_window_s: float = 60.0       # CCFD_HEAL_FLAP_WINDOW_S
    # standing device-fault plan (CCFD_DEVICE_FAULTS,
    # "device_hang:ms=400;put_fail" — runtime/faults.py device faults,
    # injected at the scorer dispatch / staging-put / compile seams).
    # "" = none. The chaos CR block's `device_faults` option is the
    # storm-scheduled form of the same syntax.
    device_faults_spec: str = ""

    # --- fleet serving (fleet/; CR block `fleet:`) ---
    # this process's member name within the fleet ("" = member-<pid>);
    # stamps every heartbeat, fleet gauge and ledger entry
    # (CCFD_FLEET_MEMBER)
    fleet_member: str = ""
    # heartbeat HTTP port (0 = ephemeral; fleets pin real ports so the
    # peer list can be written before any process exists)
    # (CCFD_FLEET_HEARTBEAT_PORT)
    fleet_heartbeat_port: int = 0
    # comma-separated peer heartbeat endpoints,
    # "http://127.0.0.1:7101,http://127.0.0.1:7102" (CCFD_FLEET_PEERS)
    fleet_peers: str = ""
    # membership lease: a member whose last heartbeat is older than this
    # is DEAD to the fleet — its partitions re-adopted (bus fence), its
    # admission share redistributed (CCFD_FLEET_TTL_S)
    fleet_ttl_s: float = 3.0
    # gossip tick: peer heartbeat dial + fleet-actuator cadence
    # (CCFD_FLEET_GOSSIP_INTERVAL_S)
    fleet_gossip_interval_s: float = 0.5
    # fleet-wide admission ceiling, split equally over LIVE members and
    # applied as each member's AIMD budget ceiling; 0 = no fleet bound
    # (each member keeps its own overload max_inflight)
    # (CCFD_FLEET_GLOBAL_MAX_INFLIGHT)
    fleet_global_max_inflight: int = 0
    # bus topic carrying per-transaction route dispositions — the fleet's
    # durable conservation ledger (CCFD_FLEET_LEDGER_TOPIC)
    fleet_ledger_topic: str = "fleet.ledger"

    # --- multi-chip mesh serving (parallel/partition.py; CR block
    # `mesh:`) ---
    # device count for the serving/retrain mesh: 1 = single-device (the
    # historical default), 0 = every local device, N = the first N.
    # With >1 the operator builds the named (data, fsdp, tp) mesh, wraps
    # it in a partitioner and serves data-parallel through the live
    # stack (CCFD_MESH_DEVICES)
    mesh_devices: int = 1
    # fsdp / tensor-parallel axis sizes; the data axis absorbs the
    # remainder (CCFD_MESH_FSDP / CCFD_MESH_TP)
    mesh_fsdp: int = 1
    mesh_tp: int = 1
    # param layout: "replicated" (pure data parallel, the serving
    # default) or "rules" (the model family's regex rule table over
    # fsdp/tp — partition.mlp_rules/seq_rules) (CCFD_MESH_PARAM_PARTITION)
    mesh_param_partition: str = "replicated"
    # sequence-parallel attention for the seq family: none | ring |
    # ulysses — shards attention L over the tp axis (the previously
    # dormant ring_attention flag, now operator-selectable)
    # (CCFD_MESH_SEQ_PARALLEL)
    mesh_seq_parallel: str = "none"

    # --- sequence serving (serving/history.py; CR block `scorer.seq_*`) ---
    # HistoryStore stripe count: per-stripe locks keep ParallelRouter
    # workers from convoying on one global lock (CCFD_SEQ_STRIPES)
    seq_stripes: int = 8
    # async dispatches in flight before the scoring loop blocks on the
    # oldest; 0 restores the synchronous chunk loop (CCFD_SEQ_INFLIGHT)
    seq_inflight: int = 2
    # short-sequence ladder: a row whose post-append history depth fits a
    # bucket dispatches through that (bucket, F) executable instead of
    # padding to full L. OFF by default (empty): short windows attend
    # fewer zero-pad tokens than the full-L graph (no padding mask in
    # the attention), so cold-row scores differ between rungs — arm it
    # explicitly for dispatch-bound deployments where that tradeoff is
    # acceptable (CCFD_SEQ_LEN_BUCKETS, comma-separated, e.g. "1,8")
    seq_len_buckets: Sequence[int] = ()

    # --- TPU scorer knobs (new) ---
    model_name: str = "mlp"
    graph_cr: str = ""  # SeldonDeployment-shaped CR file -> serving/graph.py
    compute_dtype: str = "bfloat16"
    batch_sizes: Sequence[int] = (16, 128, 1024, 4096, 16384)
    batch_deadline_ms: float = 2.0
    batch_workers: int = 4  # overlapped dispatches (device-RTT pipelining)
    dynamic_batching: bool = True  # serving-side request coalescing
    native_front: bool = True  # C++ HTTP front when the toolchain allows
    host_tier_rows: int = -1  # request batches of at most this many rows
    # score on the host in numpy instead of the device; -1 = auto, which
    # is off (0) on every backend; >0 = explicit threshold
    dispatch_deadline_ms: float = -1.0  # server-side device-dispatch bound
    # (the reference's SELDON_TIMEOUT applied inside the server): -1 = auto
    # (accelerator backends: seldon_timeout_ms; cpu/mesh: off), 0 = off,
    # >0 = explicit deadline
    # --- fused decision kernel (ops/fused_decision.py, serving/fused.py;
    # CR `scorer.fused_decision`) ---
    # one jitted executable per batch bucket returns (proba, fired rule)
    # together: score, FRAUD_THRESHOLD compare and the vectorizable rule
    # base all on device, ONE transfer back. Off by default: arming it is
    # a routing-semantics statement (device-evaluated rules), even though
    # parity with the staged path is bit-exact (CCFD_FUSED_DECISION)
    fused_decision: bool = False
    # strict = refuse to start (RuntimeError) when the fused plane cannot
    # arm (unvectorizable rules, incompatible scorer) instead of the
    # default warn-and-serve-staged (CCFD_FUSED_DECISION_STRICT)
    fused_decision_strict: bool = False
    serve_host: str = "0.0.0.0"
    serve_port: int = 8000

    def parsed_retention_overrides(self) -> dict[str, int | None]:
        """``"topic:cap,topic2:0"`` -> {topic: cap, topic2: None}; the form
        ``Broker(retention_overrides=)`` takes (0 = retain everything for
        that topic). Malformed entries raise here, at config time, not in
        the broker's append path."""
        out: dict[str, int | None] = {}
        for item in self.bus_retention_overrides.split(","):
            item = item.strip()
            if not item:
                continue
            topic, sep, cap = item.partition(":")
            if not sep or not topic:
                raise ValueError(
                    f"CCFD_BUS_RETENTION_OVERRIDES entry {item!r}: "
                    "expected topic:records")
            n = int(cap)
            out[topic] = n if n > 0 else None
        return out

    def scorer_dispatch_deadline_ms(self) -> float | None:
        """The value serving code passes to ``Scorer(dispatch_deadline_ms=)``.

        Explicit (>= 0) wins; auto (-1) resolves to the SELDON_TIMEOUT bound
        so the server-side deadline tracks the client-side knob, and returns
        it as a number so a programmatically-built Config is honored (the
        scorer still disables the guard itself on cpu/mesh backends when
        handed None — which only happens for scorers built without a Config).
        """
        if self.dispatch_deadline_ms >= 0:
            return self.dispatch_deadline_ms
        import jax

        if jax.default_backend() in ("cpu",):
            return 0.0
        return float(self.seldon_timeout_ms)

    @staticmethod
    def from_env(env: Mapping[str, str] | None = None) -> "Config":
        e = dict(os.environ if env is None else env)
        sizes = e.get("CCFD_BATCH_SIZES", "")
        seq_lb = e.get("CCFD_SEQ_LEN_BUCKETS", "")
        return Config(
            mesh_devices=int(
                e.get("CCFD_MESH_DEVICES", str(Config.mesh_devices))),
            mesh_fsdp=int(e.get("CCFD_MESH_FSDP", str(Config.mesh_fsdp))),
            mesh_tp=int(e.get("CCFD_MESH_TP", str(Config.mesh_tp))),
            mesh_param_partition=e.get(
                "CCFD_MESH_PARAM_PARTITION", Config.mesh_param_partition),
            mesh_seq_parallel=e.get(
                "CCFD_MESH_SEQ_PARALLEL", Config.mesh_seq_parallel),
            seq_stripes=int(e.get("CCFD_SEQ_STRIPES", str(Config.seq_stripes))),
            seq_inflight=int(
                e.get("CCFD_SEQ_INFLIGHT", str(Config.seq_inflight))
            ),
            seq_len_buckets=(
                tuple(int(s) for s in seq_lb.split(",") if s.strip())
                if seq_lb else Config.seq_len_buckets
            ),
            broker_url=e.get("BROKER_URL", Config.broker_url),
            bus_log_dir=e.get("CCFD_BUS_DIR", Config.bus_log_dir),
            bus_fsync=e.get("CCFD_BUS_FSYNC", "") in ("1", "true", "yes"),
            bus_retention_records=int(
                e.get("CCFD_BUS_RETENTION_RECORDS",
                      Config.bus_retention_records)
            ),
            bus_retention_overrides=e.get(
                "CCFD_BUS_RETENTION_OVERRIDES",
                Config.bus_retention_overrides,
            ),
            kafka_topic=e.get("KAFKA_TOPIC", Config.kafka_topic),
            customer_notification_topic=e.get(
                "CUSTOMER_NOTIFICATION_TOPIC", Config.customer_notification_topic
            ),
            customer_response_topic=e.get(
                "CUSTOMER_RESPONSE_TOPIC", Config.customer_response_topic
            ),
            kie_server_url=e.get("KIE_SERVER_URL", Config.kie_server_url),
            seldon_url=e.get("SELDON_URL", Config.seldon_url),
            seldon_endpoint=e.get("SELDON_ENDPOINT", Config.seldon_endpoint),
            seldon_token=e.get("SELDON_TOKEN", Config.seldon_token),
            fraud_threshold=float(e.get("FRAUD_THRESHOLD", str(Config.fraud_threshold))),
            rules_file=e.get("CCFD_RULES", Config.rules_file),
            confidence_threshold=float(
                e.get("CONFIDENCE_THRESHOLD", str(Config.confidence_threshold))
            ),
            seldon_timeout_ms=int(e.get("SELDON_TIMEOUT", str(Config.seldon_timeout_ms))),
            dispatch_deadline_ms=float(
                e.get("CCFD_DISPATCH_DEADLINE_MS", str(Config.dispatch_deadline_ms))
            ),
            seldon_pool_size=int(e.get("SELDON_POOL_SIZE", str(Config.seldon_pool_size))),
            client_retries=int(e.get("CCFD_CLIENT_RETRIES", str(Config.client_retries))),
            faults_spec=e.get("CCFD_FAULTS", Config.faults_spec),
            producer_topic=e.get("topic", Config.producer_topic),
            s3_endpoint=e.get("s3endpoint", Config.s3_endpoint),
            s3_bucket=e.get("s3bucket", Config.s3_bucket),
            filename=e.get("filename", Config.filename),
            bootstrap=e.get("bootstrap", Config.bootstrap),
            access_key_id=e.get("ACCESS_KEY_ID", Config.access_key_id),
            secret_access_key=e.get("SECRET_ACCESS_KEY", Config.secret_access_key),
            customer_reply_timeout_s=float(
                e.get("CCFD_REPLY_TIMEOUT_S", str(Config.customer_reply_timeout_s))
            ),
            low_amount_threshold=float(
                e.get("CCFD_LOW_AMOUNT", str(Config.low_amount_threshold))
            ),
            low_proba_threshold=float(
                e.get("CCFD_LOW_PROBA", str(Config.low_proba_threshold))
            ),
            labels_topic=e.get("CCFD_LABELS_TOPIC", Config.labels_topic),
            audit_topic=e.get("CCFD_AUDIT_TOPIC", Config.audit_topic),
            retrain_batch=int(e.get("CCFD_RETRAIN_BATCH", str(Config.retrain_batch))),
            retrain_min_labels=int(
                e.get("CCFD_RETRAIN_MIN_LABELS", str(Config.retrain_min_labels))
            ),
            shadow_topic=e.get(
                "CCFD_LIFECYCLE_SHADOW_TOPIC", Config.shadow_topic
            ),
            lifecycle_dir=e.get("CCFD_LIFECYCLE_DIR", Config.lifecycle_dir),
            lifecycle_min_labels=int(
                e.get("CCFD_LIFECYCLE_MIN_LABELS",
                      str(Config.lifecycle_min_labels))
            ),
            lifecycle_min_shadow_rows=int(
                e.get("CCFD_LIFECYCLE_MIN_SHADOW_ROWS",
                      str(Config.lifecycle_min_shadow_rows))
            ),
            lifecycle_auc_margin=float(
                e.get("CCFD_LIFECYCLE_AUC_MARGIN",
                      str(Config.lifecycle_auc_margin))
            ),
            lifecycle_max_alert_delta=float(
                e.get("CCFD_LIFECYCLE_MAX_ALERT_DELTA",
                      str(Config.lifecycle_max_alert_delta))
            ),
            lifecycle_max_psi=float(
                e.get("CCFD_LIFECYCLE_MAX_PSI", str(Config.lifecycle_max_psi))
            ),
            lifecycle_canary_weight=float(
                e.get("CCFD_LIFECYCLE_CANARY_WEIGHT",
                      str(Config.lifecycle_canary_weight))
            ),
            lifecycle_canary_min_labels=int(
                e.get("CCFD_LIFECYCLE_CANARY_MIN_LABELS",
                      str(Config.lifecycle_canary_min_labels))
            ),
            lifecycle_min_submit_interval_s=float(
                e.get("CCFD_LIFECYCLE_MIN_SUBMIT_INTERVAL_S",
                      str(Config.lifecycle_min_submit_interval_s))
            ),
            slo_enabled=e.get("CCFD_SLO", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            heal_enabled=e.get("CCFD_HEAL", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            heal_interval_s=float(
                e.get("CCFD_HEAL_INTERVAL_S", str(Config.heal_interval_s))
            ),
            heal_canary_deadline_ms=float(
                e.get("CCFD_HEAL_CANARY_DEADLINE_MS",
                      str(Config.heal_canary_deadline_ms))
            ),
            heal_suspect_strikes=int(
                e.get("CCFD_HEAL_SUSPECT_STRIKES",
                      str(Config.heal_suspect_strikes))
            ),
            heal_probation_canaries=int(
                e.get("CCFD_HEAL_PROBATION_CANARIES",
                      str(Config.heal_probation_canaries))
            ),
            heal_parity_tol=float(
                e.get("CCFD_HEAL_PARITY_TOL", str(Config.heal_parity_tol))
            ),
            heal_oom_ratio=float(
                e.get("CCFD_HEAL_OOM_RATIO", str(Config.heal_oom_ratio))
            ),
            heal_compile_storm_per_s=float(
                e.get("CCFD_HEAL_COMPILE_STORM_PER_S",
                      str(Config.heal_compile_storm_per_s))
            ),
            heal_backoff_base_s=float(
                e.get("CCFD_HEAL_BACKOFF_BASE_S",
                      str(Config.heal_backoff_base_s))
            ),
            heal_backoff_cap_s=float(
                e.get("CCFD_HEAL_BACKOFF_CAP_S",
                      str(Config.heal_backoff_cap_s))
            ),
            heal_flap_window_s=float(
                e.get("CCFD_HEAL_FLAP_WINDOW_S",
                      str(Config.heal_flap_window_s))
            ),
            device_faults_spec=e.get("CCFD_DEVICE_FAULTS",
                                     Config.device_faults_spec),
            fleet_member=e.get("CCFD_FLEET_MEMBER", Config.fleet_member),
            fleet_heartbeat_port=int(
                e.get("CCFD_FLEET_HEARTBEAT_PORT",
                      str(Config.fleet_heartbeat_port))
            ),
            fleet_peers=e.get("CCFD_FLEET_PEERS", Config.fleet_peers),
            fleet_ttl_s=float(
                e.get("CCFD_FLEET_TTL_S", str(Config.fleet_ttl_s))
            ),
            fleet_gossip_interval_s=float(
                e.get("CCFD_FLEET_GOSSIP_INTERVAL_S",
                      str(Config.fleet_gossip_interval_s))
            ),
            fleet_global_max_inflight=int(
                e.get("CCFD_FLEET_GLOBAL_MAX_INFLIGHT",
                      str(Config.fleet_global_max_inflight))
            ),
            fleet_ledger_topic=e.get("CCFD_FLEET_LEDGER_TOPIC",
                                     Config.fleet_ledger_topic),
            audit_enabled=e.get("CCFD_AUDIT", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            audit_dir=e.get("CCFD_AUDIT_DIR", Config.audit_dir),
            audit_ring=int(e.get("CCFD_AUDIT_RING", str(Config.audit_ring))),
            audit_segment_bytes=int(
                e.get("CCFD_AUDIT_SEGMENT_BYTES",
                      str(Config.audit_segment_bytes))
            ),
            audit_segments=int(
                e.get("CCFD_AUDIT_SEGMENTS", str(Config.audit_segments))
            ),
            audit_flush_interval_s=float(
                e.get("CCFD_AUDIT_FLUSH_INTERVAL_S",
                      str(Config.audit_flush_interval_s))
            ),
            replay_enabled=e.get("CCFD_REPLAY", "0").strip().lower()
            in ("1", "true", "yes", "on"),
            replay_batch=int(
                e.get("CCFD_REPLAY_BATCH", str(Config.replay_batch))
            ),
            replay_timeout_s=float(
                e.get("CCFD_REPLAY_TIMEOUT_S", str(Config.replay_timeout_s))
            ),
            replay_retries=int(
                e.get("CCFD_REPLAY_RETRIES", str(Config.replay_retries))
            ),
            replay_bulk_ceiling=float(
                e.get("CCFD_REPLAY_BULK_CEILING",
                      str(Config.replay_bulk_ceiling))
            ),
            replay_pacing_rows_s=float(
                e.get("CCFD_REPLAY_PACING", str(Config.replay_pacing_rows_s))
            ),
            replay_dir=e.get("CCFD_REPLAY_DIR", Config.replay_dir),
            storage_retain=int(
                e.get("CCFD_STORAGE_RETAIN", str(Config.storage_retain))
            ),
            storage_fsync=e.get("CCFD_STORAGE_FSYNC", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            storage_sweep=e.get("CCFD_STORAGE_SWEEP", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            storage_faults_spec=e.get("CCFD_STORAGE_FAULTS",
                                      Config.storage_faults_spec),
            device_enabled=e.get("CCFD_DEVICE", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            incident_enabled=e.get("CCFD_INCIDENT", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            incident_interval_s=float(
                e.get("CCFD_INCIDENT_INTERVAL_S",
                      str(Config.incident_interval_s))
            ),
            incident_ring=int(
                e.get("CCFD_INCIDENT_RING", str(Config.incident_ring))
            ),
            incident_dir=e.get("CCFD_INCIDENT_DIR", Config.incident_dir),
            capacity_enabled=e.get("CCFD_CAPACITY", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            capacity_interval_s=float(
                e.get("CCFD_CAPACITY_INTERVAL_S",
                      str(Config.capacity_interval_s))
            ),
            capacity_baseline_file=e.get("CCFD_CAPACITY_BASELINE",
                                         Config.capacity_baseline_file),
            capacity_regression_tolerance=float(
                e.get("CCFD_CAPACITY_REGRESSION_TOL",
                      str(Config.capacity_regression_tolerance))
            ),
            capacity_min_samples=int(
                e.get("CCFD_CAPACITY_MIN_SAMPLES",
                      str(Config.capacity_min_samples))
            ),
            slo_interval_s=float(
                e.get("CCFD_SLO_INTERVAL_S", str(Config.slo_interval_s))
            ),
            slo_e2e_target_ms=float(
                e.get("CCFD_SLO_E2E_TARGET_MS",
                      str(Config.slo_e2e_target_ms))
            ),
            slo_rest_target_ms=float(
                e.get("CCFD_SLO_REST_TARGET_MS",
                      str(Config.slo_rest_target_ms))
            ),
            slo_objective=float(
                e.get("CCFD_SLO_OBJECTIVE", str(Config.slo_objective))
            ),
            slo_max_error_rate=float(
                e.get("CCFD_SLO_MAX_ERROR_RATE",
                      str(Config.slo_max_error_rate))
            ),
            slo_windows=e.get("CCFD_SLO_WINDOWS", Config.slo_windows),
            slo_fast_burn=float(
                e.get("CCFD_SLO_FAST_BURN", str(Config.slo_fast_burn))
            ),
            slo_transport_floor_ms=float(
                e.get("CCFD_SLO_TRANSPORT_FLOOR_MS",
                      str(Config.slo_transport_floor_ms))
            ),
            trace_sample=float(
                e.get("CCFD_TRACE_SAMPLE", str(Config.trace_sample))
            ),
            trace_slow_ms=float(
                e.get("CCFD_TRACE_SLOW_MS", str(Config.trace_slow_ms))
            ),
            router_workers=int(
                e.get("CCFD_ROUTER_WORKERS", str(Config.router_workers))
            ),
            router_coalesce=e.get("CCFD_ROUTER_COALESCE", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            overload_enabled=e.get("CCFD_OVERLOAD", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            overload_target_ms=float(
                e.get("CCFD_OVERLOAD_TARGET_MS",
                      str(Config.overload_target_ms))
            ),
            overload_serve_target_ms=float(
                e.get("CCFD_OVERLOAD_SERVE_TARGET_MS",
                      str(Config.overload_serve_target_ms))
            ),
            overload_min_inflight=int(
                e.get("CCFD_OVERLOAD_MIN_INFLIGHT",
                      str(Config.overload_min_inflight))
            ),
            overload_max_inflight=int(
                e.get("CCFD_OVERLOAD_MAX_INFLIGHT",
                      str(Config.overload_max_inflight))
            ),
            overload_codel_target_ms=float(
                e.get("CCFD_OVERLOAD_CODEL_TARGET_MS",
                      str(Config.overload_codel_target_ms))
            ),
            overload_serve_codel_target_ms=float(
                e.get("CCFD_OVERLOAD_SERVE_CODEL_TARGET_MS",
                      str(Config.overload_serve_codel_target_ms))
            ),
            overload_rest_queue_rows=int(
                e.get("CCFD_OVERLOAD_REST_QUEUE_ROWS",
                      str(Config.overload_rest_queue_rows))
            ),
            overload_dispatch_deadline_ms=float(
                e.get("CCFD_OVERLOAD_DISPATCH_DEADLINE_MS",
                      str(Config.overload_dispatch_deadline_ms))
            ),
            model_name=e.get("CCFD_MODEL", Config.model_name),
            graph_cr=e.get("CCFD_GRAPH_CR", Config.graph_cr),
            compute_dtype=e.get("CCFD_DTYPE", Config.compute_dtype),
            batch_sizes=tuple(int(s) for s in sizes.split(",")) if sizes else Config.batch_sizes,
            batch_deadline_ms=float(
                e.get("CCFD_BATCH_DEADLINE_MS", str(Config.batch_deadline_ms))
            ),
            batch_workers=int(
                e.get("CCFD_BATCH_WORKERS", str(Config.batch_workers))
            ),
            dynamic_batching=e.get("CCFD_DYNAMIC_BATCHING", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            native_front=e.get("CCFD_NATIVE_FRONT", "1").strip().lower()
            not in ("0", "false", "no", "off"),
            host_tier_rows=int(
                e.get("CCFD_HOST_TIER_ROWS", str(Config.host_tier_rows))
            ),
            fused_decision=e.get("CCFD_FUSED_DECISION", "0").strip().lower()
            in ("1", "true", "yes", "on"),
            fused_decision_strict=e.get(
                "CCFD_FUSED_DECISION_STRICT", "0").strip().lower()
            in ("1", "true", "yes", "on"),
            serve_host=e.get("CCFD_SERVE_HOST", Config.serve_host),
            serve_port=int(e.get("CCFD_SERVE_PORT", str(Config.serve_port))),
        )
