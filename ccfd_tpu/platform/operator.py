"""Platform operator: CR-shaped spec -> running pipeline, in run-book order.

The reference is deployed by an OpenDataHub operator CR whose spec toggles
each platform component (Seldon, Kafka, monitoring, notebooks — reference
deploy/frauddetection_cr.yaml:1-89) followed by a 600-line run-book whose
step order is a dependency sort (reference README.md:44-537; SURVEY.md §3 D:
project → operator → Kafka → Ceph/S3 → model → data → KIE → notification →
router → producer → monitoring). This module is both: a declarative spec
(`PlatformSpec`, loadable from a CR-shaped YAML) and the operator that
brings components up in that topological order with readiness gates between
steps, running every long-lived service under the runtime Supervisor
(restart-on-crash) with health probes and a single Prometheus exporter.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from typing import Any, Mapping

from ccfd_tpu.config import Config


@dataclasses.dataclass(frozen=True)
class ComponentSpec:
    enabled: bool = True
    options: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def opt(self, key: str, default: Any = None) -> Any:
        return self.options.get(key, default)


_COMPONENTS = (
    "store",      # Ceph/S3 analog (L0)
    "bus",        # Strimzi Kafka analog (L2)
    "scorer",     # Seldon model serving (L4)
    "engine",     # KIE server (L5)
    "notify",     # notification service (L6)
    "router",     # Camel router (L3)
    "producer",   # Kafka producer (L1) — one-shot job semantics
    "retrain",    # online retrain (new; BASELINE.json configs[4])
    "investigator",  # investigator simulation working the task queue
                  # (the reference demo's Business Central humans,
                  # README.md:547-581) — trains the user-task model
    "analytics",  # batch analytics + drift (JupyterHub/Spark analog,
                  # reference frauddetection_cr.yaml:7-53)
    "monitoring", # Prometheus exporter (L7)
    "health",     # runtime probes (platform)
    "chaos",      # seeded fault injection (new; no reference analog)
    "tracing",    # distributed tracing + tail sampler (new; round 7)
    "lifecycle",  # model lifecycle: shadow -> canary -> gated promotion
                  # with auto-rollback (new; round 9, lifecycle/)
    "overload",   # overload control: adaptive AIMD admission, priority-
                  # aware shedding, REST 429s (new; runtime/overload.py)
    "slo",        # stage profiler + SLO engine: queueing/service/dispatch
                  # decomposition, burn-rate monitoring, budget ledger
                  # (new; observability/profile.py, observability/slo.py)
    "device",     # device & transfer telemetry: per-device memory gauges,
                  # measured H2D accounting, executable inventory,
                  # /debug/profile capture (new; observability/device.py)
    "incident",   # SLO-breach incident flight recorder: snapshot ring +
                  # schema-validated post-mortem bundles served at
                  # /incidents (new; observability/incident.py)
    "heal",       # device self-healing: per-device health state machine,
                  # canary dispatches, quarantine -> heal ladder -> warm
                  # re-promotion (new; runtime/heal.py)
    "mesh",       # multi-chip partitioning layer: named (data, fsdp, tp)
                  # mesh + partitioner for data-parallel sharded serving
                  # and donated sharded retrain (new; parallel/partition.py;
                  # armed when devices > 1)
    "durability", # durable-state integrity plane: checksummed artifacts,
                  # quarantine + last-good recovery, orphan-tmp sweep,
                  # rules-tier pin when nothing verifies (new;
                  # runtime/durability.py)
    "audit",      # decision provenance plane: one DecisionRecord per
                  # routed transaction stamped at the route seam, ring +
                  # segmented crash-safe log, /decisions endpoints (new;
                  # observability/audit.py)
    "fleet",      # multi-host fleet plane: heartbeat gossip membership,
                  # fleet-wide admission shares, champion-parity
                  # quarantine, per-tx conservation ledger over the
                  # SHARED bus (new; fleet/ — one member per process,
                  # processes spawned by fleet/supervisor.py)
    "replay",     # bulk replay & backtest plane: re-score recorded audit
                  # windows through the live stack under bulk admission,
                  # verdict-parity conservation with classified
                  # divergences, crash-resumable cursor (new; replay/)
    "capacity",   # capacity observatory: queueing model fitted over the
                  # live stage profile — predicted p50/p99, bottleneck
                  # attribution, headroom, what-if evaluation, and a
                  # service-curve regression sentinel (new;
                  # observability/capacity.py)
)


@dataclasses.dataclass(frozen=True)
class PlatformSpec:
    components: Mapping[str, ComponentSpec]
    cfg: Config

    @staticmethod
    def from_cr(cr: Mapping[str, Any], cfg: Config | None = None) -> "PlatformSpec":
        """Parse a CR-shaped mapping: top-level ``spec`` holds one block per
        component (the frauddetection_cr.yaml shape), each with ``enabled``
        plus free-form options."""
        spec = cr.get("spec", cr)
        comps: dict[str, ComponentSpec] = {}
        for name in _COMPONENTS:
            block = spec.get(name, {})
            if isinstance(block, bool):
                block = {"enabled": block}
            comps[name] = ComponentSpec(
                # absent blocks default on, EXCEPT: producer/store (traffic
                # and data sources are explicit choices), chaos (fault
                # injection is opt-in), the investigator simulation
                # (a real deployment has real humans on the console), and
                # fleet (a single-process platform is the default shape)
                enabled=bool(
                    block.get(
                        "enabled",
                        name not in ("producer", "store", "chaos",
                                     "investigator", "fleet", "replay"),
                    )
                ),
                options={k: v for k, v in block.items() if k != "enabled"},
            )
        return PlatformSpec(components=comps, cfg=cfg or Config.from_env())

    @staticmethod
    def from_yaml(path: str, cfg: Config | None = None) -> "PlatformSpec":
        import yaml

        with open(path) as f:
            return PlatformSpec.from_cr(yaml.safe_load(f) or {}, cfg=cfg)

    def component(self, name: str) -> ComponentSpec:
        return self.components.get(name, ComponentSpec(enabled=False))


class Platform:
    """Brings a PlatformSpec up/down; owns every component's lifecycle."""

    def __init__(self, spec: PlatformSpec):
        self.spec = spec
        self.cfg = spec.cfg
        self.registries: dict[str, Any] = {}
        self.supervisor = None
        self.broker = None
        self.scorer = None
        self.engine = None
        self.usertask_model = None
        self.engine_server = None
        self.engine_port = None
        self.store_server = None
        self.prediction_server = None
        self.prediction_host = "127.0.0.1"
        self.prediction_port = 0
        self.exporter = None
        self.health_server = None
        self.chaos = None
        self.fault_plan = None  # runtime/faults.FaultPlan when configured
        self.trace_sink = None  # observability/trace.SpanSink when enabled
        self.profiler = None    # observability/profile.StageProfiler
        self.slo = None         # observability/slo.SLOEngine when enabled
        self.device = None      # observability/device.DeviceTelemetry
        self.recorder = None    # observability/incident.FlightRecorder
        self.capacity = None    # observability/capacity.CapacityModel
        self.heal = None        # runtime/heal.DeviceSupervisor
        self.mesh = None        # jax.sharding.Mesh when mesh serving armed
        self.partitioner = None  # parallel/partition.Partitioner
        self.device_fault_plan = None  # runtime/faults.DeviceFaultPlan
        self._device_storm_driven = False  # ChaosMonkey owns its duty cycle
        self.storage_fault_plan = None  # runtime/faults.StorageFaultPlan
        self._storage_storm_driven = False
        self.storage_gate = None  # runtime/durability.StoragePinGate
        self.audit = None       # observability/audit.AuditLog when enabled
        self.fleet = None       # fleet/member.FleetMember when enabled
        self.replay = None      # replay/service.ReplayService when enabled
        self.replay_tap = None  # replay/service.ReplayVerdictTap (replay on)
        self.fleet_ledger = None  # fleet/ledger.FleetLedgerTap (fleet on)
        self.fused_decision = None  # serving/fused.FusedDecisionScorer
        self._overload = None   # runtime/overload.OverloadControl (router)
        self.lifecycle = None   # lifecycle.LifecycleController when enabled
        self.router = None
        self.investigator = None
        self.recovery = None  # CheckpointCoordinator when crash_recovery on
        self._engine_factory = None
        self._producer_done = threading.Event()
        self._broker_is_client = False  # bus.url: RemoteBroker/adapter
        self._up = False

    # -- bring-up, in the run-book's dependency order ---------------------
    def up(self, wait_ready_s: float = 30.0) -> "Platform":
        """Build the platform: one ``startup.platform`` phase of the
        process's start-up trace (``observability/trace.py``), whose end
        is the trace's ``ready()``; then ``ccfd_startup_seconds{phase}``
        is set from its closed spans and the trace is handed to the
        platform's span sink, which serves it at ``/traces/<id>``."""
        if self._up:
            return self
        from ccfd_tpu.observability import trace

        record = trace.startup
        with record.phase("startup.platform"):
            self._build(wait_ready_s)
        record.ready()
        seconds = self._registry("startup").gauge(
            "ccfd_startup_seconds",
            "seconds of this process's start-up by phase (process start "
            "to ready = total; head = before the program's first phase)")
        for name, secs in record.seconds().items():
            seconds.set(secs, labels={"phase": name})
        if self.trace_sink is not None:
            for span in record.spans():
                self.trace_sink.add(span)
        return self

    def _build(self, wait_ready_s: float) -> None:
        from ccfd_tpu.runtime.supervisor import Supervisor

        spec, cfg = self.spec, self.cfg
        self.supervisor = Supervisor()

        # 0. network fault plan (runtime/faults.py): CR `chaos.faults`
        # (ONLY when the chaos component is enabled — chaos is always
        # opt-in, and a disabled block must not leave standing faults
        # wired into production edges) or the CCFD_FAULTS env (its own
        # explicit opt-in). A standing (env) plan starts ACTIVE; a
        # storm-scheduled plan (chaos.fault_interval_s) starts inactive
        # and the ChaosMonkey drives its duty cycle. Edges wire up as
        # each component builds below.
        chaos_spec = spec.component("chaos")
        fault_text = (chaos_spec.opt("faults", "")
                      if chaos_spec.enabled else "") or cfg.faults_spec
        storm_interval = (chaos_spec.opt("fault_interval_s", None)
                          if chaos_spec.enabled else None)
        if fault_text:
            from ccfd_tpu.runtime.faults import FaultPlan

            self.fault_plan = FaultPlan.from_string(
                fault_text,
                seed=int(chaos_spec.opt("seed", 0)),
                active=storm_interval is None,
            )
        # device faults (runtime/faults.py DeviceFaultPlan): same opt-in
        # rules as edge faults — CR `chaos.device_faults` (chaos enabled)
        # or the CCFD_DEVICE_FAULTS env. Installed process-wide because
        # the seams (scorer dispatch / staging put / telemetry overlay)
        # sit inside helpers no injector proxy can wrap.
        cr_dev_text = (chaos_spec.opt("device_faults", "")
                       if chaos_spec.enabled else "")
        dev_fault_text = cr_dev_text or cfg.device_faults_spec
        # only a CR-configured plan under a storm interval is duty-cycled
        # by the ChaosMonkey; a standing CCFD_DEVICE_FAULTS env plan stays
        # ACTIVE — an unrelated edge-storm schedule must not disarm it
        self._device_storm_driven = bool(cr_dev_text) and \
            storm_interval is not None
        if dev_fault_text:
            from ccfd_tpu.runtime.faults import (
                DeviceFaultPlan,
                install_device_faults,
            )

            self.device_fault_plan = DeviceFaultPlan.from_string(
                dev_fault_text,
                seed=int(chaos_spec.opt("seed", 0)),
                active=not self._device_storm_driven,
            )
            install_device_faults(self.device_fault_plan)
        # storage faults (runtime/faults.py StorageFaultPlan): same opt-in
        # and storm rules — CR `chaos.storage_faults` or CCFD_STORAGE_FAULTS.
        # Installed process-wide: the seam (durability.atomic_write_bytes)
        # sits inside constructors and module helpers.
        cr_sto_text = (chaos_spec.opt("storage_faults", "")
                       if chaos_spec.enabled else "")
        sto_fault_text = cr_sto_text or cfg.storage_faults_spec
        self._storage_storm_driven = bool(cr_sto_text) and \
            storm_interval is not None
        if sto_fault_text:
            from ccfd_tpu.runtime.faults import (
                StorageFaultPlan,
                install_storage_faults,
            )

            self.storage_fault_plan = StorageFaultPlan.from_string(
                sto_fault_text,
                seed=int(chaos_spec.opt("seed", 0)),
                active=not self._storage_storm_driven,
            )
            install_storage_faults(self.storage_fault_plan)

        # 0b. durable-state integrity plane (runtime/durability.py): the
        # CR `durability:` block overlays the CCFD_STORAGE_* knobs, the
        # ccfd_storage_* counters land in a scraped registry, and the
        # StoragePinGate (rules-tier pin when NO params generation
        # verifies) is created here so the lifecycle controller (step 7)
        # can arm it before the router (step 6c... order: router then
        # heal compose it into the heal-gate seam).
        from ccfd_tpu.runtime import durability

        dur_spec = spec.component("durability")
        if dur_spec.enabled:
            durability.configure(
                retain=int(dur_spec.opt("retain", cfg.storage_retain)),
                fsync=bool(dur_spec.opt("fsync", cfg.storage_fsync)),
                sweep=bool(dur_spec.opt("sweep", cfg.storage_sweep)),
            )
            durability.bind_registry(self._registry("storage"))
            self.storage_gate = durability.StoragePinGate(
                registry=self._registry("storage"))
        else:
            # legacy mode: no retention copies, no sweep, no rules pin —
            # reads still verify frames they find (integrity itself has
            # no off switch; a checksum mismatch is never servable)
            durability.configure(retain=0, sweep=False)

        # 0a. overload control (runtime/overload.py): the CR `overload:`
        # block overlays the CCFD_OVERLOAD_* env KNOBS once, here, so the
        # scorer's REST admission gate (built in step 3) and the router's
        # adaptive budget (step 6) read the same resolved values.
        # Precedence for the on/off switch: either side can DISABLE the
        # plane (CR `enabled: false` OR env CCFD_OVERLOAD=0) — the env
        # form is the emergency kill switch and a CR cannot override it
        # (an absent CR block is indistinguishable from a default-enabled
        # one, so "CR re-enables over env" is not expressible anyway).
        ov_spec = spec.component("overload")
        ov_overrides: dict[str, Any] = {}
        if not ov_spec.enabled:
            ov_overrides["overload_enabled"] = False
        else:
            for opt, field in (
                ("target_ms", "overload_target_ms"),
                ("serve_target_ms", "overload_serve_target_ms"),
                ("min_inflight", "overload_min_inflight"),
                ("max_inflight", "overload_max_inflight"),
                ("codel_target_ms", "overload_codel_target_ms"),
                ("serve_codel_target_ms", "overload_serve_codel_target_ms"),
                ("rest_queue_rows", "overload_rest_queue_rows"),
                ("dispatch_deadline_ms", "overload_dispatch_deadline_ms"),
            ):
                if ov_spec.opt(opt) is not None:
                    ov_overrides[field] = type(getattr(cfg, field))(
                        ov_spec.opt(opt))
        if ov_overrides:
            self.cfg = cfg = dataclasses.replace(cfg, **ov_overrides)

        # 0b. distributed tracing (observability/trace.py): ONE tail-
        # sampling span sink shared by every component tracer; the tracers
        # themselves are built per component below, registry-injected so
        # span latency lands on the SAME scraped registries the exporter
        # serves (the old utils/tracing global wrote to a private registry
        # nothing scraped). Sampler knobs: CR `tracing.sample`/`slow_ms`
        # over the CCFD_TRACE_SAMPLE / CCFD_TRACE_SLOW_MS env defaults.
        tr_spec = spec.component("tracing")
        if tr_spec.enabled:
            from ccfd_tpu.observability.trace import SpanSink

            self.trace_sink = SpanSink(
                sample=float(tr_spec.opt("sample", cfg.trace_sample)),
                slow_s=float(tr_spec.opt("slow_ms", cfg.trace_slow_ms)) / 1e3,
                max_retained=int(tr_spec.opt("max_retained", 256)),
                registry=self._registry("tracing"),
            )
            if tr_spec.opt("json_logs", True):
                # trace-correlated structured logs for the framework's own
                # logger namespace (observability/slog.py); the embedding
                # application's root logger is left alone
                from ccfd_tpu.observability import slog

                slog.configure("platform")

        # 0c. stage profiler (observability/profile.py): ONE profiler for
        # the whole platform, fed directly by the router (bus queue,
        # decode/route service, scorer dispatch) and the serving batcher
        # (REST wait/dispatch), plus span ingestion off the tail sampler
        # for the stages with no hot-path feed (producer, engine REST,
        # notify, serving). Exported live at the exporter's /profile —
        # the machine-readable planner input (ROADMAP item 3). The SLO
        # engine over it is built in step 7c, once the components whose
        # histograms it reads exist. CCFD_SLO=0 (or CR slo.enabled:
        # false) disables the whole plane.
        slo_spec = spec.component("slo")
        if slo_spec.enabled and cfg.slo_enabled:
            from ccfd_tpu.observability.profile import StageProfiler

            self.profiler = StageProfiler(
                registry=self._registry("slo"),
                overload_registry=self._registry("router"),
            )
            if self.trace_sink is not None:
                self.trace_sink.add_listener(self.profiler.on_span)
            if bool(slo_spec.opt("compile_events", True)):
                self.profiler.arm_compile_listener()

        # 0d. device & transfer telemetry (observability/device.py): ONE
        # plane for the whole platform — the scorer built below stages
        # through it (measured H2D), the exporter refreshes its per-device
        # memory gauges on every scrape, and the SLO engine's budget
        # ledger (7c) reads its transfer digest in place of the h2d
        # reservation. CCFD_DEVICE=0 (or CR device.enabled: false) kills
        # the plane; everything downstream then keeps the pre-telemetry
        # fallbacks.
        dev_spec = spec.component("device")
        if dev_spec.enabled and cfg.device_enabled:
            from ccfd_tpu.observability.device import DeviceTelemetry

            self.device = DeviceTelemetry(registry=self._registry("device"))

        # 0f. decision provenance plane (observability/audit.py): ONE
        # AuditLog shared by every router worker — the route seam stamps
        # one DecisionRecord per routed transaction into a bounded ring
        # plus (with a dir) a segmented crash-safe log written through
        # the durability seam's framing. Built before the router so the
        # workers construct against it; the lifecycle (3b) wires the
        # per-batch lineage sample and the incident recorder (7d) the
        # open-incident join. CCFD_AUDIT=0 (or CR audit.enabled: false)
        # kills the plane: no records stamped, /decisions 404s.
        aud_spec = spec.component("audit")
        if aud_spec.enabled and cfg.audit_enabled:
            from ccfd_tpu.observability.audit import AuditLog
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            self.audit = AuditLog(
                dir=(aud_spec.opt("dir", cfg.audit_dir) or None),
                max_records=int(aud_spec.opt("ring", cfg.audit_ring)),
                segment_bytes=int(
                    aud_spec.opt("segment_bytes", cfg.audit_segment_bytes)),
                retain_segments=int(
                    aud_spec.opt("segments", cfg.audit_segments)),
                registry=self._registry("audit"),
            )
            flush_s = float(
                aud_spec.opt("flush_interval_s", cfg.audit_flush_interval_s))
            self.supervisor.add_thread_service(
                "audit",
                lambda: self.audit.run(interval_s=flush_s),
                self.audit.stop,
                policy=RestartPolicy.ALWAYS,
                reset=self.audit.reset,
            )

        # 0e. multi-chip partitioning layer (parallel/partition.py): the
        # named (data, fsdp, tp) mesh + partitioner the serving/retrain
        # components below build AGAINST — constructed first so the scorer
        # (step 3) shards its params/batches from birth and the trainer
        # (step 7) jits its donated sharded step through the same layout.
        # Armed only when the resolved device count is > 1; a 1-device
        # platform keeps the historical unsharded path byte-for-byte.
        if spec.component("mesh").enabled:
            self._up_mesh(spec.component("mesh"))

        # 1. store (Ceph/S3, README.md:136-269) — serves the dataset
        if spec.component("store").enabled:
            self._up_store()

        # 2. bus (Kafka, README.md:87-134). With a `bus.url` (or a
        # non-inproc BROKER_URL) the platform is a CLIENT of a shared
        # networked bus — the fleet shape: N operator processes over ONE
        # broker, partition ownership via the bus's consumer groups.
        # Without one, the historical in-process Broker.
        if spec.component("bus").enabled:
            bus_spec = spec.component("bus")
            bus_url = bus_spec.opt("url", "") or (
                "" if cfg.broker_url.startswith("inproc")
                else cfg.broker_url)
            if bus_url:
                from ccfd_tpu.bus.client import broker_from_url

                self._broker_is_client = True
                self.broker = broker_from_url(
                    bus_url, registry=self._registry("bus"))
                if self.broker is None:
                    raise ValueError(
                        f"bus.url {bus_url!r}: expected http:// (networked "
                        "bus server) or kafka:// (real cluster)")
            else:
                from ccfd_tpu.bus.broker import Broker

                log_dir = bus_spec.opt("log_dir", "") or None
                self.broker = Broker(
                    default_partitions=int(bus_spec.opt("partitions", 3)),
                    log_dir=log_dir,
                    fsync=bool(bus_spec.opt("fsync", False)),
                )
        else:
            needs_bus = [
                n for n in ("engine", "notify", "router", "retrain",
                            "analytics", "producer")
                if spec.component(n).enabled
            ]
            if needs_bus:
                raise ValueError(
                    f"bus disabled in CR but required by: {needs_bus}"
                )

        # 3. model serving (Seldon, README.md:271-301)
        if spec.component("scorer").enabled:
            self._up_scorer()

        # 3b. model lifecycle (lifecycle/): governs how retrain candidates
        #     reach the scorer — shadow -> canary -> gated promotion with
        #     auto-rollback. Built BEFORE the router so the router's score
        #     lane can be wrapped with the shadow tap + canary gate, and
        #     before retrain so the trainer hands candidates to it. Needs
        #     a local scorer with a host forward (the challenger slot
        #     scores off-device by design) and the bus (shadow pairs +
        #     label joins ride topics).
        if (spec.component("lifecycle").enabled
                and self.scorer is not None and self.broker is not None):
            self._up_lifecycle()

        # 4. process engine (KIE, README.md:345-408)
        if spec.component("engine").enabled:
            self._up_engine()

        # 5. notification service (README.md:410-422)
        if spec.component("notify").enabled:
            self._up_notify()

        # 6. router (README.md:424-459)
        if spec.component("router").enabled:
            self._up_router()

        # 6b. engine crash recovery (engine opt `crash_recovery`): aligned
        #     checkpoints + bus-offset-rewind restore, the stronger story
        #     than the file-based `state_file` persistence — crash-
        #     consistent with the bus, and chaos-killable as a supervised
        #     service (runtime/recovery.py; drilled by tools/chaos_soak.py)
        if (spec.component("engine").enabled
                and spec.component("engine").opt("crash_recovery", False)
                and self.engine is not None and self.router is not None):
            self._up_crash_recovery()

        # 6c. investigator simulation (the demo's Business Central humans,
        #     reference README.md:547-581) — drains the task queue and
        #     feeds the user-task model its training labels
        if (spec.component("investigator").enabled
                and self.engine is not None):
            self._up_investigator()

        # 7. online retrain (new capability; BASELINE.json configs[4]) —
        #    the trainer's step is the MLP's; a history-aware seq scorer
        #    cannot consume it (and a hot-swap would publish MLP params
        #    into the seq jit), so retrain is skipped for model=seq
        if spec.component("retrain").enabled and self.scorer is not None:
            from ccfd_tpu.serving.history import SeqScorer

            if isinstance(self.scorer, SeqScorer):
                logging.getLogger(__name__).warning(
                    "retrain enabled but scorer model is 'seq': online "
                    "retrain targets the MLP family; skipping retrain"
                )
            else:
                self._up_retrain()

        # 7b. analytics / drift monitor (notebooks+spark analog,
        #     reference frauddetection_cr.yaml:7-53)
        if spec.component("analytics").enabled:
            self._up_analytics()

        # 7c. SLO engine (observability/slo.py): built once the components
        #     whose histograms/counters it reads exist. Declarative specs
        #     from the CR `slo:` block (or the CCFD_SLO_* defaults:
        #     e2e-p99 / rest-p99 / error-rate), multi-window burn-rate
        #     gauges + breach alerts, and the REST-path budget ledger over
        #     the stage profiler. Runs as a supervised service.
        if self.profiler is not None:
            from ccfd_tpu.observability.slo import SLOEngine
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            self.slo = SLOEngine.from_config(
                cfg, self.registries, self._registry("slo"),
                profiler=self.profiler, options=slo_spec.options,
                telemetry=self.device,
            )
            interval = float(slo_spec.opt("interval_s", cfg.slo_interval_s))
            self.supervisor.add_thread_service(
                "slo",
                lambda: self.slo.run(interval_s=interval),
                self.slo.stop,
                policy=RestartPolicy.ALWAYS,
                reset=self.slo.reset,
            )

        # 7c2. capacity observatory (observability/capacity.py): the
        #      queueing model fitted over the live stage profile —
        #      predicted p50/p99 per stage and end-to-end, bottleneck
        #      attribution + headroom, what-if evaluation over the PR 6
        #      actuator vocabulary, and a service-curve regression
        #      sentinel persisting its baseline through the durability
        #      seam. Served at /capacity (+ /capacity/whatif) below.
        #      CCFD_CAPACITY=0 (or CR capacity.enabled: false) kills it.
        cap_spec = spec.component("capacity")
        if (cap_spec.enabled and cfg.capacity_enabled
                and self.profiler is not None):
            from ccfd_tpu.observability.capacity import CapacityModel
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            self.capacity = CapacityModel(
                self.profiler,
                registry=self._registry("capacity"),
                baseline_path=(
                    cap_spec.opt("baseline_file", cfg.capacity_baseline_file)
                    or None),
                regression_tolerance=float(
                    cap_spec.opt("regression_tolerance",
                                 cfg.capacity_regression_tolerance)),
                min_samples=int(
                    cap_spec.opt("min_samples", cfg.capacity_min_samples)),
            )
            # seed the what-if evaluator with the live actuator values so
            # "what if workers=N" is a delta against what actually runs
            workers = int(self.spec.component("router")
                          .opt("workers", cfg.router_workers))
            self.capacity.set_actuators(
                workers=max(1, workers),
                batch=(max(cfg.batch_sizes) if cfg.batch_sizes else None),
                deadline_ms=cfg.batch_deadline_ms,
                max_inflight=(int(self._overload.budget.limit)
                              if self._overload is not None else None),
            )
            cap_interval = float(
                cap_spec.opt("interval_s", cfg.capacity_interval_s))
            self.supervisor.add_thread_service(
                "capacity",
                lambda: self.capacity.run(interval_s=cap_interval),
                self.capacity.stop,
                policy=RestartPolicy.ALWAYS,
                reset=self.capacity.reset,
            )

        # 7d. incident flight recorder (observability/incident.py): the
        #     bounded snapshot ring runs as a supervised service; the SLO
        #     engine's breach edge dumps a schema-validated bundle, and a
        #     dispatch-watchdog kill snapshots into the ring. Served at
        #     the exporter's /incidents endpoints below. CCFD_INCIDENT=0
        #     (or CR incident.enabled: false) kills the plane.
        inc_spec = spec.component("incident")
        if inc_spec.enabled and cfg.incident_enabled:
            from ccfd_tpu.observability.incident import FlightRecorder
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            self.recorder = FlightRecorder(
                self.registries,
                registry=self._registry("incident"),
                profiler=self.profiler,
                telemetry=self.device,
                sink=self.trace_sink,
                ring=int(inc_spec.opt("ring", cfg.incident_ring)),
                out_dir=(inc_spec.opt("dir", cfg.incident_dir) or None),
                max_bundles=int(inc_spec.opt("max_bundles", 16)),
                timeout_debounce_s=float(
                    inc_spec.opt("timeout_debounce_s", 2.0)),
                audit=self.audit,  # bundles embed in-flight decisions
                capacity=self.capacity,  # + capacity snapshot at breach
            )
            if self.slo is not None:
                self.slo.add_breach_listener(self.recorder.on_breach)
            if self.audit is not None:
                # open-incident join for the decision records: while any
                # SLO is in the breaching state, routed transactions are
                # stamped with the newest bundle's id — "this score was
                # made DURING inc-0007" is a query, not a log dig. With
                # no burn-rate state (CCFD_SLO=0) there is no notion of
                # "still open", so nothing links (documented).
                rec, eng = self.recorder, self.slo

                def _open_incident():
                    if eng is None or not eng.any_breaching():
                        return None
                    return rec.last_incident_id()

                self.audit.incident_fn = _open_incident
            if self._overload is not None:
                self._overload.recorder = self.recorder
            if self.storage_gate is not None:
                # storage quarantines dump a post-mortem bundle too
                from ccfd_tpu.runtime import durability

                durability.set_recorder(self.recorder.incident)
            inc_interval = float(
                inc_spec.opt("interval_s", cfg.incident_interval_s))
            self.supervisor.add_thread_service(
                "incident",
                lambda: self.recorder.run(interval_s=inc_interval),
                self.recorder.stop,
                policy=RestartPolicy.ALWAYS,
                reset=self.recorder.reset,
            )

        # 7e. device heal supervisor (runtime/heal.py): the health state
        #     machine over the local scorer — canary dispatches bounded by
        #     the router's PR 6 watchdog, quarantine pins the router's
        #     degradation ladder to the host tier, the heal ladder's
        #     respawn rung restores the lifecycle champion checkpoint, and
        #     re-promotion is warm (full executable inventory precompiled
        #     under the heal.warm label). Default on with a local scorer;
        #     CCFD_HEAL=0 (or CR heal.enabled: false) kills the plane.
        heal_spec = spec.component("heal")
        if (heal_spec.enabled and cfg.heal_enabled
                and self.scorer is not None):
            self._up_heal(heal_spec)

        # 8. monitoring (README.md:487-537)
        if spec.component("monitoring").enabled:
            from ccfd_tpu.metrics.exporter import MetricsExporter

            mon = spec.component("monitoring")
            self.exporter = MetricsExporter(
                self.registries,
                host=mon.opt("host", "127.0.0.1"),
                port=int(mon.opt("port", 0)),
                sink=self.trace_sink,  # /traces + /traces/<id> endpoints
                profiler=self.profiler,  # /profile StageProfile endpoint
                telemetry=self.device,  # device gauges + /debug endpoints
                recorder=self.recorder,  # /incidents + /incidents/<id>
                audit=self.audit,  # /decisions + /decisions/<tx_id>
                capacity=self.capacity,  # /capacity + /capacity/whatif
                health=self._health_verdict,  # /healthz readiness rollup
            ).start()
            self._wire_memory_probes()

        if spec.component("health").enabled:
            from ccfd_tpu.runtime.health import HealthServer

            h = spec.component("health")
            self.health_server = HealthServer(
                self.supervisor,
                host=h.opt("host", "127.0.0.1"),
                port=int(h.opt("port", 0)),
            ).start()

        # 8b. fleet member plane (fleet/member.py): heartbeat endpoint +
        #     gossip loop + fleet actuators (admission rescale, parity
        #     quarantine, aggregator duty). Built after everything it
        #     observes (router, overload, scorer, recorder) and before
        #     the supervisor starts so the gossip loop runs supervised.
        fl_spec = spec.component("fleet")
        if fl_spec.enabled and self.broker is not None:
            self._up_fleet(fl_spec)

        self.supervisor.start()
        if not self.supervisor.wait_ready(timeout_s=wait_ready_s):
            raise TimeoutError(
                f"platform not ready after {wait_ready_s}s: "
                f"{self.supervisor.status()}"
            )

        # 9. producer last (README.md:461-485) — starts the traffic
        if spec.component("producer").enabled:
            self._up_producer()

        # 10. chaos (opt-in; no reference analog): seeded fault injection
        # over the supervised services, so recovery machinery is exercised
        # continuously instead of trusted
        if spec.component("chaos").enabled:
            from ccfd_tpu.runtime.chaos import ChaosMonkey

            c = spec.component("chaos")
            targets = c.opt("targets", None)
            self.chaos = ChaosMonkey(
                self.supervisor,
                interval_s=float(c.opt("interval_s", 30.0)),
                seed=int(c.opt("seed", 0)),
                # targets: [] is a valid choice — storms only, no kills
                targets=(list(targets) if targets is not None else None),
                registry=self._registry("chaos"),
                fault_plan=self.fault_plan,
                device_fault_plan=(self.device_fault_plan
                                   if self._device_storm_driven else None),
                storage_fault_plan=(self.storage_fault_plan
                                    if self._storage_storm_driven else None),
                fault_interval_s=(float(c.opt("fault_interval_s"))
                                  if c.opt("fault_interval_s") else None),
                fault_duration_s=float(c.opt("fault_duration_s", 2.0)),
            ).start()

        self._up = True

    # -- per-component builders -------------------------------------------
    def _registry(self, name: str):
        from ccfd_tpu.metrics.prom import Registry

        if name not in self.registries:
            self.registries[name] = Registry()
            if self.exporter is not None:  # registries created post-start
                self.exporter.add(name, self.registries[name])
        return self.registries[name]

    def _tracer(self, component: str):
        """Component tracer wired to the component's SCRAPED registry and
        the shared tail-sampling sink; None with tracing disabled (every
        consumer treats a None tracer as 'tracing off')."""
        if self.trace_sink is None:
            return None
        from ccfd_tpu.observability.trace import Tracer

        return Tracer(self._registry(component), component=component,
                      sink=self.trace_sink)

    def _up_store(self) -> None:
        from ccfd_tpu.data.ccfd import load_dataset, to_csv_bytes
        from ccfd_tpu.store.objectstore import Credentials, ObjectStore
        from ccfd_tpu.store.server import StoreServer

        c = self.spec.component("store")
        cfg = self.cfg
        store = ObjectStore(root=c.opt("root"))
        store.add_credentials(
            Credentials(
                cfg.access_key_id or "ccfd-access",
                cfg.secret_access_key or "ccfd-secret",
            )
        )
        store.create_bucket(cfg.s3_bucket)
        if c.opt("seed_dataset", True):
            try:
                store.get(cfg.s3_bucket, cfg.filename)
            except Exception:  # noqa: BLE001 — absent: upload (README.md:303-343)
                store.put(cfg.s3_bucket, cfg.filename, to_csv_bytes(load_dataset()))
        self.store_server = StoreServer(
            store, host=c.opt("host", "127.0.0.1"), port=int(c.opt("port", 0))
        ).start()
        # repoint the producer's endpoint at the live store
        self.cfg = dataclasses.replace(
            self.cfg,
            s3_endpoint=self.store_server.endpoint,
            access_key_id=self.cfg.access_key_id or "ccfd-access",
            secret_access_key=self.cfg.secret_access_key or "ccfd-secret",
        )

    def _up_mesh(self, c: ComponentSpec) -> None:
        """Build the serving mesh + partitioner (parallel/partition.py).

        CR ``mesh:`` block over the ``CCFD_MESH_*`` env twins: ``devices``
        (1 = single-device, 0 = every local device, N = the first N),
        ``fsdp``/``tp`` axis sizes (data absorbs the remainder),
        ``param_partition`` (replicated | rules) and ``seq_parallel``
        (none | ring | ulysses — the seq family's L-sharded attention).
        """
        import jax

        cfg = self.cfg
        log_ = logging.getLogger(__name__)
        n = int(c.opt("devices", cfg.mesh_devices))
        avail = len(jax.devices())
        if n == 0:
            n = avail
        fsdp = max(1, int(c.opt("fsdp", cfg.mesh_fsdp)))
        tp = max(1, int(c.opt("tp", cfg.mesh_tp)))
        self._mesh_seq_parallel = str(
            c.opt("seq_parallel", cfg.mesh_seq_parallel) or "none")
        if n > avail:
            # a CR sized for an 8-chip pod brought up on a laptop must
            # still serve — clamp, but LOUDLY: the operator asked for
            # hardware that is not there. The clamped count may break the
            # CR's fsdp/tp factorization and a 1-device clamp cannot
            # carry seq_parallel at all, so the whole shape degrades to
            # what the clamped hardware CAN serve (pure data parallel)
            # rather than crashing scorer construction.
            logging.getLogger(__name__).warning(
                "mesh.devices=%d but only %d local devices; clamping "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count "
                "for a virtual CPU mesh)", n, avail)
            n = avail
            if n % (fsdp * tp) != 0:
                log_.warning(
                    "clamped mesh: %d devices not divisible by "
                    "fsdp*tp=%d; serving pure data-parallel instead",
                    n, fsdp * tp)
                fsdp = tp = 1
        if tp <= 1 and self._mesh_seq_parallel != "none":
            if n > 1:
                log_.warning(
                    "mesh.seq_parallel=%s needs a tp axis > 1 (have "
                    "tp=%d); disabling sequence parallelism",
                    self._mesh_seq_parallel, tp)
            self._mesh_seq_parallel = "none"
        if n <= 1:
            self._mesh_seq_parallel = "none"
            return
        from ccfd_tpu.parallel.mesh import make_named_mesh
        from ccfd_tpu.parallel.partition import partitioner_from_config

        model = self.spec.component("scorer").opt("model", cfg.model_name)
        self.mesh = make_named_mesh(jax.devices()[:n], fsdp=fsdp, tp=tp)
        self._mesh_param_partition = str(
            c.opt("param_partition", cfg.mesh_param_partition))
        self.partitioner = partitioner_from_config(
            self.mesh, self._mesh_param_partition, model=str(model),
        )
        reg = self._registry("mesh")
        reg.gauge(
            "ccfd_mesh_devices",
            "devices in the live serving mesh (absent/0 = unsharded)",
        ).set(float(n))
        g_axis = reg.gauge(
            "ccfd_mesh_axis_size", "named serving-mesh axis sizes")
        for axis, size in self.mesh.shape.items():
            g_axis.set(float(size), labels={"axis": str(axis)})

    def _up_scorer(self) -> None:
        from ccfd_tpu.serving.scorer import Scorer

        c = self.spec.component("scorer")
        cfg = self.cfg
        if c.opt("model", cfg.model_name) in ("seq", "seq_q8"):
            # history-aware long-context family (serving/history.py):
            # streamed through the router (history lives where the stream
            # is); the stateless REST front stays row-based by design
            import jax

            from ccfd_tpu.data.ccfd import synthetic_dataset
            from ccfd_tpu.models import seq as seq_mod
            from ccfd_tpu.serving.history import SeqScorer

            sparams = seq_mod.init(jax.random.PRNGKey(0))
            ds = synthetic_dataset(n=4096, fraud_rate=0.01, seed=0)
            sparams = seq_mod.set_normalizer(
                sparams, ds.X.mean(0), ds.X.std(0)
            )
            if c.opt("model", cfg.model_name) == "seq_q8":
                # int8 serving variant (ops/seq_quant.py) straight from
                # the CR — the governed route is still the lifecycle
                # shadow lane; this is the explicit operator choice
                from ccfd_tpu.ops.seq_quant import quantize_seq

                sparams = quantize_seq(sparams)
            self.scorer = SeqScorer(
                sparams,
                length=int(c.opt("history_length", 64)),
                batch_sizes=cfg.batch_sizes,
                compute_dtype=c.opt("dtype", cfg.compute_dtype),
                max_customers=int(c.opt("max_customers", 20_000)),
                registry=self._registry("seldon"),
                stripes=int(c.opt("seq_stripes", cfg.seq_stripes)),
                inflight=int(c.opt("seq_inflight", cfg.seq_inflight)),
                len_buckets=tuple(
                    c.opt("seq_len_buckets", cfg.seq_len_buckets)),
                telemetry=self.device,
                partitioner=self.partitioner,
                seq_parallel=getattr(self, "_mesh_seq_parallel", "none"),
            )
            self.scorer.warmup()
            if self.device is not None:
                self.device.register_executable_source(
                    "seq", self.scorer.executable_grid)
            return
        params = None
        if c.opt("train_steps", 0):
            from ccfd_tpu.data.ccfd import load_dataset
            from ccfd_tpu.parallel.train import TrainConfig, fit_mlp

            ds = load_dataset()
            params = fit_mlp(
                ds.X, ds.y, steps=int(c.opt("train_steps")),
                tc=TrainConfig(compute_dtype="float32"),
            )
        self.scorer = Scorer(
            model_name=c.opt("model", cfg.model_name),
            params=params,
            compute_dtype=c.opt("dtype", cfg.compute_dtype),
            batch_sizes=cfg.batch_sizes,
            host_tier_rows=None if cfg.host_tier_rows < 0 else cfg.host_tier_rows,
            dispatch_deadline_ms=cfg.scorer_dispatch_deadline_ms(),
            telemetry=self.device,
            partitioner=self.partitioner,
        )
        self.scorer.warmup()
        if self.device is not None:
            self.device.register_executable_source(
                "scorer", self.scorer.executable_grid)
        if c.opt("rest", False):
            from ccfd_tpu.serving.server import PredictionServer

            self.prediction_server = PredictionServer(
                self.scorer, self.cfg, self._registry("seldon"),
                tracer=self._tracer("seldon"),
                profiler=self.profiler,
            )
            self.prediction_host = c.opt("host", "127.0.0.1")
            self.prediction_port = self.prediction_server.start(
                self.prediction_host, int(c.opt("port", 0))
            )

    def _up_lifecycle(self) -> None:
        from ccfd_tpu.runtime.supervisor import RestartPolicy
        from ccfd_tpu.serving.history import SeqScorer

        is_seq = isinstance(self.scorer, SeqScorer)
        if not is_seq and not getattr(self.scorer, "has_host_forward", False):
            logging.getLogger(__name__).warning(
                "lifecycle enabled but the scorer has no host forward "
                "(model=%s): the challenger slot scores off-device by "
                "design; skipping lifecycle",
                getattr(getattr(self.scorer, "spec", None), "name", "?"),
            )
            return
        from ccfd_tpu.lifecycle.controller import (
            Guardrails,
            LifecycleController,
        )
        from ccfd_tpu.lifecycle.evaluator import ShadowEvaluator
        from ccfd_tpu.lifecycle.shadow import ShadowTap
        from ccfd_tpu.lifecycle.versions import VersionStore
        from ccfd_tpu.parallel.checkpoint import CheckpointManager

        c = self.spec.component("lifecycle")
        cfg = self.cfg
        registry = self._registry("lifecycle")
        state_dir = c.opt("state_dir", cfg.lifecycle_dir) or ""
        store = VersionStore(
            os.path.join(state_dir, "versions.json") if state_dir else None
        )
        if state_dir:
            ckpt_dir = os.path.join(state_dir, "checkpoints")
        else:
            # in-memory lineage still needs somewhere for rollback
            # checkpoints to live for the process lifetime
            import tempfile

            ckpt_dir = tempfile.mkdtemp(prefix="ccfd_lifecycle_")
        checkpoints = CheckpointManager(
            ckpt_dir, keep=int(c.opt("keep_checkpoints", 8))
        )
        shadow = ShadowTap(
            self.scorer, self.broker, cfg.shadow_topic, registry,
            max_queued_batches=int(c.opt("shadow_queue_batches", 64)),
        )
        evaluator = ShadowEvaluator(
            cfg, self.broker, self.scorer, registry,
            k_frac=float(c.opt("precision_k_frac", 0.05)),
        )
        guardrails = Guardrails(
            min_labels=int(c.opt("min_labels", cfg.lifecycle_min_labels)),
            min_shadow_rows=int(
                c.opt("min_shadow_rows", cfg.lifecycle_min_shadow_rows)),
            auc_margin=float(c.opt("auc_margin", cfg.lifecycle_auc_margin)),
            max_alert_rate_delta=float(
                c.opt("max_alert_rate_delta", cfg.lifecycle_max_alert_delta)),
            max_score_psi=float(
                c.opt("max_score_psi", cfg.lifecycle_max_psi)),
            canary_weight=float(
                c.opt("canary_weight", cfg.lifecycle_canary_weight)),
            canary_min_labels=int(
                c.opt("canary_min_labels", cfg.lifecycle_canary_min_labels)),
            min_submit_interval_s=float(
                c.opt("min_submit_interval_s",
                      cfg.lifecycle_min_submit_interval_s)),
        )
        self.lifecycle = LifecycleController(
            cfg, self.scorer, store=store, checkpoints=checkpoints,
            shadow=shadow, evaluator=evaluator, guardrails=guardrails,
            registry=registry,
            # storage-integrity pin (runtime/durability.py): when no
            # champion checkpoint generation verifies at restore, serving
            # pins to the rules tier through the heal-gate seam instead
            # of publishing an unverified tree
            storage_pin=(self.storage_gate.pin
                         if self.storage_gate is not None else None),
            storage_unpin=(self.storage_gate.unpin
                           if self.storage_gate is not None else None),
        )
        if is_seq:
            # the router calls a SeqScorer as an OBJECT (score_with_ids),
            # so there is no score_fn lane to wrap — the scorer offers
            # each resolved batch to the tap itself (challenger slot —
            # typically the int8 seq_q8 variant — scores tapped histories
            # on the tap's worker thread, sample-bounded) and serves the
            # canary gate's deterministic challenger slice against the
            # same assembled contexts
            self.scorer.shadow_tap = shadow
            self.scorer.canary_gate = self.lifecycle.gate
            if len(self.scorer.len_buckets) > 1:
                # ladder + lifecycle: tapped champion scores come from
                # short-rung executables while the challenger re-scores
                # the full-L contexts, so the PSI/alert evidence absorbs
                # rung noise on cold rows (conservative bias — breaches
                # read larger, never smaller). Judge candidates with the
                # ladder off for a clean variant-only verdict.
                logging.getLogger(__name__).warning(
                    "lifecycle shadow evaluation with seq len_buckets=%s "
                    "armed: champion scores ride short-L rungs while the "
                    "challenger scores full-L contexts — distribution "
                    "gates will include ladder-rung noise (conservative)",
                    self.scorer.len_buckets,
                )
        if self.audit is not None:
            # per-batch lineage sample for the decision records: the route
            # seam joins each batch to the serving champion's version id +
            # checkpoint hash — sampled once per batch, never per row
            def _lineage_sample(store=store):
                v = store.champion()
                return ((v.version, v.checkpoint_hash)
                        if v is not None else (None, None))

            self.audit.lineage_fn = _lineage_sample
        interval = float(c.opt("interval_s", 0.25))
        self.supervisor.add_thread_service(
            "lifecycle",
            lambda: self.lifecycle.run(interval_s=interval),
            self.lifecycle.stop,
            policy=RestartPolicy.ALWAYS,
            reset=self.lifecycle.reset,
        )
        self.supervisor.add_thread_service(
            "lifecycle-shadow",
            lambda: shadow.run(interval_s=0.05),
            shadow.stop,
            policy=RestartPolicy.ALWAYS,
            reset=shadow.reset,
        )

    def _up_engine(self) -> None:
        from ccfd_tpu.process.fraud import build_engine
        from ccfd_tpu.process.prediction import ScorerPredictionService

        c = self.spec.component("engine")
        listener = None
        if c.opt("usertask_model", False):
            # dedicated learned user-task model (the reference's second
            # Seldon model, README.md:347-353): trains on investigator
            # decisions, replaces the fraud-scorer-backed service
            from ccfd_tpu.process.usertask_model import OnlineUserTaskModel

            self.usertask_model = OnlineUserTaskModel(
                min_examples=int(c.opt("usertask_min_examples", 32)),
            )
            self._usertask_state_file = c.opt("usertask_state_file", "") or None
            if self._usertask_state_file and os.path.exists(self._usertask_state_file):
                try:
                    self.usertask_model.load(self._usertask_state_file)
                except Exception:  # noqa: BLE001 - an unrecoverable state
                    # file (quarantined by the durability layer, no
                    # verifiable generation) must read as a cold model,
                    # never brick bring-up
                    logging.getLogger(__name__).exception(
                        "usertask state %s unusable; starting cold",
                        self._usertask_state_file)
            pred = self.usertask_model
            listener = self.usertask_model.observe
        else:
            pred = (
                ScorerPredictionService(self.scorer.score)
                if self.scorer is not None
                else None
            )
        def engine_factory():
            # crash recovery rebuilds with the same wiring (definitions are
            # code; the shared registry keeps counters cumulative across
            # engine epochs)
            return build_engine(
                self.cfg, self.broker, self._registry("kie"),
                prediction_service=pred, task_listener=listener,
            )

        self._engine_factory = engine_factory
        self.engine = engine_factory()
        # jBPM-style engine persistence: restore process state across
        # restarts (overdue timers fire promptly after restore)
        state_file = c.opt("state_file", "")
        self._engine_state_file = state_file or None
        if state_file and os.path.exists(state_file):
            try:
                self.engine.load(state_file)
            except Exception:  # noqa: BLE001 - corrupt beyond every
                # retained generation: cold engine beats a bricked boot
                logging.getLogger(__name__).exception(
                    "engine state %s unusable; starting cold", state_file)
        if state_file or getattr(self, "_usertask_state_file", None):
            # periodic checkpoint: a crash between saves loses at most
            # save_interval_s of process state — save-on-down alone would
            # lose everything exactly when persistence matters (SIGKILL/OOM)
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            interval = float(c.opt("save_interval_s", 5.0))
            stop = threading.Event()

            def checkpoint_loop() -> None:
                while not stop.wait(interval):
                    self._save_engine_state()

            self.supervisor.add_thread_service(
                "engine-persist", checkpoint_loop, stop.set,
                policy=RestartPolicy.ALWAYS, reset=stop.clear,
            )
        if c.opt("rest", False):
            # KIE-shaped REST surface (reference :8090, README.md:509-515).
            # Started strictly AFTER the snapshot restore: an early remote
            # start_process would populate the engine and make restore()
            # refuse ("requires an empty engine").
            from ccfd_tpu.process.server import EngineServer

            self.engine_server = EngineServer(
                self.engine, tracer=self._tracer("kie"))
            self.engine_port = self.engine_server.start(
                c.opt("rest_host", "127.0.0.1"), int(c.opt("rest_port", 0))
            )

    def _up_notify(self) -> None:
        from ccfd_tpu.notify.service import NotificationService
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("notify")
        notify = NotificationService(
            self.cfg, self.broker, self._registry("notify"),
            seed=int(c.opt("seed", 0)),
            tracer=self._tracer("notify"),
        )
        self.supervisor.add_thread_service(
            "notify",
            lambda: notify.run(poll_timeout_s=0.02),
            notify.stop,
            policy=RestartPolicy.ALWAYS,
            reset=notify.reset,
        )

    def _up_router(self) -> None:
        from ccfd_tpu.router.router import Router
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("router")
        reg = self._registry("router")
        router_tracer = self._tracer("router")
        host_score_fn = None
        if self.scorer is not None:
            from ccfd_tpu.serving.history import SeqScorer

            # a history-aware scorer goes in as the OBJECT so the router
            # detects score_with_ids and feeds it the decoded records
            score_fn = (self.scorer if isinstance(self.scorer, SeqScorer)
                        else self.scorer.score)
            if getattr(self.scorer, "has_host_forward", False):
                # the ladder's host tier: a numpy forward that never
                # touches the (possibly partitioned) device edge
                host_score_fn = self.scorer.host_score
        else:  # remote scorer over the Seldon REST contract
            from ccfd_tpu.serving.client import SeldonClient

            score_fn = SeldonClient(
                self.cfg,
                faults=(self.fault_plan.injector("scorer", reg)
                        if self.fault_plan else None),
                tracer=router_tracer,
            ).score
        if self.fault_plan is not None and self.scorer is not None:
            # in-process scorer edge: same injection point the REST client
            # gets, wrapped around the callable
            inj = self.fault_plan.injector("scorer", reg)
            if inj is not None:
                if hasattr(score_fn, "score_with_ids"):
                    score_fn = inj.wrap(score_fn)  # SeqScorer object
                else:
                    score_fn = inj.wrap_fn(score_fn)
        breaker = None
        if self.lifecycle is not None and not hasattr(
                score_fn, "score_with_ids"):
            # lifecycle serving lane: shadow tap inside (pure champion
            # pairs), canary gate outside (challenger-arm override). Sits
            # UNDER the ParallelRouter's coalescing batcher, so the tap
            # observes the same coalesced batches the device scores.
            # Faults injected above stay inside the wrap: a fault-storm
            # failure degrades the ladder, not the lifecycle accounting.
            score_fn = self.lifecycle.wrap_score(score_fn)
            # one scorer-edge breaker, shared between the router's
            # degradation ladder and the controller's canary guardrail
            # (a breaker leaving CLOSED mid-canary is a rollback trigger)
            if bool(c.opt("degrade", True)):
                from ccfd_tpu.router.router import default_scorer_breaker

                breaker = default_scorer_breaker(reg)
                self.lifecycle.breaker = breaker
        engine = self.engine
        if engine is None and self.cfg.kie_server_url.startswith("http"):
            # remote engine over the KIE-shaped REST contract
            from ccfd_tpu.process.client import EngineRestClient

            engine = EngineRestClient(
                self.cfg.kie_server_url,
                timeout_s=self.cfg.seldon_timeout_ms / 1000.0,
                retries=self.cfg.client_retries,
                tracer=router_tracer,
            )
        if self.fault_plan is not None and engine is not None:
            inj = self.fault_plan.injector("engine", reg)
            if inj is not None:
                engine = inj.wrap(
                    engine,
                    methods=("start_process", "start_process_batch",
                             "signal"),
                )
        # overload-control plane (runtime/overload.py): default on — the
        # static in-flight cap becomes an adaptive AIMD limit derived
        # from the scorer stage's observed latency, sheds become
        # priority-aware, and a hung dispatch is watchdog-killed into the
        # breaker. One OverloadControl per router pool: with workers > 1
        # every worker shares it, so the adaptive bound is global.
        workers = int(c.opt("workers", self.cfg.router_workers))
        overload = None
        if self.cfg.overload_enabled:
            from ccfd_tpu.runtime.overload import OverloadControl

            n_eff = workers if workers > 0 else max(
                1, len(self.broker.end_offsets(self.cfg.kafka_topic)))
            overload = OverloadControl.from_config(
                self.cfg, reg, max_batch=4096, workers=n_eff)
            mi = c.opt("max_inflight")
            if overload is not None and mi is not None:
                # an explicit CR cap stays a hard ceiling on the
                # adaptive limit — AIMD moves below it, never above.
                # min_limit clamps too: a floor above the cap would let
                # the first AIMD decrease snap the limit back OVER the
                # operator's bound (max(min_limit, limit*beta))
                b = overload.budget
                b.max_limit = min(b.max_limit, int(mi))
                b.min_limit = min(b.min_limit, int(mi))
                b.limit = min(b.limit, int(mi))
        # kept for the incident recorder (7d): a dispatch-watchdog kill
        # snapshots into the flight recorder's ring
        self._overload = overload
        # fleet mode (fleet/): the audit seam is wrapped with the ledger
        # tap (per-tx dispositions onto the shared bus, stamped with the
        # poll epoch) and offsets move to commit-after-route — a member
        # SIGKILLed mid-batch leaves the batch uncommitted for a survivor
        # to redeliver, and its own late commit is fenced by the bus
        fleet_spec = self.spec.component("fleet")
        audit_sink = self.audit
        commit_after_route = False
        if fleet_spec.enabled and self.broker is not None:
            from ccfd_tpu.fleet.ledger import FleetLedgerTap

            member_name = str(
                fleet_spec.opt("member", self.cfg.fleet_member)
                or f"member-{os.getpid()}")
            self.fleet_ledger = FleetLedgerTap(
                self.broker,
                member_name,
                topic=str(fleet_spec.opt("ledger_topic",
                                         self.cfg.fleet_ledger_topic)),
                inner=self.audit,
                registry=self._registry("fleet"),
            )
            audit_sink = self.fleet_ledger
            commit_after_route = True
        # replay plane (replay/): the verdict tap wraps the (possibly
        # fleet-wrapped) audit seam — live decisions pass through to the
        # provenance log; replay-marked ones divert to the parity join.
        # The tap also answers capture_rows for the route seam, arming
        # feature-row embeds so recorded windows are re-scorable.
        replay_spec = self.spec.component("replay")
        if ((replay_spec.enabled or self.cfg.replay_enabled)
                and self.audit is not None and self.broker is not None):
            from ccfd_tpu.replay.service import ReplayVerdictTap

            self.replay_tap = ReplayVerdictTap(
                inner=audit_sink, registry=self._registry("replay"))
            audit_sink = self.replay_tap
        # fused decision plane (ops/fused_decision.py, serving/fused.py):
        # CR `scorer.fused_decision` over CCFD_FUSED_DECISION. One device
        # dispatch returns (proba, fired rule index) — score, threshold
        # and the vectorizable rule base in ONE executable — and the
        # router's host rules pass disappears on the healthy path. Armed
        # only for an in-process row Scorer: seq/remote scorers have no
        # fusable decision program, and the lifecycle canary gate rewrites
        # scores AFTER the scorer returns — a fused verdict would have
        # fired on the pre-override score, splitting proba and rule.
        decision_fn = None
        rules = None
        sc_spec = self.spec.component("scorer")
        if bool(sc_spec.opt("fused_decision", self.cfg.fused_decision)):
            from ccfd_tpu.serving.history import SeqScorer

            fused_strict = bool(sc_spec.opt(
                "fused_decision_strict", self.cfg.fused_decision_strict))
            log_f = logging.getLogger(__name__)
            if self.scorer is None or isinstance(self.scorer, SeqScorer):
                msg = ("scorer.fused_decision needs an in-process row "
                       "Scorer (remote and seq scorers have no fusable "
                       "decision program); serving the staged path")
                if fused_strict:
                    raise RuntimeError(msg)
                log_f.warning(msg)
            elif self.lifecycle is not None:
                msg = ("scorer.fused_decision is incompatible with the "
                       "lifecycle serving lane (the canary gate overrides "
                       "scores after the fused verdict fires); serving "
                       "the staged path")
                if fused_strict:
                    raise RuntimeError(msg)
                log_f.warning(msg)
            else:
                from ccfd_tpu.router.rules import RuleSet, default_rules
                from ccfd_tpu.serving.fused import FusedDecisionScorer

                # the Router's own precedence (explicit arg > CCFD_RULES
                # file > threshold default), applied HERE so the fused
                # plan and the router provably share ONE RuleSet instance
                # (the router disarms on identity mismatch)
                rules = (RuleSet.from_file(self.cfg.rules_file)
                         if self.cfg.rules_file
                         else default_rules(self.cfg.fraud_threshold))
                fds = FusedDecisionScorer(
                    self.scorer, rules, registry=reg,
                    profiler=self.profiler, strict=fused_strict)
                if fds.enabled:
                    fds.warmup()  # every (L,B) bucket under fused.warm
                    if self.device is not None:
                        self.device.register_executable_source(
                            "fused_decision", fds.executable_grid)
                    # param swaps precompile the fused grid against the
                    # STAGED tree before publishing (scorer prepublish
                    # seam) — zero serving-stage compiles after a swap
                    self.scorer.add_prepublish_hook(fds.prepublish)
                    decision_fn = fds
                    self.fused_decision = fds
                else:  # refused (unvectorizable rules, mesh scorer):
                    rules = None  # the warning already said why; staged
        common = dict(
            rules=rules,
            decision_fn=decision_fn,
            host_score_fn=host_score_fn,
            breaker=breaker,
            # the ladder is the production default: a sick scorer edge
            # degrades scoring quality instead of dropping batches
            # (router.degrade: false restores the historical drop path)
            degrade=bool(c.opt("degrade", True)),
            max_inflight=(int(c.opt("max_inflight"))
                          if c.opt("max_inflight") is not None else None),
            tracer=router_tracer,
            overload=overload,
            profiler=self.profiler,
            audit=audit_sink,
            commit_after_route=commit_after_route,
        )
        # partition-parallel fan-out (router/parallel.py): CR
        # `router.workers` over CCFD_ROUTER_WORKERS; 1 = the historical
        # single Router, 0 = one worker per bus partition. Workers split
        # partitions via the consumer group and share one scorer through
        # a coalescing batcher, one in-flight budget, one breaker and a
        # group-wide pause barrier — the checkpoint/recovery machinery
        # below drives either shape through the same surface.
        if workers == 1:
            router = Router(self.cfg, self.broker, score_fn, engine, reg,
                            **common)
        else:
            from ccfd_tpu.router.parallel import ParallelRouter

            router = ParallelRouter(
                self.cfg, self.broker, score_fn, engine, reg,
                workers=workers,
                coalesce=bool(c.opt("coalesce", self.cfg.router_coalesce)),
                **common,
            )
        self.router = router
        if self.fleet_ledger is not None:
            # ledger entries stamp the tx consumer's poll epoch (members
            # run workers=1, so the consumer read through the router IS
            # the one that polled the batch; read lazily — the consumer
            # is rebuilt on crash-recycle). A ParallelRouter has no
            # single consumer: entries stay epoch=None, which the
            # conservation checker treats conservatively.
            self.fleet_ledger.epoch_fn = lambda: getattr(
                getattr(router, "_tx_consumer", None), "epoch", None)
        if self.replay_tap is not None:
            # replay plane (replay/): the service re-produces recorded
            # windows through THIS router under bulk admission; the tap
            # (already wrapping the audit seam) hands the replayed
            # verdicts to its parity join. Registered as a supervised
            # component so a crashed worker restarts and resumes from
            # its durable cursor.
            from ccfd_tpu.replay.service import ReplayService
            from ccfd_tpu.runtime.supervisor import RestartPolicy

            rcfg = self.cfg

            def _replay_lineage():
                fn = getattr(self.audit, "lineage_fn", None)
                return fn() if fn is not None else (None, None)

            self.replay = ReplayService(
                rcfg, self.broker, self.audit, tap=self.replay_tap,
                registry=self._registry("replay"),
                state_dir=(str(replay_spec.opt("dir", rcfg.replay_dir))
                           or None),
                overload=overload,
                lineage_fn=_replay_lineage,
            )
            self.replay.batch = max(1, int(
                replay_spec.opt("batch", rcfg.replay_batch)))
            self.replay.timeout_s = float(
                replay_spec.opt("timeout_s", rcfg.replay_timeout_s))
            self.replay.retries = max(0, int(
                replay_spec.opt("retries", rcfg.replay_retries)))
            self.replay.bulk_ceiling = min(1.0, max(0.0, float(
                replay_spec.opt("bulk_ceiling", rcfg.replay_bulk_ceiling))))
            self.replay.set_pacing(float(
                replay_spec.opt("pacing_rows_s", rcfg.replay_pacing_rows_s)))
            self.supervisor.add_thread_service(
                "replay",
                self.replay.run,
                self.replay.stop,
                policy=RestartPolicy.ALWAYS,
                reset=self.replay.reset,
            )
        if self.storage_gate is not None and hasattr(router,
                                                     "set_heal_gate"):
            # the storage pin binds even with the heal component off
            # (CCFD_HEAL=0): unverifiable params pin serving to the rules
            # tier regardless; _up_heal composes the DeviceSupervisor in
            router.set_heal_gate(self.storage_gate)
        if self.partitioner is not None and self.scorer is not None:
            # swap-vs-dispatch publish path (parallel/partition.py): arm
            # the partitioner's PublishGate with the router pool's group
            # pause barrier and route the scorer's swap_params through it,
            # so a lifecycle promotion/rollback publishing SHARDED params
            # never interleaves with a worker's in-flight SPMD dispatch
            self.partitioner.set_barrier(
                router, registry=self._registry("mesh"))
            if hasattr(self.scorer, "set_swap_gate"):
                self.scorer.set_swap_gate(self.partitioner.gate)
        self.supervisor.add_thread_service(
            "router",
            lambda: router.run(poll_timeout_s=0.02),
            router.stop,
            policy=RestartPolicy.ALWAYS,
            reset=router.reset,
        )

    def _up_heal(self, c: ComponentSpec) -> None:
        from ccfd_tpu.runtime.heal import DeviceSupervisor
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        cfg = self.cfg
        # respawn rung: with the lifecycle up, respawn restores the
        # champion CHECKPOINT (serialized under the controller lock so a
        # respawn racing a rollback leaves one consistent champion tree);
        # without it, the supervisor's default re-publishes the current
        # params into fresh device buffers
        respawn_fn = (self.lifecycle.restore_champion
                      if self.lifecycle is not None else None)
        self.heal = DeviceSupervisor(
            self.scorer,
            registry=self._registry("heal"),
            breaker=getattr(self.router, "_breaker", None),
            telemetry=self.device,
            profiler=self.profiler,
            recorder=self.recorder,
            overload=self._overload,
            canary_rows=int(c.opt("canary_rows", 16)),
            canary_deadline_ms=float(
                c.opt("canary_deadline_ms", cfg.heal_canary_deadline_ms)),
            suspect_strikes=int(
                c.opt("suspect_strikes", cfg.heal_suspect_strikes)),
            probation_canaries=int(
                c.opt("probation_canaries", cfg.heal_probation_canaries)),
            parity_tol=float(c.opt("parity_tol", cfg.heal_parity_tol)),
            oom_ratio=float(c.opt("oom_ratio", cfg.heal_oom_ratio)),
            compile_storm_per_s=float(
                c.opt("compile_storm_per_s", cfg.heal_compile_storm_per_s)),
            backoff_base_s=float(
                c.opt("backoff_base_s", cfg.heal_backoff_base_s)),
            backoff_cap_s=float(
                c.opt("backoff_cap_s", cfg.heal_backoff_cap_s)),
            flap_window_s=float(
                c.opt("flap_window_s", cfg.heal_flap_window_s)),
            respawn_fn=respawn_fn,
        )
        if self.router is not None and hasattr(self.router,
                                               "set_heal_gate"):
            # quarantine pins the ladder to the host tier, ABOVE the
            # breaker: even a half-open probe can't leak to a sick device.
            # Composed with the storage pin (runtime/durability.py): an
            # unverifiable-params pin blocks the HOST tier too (it would
            # forward the same unverified tree) — rules only.
            if self.storage_gate is not None:
                from ccfd_tpu.runtime.durability import ComposedHealGate

                self.router.set_heal_gate(
                    ComposedHealGate(self.storage_gate, self.heal))
            else:
                self.router.set_heal_gate(self.heal)
        interval = float(c.opt("interval_s", cfg.heal_interval_s))
        self.supervisor.add_thread_service(
            "heal",
            lambda: self.heal.run(interval_s=interval),
            self.heal.stop,
            policy=RestartPolicy.ALWAYS,
            reset=self.heal.reset,
        )

    def _up_fleet(self, c: ComponentSpec) -> None:
        from ccfd_tpu.fleet.member import FleetMember
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        cfg = self.cfg
        member = str(c.opt("member", cfg.fleet_member)
                     or f"member-{os.getpid()}")
        peers = c.opt("peers", None)
        if peers is None:
            peers = [p.strip() for p in cfg.fleet_peers.split(",")
                     if p.strip()]
        fingerprint_fn = None
        if self.scorer is not None and hasattr(self.scorer, "params"):
            from ccfd_tpu.parallel.partition import params_fingerprint

            scorer = self.scorer
            fingerprint_fn = lambda: params_fingerprint(scorer.params)  # noqa: E731
        router = self.router

        def consumers_fn():
            if router is None:
                return []
            if hasattr(router, "workers"):  # ParallelRouter pool
                return [w._tx_consumer for w in router.workers
                        if getattr(w, "_tx_consumer", None) is not None]
            tx = getattr(router, "_tx_consumer", None)
            return [tx] if tx is not None else []

        router_reg = self.registries.get("router")

        def counters_fn():
            def tot(name):
                m = (router_reg.get(name)
                     if router_reg is not None else None)
                return int(m.total()) if m is not None else 0

            return {
                "incoming": tot("transaction_incoming_total"),
                "routed": tot("transaction_outgoing_total"),
                "shed": tot("router_shed_total"),
                "errors": (tot("router_score_errors_total")
                           + tot("router_process_start_errors_total")
                           + tot("transaction_decode_errors_total")),
            }

        gmi = int(c.opt("global_max_inflight",
                        cfg.fleet_global_max_inflight))
        self.fleet = FleetMember(
            member,
            self._registry("fleet"),
            peers=peers,
            heartbeat_host=c.opt("heartbeat_host", "127.0.0.1"),
            heartbeat_port=int(
                c.opt("heartbeat_port", cfg.fleet_heartbeat_port)),
            ttl_s=float(c.opt("ttl_s", cfg.fleet_ttl_s)),
            overload=self._overload if gmi > 0 else None,
            recorder=self.recorder,
            fingerprint_fn=fingerprint_fn,
            consumers_fn=consumers_fn,
            counters_fn=counters_fn,
            global_max_inflight=gmi or None,
        )
        self.fleet.start_server()
        if router is not None and hasattr(router, "set_heal_gate"):
            # the parity gate composes with whatever already guards the
            # ladder (storage pin, device heal): ANY quarantine pins
            # DOWN, and a stale champion blocks the host tier too (the
            # host forward serves the same stale tree) — rules only
            gates = [g for g in (self.storage_gate, self.heal,
                                 self.fleet.parity_gate) if g is not None]
            if len(gates) > 1:
                from ccfd_tpu.runtime.durability import ComposedHealGate

                router.set_heal_gate(ComposedHealGate(*gates))
            else:
                router.set_heal_gate(gates[0])
        interval = float(
            c.opt("gossip_interval_s", cfg.fleet_gossip_interval_s))
        self.supervisor.add_thread_service(
            "fleet",
            lambda: self.fleet.run(interval_s=interval),
            self.fleet.stop,
            policy=RestartPolicy.ALWAYS,
            reset=self.fleet.reset,
        )

    def _up_investigator(self) -> None:
        from ccfd_tpu.process.investigator import InvestigatorService
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("investigator")
        svc = InvestigatorService(
            self.engine, self._registry("investigator"),
            rate_per_s=float(c.opt("rate_per_s", 50.0)),
            trust_threshold=float(c.opt("trust_threshold", 0.9)),
            base_fraud_rate=float(c.opt("base_fraud_rate", 0.05)),
            seed=int(c.opt("seed", 0)),
        )
        self.investigator = svc
        self.supervisor.add_thread_service(
            "investigator", svc.run, svc.stop,
            policy=RestartPolicy.ALWAYS, reset=svc.reset,
        )

    def _up_crash_recovery(self) -> None:
        """Aligned checkpoints + engine-as-supervised-service: an engine
        crash (chaos or real) restores the last cut and re-drives the
        bus through the LIVE router (runtime/recovery.py). The engine's
        other referents (this platform object, the KIE REST server)
        re-point via on_swap inside the barrier."""
        from ccfd_tpu.runtime.recovery import (
            CheckpointCoordinator,
            attach_engine_service,
        )

        c = self.spec.component("engine")

        def on_swap(engine) -> None:
            self.engine = engine
            if self.engine_server is not None:
                self.engine_server.engine = engine
            if self.investigator is not None:
                self.investigator.engine = engine

        self.recovery = CheckpointCoordinator(
            self.router, self.broker, self._engine_factory,
            interval_s=float(c.opt("checkpoint_interval_s", 5.0)),
            on_swap=on_swap,
            path=c.opt("checkpoint_file", "") or None,
        )
        from ccfd_tpu.serving.history import SeqScorer

        if isinstance(self.scorer, SeqScorer):
            # per-customer histories are pipeline state: they must reset
            # to the cut before a rewind replays records, or replay
            # double-appends every transaction (serving/history.py)
            self.recovery.register_state(
                "history", self.scorer.store.snapshot,
                self.scorer.store.restore,
            )
        # full-process crash recovery: the services haven't started yet,
        # so a persisted cut restores cleanly here — engine state from
        # the cut, the gap re-driven from the (durable) bus after start.
        # Takes precedence over the file-based `state_file` load (the cut
        # is crash-consistent with the bus; state_file is not).
        self.recovery.restore_from_disk()
        attach_engine_service(self.supervisor, self.recovery)
        self.recovery.start()

    def _up_retrain(self) -> None:
        from ccfd_tpu.parallel.online import OnlineTrainer
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("retrain")
        # governed rollout by default when the lifecycle component is up;
        # retrain.direct_swap: true keeps the legacy unvalidated hot swap
        lifecycle = (None if bool(c.opt("direct_swap", False))
                     else self.lifecycle)
        trainer = OnlineTrainer(
            self.cfg, self.broker, self.scorer, self.scorer.params,
            registry=self._registry("retrain"),
            seed=int(c.opt("seed", 0)),
            lifecycle=lifecycle,
            partitioner=self.partitioner,
        )
        if lifecycle is not None:
            # REJECT/ROLLBACK re-bases the trainer onto the champion so
            # the next candidate descends from its recorded parent
            lifecycle.trainer_rebase = trainer.rebase
        interval = float(c.opt("interval_s", 0.5))
        self.supervisor.add_thread_service(
            "retrain",
            lambda: trainer.run(interval_s=interval),
            trainer.stop,
            policy=RestartPolicy.ALWAYS,
            reset=trainer.reset,
        )

    def _up_analytics(self) -> None:
        from ccfd_tpu.analytics.engine import AnalyticsEngine, DriftMonitor
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("analytics")
        registry = self._registry("analytics")
        engine = AnalyticsEngine(
            nbins=int(c.opt("nbins", 32)), registry=registry
        )

        def build_reference():
            # dataset load + two jit compiles: runs on the supervised
            # thread so bring-up (probes, exporter, producer) isn't blocked
            from ccfd_tpu.data.ccfd import load_dataset

            ds = load_dataset()
            return engine.summarize(ds.X, ds.y)

        monitor = DriftMonitor(
            self.cfg,
            self.broker,
            None,
            engine=engine,
            registry=registry,
            window=int(c.opt("window", 4096)),
            reference_builder=build_reference,
            # persisted PSI baseline (CR analytics.reference_file): a
            # restart reloads the training-distribution histogram instead
            # of rebuilding it from an empty window
            reference_path=c.opt("reference_file", "") or None,
        )
        interval = float(c.opt("interval_s", 0.25))
        self.supervisor.add_thread_service(
            "analytics",
            lambda: monitor.run(interval_s=interval),
            monitor.stop,
            policy=RestartPolicy.ALWAYS,
            reset=monitor.reset,
        )

    def _up_producer(self) -> None:
        from ccfd_tpu.producer.producer import Producer
        from ccfd_tpu.runtime.supervisor import RestartPolicy

        c = self.spec.component("producer")
        producer = Producer(
            self.cfg, self.broker, registry=self._registry("producer"),
            store_faults=(self.fault_plan.injector(
                "store", self._registry("producer"))
                if self.fault_plan else None),
            tracer=self._tracer("producer"),
        )
        limit = c.opt("transactions")
        rate = c.opt("rate")
        wire = c.opt("wire_format", "dict")
        done = self._producer_done

        def run() -> None:
            try:
                producer.run(
                    limit=int(limit) if limit is not None else None,
                    rate_per_s=float(rate) if rate else None,
                    wire_format=wire,
                )
            finally:
                done.set()

        # one-shot job semantics, like the reference's producer pod
        self.supervisor.add_thread_service(
            "producer", run, policy=RestartPolicy.NEVER
        )
        self.supervisor.start_service("producer")

    def _wire_memory_probes(self) -> None:
        """Per-component live-object counts for the memory-drift surface
        (``ccfd_component_objects`` gauges + the /memory endpoint,
        observability/memory.py). Probes resolve through ``self`` so
        crash-recovery swaps are followed automatically."""
        ex = self.exporter
        if self.engine is not None and hasattr(self.engine, "object_counts"):
            # sum over object_counts: instances + tasks + rings
            ex.add_probe("engine", lambda: sum(
                (self.engine.object_counts() or {}).values()))
        if self.broker is not None and hasattr(self.broker,
                                               "health_snapshot"):
            def bus_retained() -> int:
                snap = self.broker.health_snapshot()
                return sum(
                    e - b
                    for t in snap["topics"]
                    for e, b in zip(snap["topics"][t], snap["begins"][t])
                )

            ex.add_probe("bus_retained_records", bus_retained)
        if self.trace_sink is not None:
            ex.add_probe("trace_sink",
                         lambda: len(self.trace_sink.traces()))
        if getattr(self.router, "batcher", None) is not None:
            ex.add_probe("router_batcher_queue",
                         lambda: self.router.batcher.qsize())
        if getattr(self.prediction_server, "batcher", None) is not None:
            # the REST-side DynamicBatcher (the queue the overload
            # codel/bound knobs police) — the Overload board charts it
            ex.add_probe("serving_batcher_queue",
                         lambda: self.prediction_server.batcher.qsize())

    # -- status / teardown -------------------------------------------------
    def wait_producer(self, timeout_s: float = 60.0) -> bool:
        return self._producer_done.wait(timeout=timeout_s)

    def status(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "services": self.supervisor.status() if self.supervisor else {},
            "endpoints": {},
        }
        if self.mesh is not None:
            out["mesh"] = {
                "devices": int(self.mesh.size),
                "axes": {str(a): int(s)
                         for a, s in self.mesh.shape.items()},
                # the CR vocabulary (replicated | rules), so live status
                # diffs cleanly against the spec that produced it
                "param_partition": getattr(
                    self, "_mesh_param_partition", "replicated"),
                "seq_parallel": getattr(self, "_mesh_seq_parallel", "none"),
            }
        if self.replay is not None:
            out["replay"] = {
                "bulk_ceiling": self.replay.bulk_ceiling,
                "pacing_rows_s": self.replay.pacing_rows_s,
                "batch": self.replay.batch,
                "last_report": self.replay.last_report,
            }
        if self.store_server:
            out["endpoints"]["store"] = self.store_server.endpoint
        if self.prediction_server:
            out["endpoints"]["scorer"] = (
                f"http://{self.prediction_host}:{self.prediction_port}"
            )
        if self.exporter:
            out["endpoints"]["metrics"] = self.exporter.endpoint
        if self.health_server:
            out["endpoints"]["health"] = self.health_server.endpoint
        return out

    def _health_verdict(self) -> dict[str, Any]:
        """One strict-JSON readiness verdict for the exporter's /healthz:
        every health-bearing plane that is actually up contributes a
        source with a cause string; absent planes (kill-switched or never
        built) are simply not listed, so a minimal platform is not
        "degraded" for lacking optional components."""
        import time

        sources: dict[str, dict[str, Any]] = {}

        def add(name: str, healthy: bool, cause: str) -> None:
            sources[name] = {"healthy": bool(healthy), "cause": cause}

        if self.supervisor is not None:
            bad = []
            for name, st in self.supervisor.status().items():
                if st.get("ready"):
                    continue
                err = st.get("last_error") or ""
                bad.append(f"{name}={st.get('state')}"
                           + (f" ({err})" if err else ""))
            add("supervisor",
                not bad,
                "; ".join(bad) if bad else "all services ready")
        if self.heal is not None:
            hst = self.heal.status()
            state = str(hst.get("state", ""))
            reasons = hst.get("reasons") or []
            add("device",
                state not in ("quarantined",),
                f"state={state}"
                + (f" ({'; '.join(str(r) for r in reasons)})"
                   if reasons and state != "healthy" else ""))
        if self.storage_gate is not None:
            add("storage",
                not self.storage_gate.pinned,
                (f"pinned to rules tier: {self.storage_gate.reason}"
                 if self.storage_gate.pinned else "verified"))
        if self.fleet is not None:
            gate = getattr(self.fleet, "parity_gate", None)
            if gate is not None:
                add("fleet",
                    not gate.quarantined,
                    "parity quarantined" if gate.quarantined
                    else "parity clean")
        breaker = getattr(self.router, "_breaker", None)
        if breaker is not None:
            bstate = breaker.state
            add("scorer_edge",
                bstate != "open",
                f"breaker={bstate}")

        causes = [f"{n}: {s['cause']}"
                  for n, s in sources.items() if not s["healthy"]]
        return {
            "healthy": not causes,
            "generated_unix": time.time(),
            "sources": sources,
            "causes": causes,
        }

    def _save_engine_state(self) -> None:
        if self._engine_state_file:
            try:
                self.engine.save(self._engine_state_file)
            except Exception:  # noqa: BLE001 - persistence must not kill the host
                logging.getLogger(__name__).exception(
                    "engine state save to %s failed; process state will NOT "
                    "survive a restart", self._engine_state_file,
                )
        if getattr(self, "_usertask_state_file", None) and self.usertask_model:
            try:
                self.usertask_model.save(self._usertask_state_file)
            except Exception:  # noqa: BLE001
                logging.getLogger(__name__).exception(
                    "user-task model save to %s failed", self._usertask_state_file
                )

    def down(self) -> None:
        # chaos first: injecting failures into services that are being torn
        # down would race the orderly shutdown
        if self.chaos is not None:
            self.chaos.stop()
        if self.device_fault_plan is not None:
            # the plan installed PROCESS-wide; a torn-down platform must
            # not leave standing device faults for the next one in-process
            from ccfd_tpu.runtime.faults import install_device_faults

            install_device_faults(None)
            self.device_fault_plan = None
        if self.storage_fault_plan is not None:
            from ccfd_tpu.runtime.faults import install_storage_faults

            install_storage_faults(None)
            self.storage_fault_plan = None
        from ccfd_tpu.runtime import durability

        durability.set_recorder(None)
        if self.recovery is not None:
            self.recovery.stop()
        if self.supervisor:
            self.supervisor.stop()
        if self.audit is not None:
            # the supervised flusher's shutdown already lands the tail;
            # this covers platforms torn down before the supervisor ran
            try:
                self.audit.flush()
            except Exception:  # noqa: BLE001 - teardown must not raise
                pass
        if self.lifecycle is not None:
            try:
                self.lifecycle.close()  # releases the evaluator consumers
            except Exception:  # noqa: BLE001
                pass
        if self.fleet is not None:
            try:
                self.fleet.close()  # heartbeat server + peer clients
            except Exception:  # noqa: BLE001
                pass
        if self._broker_is_client and self.broker is not None:
            # a bus-client broker owns sockets to the SHARED bus server;
            # the in-process Broker is left alone (its segment logs are
            # torn down with the process, matching historical behavior)
            try:
                self.broker.close()
            except Exception:  # noqa: BLE001
                pass
        # a ParallelRouter owns coalescing-batcher threads the supervisor
        # doesn't know about; release any callers still parked on futures
        if getattr(self.router, "batcher", None) is not None:
            try:
                self.router.batcher.stop()
            except Exception:  # noqa: BLE001
                pass
        if self.engine is not None and (
            getattr(self, "_engine_state_file", None)
            or getattr(self, "_usertask_state_file", None)
        ):
            self._save_engine_state()
        for srv in (
            self.prediction_server,
            self.engine_server,
            self.exporter,
            self.health_server,
            self.store_server,
        ):
            if srv is not None:
                try:
                    srv.stop()
                except Exception:  # noqa: BLE001
                    pass
        self._up = False
