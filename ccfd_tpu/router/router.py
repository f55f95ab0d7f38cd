"""Stream decision router — the Camel/Fuse + Drools capability, TPU-batched.

The reference's ``ccd-fuse`` router consumes transactions from Kafka one
message at a time, POSTs each to Seldon, applies a Drools rule against
``FRAUD_THRESHOLD`` and starts a "fraud" or "standard" process on the KIE
server; it also forwards customer responses from the response topic as
process signals (reference deploy/router.yaml:54-70, README.md:424-459,
547-552, 567-569).

The TPU-native difference is the dispatch unit: **the Kafka poll IS the
micro-batch**. Each ``step()`` drains up to ``max_batch`` records within a
poll deadline, decodes them into one (B, 30) matrix, and makes a single
scorer dispatch — one XLA executable launch amortized over the whole batch —
instead of one HTTP round-trip per transaction. Threshold routing then runs
vectorized on the returned probability array.

Business counters match the reference metric names (README.md:522-530,
Router.json:88-326): ``transaction_incoming_total``,
``transaction_outgoing_total{type}``, ``notifications_outgoing_total``,
``notifications_incoming_total{response}``.

**Degradation ladder** (round 6; runtime/breaker.py): with ``degrade`` on
(implicit when a ``host_score_fn`` or ``breaker`` is supplied), a sick
scorer edge degrades scoring quality instead of stalling or dropping the
ingest loop — device scorer → host-tier numpy forward → rules-only
conservative scoring — with per-tier ``router_degraded_total{tier}``
counters, a circuit breaker on the scorer edge (an OPEN circuit skips the
device instantly, so a blackholed endpoint costs one bounded stall per
breaker window, not one per micro-batch), response validation (a corrupt
scorer reply — wrong shape, non-finite probabilities — counts as an edge
failure and falls down the ladder), and bounded in-flight load shedding
(``max_inflight`` records consumed-but-unrouted; oldest dropped first,
counted in ``router_shed_total``). Without the ladder the historical
semantics hold: a scorer failure drops that batch, counted.
"""

from __future__ import annotations

import logging
import operator
import threading
import time
from typing import Any, Callable, Mapping, Protocol

import numpy as np

from ccfd_tpu.bus.broker import Broker, StaleEpochError
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.native import decode_csv as native_decode_csv
from ccfd_tpu.observability import trace
from ccfd_tpu.observability.trace import extract_context, phase
from ccfd_tpu.process.fraud import CUSTOMER_RESPONSE_SIGNAL
from ccfd_tpu.router.rules import RuleSet, default_rules


class EngineClient(Protocol):
    """KIE-server-shaped surface the router needs (in-process or REST)."""

    def start_process(self, def_id: str, variables: Mapping[str, Any]) -> int: ...

    def signal(self, pid: int, name: str, payload: Any = None) -> bool: ...


_SCHEMA_GETTER = operator.itemgetter(*FEATURE_NAMES)
_ZERO_ROW = (0.0,) * len(FEATURE_NAMES)


def default_scorer_breaker(registry):
    """The scorer-edge breaker the degradation ladder builds when none is
    supplied — ONE definition so the single Router and the ParallelRouter
    pool degrade on the same profile (an open circuit is what keeps a
    blackholed scorer from stalling every micro-batch)."""
    from ccfd_tpu.runtime.breaker import CircuitBreaker

    return CircuitBreaker(
        edge="scorer", registry=registry, min_calls=3,
        failure_ratio=0.5, cooldown_s=1.0,
    )


class InflightBudget:
    """Consumed-but-unrouted row budget, shareable across router workers.

    A single Router owns a private budget (the historical ``max_inflight``
    semantics). Under :class:`~ccfd_tpu.router.parallel.ParallelRouter`
    every worker shares ONE budget, so N workers cannot hold N× the
    configured bound — the bound is a statement about how much consumed
    work the process may have in flight, not about any one loop.

    ``reserve`` grants up to ``n`` rows and the caller sheds the rest;
    ``release`` returns rows once they are fully routed (or dropped).

    With a ``registry``, the current limit and utilization export as
    ``ccfd_inflight_limit`` / ``ccfd_inflight_used`` gauges labeled by
    ``stage`` — a fixed cap used to be invisible (you saw the sheds, not
    the bound), and the adaptive subclass
    (:class:`~ccfd_tpu.runtime.overload.AdaptiveInflightBudget`) MOVES the
    limit, which the Resilience/Overload boards chart.
    """

    __slots__ = ("limit", "_n", "_mu", "_g_limit", "_g_used", "_stage")

    def __init__(self, limit: int, registry=None, stage: str = "router"):
        self.limit = int(limit)
        self._n = 0
        self._mu = threading.Lock()
        self._stage = {"stage": stage}
        self._g_limit = self._g_used = None
        if registry is not None:
            self._g_limit = registry.gauge(
                "ccfd_inflight_limit",
                "in-flight row budget per stage (adaptive when the "
                "overload plane is armed)",
            )
            self._g_used = registry.gauge(
                "ccfd_inflight_used", "in-flight rows reserved per stage"
            )
            self._set_gauges_locked()

    def _set_gauges_locked(self) -> None:
        if self._g_limit is not None:
            self._g_limit.set(self.limit, labels=self._stage)
            self._g_used.set(self._n, labels=self._stage)

    def reserve(self, n: int) -> int:
        """Take up to ``n`` rows from the budget; returns rows granted."""
        with self._mu:
            take = min(n, max(0, self.limit - self._n))
            self._n += take
            self._set_gauges_locked()
            return take

    def try_reserve(self, n: int, ceiling: float = 1.0) -> bool:
        """All-or-nothing reserve (request-atomic admission): grant only
        when the post-grant utilization stays at or under ``ceiling``.
        An idle stage always grants — a lone request bigger than the
        (possibly adapted-down) limit must run alone, not starve."""
        with self._mu:
            if self._n == 0 or self._n + n <= int(self.limit * ceiling):
                self._n += n
                self._set_gauges_locked()
                return True
            return False

    def release(self, n: int) -> None:
        with self._mu:
            self._n = max(0, self._n - n)
            self._set_gauges_locked()

    def room(self) -> int:
        """Rows the budget could grant right now (backpressure probe)."""
        with self._mu:
            return max(0, self.limit - self._n)

    @property
    def inflight(self) -> int:
        return self._n


def _decode_row_lenient(tx: Any, out_row: np.ndarray) -> int:
    """Field-by-field decode for rows the fast path rejected; returns #bad."""
    if not (type(tx) is dict or isinstance(tx, Mapping)):
        return 1
    bad = 0
    for j, name in enumerate(FEATURE_NAMES):
        v = tx.get(name)
        if v is None:
            continue
        try:
            out_row[j] = float(v)
        except (TypeError, ValueError):
            bad += 1
    return bad


def decode_features(values: list[Mapping[str, Any]]) -> tuple[np.ndarray, int]:
    """Transaction dicts -> ((B, 30) float32 matrix in schema order, #bad fields).

    Hot path: well-formed transactions carry the full schema, so one
    ``itemgetter`` call per row pulls all 30 fields in C, and ONE
    ``np.asarray`` converts the whole batch — ~10x over per-field Python
    loops, which matters because this runs per micro-batch at wire rate
    (it was the single largest cost in the router loop profile).

    Malformed rows (missing fields, non-numeric values, non-mappings) fall
    back to the field-by-field lenient decode: they cost more but decode to
    0.0 per bad field instead of raising — a poison-pill message must not
    take down the scoring loop.
    """
    n = len(values)
    rows: list[tuple] = []
    slow: list[int] = []
    for i, tx in enumerate(values):
        try:
            rows.append(_SCHEMA_GETTER(tx))
        except (KeyError, TypeError):
            rows.append(_ZERO_ROW)
            slow.append(i)
    try:
        out = np.asarray(rows, np.float32)
        if out.shape != (n, len(FEATURE_NAMES)):
            raise ValueError("ragged rows")
    except (TypeError, ValueError):
        # some row carried an unparseable value: redo per row, diverting
        # failures to the lenient path
        out = np.zeros((n, len(FEATURE_NAMES)), np.float32)
        fast_ok = set(range(n)) - set(slow)
        slow = list(slow)
        for i in sorted(fast_ok):
            try:
                out[i] = np.asarray(rows[i], np.float32)
            except (TypeError, ValueError):
                slow.append(i)
    bad = 0
    for i in slow:
        out[i] = 0.0
        bad += _decode_row_lenient(values[i], out[i])
    return out, bad


def decode_records(records) -> tuple[np.ndarray, list[Mapping[str, Any]], int]:
    """Bus records -> ((B, 30) matrix, per-row tx dicts, #malformed fields).

    The one decoder for the transaction topic's mixed wire formats — the
    router's scoring batches and the drift monitor's windows must see the
    SAME rows. Two formats share the batch: dict transactions (decoded in
    Python) and raw CSV lines (decoded by the native C++ fast path in one
    pass). Rows keep their arrival order; a poison pill decodes to an
    all-zero row rather than crashing the loop.
    """
    n = len(records)
    x = np.zeros((n, len(FEATURE_NAMES)), np.float32)
    txs: list[Mapping[str, Any]] = [{}] * n
    bad = 0
    dict_rows: list[int] = []
    dict_vals: list[Mapping[str, Any]] = []
    csv_rows: list[int] = []
    csv_lines: list[bytes] = []
    # per-record dispatch loop: bound methods hoisted — this runs per
    # record at wire rate and its GIL-bound constant is part of the
    # parallel fan-out's scaling ceiling
    app_di, app_dv = dict_rows.append, dict_vals.append
    app_ci, app_cl = csv_rows.append, csv_lines.append
    for i, rec in enumerate(records):
        v = rec.value
        # exact-type checks first: typing/ABC __instancecheck__ costs ~1us
        # and this runs per record at wire rate — a CSV record must not
        # pay a failed Mapping protocol check before its cheap bytes test
        tv = type(v)
        if tv is dict:
            app_di(i)
            app_dv(v)
        elif tv is bytes or tv is str or isinstance(v, (bytes, str)):
            raw = v.encode() if isinstance(v, str) else v
            # one record == one CSV row; embedded newlines would desync
            # the joined decode below. The common case has none — a
            # memchr find beats allocating a splitlines list per record.
            if raw.find(b"\n") >= 0:
                lines = raw.splitlines() or [b""]
                bad += len(lines) - 1
                raw = lines[0]
            app_ci(i)
            app_cl(raw)
        elif isinstance(v, Mapping):  # non-dict mappings: same dict path
            app_di(i)
            app_dv(v)
        else:  # poison pill: score as all-zeros rather than crash the loop
            bad += 1
    if dict_vals:
        xd, bad_fields = decode_features(dict_vals)
        bad += bad_fields
        if len(dict_vals) == n:  # homogeneous batch: no row scatter needed
            x = xd
            txs = dict_vals
        else:
            x[dict_rows] = xd
            for j, i in enumerate(dict_rows):
                txs[i] = dict_vals[j]
    if csv_lines:
        xc, bad_csv = native_decode_csv(
            b"\n".join(csv_lines) + b"\n", len(FEATURE_NAMES)
        )
        bad += bad_csv
        amount_col = FEATURE_NAMES.index("Amount")
        if xc.shape[0] == n and len(csv_lines) == n:
            x = np.ascontiguousarray(xc, np.float32)
        else:
            for j, i in enumerate(csv_rows):
                if j < xc.shape[0]:
                    x[i] = xc[j]
        # one vectorized column read + tolist instead of a numpy-scalar
        # float() per row (~6x on this loop)
        amounts = x[:, amount_col][csv_rows].tolist() if len(
            csv_rows) != n else x[:, amount_col].tolist()
        for i, amt in zip(csv_rows, amounts):
            txs[i] = {"id": records[i].key, "Amount": amt}
    return x, txs, bad


class DeferrableRecords(list):
    """A micro-batch's decoded records as the pipelined loop hands them to
    a ``score_with_ids`` scorer: the mark says that this caller takes the
    scores before they are ready (anything whose ``np.asarray`` blocks
    until they are, with ``deferred`` true and, once ready, ``ready_at``:
    serving/history.py DeferredScores) and forces them itself, on its
    loop thread, before it routes the batch. A scorer that ignores the
    mark returns host memory as ever."""

    __slots__ = ()
    takes_deferred = True


def _deferred(scores: Any) -> bool:
    return getattr(scores, "deferred", False)


def _host_scores(scores: Any) -> Any:
    """Host memory, forced here, unless the scorer deferred the result."""
    return scores if _deferred(scores) else np.asarray(scores)


class Router:
    @trace.startup_phase("startup.router")
    def __init__(
        self,
        cfg: Config,
        broker: Broker,
        score_fn: Callable[[np.ndarray], np.ndarray],
        engine: EngineClient,
        registry: Registry | None = None,
        max_batch: int = 4096,
        rules: RuleSet | None = None,
        host_score_fn: Callable[[np.ndarray], np.ndarray] | None = None,
        breaker: "Any | None" = None,
        degrade: bool | None = None,
        max_inflight: int | None = None,
        tracer: "Any | None" = None,
        inflight_budget: InflightBudget | None = None,
        worker_id: int | None = None,
        overload: "Any | None" = None,
        profiler: "Any | None" = None,
        heal_gate: "Any | None" = None,
        audit: "Any | None" = None,
        commit_after_route: bool = False,
        decision_fn: "Any | None" = None,
    ):
        self.cfg = cfg
        self.broker = broker
        self.score = score_fn
        # observability/trace.py: per micro-batch, the router RESUMES the
        # trace context the producer stamped on the records ("router.batch"
        # span parented on the producer's span) and opens child spans for
        # decode/score/route — the per-stage latency attribution the
        # Tracing board and tools/trace_report.py decompose. Fraud-routed
        # and degraded-tier batches flag their spans, which the tail
        # sampler always keeps. Each stage is a trace.phase: with no
        # tracer it still shows in a device capture, by the same name.
        self.tracer = tracer
        # history-aware scorers (serving/history.py SeqScorer) score each
        # transaction against the customer's history: they expose
        # score_with_ids(txs, x) and the router feeds them the decoded
        # records alongside the feature matrix; plain scorers get (x,)
        score_with_ids = getattr(score_fn, "score_with_ids", None)
        if callable(score_with_ids):
            self._score2 = lambda x, txs: (
                _host_scores(score_with_ids(txs, x)), None)
        else:
            self._score2 = lambda x, txs: (np.asarray(self.score(x)), None)
        self.engine = engine
        self.registry = registry or Registry()
        self.max_batch = max_batch
        # Drools-analog rule base (ccfd_tpu/router/rules.py). Precedence:
        # explicit arg > CCFD_RULES file > the reference's threshold rule.
        if rules is None:
            rules = (
                RuleSet.from_file(cfg.rules_file)
                if cfg.rules_file
                else default_rules(cfg.fraud_threshold)
            )
        self.rules = rules
        # Fused decision plane (serving/fused.py): one device dispatch
        # returns (proba, fired) — score, threshold and the vectorizable
        # rule base evaluated in ONE executable, so _route_inner skips the
        # host rules pass entirely. The decision fn REPLACES the score
        # seam (same tuple contract as _score2); its staged fallback
        # returns fired=None and the host rules pass resumes — the
        # degradation ladder below it (host forward, rules floor) is
        # untouched. Guard: the fused plan must have been compiled from
        # THIS router's rule base, or device-computed fired indices would
        # silently index a different rule table.
        if decision_fn is not None:
            dec_rules = getattr(decision_fn, "rules", None)
            if dec_rules is not None and dec_rules is not self.rules:
                logging.getLogger("ccfd_tpu.router").warning(
                    "decision_fn was compiled against a different RuleSet "
                    "than this router serves; fused decisions disarmed — "
                    "pass the same RuleSet instance to both")
                decision_fn = None
            else:
                dec = getattr(decision_fn, "decide", decision_fn)
                self._score2 = lambda x, txs: dec(x)
        self._decision_fn = decision_fn
        # Fail fast on a rule naming a process the engine doesn't have —
        # discovering it on the first matching transaction would kill the
        # routing loop mid-batch. Remote (REST) engines don't expose a
        # definition list; those fall back to the runtime guard in step().
        list_defs = getattr(engine, "definitions", None)
        if callable(list_defs):
            known = set(list_defs())
            missing = {r.process for r in rules.rules} - known
            if missing:
                raise ValueError(
                    f"rules reference unregistered processes {sorted(missing)}; "
                    f"engine has {sorted(known)}"
                )

        # engines (in-process or REST) exposing the batched start API get
        # one call per (rule, micro-batch) group instead of one per tx
        self._start_batch = getattr(engine, "start_process_batch", None)
        # in-process engines advertise copy_vars=False support (the
        # router's variables dicts are freshly built and never reused, so
        # the engine's defensive copy is pure overhead on the hot path);
        # the flag passes through method proxies where a signature
        # inspection would not
        self._start_nocopy = bool(getattr(engine, "start_batch_nocopy",
                                          False))

        # commit-after-route discipline (fleet plane, ISSUE 16): the tx
        # consumer runs manual-commit — a batch's offsets commit only
        # once every record has a terminal disposition (routed, shed, or
        # counted error). A member killed mid-batch leaves its offsets
        # UNcommitted, so the batch redelivers to whichever member
        # re-adopts the partitions (no drop); the bus's epoch fence
        # refuses the dead member's in-flight commit (no double-route
        # within an epoch). Off by default: the single-process platform
        # keeps the historical commit-on-poll hand-off.
        self._commit_after_route = bool(commit_after_route)
        # single source of truth for the consumer wiring: __init__ AND
        # recycle_consumers (crash recovery) both build from this.
        # manual=True marks the consumer that must be built
        # auto_commit=False when commit-after-route is armed.
        self._consumer_specs = (
            ("_tx_consumer", "router", (cfg.kafka_topic,), True),
            ("_resp_consumer", "router-responses",
             (cfg.customer_response_topic,), False),
            ("_notif_watcher", "router-notifications",
             (cfg.customer_notification_topic,), False),
        )
        for attr, group, topics, manual in self._consumer_specs:
            setattr(self, attr, self._build_consumer(group, topics, manual))

        r = self.registry
        self._c_in = r.counter("transaction_incoming_total", "transactions consumed")
        self._c_out = r.counter(
            "transaction_outgoing_total", "process starts by type"
        )
        self._c_notif_out = r.counter(
            "notifications_outgoing_total", "customer notifications observed"
        )
        self._c_notif_in = r.counter(
            "notifications_incoming_total", "customer responses by result"
        )
        self._h_batch = r.histogram("router_batch_size", "scoring batch sizes",
                                    buckets=(1, 8, 64, 256, 1024, 4096, 16384))
        self._c_decode_err = r.counter(
            "transaction_decode_errors_total", "malformed transaction fields"
        )
        self._h_score_s = r.histogram("router_score_seconds", "scorer dispatch latency")
        # the business SLO the reference's SeldonCore board tracks as
        # request quantiles (reference deploy/grafana/SeldonCore.json:499):
        # wall time from a record's PRODUCE timestamp to its process-start
        # decision — queueing + micro-batching + scoring + rules + engine
        self._h_decision_s = r.histogram(
            "router_decision_seconds",
            "producer->process-start decision latency",
        )
        self._c_rule = r.counter("router_rule_fired_total", "rule activations")
        self._c_start_err = r.counter(
            "router_process_start_errors_total", "failed process starts"
        )
        self._c_signal_err = r.counter(
            "router_signal_errors_total", "failed signal forwards"
        )
        self._c_score_err = r.counter(
            "router_score_errors_total",
            "scorer-edge failures: transactions dropped, or absorbed by "
            "degraded tiers when the ladder is on",
        )
        self._c_host_err = r.counter(
            "router_host_score_errors_total",
            "host-tier numpy-forward failures while the ladder was "
            "already degraded (the fall continues to the rules tier); "
            "its own family so the device-edge series stays label-uniform",
        )
        # -- degradation ladder (see module docstring) ---------------------
        self._host_score = host_score_fn
        self._degrade = (degrade if degrade is not None
                         else (host_score_fn is not None
                               or breaker is not None))
        self._breaker = breaker
        if self._degrade and breaker is None:
            self._breaker = default_scorer_breaker(r)
        self.max_inflight = (int(max_inflight) if max_inflight is not None
                             else 2 * max_batch)
        # overload-control plane (runtime/overload.py): adaptive AIMD
        # in-flight budget, deadline (CoDel) + priority-aware shedding,
        # and the dispatch watchdog. None keeps the historical static-
        # budget / oldest-first semantics. A ParallelRouter hands every
        # worker the SAME OverloadControl, so the adaptive bound — like
        # the static one — holds globally across the pool.
        self._overload = overload
        # the bounded-in-flight budget: private by default; a
        # ParallelRouter hands every worker the SAME budget so the bound
        # holds globally (satellite of the partition-parallel fan-out)
        if inflight_budget is not None:
            self._budget = inflight_budget
        elif overload is not None:
            self._budget = overload.budget
        else:
            self._budget = InflightBudget(self.max_inflight, registry=r)
        # device heal gate (runtime/heal.py DeviceSupervisor): while the
        # device is QUARANTINED (or on heal probation) the ladder is
        # PINNED to its host tier — the check sits ABOVE the breaker so
        # not even a half-open probe leaks live traffic to a sick device.
        # The supervisor itself canaries the device back to health.
        self._heal_gate = heal_gate
        # stage profiler (observability/profile.py): per micro-batch the
        # router feeds the decomposition no histogram carries — bus
        # queueing delay (poll time minus produce timestamps), decode and
        # route service time, and the scorer dispatch round trip, batch-
        # size-conditioned. None costs one attribute read per batch.
        self._profiler = profiler
        # decision provenance plane (observability/audit.py AuditLog):
        # when armed, the route seam stamps one compact DecisionRecord
        # per routed transaction — tx/uid/score/branch, the serving tier
        # that produced the score (threaded through a per-batch meta
        # dict so the pipelined loop's concurrent score/route stages
        # can't cross batches), admission priority, and the batch-
        # sampled lineage/incident joins. None costs one attribute read
        # per batch.
        self._audit = audit
        self._rec_pri = self._pri_names = None
        if audit is not None:
            # lazy: runtime/overload.py imports this module
            from ccfd_tpu.runtime.overload import (
                PRIORITY_NAMES,
                record_priority,
            )

            self._pri_names = PRIORITY_NAMES
            self._rec_pri = record_priority
        # worker identity (ParallelRouter): labels this loop's batches and
        # trace spans so per-stage attribution survives the fan-out
        self.worker_id = worker_id
        self._worker_labels = {"worker": str(worker_id or 0)}
        self._amount_idx = FEATURE_NAMES.index("Amount")
        self._c_degraded = r.counter(
            "router_degraded_total",
            "transactions scored by a degraded tier (host numpy forward "
            "or rules-only)",
        )
        self._c_shed = r.counter(
            "router_shed_total",
            "transactions dropped by bounded-in-flight load shedding "
            "(oldest first)",
        )
        self._c_fenced = r.counter(
            "router_fenced_commits_total",
            "post-route offset commits refused by the bus epoch fence "
            "(group rebalanced mid-batch): the batch redelivers to the "
            "partitions' new owners — an at-least-once duplicate, never "
            "a silent loss",
        )
        self._c_commit_err = r.counter(
            "router_commit_errors_total",
            "post-route offset commits lost to bus transport errors "
            "(not fences): the batch stays uncommitted and redelivers",
        )
        self._c_worker_batch = r.counter(
            "router_worker_batches_total",
            "scoring batches per router worker loop (worker 0 == the "
            "single-router case); compare against "
            "router_coalesced_dispatches_total to see fan-in",
        )
        # batches begun (a poll that brought records): the ordinal every
        # router.* phase of one batch carries, so a capture's two host
        # lines are joined by it and not by order
        self._batches = 0
        # the process's start-up record until this router's first routed
        # batch has left (its ``first_verdict`` stamp), None from then on
        self._startup = trace.startup
        self._stop = threading.Event()
        # checkpoint barrier (runtime/recovery.py): pause() parks the run
        # loop at a batch boundary — consumed records fully routed into the
        # engine, nothing in flight — so an engine snapshot plus the
        # committed offsets form a consistent cut (Flink-style aligned
        # checkpoint, scaled to one source)
        self._pause_req = threading.Event()
        self._pause_ack = threading.Event()
        # pause is reference-counted: the periodic checkpointer and an
        # operator drill (or crash restore) may hold the barrier at once,
        # and one holder's resume() must not release the other's hold
        self._pause_mu = threading.Lock()
        self._pause_holders = 0

    # -- commit-after-route (fleet plane) ----------------------------------
    def _build_consumer(self, group: str, topics: tuple, manual: bool):
        """Build one bus consumer; the tx consumer (``manual=True``) gets
        auto_commit=False when commit-after-route is armed. Brokers
        without the kwarg (older test doubles) fall back to auto-commit —
        and commit-after-route disarms itself, because the discipline is
        a lie over a consumer that commits on poll."""
        if not (manual and self._commit_after_route):
            return self.broker.consumer(group, topics)
        try:
            return self.broker.consumer(group, topics, auto_commit=False)
        except TypeError:
            self._commit_after_route = False
            return self.broker.consumer(group, topics)

    @staticmethod
    def _tx_offsets(records: list) -> dict[tuple[str, int], int] | None:
        """Commit positions for one poll's records: max offset + 1 per
        (topic, partition). Computed BEFORE admission — shed records are
        disposed (counted in router_shed_total) and must commit with the
        batch, or they would redeliver forever."""
        if not records:
            return None
        offs: dict[tuple[str, int], int] = {}
        for r in records:
            tp = (r.topic, r.partition)
            nxt = r.offset + 1
            if nxt > offs.get(tp, 0):
                offs[tp] = nxt
        return offs

    def _commit_routed(self, offs: dict | None, batch: int = 0) -> None:
        """Commit a fully-disposed batch's offsets (manual mode only).

        A fence (the group rebalanced since this batch was polled) is
        COUNTED and absorbed: the records redeliver to the partitions'
        current owners — the at-least-once outcome the fleet accounting
        tracks as cross-epoch redeliveries, never a loop crash. Transport
        errors likewise leave the batch uncommitted (it redelivers)."""
        if not self._commit_after_route or offs is None:
            return
        with phase("router.commit", partitions=len(offs), batch=batch):
            try:
                self._tx_consumer.commit(offs)
            except StaleEpochError:
                self._c_fenced.inc()
            except Exception:  # noqa: BLE001 - bus edge down; batch redelivers
                self._c_commit_err.inc()

    # -- loop stages (composed by step() and the pipelined run loop) -------
    def _drain_signals(self) -> None:
        """Notification-counter drain + customer-response signal forwarding."""
        with phase("router.signals") as ph:
            for rec in self._notif_watcher.poll(self.max_batch, 0.0):
                self._c_notif_out.inc()

            responses = self._resp_consumer.poll(self.max_batch, 0.0)
            ph.set(responses=len(responses))
            for rec in responses:
                payload = rec.value or {}
                approved = bool(payload.get("approved"))
                self._c_notif_in.inc(labels={
                    "response": "approved" if approved else "non_approved"})
                pid = payload.get("process_id")
                if pid is not None:
                    try:
                        self.engine.signal(int(pid), CUSTOMER_RESPONSE_SIGNAL,
                                           payload)
                    except Exception:
                        # remote engine briefly unreachable: the rest of the
                        # already-consumed response batch must still forward
                        self._c_signal_err.inc()

    def _poll_batch(self, poll_timeout_s: float) -> list:
        """Size x deadline micro-batching (SURVEY.md §7 stage 3): after the
        first records arrive, keep accumulating until the batch bucket
        fills or batch_deadline_ms elapses — under sustained load the TPU
        dispatch amortizes over a full bucket, while the deadline bounds
        the latency a lone transaction can be held for.

        With the overload plane armed the poll is budget-PREPAID: the
        loop reserves in-flight room BEFORE consuming and polls at most
        the grant, so a record is never consumed that cannot be admitted
        (consuming past capacity would force shedding records of EVERY
        priority — the inversion the plane exists to prevent). With no
        room the loop does not consume at all: backpressure propagates —
        the backlog stays in the bus, where the producer (and the Bus
        board) observe it as lag (``bus_topic_backlog``) instead of an
        unbounded consumed-then-shed churn. Polling resumes as routed
        batches release rows."""
        with phase("router.poll") as ph:
            records = self._poll_records(poll_timeout_s)
            ph.set(rows=len(records))
        return records

    def _poll_records(self, poll_timeout_s: float) -> list:
        cap = self.max_batch
        granted = -1
        if self._overload is not None:
            granted = self._budget.reserve(self.max_batch)
            if granted <= 0:
                if poll_timeout_s > 0:
                    time.sleep(min(poll_timeout_s, 0.02))
                return []
            cap = granted
        records = self._tx_consumer.poll(cap, poll_timeout_s)
        if records:
            deadline_s = self.cfg.batch_deadline_ms / 1e3
            if deadline_s > 0 and len(records) < cap:
                deadline = time.perf_counter() + deadline_s
                while len(records) < cap:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    more = self._tx_consumer.poll(
                        cap - len(records), remaining
                    )
                    if not more:
                        break  # poll slept out the remaining deadline
                    records.extend(more)
        if granted >= 0 and granted > len(records):
            self._budget.release(granted - len(records))
        return records

    # -- tracing helpers ---------------------------------------------------
    def _begin_batch_span(self, records: list):
        """Open the micro-batch span, parented on the trace context the
        producer stamped onto the records (first stamped record wins — a
        batch mixes producer batches; per-stage attribution needs ONE
        parent and the stages are batch-granular anyway). Returns None
        when tracing is off."""
        if self.tracer is None:
            return None
        parent = None
        for rec in records[:16]:  # stamped records carry it up front
            h = getattr(rec, "headers", None)
            if h:
                parent = extract_context(h)
                if parent is not None:
                    break
        attrs: dict = {"records": len(records)}
        if self.worker_id is not None:
            attrs["worker"] = self.worker_id
        return self.tracer.start("router.batch", parent=parent, attrs=attrs)

    def _stage(self, name: str, batch_span, rows: int, **stats) -> phase:
        """One stage of a micro-batch: always an event in a device
        capture, and under a tracer a child span of the batch's."""
        return phase(name, self.tracer,
                     batch_span.context if batch_span is not None else None,
                     rows=rows, **stats)

    def _decode_batch(
        self, records: list, batch_span=None, batch: int = 0
    ) -> tuple[np.ndarray, list, np.ndarray]:
        n = len(records)
        t0 = time.perf_counter()
        # the stage whole, as the profiler's router.decode times it: the
        # counters, the decode, the timestamps and the queueing delay
        with self._stage("router.decode", batch_span, n, batch=batch):
            self._c_in.inc(n)
            self._h_batch.observe(n)
            self._c_worker_batch.inc(labels=self._worker_labels)
            x, txs, bad = decode_records(records)
            if bad:
                self._c_decode_err.inc(bad)
            # produce timestamps ride along so _route can observe the
            # end-to-end decision latency (producer -> process start)
            ts = np.fromiter((r.timestamp for r in records), np.float64, n)
            if self._profiler is not None or batch_span is not None:
                # bus queueing delay: how long this batch's rows waited on
                # the topic before the poll (mean across the batch — the
                # component that sums with service/dispatch to the
                # decision latency)
                # ccfd-lint: disable=monotonic-durations -- record timestamps are wall-clock by contract (cross-process); max(0,...) clamps an NTP step
                queue_s = max(0.0, time.time() - float(ts.mean()))
                if batch_span is not None:
                    # ride the span too: the profiler's span-ingestion
                    # path (and offline trace analysis) reads it from the
                    # attrs
                    batch_span.attrs["queue_s"] = queue_s
                if self._profiler is not None:
                    self._profiler.observe("bus", queue_s=queue_s, rows=n)
                    self._profiler.observe(
                        "router.decode",
                        service_s=time.perf_counter() - t0, batch=n, rows=n)
        return x, txs, ts

    # -- decision provenance -----------------------------------------------
    def _audit_meta(self, records: list) -> dict | None:
        """Per-batch audit context, built while the bus records (the only
        carriers of partition/offset and priority headers) are still in
        scope. Rides WITH the batch through score and route — the
        pipelined loop scores batch k while routing k-1, so batch-scoped
        state must never live on ``self``."""
        if self._audit is None:
            return None
        names, pri = self._pri_names, self._rec_pri
        return {
            "uids": [f"{r.partition}:{r.offset}" for r in records],
            "pris": [names[pri(r)] for r in records],
            "events": [],
            "tier": "device",
            "cause": None,
        }

    # -- degradation ladder ------------------------------------------------
    def _shed_oldest(self, records: list) -> list:
        """Bounded in-flight: drop the OLDEST consumed records when a poll
        would push consumed-but-unrouted work past the budget. Under
        total saturation (every tier slow AND the bus backlogged) shedding
        the stalest work keeps decision latency bounded for what remains —
        the SRE load-shedding move. Shed records still count as incoming
        (they were consumed); ``router_shed_total`` records the drops.

        The budget is RESERVED here and released once the surviving rows
        are fully routed — with a shared budget (ParallelRouter) the bound
        therefore holds across every worker, not per loop."""
        granted = self._budget.reserve(len(records))
        if granted == len(records):
            return records
        shed = len(records) - granted
        self._c_in.inc(shed)
        self._c_shed.inc(shed)
        return records[shed:] if granted else []

    def _begin_batch(self, records: list) -> tuple[int, dict | None, list]:
        """One poll's records become a batch: its ordinal, its commit
        positions and the records admission lets through."""
        self._batches += 1
        batch = self._batches
        with phase("router.admit", batch=batch, rows=len(records)) as ph:
            offs = self._tx_offsets(records)
            kept = self._admit(records)
            ph.set(admitted=len(kept), shed=len(records) - len(kept))
        return batch, offs, kept

    def _admit(self, records: list) -> list:
        """Admission for one poll's records. With the overload plane armed
        the decision is deadline- and priority-aware (stale rows drop from
        the front, budget victims are picked bulk-first/critical-last,
        runtime/overload.py); without it, the historical oldest-first
        bounded-in-flight shed. Either way the budget is reserved for
        exactly the survivors and ``router_shed_total`` counts the drops."""
        if self._overload is None:
            return self._shed_oldest(records)
        keep, shed = self._overload.admit(records, prepaid=True)
        if shed:
            self._c_in.inc(shed)  # shed records were still consumed
            self._c_shed.inc(shed)
        return keep

    def _rules_proba(self, x: np.ndarray) -> np.ndarray:
        """Rules-only tier: a conservative ``FRAUD_THRESHOLD`` stand-in
        with no model at all. High-amount transactions (the reference
        engine's own risk split, CCFD_LOW_AMOUNT) take proba exactly AT
        the threshold so the salience-ordered fraud rule fires — flagging
        for investigation is the conservative failure mode for a fraud
        system — and the rest score 0.0 (standard). Every transaction
        still gets a decision through the normal rule base."""
        thr = np.float32(self.cfg.fraud_threshold)
        risky = x[:, self._amount_idx] >= self.cfg.low_amount_threshold
        return np.where(risky, thr, np.float32(0.0)).astype(np.float32)

    def _score_tiered(self, x: np.ndarray, txs: list,
                      span=None, meta=None) -> tuple:
        """device scorer → host numpy forward → rules-only. Never raises:
        the bottom tier is pure numpy over data already in hand. ``span``
        (when tracing) gets the degraded-tier flag — a trace scored by a
        fallback tier is always tail-sampled KEEP. ``meta`` (when the
        audit plane is armed) records the tier that actually produced
        the batch's scores and why the ladder fell.

        Returns ``(proba, fired)``: ``fired`` is the device-computed rule
        index vector when the fused decision plane produced this batch's
        verdicts, else None (host rules pass runs in ``_route_inner``).
        Fallback tiers always return fired=None — a degraded score must
        re-enter the full host rule base, never a stale device verdict."""
        gate = self._heal_gate
        host_blocked = False
        if gate is not None and not gate.device_allowed():
            # device quarantined (runtime/heal.py): the ladder is pinned
            # to the host tier. Checked BEFORE the breaker so a HALF_OPEN
            # probe slot cannot route live rows to the sick device — the
            # heal supervisor's own canary is the only probe allowed.
            if span is not None:
                span.attrs["quarantined"] = True
            # storage pin (runtime/durability.StoragePinGate): when NO
            # params generation verifies, the host tier would forward the
            # very same unverified tree — the ladder pins all the way to
            # the rules floor until a verified tree is published
            host_ok = getattr(gate, "host_allowed", None)
            host_blocked = callable(host_ok) and not host_ok()
            if meta is not None:
                meta["cause"] = ("storage_pin" if host_blocked
                                 else "quarantine")
        elif self._breaker is None or self._breaker.allow():
            br = self._breaker
            t0 = time.perf_counter()
            try:
                ov = self._overload
                if ov is not None and ov.dispatch_deadline_s > 0:
                    # dispatch watchdog: a hung/slow device dispatch is
                    # killed at the deadline and lands in this except —
                    # one breaker failure and a ladder fall, not a
                    # stalled worker
                    proba, fired = ov.bounded_dispatch(
                        lambda: self._score2(x, txs))
                else:
                    proba, fired = self._score2(x, txs)
                lat = time.perf_counter() - t0
                # corrupt-response validation: a fault-injected (or truly
                # version-skewed) reply with the wrong shape or non-finite
                # values must degrade, not route garbage decisions
                if proba.shape != (len(txs),) or not np.isfinite(proba).all():
                    raise ValueError("invalid scorer response")
                # fused verdicts get the same treatment: an index vector
                # of the wrong shape or out of the rule table's range
                # must degrade this batch, not mis-route it
                if fired is not None and (
                        getattr(fired, "shape", None) != (len(txs),)
                        or int(fired.min()) < 0
                        or int(fired.max()) >= len(self.rules.rules)):
                    raise ValueError("invalid scorer response")
                if br is not None:
                    br.record_success(lat)
                return proba, fired
            except Exception as e:
                if br is not None:
                    br.record_failure(time.perf_counter() - t0)
                self._c_score_err.inc(len(txs))
                if meta is not None:
                    # a watchdog kill is its own event class: the record
                    # must say "this decision fell to a fallback tier
                    # because the device dispatch was killed", not just
                    # "an error happened"
                    ev = ("watchdog_timeout"
                          if type(e).__name__ == "ScorerTimeout"
                          else "score_error")
                    meta["events"].append(ev)
                    meta["cause"] = meta["cause"] or ev
        elif span is not None or meta is not None:
            if span is not None:
                span.attrs["breaker_open"] = True
            if meta is not None:
                meta["events"].append("breaker_open")
                meta["cause"] = meta["cause"] or "breaker_open"
        if self._host_score is not None and not host_blocked:
            try:
                proba = np.asarray(self._host_score(x), np.float32)
                if proba.shape == (len(txs),) and np.isfinite(proba).all():
                    self._c_degraded.inc(len(txs), labels={"tier": "host"})
                    if span is not None:
                        span.attrs["degraded"] = "host"
                    if meta is not None:
                        meta["tier"] = "host"
                    return proba, None
            except Exception:  # noqa: BLE001 - fall to the rules tier
                # a host-forward failure was invisible before: the ladder
                # fell straight through and only the rules-tier counter
                # moved, so "host tier is broken" never had its own signal
                self._c_host_err.inc(len(txs))
        self._c_degraded.inc(len(txs), labels={"tier": "rules"})
        if span is not None:
            span.attrs["degraded"] = "rules"
        if meta is not None:
            meta["tier"] = "rules"
        return self._rules_proba(x), None

    def _score_direct(self, x: np.ndarray, txs: list,
                      span=None, meta=None) -> tuple:
        """Legacy non-ladder path — but the heal gate still binds: a
        quarantined device must not see live rows even when the
        degradation ladder is off (``router.degrade: false`` CRs). With
        no host tier wired here, the always-available rules tier makes
        the conservative decision until the supervisor re-promotes."""
        gate = self._heal_gate
        if gate is not None and not gate.device_allowed():
            if span is not None:
                span.attrs["quarantined"] = True
                span.attrs["degraded"] = "rules"
            if meta is not None:
                meta["tier"] = "rules"
                meta["cause"] = "quarantine"
            self._c_degraded.inc(len(txs), labels={"tier": "rules"})
            return self._rules_proba(x), None
        return self._score2(x, txs)

    def _score_batch(self, x: np.ndarray, txs: list,
                     batch_span=None, meta=None, **stats) -> tuple:
        with self._stage("router.score", batch_span, len(txs),
                         **stats) as ph:
            if self._degrade:
                return self._score_tiered(x, txs, span=ph.span, meta=meta)
            return self._score_direct(x, txs, span=ph.span, meta=meta)

    # -- one synchronous cycle (used by tests and the run loop) ------------
    def step(self, poll_timeout_s: float = 0.0) -> int:
        """Route one poll's worth of work; returns #transactions scored."""
        self._drain_signals()
        records = self._poll_batch(poll_timeout_s)
        if not records:
            return 0
        batch, offs, records = self._begin_batch(records)
        if not records:
            # fully shed: every record is disposed (counted), the batch
            # is complete — commit it
            self._commit_routed(offs, batch)
            return 0
        batch_sp = None
        meta = self._audit_meta(records)
        try:
            batch_sp = self._begin_batch_span(records)
            x, txs, ts = self._decode_batch(records, batch_sp, batch)
            t0 = time.perf_counter()
            proba, fired = self._score_batch(x, txs, batch_sp, meta,
                                             batch=batch)
            score_s = time.perf_counter() - t0
            self._h_score_s.observe(
                score_s,
                exemplar=({"trace_id": batch_sp.trace_id}
                          if batch_sp is not None else None))
            if self._overload is not None:
                # AIMD feedback: the scorer stage's measured latency vs its
                # budget is what moves the adaptive in-flight limit
                self._overload.observe_stage(score_s)
            if self._profiler is not None:
                self._profiler.observe("router.score", dispatch_s=score_s,
                                       batch=len(txs), rows=len(txs))
            n = self._route(x, txs, proba, ts, batch_span=batch_sp,
                            meta=meta, fired=fired, batch=batch)
            # commit ONLY after every record has a terminal disposition
            # (routed/shed/errored); a crash above leaves the batch
            # uncommitted, so it redelivers instead of vanishing
            self._commit_routed(offs, batch)
            return n
        except BaseException:
            # a crashed batch is exactly the trace an operator needs:
            # error status forces the tail sampler's keep
            if batch_sp is not None:
                batch_sp.status = "error"
            raise
        finally:
            self._budget.release(len(records))
            if batch_sp is not None:
                self.tracer.finish(batch_sp)

    def _route(self, x: np.ndarray, txs: list, proba: np.ndarray,
               ts: np.ndarray | None = None, batch_span=None,
               meta=None, fired: np.ndarray | None = None,
               batch: int = 0) -> int:
        t0 = time.perf_counter() if self._profiler is not None else 0.0
        try:
            # under a tracer the phase's span is ACTIVE on this thread:
            # the engine calls below (and the notification records the
            # engine produces inside them, process/fraud.py notify) read
            # current_context() to join the trace — an unactivated span
            # would orphan the engine/notify leg
            with self._stage("router.route", batch_span, len(txs),
                             batch=batch) as ph:
                n = self._route_inner(x, txs, proba, ts, batch_span,
                                      ph.span, meta, fired)
            if self._startup is not None:
                self._startup.first_verdict()
                self._startup = None
            return n
        finally:
            if self._profiler is not None:
                self._profiler.observe(
                    "router.route", service_s=time.perf_counter() - t0,
                    batch=len(txs), rows=len(txs))

    def _route_inner(self, x: np.ndarray, txs: list, proba: np.ndarray,
                     ts: np.ndarray | None, batch_span, route_sp,
                     meta=None, fired: np.ndarray | None = None) -> int:
        if fired is None:
            fired = self.rules.evaluate(x, proba)
        # group the micro-batch by fired rule: one batched process-start per
        # (rule, process) instead of one engine round-trip per transaction —
        # the engine amortizes its lock (and the remote client its HTTP hop)
        # over the group, which is what lets L5 absorb the TPU scorer's
        # output rate (VERDICT r1: engine throughput >= scorer throughput).
        # tolist() first: iterating numpy arrays yields numpy scalars whose
        # per-element unboxing (and float() calls) dominated this loop's
        # profile — one C-speed conversion, then plain-Python iteration.
        # This loop is GIL-bound and runs once per worker batch, so its
        # constant factor IS the parallel fan-out's scaling ceiling.
        groups: dict[int, list[dict]] = {}
        rules = self.rules.rules
        plist = proba.tolist()
        # audit plane armed: track each group's original row indices so a
        # successful start stamps THAT row's tx/uid/priority/timestamp —
        # and only successful starts (conservation: routed == recorded;
        # a failed start is counted in router_process_start_errors_total,
        # not in the provenance stream)
        gidx: dict[int, list[int]] | None = \
            {} if (self._audit is not None and meta is not None) else None
        audit_rows: list[dict] = []
        ts_list = (ts.tolist()
                   if gidx is not None and ts is not None else None)
        # replay plane armed: embed the DECODED feature row per record so
        # audit segments alone reconstruct a re-scorable window (one
        # C-speed tolist outside the loop; off = zero cost)
        x_list = (x.tolist()
                  if gidx is not None
                  and getattr(self._audit, "capture_rows", False) else None)
        for i, (tx, p, ridx) in enumerate(zip(txs, plist, fired.tolist())):
            variables = {
                "transaction": tx,
                "proba": p,
                "customer_id": tx.get("id"),
            }
            set_vars = rules[ridx].set_vars
            if set_vars:
                variables.update(set_vars)
            g = groups.get(ridx)
            if g is None:
                groups[ridx] = [variables]
            else:
                g.append(variables)
            if gidx is not None:
                gi = gidx.get(ridx)
                if gi is None:
                    gidx[ridx] = [i]
                else:
                    gi.append(i)
        for ridx, vars_list in groups.items():
            rule = self.rules.rules[ridx]
            try:
                if self._start_batch is not None:
                    pids = (self._start_batch(rule.process, vars_list,
                                              copy_vars=False)
                            if self._start_nocopy
                            else self._start_batch(rule.process, vars_list))
                else:  # engine without the batch API: per-item, isolated
                    pids = []
                    for variables in vars_list:
                        try:
                            pids.append(
                                self.engine.start_process(rule.process, variables)
                            )
                        # ccfd-lint: disable=counted-drops -- the None sentinel is counted below (n_err -> router_process_start_errors_total)
                        except Exception:
                            pids.append(None)
            except Exception:
                # bad rule target or unreachable remote engine: the whole
                # group failed to start, but the routing loop (and the other
                # groups in this poll) must keep going
                self._c_start_err.inc(len(vars_list), labels={"type": rule.process})
                continue
            n_err = sum(1 for p in pids if p is None)
            if n_err:
                self._c_start_err.inc(n_err, labels={"type": rule.process})
            n_ok = len(pids) - n_err
            if n_ok:
                self._c_out.inc(n_ok, labels={"type": rule.process})
                self._c_rule.inc(n_ok, labels={"rule": rule.name})
                if route_sp is not None and "fraud" in rule.process:
                    # fraud-routed batches are always tail-sampled KEEP
                    route_sp.attrs["fraud"] = True
                if gidx is not None:
                    idx_list = gidx[ridx]
                    for j, pid in enumerate(pids):
                        if pid is None:
                            continue
                        i = idx_list[j]
                        row = {
                            "tx": txs[i].get("id"),
                            "uid": meta["uids"][i],
                            "ts": ts_list[i] if ts_list is not None else None,
                            "proba": plist[i],
                            "rule": rule.name,
                            "branch": rule.process,
                            "pid": pid,
                            "priority": meta["pris"][i],
                        }
                        # a replayed transaction carries its origin marker
                        # through the decode seam; stamping it onto the
                        # record lets the ReplayVerdictTap divert the
                        # verdict to the parity join instead of the
                        # provenance log
                        mk = txs[i].get("_replay")
                        if mk is not None:
                            row["replay"] = mk
                        if x_list is not None:
                            row["row"] = x_list[i]
                        audit_rows.append(row)
        if audit_rows:
            self._audit.record_batch(
                audit_rows,
                tier=meta.get("tier", "device"),
                cause=meta.get("cause"),
                events=tuple(meta.get("events", ())),
                worker=self.worker_id,
                trace_id=(batch_span.trace_id
                          if batch_span is not None else None),
                threshold=self.cfg.fraud_threshold,
            )
        if ts is not None and len(ts):
            # ccfd-lint: disable=monotonic-durations -- produce stamps are wall-clock record timestamps (cross-process decision latency)
            self._h_decision_s.observe_many(time.time() - ts)
        return len(txs)

    # -- checkpoint barrier ------------------------------------------------
    def pause(self, timeout_s: float = 10.0) -> bool:
        """Request a batch-boundary hold and wait for the loop to ack.

        On True, the loop is parked with every consumed record fully routed
        (in-flight scoring batch finished and started into the engine) and
        will stay parked until :meth:`resume` — the window in which an
        engine snapshot + committed offsets are a consistent cut. Returns
        False if no ack arrived (router stopped/crashed/not running); the
        caller decides whether proceeding is safe (a dead router isn't
        mutating engine state either).

        Holds nest: every pause() needs a matching resume(); the loop
        stays parked until the last holder releases."""
        self.request_pause()
        return self.await_pause(timeout_s)

    def request_pause(self) -> None:
        """Take a pause hold and signal the loop, WITHOUT waiting for the
        ack. The group-wide barrier (ParallelRouter) requests every
        worker's hold first, then awaits all acks — requesting
        sequentially with per-worker waits would let later workers keep
        consuming while earlier ones park, and the combined wait could
        take N× the timeout."""
        with self._pause_mu:
            self._pause_holders += 1
            self._pause_req.set()

    def await_pause(self, timeout_s: float) -> bool:
        """Wait for a previously requested pause to be acked."""
        return self._pause_ack.wait(timeout=timeout_s)

    def resume(self) -> None:
        with self._pause_mu:
            if self._pause_holders > 0:
                self._pause_holders -= 1
            if self._pause_holders == 0:
                self._pause_req.clear()

    def _pause_point(self) -> None:
        """Called by the run loops at a batch boundary."""
        self._pause_ack.set()
        while self._pause_req.is_set() and not self._stop.is_set():
            time.sleep(0.005)
        self._pause_ack.clear()

    def recycle_consumers(self) -> None:
        """Close and recreate the bus consumers — with the loop parked at
        the pause barrier (or stopped). Crash recovery calls this before
        rewinding group offsets: a parked loop still leaves the old
        consumers as LIVE group members on a real Kafka cluster
        (kafka-python heartbeats run on a background thread), and Kafka
        refuses offset resets for a non-empty group. In-process the same
        sequence is a cheap rebalance. The recreated consumers resume at
        the (about-to-be-rewound) committed offsets, like any group
        member."""
        for attr, group, topics, manual in self._consumer_specs:
            try:
                getattr(self, attr).close()
            except Exception:  # noqa: BLE001 - a dead consumer is fine here
                logging.getLogger("ccfd_tpu.router").debug(
                    "stale consumer %s failed to close during recycle",
                    attr, exc_info=True)
            setattr(self, attr, self._build_consumer(group, topics, manual))

    def set_heal_gate(self, gate: Any) -> None:
        """Arm (or, with None, disarm) the device heal gate after
        construction — the operator builds the DeviceSupervisor after the
        router (it needs the flight recorder from a later bring-up step)
        and points the ladder at it here. One attribute publish; the next
        batch sees it."""
        self._heal_gate = gate

    def swap_engine(self, engine: EngineClient) -> None:
        """Point the router at a replacement engine — crash recovery swaps
        in a snapshot-restored instance (runtime/recovery.py). The router
        must be paused or stopped. Re-validates rule targets and rebinds
        the cached batched-start path."""
        list_defs = getattr(engine, "definitions", None)
        if callable(list_defs):
            missing = {r.process for r in self.rules.rules} - set(list_defs())
            if missing:
                raise ValueError(
                    f"replacement engine lacks processes {sorted(missing)}"
                )
        self.engine = engine
        self._start_batch = getattr(engine, "start_process_batch", None)
        self._start_nocopy = bool(getattr(engine, "start_batch_nocopy",
                                          False))

    # -- daemon loop -------------------------------------------------------
    def reset(self) -> None:
        """Re-arm after stop() so the next run() actually loops. Called by
        the supervisor before each respawn (NOT inside run(): clearing on
        the service thread would race a concurrent stop() and erase it)."""
        self._stop.clear()

    def run(self, poll_timeout_s: float = 0.05, pipeline: bool = True) -> None:
        if pipeline:
            self._run_pipelined(poll_timeout_s)
        else:
            while not self._stop.is_set():
                if self._pause_req.is_set():
                    self._pause_point()
                    continue
                self.step(poll_timeout_s)

    def _run_pipelined(self, poll_timeout_s: float) -> None:
        """Overlap the device dispatch with everything else.

        ``step`` blocks the loop for the full scorer round trip, during
        which no polling, rule eval, or process starts happen. Here batch k's dispatch runs on a dedicated
        thread (XLA releases the GIL for the device wait) while the loop
        routes batch k-1's results into the engine and polls batch k+1:
        the device and the Python/engine work pipeline instead of taking
        turns. One stage in flight is enough — depth beyond 1 only adds
        queueing latency because the loop itself is busy between waits.

        A ``score_with_ids`` scorer may return batch k's scores before
        they are ready (the records it gets are marked: it keeps k open
        and resolves it inside its call for k+1, whose gather and
        transfer so run beside k's device time). The worker then forces
        nothing; ``finish`` does, on this thread, before it routes. Two
        batches consumed and unrouted is still all there is: k+1 is
        submitted before k is finished, as it always was, and routing,
        offset commits and budget release stay in batch order. Not for a
        pool's worker (its scorer is shared, and the workers' calls
        already run beside one another's device time), nor under the
        ladder, which has to see the scores to judge them.
        """
        from concurrent.futures import ThreadPoolExecutor, wait

        defer = (self.worker_id is None and not self._degrade
                 and self._decision_fn is None)

        def observe_score(score_s: float, batch_sp, rows: int) -> None:
            self._h_score_s.observe(
                score_s,
                exemplar=({"trace_id": batch_sp.trace_id}
                          if batch_sp is not None else None))
            if self._overload is not None:
                self._overload.observe_stage(score_s)
            if self._profiler is not None:
                self._profiler.observe("router.score", dispatch_s=score_s,
                                       batch=rows, rows=rows)

        returned_ns = 0  # the worker's clock when its last call returned

        def timed_score(x: np.ndarray, txs: list, batch_sp, meta,
                        batch: int, submitted_ns: int) -> tuple:
            # time INSIDE the worker so the histogram records the scorer
            # round trip, not dispatch + however long the loop polled
            # (a deferred result's round trip ends when it is ready:
            # finish observes it). batch_sp (and the audit meta) ride
            # along explicitly — the worker thread has no ambient trace
            # context (contextvars are per-thread), and batch-scoped audit
            # state must never live on self while two batches are in flight.
            # The hand-over is measured where it happens: ``handoff_ns``
            # from the loop's submit to this first statement (both threads
            # and the feeder share one interpreter lock), ``idle_ns`` since
            # this worker's last return (0 for its first batch)
            nonlocal returned_ns
            start_ns = time.perf_counter_ns()
            t0 = start_ns / 1e9
            try:
                proba, fired = self._score_batch(
                    x, txs, batch_sp, meta, batch=batch,
                    handoff_ns=start_ns - submitted_ns,
                    idle_ns=start_ns - returned_ns if returned_ns else 0)
            finally:
                returned_ns = time.perf_counter_ns()
            if not _deferred(proba):
                observe_score(returned_ns / 1e9 - t0, batch_sp, len(txs))
            return proba, fired, t0

        def finish(pending: tuple, newer: tuple | None = None) -> None:
            pfut, px, ptxs, pts, psp, pmeta, poffs, pn = pending
            try:
                try:
                    # the loop blocked on the score worker: for the whole
                    # dispatch where the scorer resolves inside its call,
                    # for the worker's call for the NEWER batch where it
                    # defers
                    with self._stage("router.await", psp, len(ptxs),
                                     batch=pn) as ph:
                        proba, fired, t0 = pfut.result()
                        deferred = _deferred(proba)
                        ph.set(deferred=int(deferred))
                        if deferred and newer is not None:
                            # the worker readies these scores inside its
                            # call for the newer batch, which is already
                            # submitted: forcing them before it got there
                            # would take the scorer from it and put the
                            # two batches back in series
                            wait((newer[0],))
                    if deferred:
                        # ready already unless this is the stream's last
                        # batch, light load or a pause point: then the
                        # scorer's wait and commit run here, on this thread
                        with self._stage("router.force", psp, len(ptxs),
                                         batch=pn):
                            scores, proba = proba, np.asarray(proba)
                        observe_score(scores.ready_at - t0, psp, len(ptxs))
                except Exception:
                    # a transient scorer failure (e.g. remote model timeout)
                    # drops this batch, not the routing loop. The drop IS
                    # a terminal disposition (counted in
                    # router_score_errors_total), so the batch commits —
                    # redelivering it would double-count the error
                    self._c_score_err.inc(len(ptxs))
                    if psp is not None:
                        psp.status = "error"
                    self._commit_routed(poffs, pn)
                    return
                self._route(px, ptxs, proba, pts, batch_span=psp,
                            meta=pmeta, fired=fired, batch=pn)
                self._commit_routed(poffs, pn)
            except BaseException:
                if psp is not None:  # _route crashed: force-keep the trace
                    psp.status = "error"
                raise
            finally:
                self._budget.release(len(ptxs))
                if psp is not None:
                    self.tracer.finish(psp)

        ex = ThreadPoolExecutor(1, thread_name_prefix="ccfd-router-score")
        # (future, x, txs, ts, batch_span, meta, offsets, ordinal)
        pending: tuple | None = None
        try:
            while not self._stop.is_set():
                if self._pause_req.is_set():
                    # finish the in-flight batch BEFORE acking: the ack
                    # promises nothing consumed-but-unrouted exists.
                    # (swap-then-finish everywhere in this loop: if
                    # finish raises, the batch must NOT still be pending —
                    # the outer finally would finish it a second time,
                    # double-routing its groups into the engine and
                    # double-releasing its rows from the SHARED budget)
                    if pending is not None:
                        done, pending = pending, None
                        finish(done)
                    self._pause_point()
                    continue
                self._drain_signals()
                # with a batch in flight, don't sleep on an empty topic:
                # grab whatever is already queued and route the in-flight
                # result promptly — a lone transaction's end-to-end latency
                # stays ~one scorer round trip instead of round trip +
                # poll_timeout (sparse-traffic p99)
                records = self._poll_batch(
                    0.0 if pending is not None else poll_timeout_s
                )
                if records:
                    # bounded in-flight: batch k-1's rows are still
                    # reserved (consumed-but-unrouted) while k is being
                    # submitted — the budget reserve inside _admit
                    # accounts for them (and, under ParallelRouter, for
                    # every other worker's in-flight rows too)
                    batch, offs, records = self._begin_batch(records)
                    if not records:
                        # fully shed: disposed (counted) — commit now
                        self._commit_routed(offs, batch)
                fut = None
                if records:
                    batch_sp = None
                    meta = self._audit_meta(records)
                    try:
                        batch_sp = self._begin_batch_span(records)
                        x, txs, ts = self._decode_batch(records, batch_sp,
                                                        batch)
                        # the loop's side of the hand-over: the mark, the
                        # executor's queue, waking the worker and getting
                        # the interpreter back from it
                        with self._stage("router.submit", batch_sp,
                                         len(txs), batch=batch):
                            if defer:
                                txs = DeferrableRecords(txs)
                            fut = ex.submit(
                                timed_score, x, txs, batch_sp, meta, batch,
                                time.perf_counter_ns())
                    except BaseException:
                        # reserved rows must not leak out of a crashed
                        # loop (with a SHARED budget the leak would
                        # throttle every other worker forever), and the
                        # crashed batch's span is exactly the post-mortem
                        # trace the tail sampler must keep
                        self._budget.release(len(records))
                        if batch_sp is not None:
                            batch_sp.status = "error"
                            self.tracer.finish(batch_sp)
                        raise
                done, pending = pending, (
                    (fut, x, txs, ts, batch_sp, meta, offs, batch)
                    if fut is not None else None)
                if done is not None:
                    try:
                        finish(done, pending)
                    except BaseException:
                        # the loop is going down and the batch just
                        # submitted can never be routed: release its rows
                        # (shared-budget leak-proofing), count it as
                        # dropped, and keep its trace
                        if pending is not None:
                            _, _, ptxs, _, psp, _pm, _po, _pn = pending
                            pending = None
                            self._budget.release(len(ptxs))
                            self._c_score_err.inc(len(ptxs))
                            if psp is not None:
                                psp.status = "error"
                                self.tracer.finish(psp)
                        raise
        finally:
            try:
                if pending is not None:
                    finish(pending)
            finally:
                ex.shutdown()

    def start(
        self, poll_timeout_s: float = 0.05, pipeline: bool = True
    ) -> threading.Thread:
        with trace.startup.phase("startup.router", threads=1):
            # direct (unsupervised) start: re-arm here, before the thread
            # exists
            self.reset()
            t = threading.Thread(
                target=self.run, args=(poll_timeout_s, pipeline),
                daemon=True, name="ccfd-router",
            )
            t.start()
        trace.startup.ready()  # the first router started ends the build
        return t

    def stop(self) -> None:
        self._stop.set()

    def close(self) -> None:
        self.stop()
        self._tx_consumer.close()
        self._resp_consumer.close()
        self._notif_watcher.close()
