"""Pipeline-wide distributed tracing: spans, context propagation, tail sampling.

The reference demo exposes per-service JVM introspection ports and nothing
application-level (SURVEY.md §5); the old ``utils/tracing.py`` recorded
process-local spans into a private registry the exporter never served. This
module replaces it with a real tracing subsystem, shaped by the needs of
pipeline-latency attribution (InferLine, arXiv:1812.01776: tight pipeline
SLOs need per-stage critical-path visibility, not endpoint histograms):

- **Context propagation** — W3C ``traceparent`` (``00-<trace>-<span>-<flags>``)
  injected by every HTTP client hop (utils/httpclient.py, serving/client.py,
  store/client.py) and extracted by every server surface (serving, engine
  REST, bus server, metrics exporter), plus carriage through bus records
  (``Broker.produce(..., headers=...)``) so one produced batch yields one
  end-to-end trace from producer through router → scorer → engine → notify.
- **Per-component tracers** — :class:`Tracer` records span durations into the
  component's SCRAPED registry (``trace_span_seconds{span=...}``; the
  operator wires each tracer to the same registry the exporter serves —
  fixing the old unscraped-private-registry bug) and feeds finished spans to
  a shared in-process :class:`SpanSink`.
- **Tail-based sampling** — the sink keeps every trace that is slow, errored
  or flagged (fraud-routed, degraded-tier, breaker-refused — callers set
  span attrs), and a deterministic hash fraction (``CCFD_TRACE_SAMPLE``) of
  the boring rest. Decisions happen at the TAIL (after spans arrive), which
  is the only way "always keep the interesting ones" can be honored.
- **Exemplars** — span trace-ids attach to the existing latency histograms
  (metrics/prom.py exemplar support), so a Grafana heat-map cell links to
  the exact retained trace via the exporter's ``/traces/<id>`` endpoint.

- **Phases on the profiler's timeline** — :class:`phase` marks one stretch
  of the served path (router loop, score worker, scorer, store, device
  wait). It always opens a ``jax.profiler.TraceAnnotation`` of the same
  name, so the phase is an event on the host plane of ANY device capture,
  beside ``XLA Ops`` on one timeline, whoever started the capture; under an
  active span it is also a child :class:`Span` in the same sink. The
  phases that exist: on the pipelined router's loop thread, which they
  cover end to end, ``router.signals``, ``router.poll``, ``router.admit``,
  ``router.decode``, ``router.submit`` (the loop's side of the hand-over),
  ``router.await`` (blocked on the score worker), ``router.force`` (a
  deferred result made ready here), ``router.route``, ``router.commit``;
  on its score worker ``router.score`` (with ``handoff_ns`` and
  ``idle_ns``: the hand-over between the two threads, read where it
  happens) > ``seq.score`` > ``seq.gather``, ``seq.pad``,
  ``seq.enqueue``, ``seq.wait`` (> ``seq.fetch``, ``seq.tap`` where the
  family hands back ``aux``), ``seq.commit``. One batch, one ordinal:
  ``batch`` on its ``router.*`` phases, ``seq_batch`` on what the scorer
  does for it, whichever call or thread that is.

- **The start-up trace** — :data:`startup`, one record a process: a root
  span ``startup`` that begins where the OS says the process began, and
  under it every ``startup.*`` phase the building of the service opens
  (``startup.head``: interpreter, imports, the accelerator's runtime, up
  to the program's first phase; ``startup.weights``, ``startup.store``,
  ``startup.executable`` once an (L, B) with JAX's own trace, lower,
  compile and cache-load seconds (``observability/profile.py``'s one
  ``jax.monitoring`` hook bills them), ``startup.inventory``,
  ``startup.restore``, ``startup.gc``, ``startup.router``,
  ``startup.platform``). Nobody opens it: :meth:`Startup.phase` does at a
  process's first such phase. Every span of it is kept (no sampling; a
  bounded list, read after the fact), and each carries its
  ``perf_counter`` start (:attr:`Span.t0`) beside the wall one, so the
  record lays over any host-clock mark without conversion.

Span context is tracked per-thread via ``contextvars``; pipelined code that
hops threads (the router's score worker) passes ``parent=`` explicitly.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import os
import threading
import time
import zlib
from typing import Any, Iterator, Mapping, NamedTuple

from ccfd_tpu.metrics.prom import Registry

TRACEPARENT = "traceparent"
_TRACEPARENT_B = b"traceparent"


class SpanContext(NamedTuple):
    trace_id: str  # 32 lowercase hex chars
    span_id: str   # 16 lowercase hex chars
    sampled: bool = True


# the active span's context on this thread and the tracer that activated
# it: a phase opened under it records its child span through that tracer
_current: "contextvars.ContextVar[tuple[SpanContext, Tracer] | None]" = (
    contextvars.ContextVar("ccfd_trace_ctx", default=None))


def current_context() -> SpanContext | None:
    """The active span's context on THIS thread (None outside any span)."""
    cur = _current.get()
    return cur[0] if cur is not None else None


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def format_traceparent(ctx: SpanContext | None) -> str | None:
    if ctx is None:
        return None
    return f"00-{ctx.trace_id}-{ctx.span_id}-{'01' if ctx.sampled else '00'}"


def parse_traceparent(value: Any) -> SpanContext | None:
    """``00-<32 hex>-<16 hex>-<2 hex>`` -> SpanContext; anything else None.

    Tolerant by design (a malformed header from a version-skewed peer must
    start a fresh trace, never 500 the request)."""
    if isinstance(value, bytes):
        try:
            value = value.decode("ascii")
        except UnicodeDecodeError:
            return None
    if not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return SpanContext(trace_id.lower(), span_id.lower(),
                       sampled=bool(int(flags, 16) & 1))


def inject_headers(headers: dict | None = None,
                   ctx: SpanContext | None = None) -> dict:
    """Add a ``traceparent`` entry for ``ctx`` (default: the current span)
    to ``headers`` (created if None). No-op when there is no active span."""
    headers = {} if headers is None else headers
    tp = format_traceparent(ctx if ctx is not None else current_context())
    if tp is not None:
        headers[TRACEPARENT] = tp
    return headers


def extract_context(headers: Mapping | None) -> SpanContext | None:
    """Pull a SpanContext out of an HTTP-header-shaped mapping. Accepts str
    or bytes keys (the fasthttp server lowercases bytes keys; stdlib
    handlers expose case-insensitive str mappings)."""
    if not headers:
        return None
    v = headers.get(TRACEPARENT)
    if v is None and hasattr(headers, "get"):
        v = headers.get(_TRACEPARENT_B)
    if v is None:  # stdlib email.message headers are case-insensitive,
        # plain dicts are not: scan as the last resort
        for k in headers:
            name = k.decode("latin-1") if isinstance(k, bytes) else str(k)
            if name.lower() == TRACEPARENT:
                v = headers[k]
                break
    return parse_traceparent(v)


class Span:
    """One timed operation. Mutable so callers can set ``attrs`` mid-span
    (degraded tier, fraud flag, HTTP status); finished spans are handed to
    the sink and must not be mutated afterward."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "component",
                 "start", "duration_s", "status", "attrs", "_t0")

    def __init__(self, trace_id: str, span_id: str, parent_id: str | None,
                 name: str, component: str, start: float,
                 attrs: dict | None = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.component = component
        self.start = start            # wall clock: cross-process alignment
        self._t0 = time.perf_counter()  # monotonic: duration must survive
        self.duration_s = 0.0           # NTP steps/smears
        self.status = "ok"
        self.attrs = attrs if attrs is not None else {}

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    @property
    def t0(self) -> float:
        """The span's start on ``time.perf_counter`` (the clock durations
        are taken on): what lays it beside another host-clock mark."""
        return self._t0

    def to_dict(self) -> dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "component": self.component,
            "start": self.start,
            "duration_s": self.duration_s,
            "status": self.status,
            "attrs": dict(self.attrs),
        }


# span attrs whose truthiness forces a tail-sampling KEEP: the conditions
# an operator always wants the trace for (the router sets fraud/degraded,
# clients set breaker_open on CircuitOpenError)
FLAG_ATTRS = ("fraud", "degraded", "breaker_open")


class _TraceBuf:
    __slots__ = ("spans", "last", "reason")

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self.last = 0.0
        self.reason: str | None = None  # first forced-keep reason seen


class SpanSink:
    """In-process span collector with tail-based sampling.

    Spans buffer per trace; a trace is FINALIZED (keep/drop decided) when it
    has been idle for ``decision_window_s`` (flushed lazily on read/eviction
    — no background thread) or when the pending set overflows. Keep rules,
    in order: any span errored; any span >= ``slow_s``; any span carries a
    truthy flag attr (:data:`FLAG_ATTRS`); else a deterministic hash of the
    trace id keeps ``sample`` of the remainder — deterministic so every
    component of a distributed deployment makes the SAME decision without
    coordination. Retained traces live in a bounded ring (oldest evicted).
    """

    def __init__(
        self,
        sample: float = 0.01,
        slow_s: float = 0.1,
        max_pending: int = 1024,
        max_retained: int = 256,
        decision_window_s: float = 5.0,
        registry: Registry | None = None,
    ):
        self.sample = min(1.0, max(0.0, float(sample)))
        self.slow_s = float(slow_s)
        self.max_pending = int(max_pending)
        self.max_retained = int(max_retained)
        self.decision_window_s = float(decision_window_s)
        self._lock = threading.Lock()
        # span listeners (observability/profile.py StageProfiler): called
        # for EVERY finished span, before sampling — the stage profile
        # must see the full population, not the tail-sampled keeps
        self._listeners: list = []
        self._pending: "collections.OrderedDict[str, _TraceBuf]" = (
            collections.OrderedDict()
        )
        self._retained: "collections.OrderedDict[str, list[Span]]" = (
            collections.OrderedDict()
        )
        r = registry if registry is not None else Registry()
        self.registry = r
        self._c_spans = r.counter("ccfd_trace_spans_total",
                                  "spans recorded by component")
        self._c_kept = r.counter("ccfd_traces_kept_total",
                                 "tail-sampled traces kept, by reason")
        self._c_dropped = r.counter("ccfd_traces_dropped_total",
                                    "tail-sampled traces dropped")
        self._g_retained = r.gauge("ccfd_traces_retained",
                                   "traces currently held for /traces")
        self._g_pending = r.gauge("ccfd_traces_pending",
                                  "traces awaiting a sampling decision")
        self._c_listener_err = r.counter(
            "ccfd_trace_listener_errors_total",
            "span-listener callbacks that raised (the span still lands; "
            "the listener — profiler ingestion, incident taps — missed it)",
        )

    # -- ingestion ---------------------------------------------------------
    def add_listener(self, fn) -> None:
        """Subscribe ``fn(span)`` to every finished span (unsampled). A
        raising listener is the listener's bug, not a span-loss event —
        exceptions are swallowed in :meth:`add`."""
        self._listeners.append(fn)

    def add(self, span: Span) -> None:
        for fn in self._listeners:
            try:
                fn(span)
            except Exception:  # noqa: BLE001 - listener bug must not drop spans
                self._c_listener_err.inc()
        self._c_spans.inc(labels={"component": span.component})
        with self._lock:
            retained = self._retained.get(span.trace_id)
            if retained is not None:
                # decision already made for this trace: append, keep a
                # bounded span count so a runaway trace can't grow forever
                if len(retained) < 512:
                    retained.append(span)
                return
            buf = self._pending.get(span.trace_id)
            if buf is None:
                buf = self._pending[span.trace_id] = _TraceBuf()
            if len(buf.spans) < 512:
                buf.spans.append(span)
            buf.last = time.monotonic()
            if buf.reason is None:
                buf.reason = self._forced_reason(span)
            self._g_pending.set(len(self._pending))
            if len(self._pending) > self.max_pending:
                oldest, oldbuf = next(iter(self._pending.items()))
                del self._pending[oldest]
                self._decide_locked(oldest, oldbuf)

    def _forced_reason(self, span: Span) -> str | None:
        if span.status != "ok":
            return "error"
        if span.duration_s >= self.slow_s:
            return "slow"
        for flag in FLAG_ATTRS:
            if span.attrs.get(flag):
                return flag
        return None

    def _hash_keep(self, trace_id: str) -> bool:
        if self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        return (zlib.crc32(trace_id.encode()) & 0xFFFFFFFF) < (
            self.sample * 4294967296.0
        )

    def _decide_locked(self, trace_id: str, buf: _TraceBuf) -> None:
        reason = buf.reason or ("sampled" if self._hash_keep(trace_id)
                                else None)
        if reason is None:
            self._c_dropped.inc()
            return
        self._c_kept.inc(labels={"reason": reason})
        self._retained[trace_id] = buf.spans
        while len(self._retained) > self.max_retained:
            self._retained.popitem(last=False)
        self._g_retained.set(len(self._retained))

    def flush(self, older_than_s: float | None = None) -> None:
        """Finalize pending traces idle longer than ``older_than_s``
        (default: the decision window; pass 0.0 to decide everything now)."""
        window = (self.decision_window_s if older_than_s is None
                  else float(older_than_s))
        now = time.monotonic()
        with self._lock:
            due = [tid for tid, buf in self._pending.items()
                   if now - buf.last >= window]
            for tid in due:
                self._decide_locked(tid, self._pending.pop(tid))
            self._g_pending.set(len(self._pending))

    # -- read side (the exporter's /traces endpoints; tools) ---------------
    def trace(self, trace_id: str) -> list[dict[str, Any]] | None:
        self.flush()
        with self._lock:
            spans = self._retained.get(trace_id)
            if spans is None:
                buf = self._pending.get(trace_id)
                spans = buf.spans if buf is not None else None
            if spans is None:
                return None
            return sorted((s.to_dict() for s in spans),
                          key=lambda d: d["start"])

    def traces(self) -> list[dict[str, Any]]:
        """Retained-trace summaries, newest first."""
        self.flush()
        with self._lock:
            items = list(self._retained.items())
        out = []
        for tid, spans in reversed(items):
            starts = [s.start for s in spans]
            ends = [s.start + s.duration_s for s in spans]
            roots = [s for s in spans if s.parent_id is None]
            out.append({
                "trace_id": tid,
                "spans": len(spans),
                "root": roots[0].name if roots else spans[0].name,
                "components": sorted({s.component for s in spans}),
                "start": min(starts),
                "duration_s": max(ends) - min(starts),
                "errored": any(s.status != "ok" for s in spans),
            })
        return out


class Tracer:
    """Per-component span factory.

    ``registry`` must be the component's SCRAPED registry (the operator
    wires it; span latency lands on the same scrape surface as the
    component's own series — the fix for the old global tracer whose
    private registry the exporter never served). ``sink`` is the shared
    :class:`SpanSink`; a tracer without one still times spans into the
    histogram, it just feeds no retained traces.
    """

    def __init__(self, registry: Registry | None = None,
                 component: str = "ccfd", sink: SpanSink | None = None):
        self.registry = registry or Registry()
        self.component = component
        self.sink = sink
        self._hist = self.registry.histogram(
            "trace_span_seconds", "span durations by name"
        )

    # -- explicit begin/finish (thread-hopping pipelines) ------------------
    def start(self, name: str, parent: SpanContext | None = None,
              attrs: dict | None = None) -> Span:
        """Begin a span WITHOUT activating it on this thread — for
        pipelined code whose span outlives the current stack frame (the
        router's in-flight batch). Pair with :meth:`finish`."""
        if parent is None:
            parent = current_context()
        trace_id = parent.trace_id if parent is not None else new_trace_id()
        parent_id = parent.span_id if parent is not None else None
        return Span(trace_id, new_span_id(), parent_id, name,
                    self.component, time.time(), attrs)

    def finish(self, span: Span, status: str | None = None,
               duration_s: float | None = None) -> None:
        """``duration_s``: the caller already timed the span (a
        :class:`phase` reads the clock once for the span, the annotation's
        stats and the histogram its call site feeds)."""
        span.duration_s = (duration_s if duration_s is not None
                           else max(0.0, time.perf_counter() - span._t0))
        if status is not None:
            span.status = status
        self._hist.observe(span.duration_s, labels={"span": span.name},
                           exemplar={"trace_id": span.trace_id})
        if self.sink is not None:
            self.sink.add(span)

    # -- the common path ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, parent: SpanContext | None = None,
             attrs: dict | None = None) -> Iterator[Span]:
        sp = self.start(name, parent=parent, attrs=attrs)
        token = _current.set((sp.context, self))
        try:
            yield sp
        except BaseException:
            sp.status = "error"
            raise
        finally:
            _current.reset(token)
            self.finish(sp)

    @contextlib.contextmanager
    def activate(self, ctx: SpanContext | None) -> Iterator[None]:
        """Make ``ctx`` the current context on this thread without opening
        a span (consumers resuming a bus-carried context around work whose
        spans are created piecemeal)."""
        token = _current.set((ctx, self) if ctx is not None else None)
        try:
            yield
        finally:
            _current.reset(token)


_annotation: Any = None  # jax.profiler.TraceAnnotation, bound at first use:
# HTTP clients and load generators import this module and must stay JAX-free


class phase:  # noqa: N801 - reads as a statement: ``with phase("seq.pad"):``
    """One phase of the served path, per router batch or per dispatch
    (never per record): the ONE way the path marks where its time goes.

    Always a ``jax.profiler.TraceAnnotation`` named ``name``: with a device
    capture running (the benchmark's traced run, the exporter's
    ``profile_device`` endpoint) the phase is an event on the capture's host
    plane, on the timeline of ``XLA Ops``, carrying ``stats`` and the
    thread's CPU time inside it (``cpu_ns``; wall minus CPU is time spent
    off the CPU — on this path, waiting for the interpreter lock or the
    device). With no capture running the annotation costs under a
    microsecond (the whole phase, with its stats and two clock reads,
    about two) and the CPU clock is not read.

    Under an active span — ``parent`` given with its ``tracer`` (the
    router's stages, parented on the in-flight batch span that hops
    threads), or a span activated on this thread (everything the scorer
    and the store open inside ``router.score``) — it is also a child
    :class:`Span` of it: same trace id, same sink, ``stats`` as attrs,
    activated for whatever opens below. With neither it is the annotation
    alone.

    ``seconds`` holds the phase's duration after exit: call sites that keep
    a histogram of the interval feed it from this, so the interval is
    timed once.
    """

    __slots__ = ("name", "stats", "span", "seconds", "_tracer", "_parent",
                 "_ann", "_token", "_t0", "_cpu0")

    def __init__(self, name: str, tracer: "Tracer | None" = None,
                 parent: SpanContext | None = None, **stats: Any):
        self.name = name
        self.stats = stats
        self.span: Span | None = None
        self.seconds = 0.0
        self._tracer = tracer
        self._parent = parent

    def set(self, **stats: Any) -> None:
        """Stats known only once the work is done (rows polled, bucket
        chosen); ``stats`` doubles as the span's attrs."""
        self.stats.update(stats)

    def __enter__(self) -> "phase":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        cur = _current.get() or (None, None)
        parent = self._parent or cur[0]
        tracer = self._tracer = self._tracer or cur[1]
        self._token = None
        if tracer is not None and parent is not None:
            self.span = tracer.start(self.name, parent=parent,
                                     attrs=self.stats)
            self._token = _current.set((self.span.context, tracer))
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._cpu0 = (time.thread_time_ns() if _annotation.is_enabled()
                      else None)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._cpu0 is not None:
            self._ann.set_metadata(
                cpu_ns=time.thread_time_ns() - self._cpu0, **self.stats)
        self._ann.__exit__(exc_type, exc, tb)
        if self.span is not None:
            _current.reset(self._token)
            self._tracer.finish(
                self.span, "error" if exc_type is not None else None,
                duration_s=self.seconds)


# -- the start-up trace --------------------------------------------------------

_IMPORTED = time.perf_counter()  # the process's start where the OS hides it


def _process_start() -> float:
    """The process's start on ``time.perf_counter``: ``/proc/self/stat``'s
    field 22 (start, in ticks since boot) against ``CLOCK_BOOTTIME``, to
    the tick (10 ms). Where that cannot be read, or reads later than this
    module's import, the import."""
    try:
        with open("/proc/self/stat", "rb") as f:
            fields = f.read().rsplit(b")", 1)[1].split()  # from field 3 on
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED
    began = time.perf_counter() - age
    return began if began <= _IMPORTED else _IMPORTED


class _Kept:
    """The start-up record's sink: every span of the one trace in the
    order it closed, none sampled away, and no more than ``CAP`` (a
    process that keeps building scorers stops adding to it)."""

    CAP = 1024

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self.dropped = 0

    def add(self, span: Span) -> None:
        if len(self.spans) < self.CAP:
            self.spans.append(span)  # GIL-atomic: any thread may close one
        else:
            self.dropped += 1


class Startup:
    """One process's start-up trace (module docstring): the root span, the
    tracer that records under it, and the two instants that end it.

    ``phase(name, **stats)`` is the one way in. The first call opens the
    record: the root ``startup`` from the process's start, a closed child
    ``startup.head`` from there to now, and the ``jax.monitoring`` hook
    that bills JAX's trace, lower, compile and cache-load events to the
    innermost ``startup.executable`` / ``startup.inventory``
    (``observability/profile.py``). A phase opened while another start-up
    phase is open on the thread is that one's child; any other is the
    root's, handed over as ``tracer=`` / ``parent=``, so nothing is left
    set in a thread's context and no ``router.*`` / ``seq.*`` phase ever
    parents on the root.

    ``ready()`` closes the root (once: the first ``Router.start()``
    returning, or ``Platform.up()``); ``first_verdict()`` stamps once. A
    ``startup.*`` phase that opens later (an inventory first asked for
    after the build, a swap's executables) still lands in the record,
    after the root's end. ``t0``: the root's start for a record built by
    hand (tests); left out, the process's.
    """

    def __init__(self, t0: float | None = None):
        self._given_t0 = t0
        self._lock = threading.Lock()
        self._kept = _Kept()
        self.tracer: Tracer | None = None
        self.root: Span | None = None
        # (l_bucket, b_bucket) -> the functions JAX traced for it in the
        # phases closed so far (observability/profile.py::billed)
        self.traced: dict[tuple, set] = {}
        self.ready_at: float | None = None          # perf_counter
        self.first_verdict_at: float | None = None  # perf_counter

    def _open(self) -> None:
        from ccfd_tpu.observability import profile

        with self._lock:
            if self.root is not None:
                return
            now = time.perf_counter()
            t0 = self._given_t0 if self._given_t0 is not None \
                else _process_start()
            tracer = Tracer(component="startup", sink=self._kept)
            # ccfd-lint: disable=monotonic-durations -- no duration: the wall-clock instant of a monotonic start (a span's ``start`` is wall by contract)
            began_wall = time.time() - (now - t0)
            root = Span(new_trace_id(), new_span_id(), None, "startup",
                        "startup", began_wall)
            root._t0 = t0
            head = Span(root.trace_id, new_span_id(), root.span_id,
                        "startup.head", "startup", root.start)
            head._t0 = t0
            tracer.finish(head, duration_s=now - t0)
            self.tracer, self.root = tracer, root
        profile.hear_compile_events()

    def phase(self, name: str, **stats: Any) -> phase:
        """``phase(name, **stats)`` under the record (class docstring)."""
        if self.root is None:
            self._open()
        cur = _current.get()
        if cur is not None and cur[1] is self.tracer:
            return phase(name, **stats)
        return phase(name, tracer=self.tracer, parent=self.root.context,
                     **stats)

    def ready(self) -> None:
        """The service is built: the root closes here, once."""
        with self._lock:
            if self.root is None or self.ready_at is not None:
                return
            self.ready_at = time.perf_counter()
            self.tracer.finish(self.root,
                               duration_s=self.ready_at - self.root.t0)

    def first_verdict(self) -> None:
        """The first routed batch has left the router: stamped once."""
        if self.first_verdict_at is None:
            self.first_verdict_at = time.perf_counter()

    def spans(self) -> list[Span]:
        """Every closed span of the record, in the order they closed."""
        return list(self._kept.spans)

    def seconds(self) -> dict[str, float]:
        """Seconds by phase over the closed spans, the ``startup.`` prefix
        dropped and the root as ``total``: what the operator's
        ``ccfd_startup_seconds{phase}`` is set from."""
        out: dict[str, float] = {}
        for sp in self._kept.spans:
            key = "total" if sp.parent_id is None else sp.name.partition(
                ".")[2]
            out[key] = out.get(key, 0.0) + sp.duration_s
        return out


# the process's record: code reads ``trace.startup`` where it runs, so a
# test can stand a fresh one in its place
startup = Startup()


def startup_phase(name: str):
    """Decorator: every call is the start-up phase ``name`` of the
    process's record (a constructor that is a phase from end to end)."""
    def decorate(fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with startup.phase(name):
                return fn(*args, **kwargs)
        return inside
    return decorate
