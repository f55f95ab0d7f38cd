"""Distributed tracing: context propagation, tail sampling, exemplars,
trace-correlated logs, and the exporter's scrape/trace contract."""

import io
import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler

import pytest

from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.observability.trace import (
    SpanContext,
    SpanSink,
    Tracer,
    current_context,
    extract_context,
    format_traceparent,
    inject_headers,
    parse_traceparent,
)


# -- context wire format -----------------------------------------------------
def test_traceparent_roundtrip():
    ctx = SpanContext("ab" * 16, "cd" * 8)
    tp = format_traceparent(ctx)
    assert tp == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(tp)
    assert back.trace_id == ctx.trace_id and back.span_id == ctx.span_id
    assert parse_traceparent(tp.encode()) == back  # bytes form (fasthttp)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-cd-01",
    "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # all-zero trace id
    "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
    b"\xff\xfe",  # undecodable bytes
])
def test_traceparent_malformed_tolerated(bad):
    assert parse_traceparent(bad) is None


def test_inject_extract_headers_str_and_bytes_keys():
    ctx = SpanContext("12" * 16, "34" * 8)
    h = inject_headers({}, ctx)
    assert extract_context(h).trace_id == ctx.trace_id
    # fasthttp servers hand lowercased BYTES keys to handlers
    hb = {b"traceparent": h["traceparent"].encode()}
    assert extract_context(hb).trace_id == ctx.trace_id
    assert extract_context({}) is None
    assert inject_headers({}) == {}  # no active span -> no header


# -- spans / tracer ----------------------------------------------------------
def test_tracer_nests_and_restores_context():
    tr = Tracer(Registry(), component="t")
    assert current_context() is None
    with tr.span("outer") as outer:
        outer_ctx = current_context()
        assert outer_ctx.span_id == outer.span_id
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        assert current_context() == outer_ctx
    assert current_context() is None


def test_tracer_spans_land_in_component_registry():
    reg = Registry()
    tr = Tracer(reg, component="router")
    with tr.span("score"):
        pass
    h = reg.histogram("trace_span_seconds")
    assert h.count({"span": "score"}) == 1
    # exemplar carries the span's trace id into the scrape
    om = reg.render(openmetrics=True)
    assert '# {trace_id="' in om


def test_span_error_status_marks_and_reraises():
    sink = SpanSink(sample=0.0, registry=Registry())
    tr = Tracer(Registry(), sink=sink)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    sink.flush(0.0)
    assert len(sink.traces()) == 1  # error traces always kept
    assert sink.traces()[0]["errored"]


# -- propagation over a real HTTP server -------------------------------------
def test_inject_extract_roundtrip_over_framework_http_server():
    """PooledHTTPClient injects traceparent; a FrameworkHTTPServer handler
    extracts it: the server-side context's trace matches the client span
    and its parent IS the client span."""
    from ccfd_tpu.utils.httpclient import PooledHTTPClient
    from ccfd_tpu.utils.httpserver import FrameworkHTTPServer

    seen: dict = {}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            seen["ctx"] = extract_context(self.headers)
            body = b'{"ok": true}'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = FrameworkHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        sink = SpanSink(sample=1.0, registry=Registry())
        tr = Tracer(Registry(), component="client", sink=sink)
        client = PooledHTTPClient(
            f"http://127.0.0.1:{httpd.server_address[1]}", 80,
            tracer=tr, trace_edge="test",
        )
        status, body = client.request("POST", "/x", {"a": 1})
        assert status == 200 and body == {"ok": True}
        client.close()
        sink.flush(0.0)
        spans = sink.trace(seen["ctx"].trace_id)
        assert spans is not None and spans[0]["name"] == "rpc.test"
        assert seen["ctx"].span_id == spans[0]["span_id"]
        assert spans[0]["attrs"]["status"] == 200
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- bus carriage ------------------------------------------------------------
def test_bus_records_carry_batch_headers_in_process():
    from ccfd_tpu.bus.broker import Broker

    b = Broker()
    tp = format_traceparent(SpanContext("aa" * 16, "bb" * 8))
    b.produce_batch("t", [b"r1", b"r2"], ["k1", "k2"],
                    headers={"traceparent": tp})
    b.produce("t", b"r3", key="k3")  # untraced: headers stay None
    recs = b.consumer("g", ("t",)).poll(10)
    stamped = [r for r in recs if r.headers]
    plain = [r for r in recs if not r.headers]
    assert len(stamped) == 2 and len(plain) == 1
    assert all(extract_context(r.headers).trace_id == "aa" * 16
               for r in stamped)


def test_trace_continuity_across_remote_bus_hop():
    """Produce over the networked bus inside a span -> the consumer's
    records carry the producing span's trace (the transport's traceparent
    header stamps the batch server-side)."""
    from ccfd_tpu.bus.client import RemoteBroker
    from ccfd_tpu.bus.server import BrokerServer

    sink = SpanSink(sample=1.0, registry=Registry())
    server = BrokerServer(tracer=Tracer(Registry(), "bus", sink))
    port = server.start("127.0.0.1", 0)
    try:
        client_tr = Tracer(Registry(), "producer", sink)
        rb = RemoteBroker(f"http://127.0.0.1:{port}", tracer=client_tr)
        with client_tr.span("producer.batch") as sp:
            rb.produce_batch("t", [b"row"], ["k"])
        c = rb.consumer("g", ("t",))
        recs = c.poll(10, timeout_s=2.0)
        assert len(recs) == 1
        got = extract_context(recs[0].headers)
        assert got is not None and got.trace_id == sp.trace_id
        # server-side bus.produce span joined the same trace
        sink.flush(0.0)
        names = {s["name"] for s in sink.trace(sp.trace_id)}
        assert {"producer.batch", "rpc.bus", "bus.produce"} <= names
        c.close()
        rb.close()
    finally:
        server.stop()


def test_router_resumes_producer_trace_and_flags_fraud():
    """The full in-process hop: producer batch span -> bus headers ->
    router batch/decode/score/route spans on ONE trace, with the fraud
    flag forcing a tail-sampling keep even at sample=0."""
    import numpy as np

    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import FEATURE_NAMES
    from ccfd_tpu.producer.producer import Producer
    from ccfd_tpu.router.router import Router

    class FakeEngine:
        contexts: list = []

        def definitions(self):
            return ["fraud", "standard"]

        def start_process(self, def_id, variables):
            # the route span must be ACTIVE here: the engine's own bus
            # produces (notifications, labels) join the trace through
            # current_context() (process/fraud.py notify)
            FakeEngine.contexts.append(current_context())
            return 1

        def signal(self, pid, name, payload=None):
            return True

    cfg = Config()
    broker = Broker()
    sink = SpanSink(sample=0.0, registry=Registry())  # ONLY flags keep
    n = 8
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, len(FEATURE_NAMES))).astype(np.float32)
    from ccfd_tpu.data.ccfd import Dataset

    ds = Dataset(X=X, y=np.zeros(n, np.int32))
    producer = Producer(cfg, broker, ds, registry=Registry(),
                        tracer=Tracer(Registry(), "producer", sink))
    router = Router(cfg, broker, lambda x: np.ones(len(x), np.float32),
                    FakeEngine(), Registry(),
                    tracer=Tracer(Registry(), "router", sink))
    assert producer.run(limit=n, wire_format="csv") == n
    assert router.step() == n
    sink.flush(0.0)
    traces = sink.traces()
    assert len(traces) == 1  # fraud-flagged: kept despite sample=0.0
    spans = sink.trace(traces[0]["trace_id"])
    names = {s["name"]: s for s in spans}
    assert {"producer.batch", "router.batch", "router.decode",
            "router.score", "router.route"} <= set(names)
    assert names["router.batch"]["parent_id"] == \
        names["producer.batch"]["span_id"]
    assert names["router.route"]["attrs"].get("fraud") is True
    # engine calls ran under the ACTIVATED route span: anything the engine
    # produces to the bus during a start joins the same trace
    assert FakeEngine.contexts and all(
        c is not None and c.trace_id == traces[0]["trace_id"]
        and c.span_id == names["router.route"]["span_id"]
        for c in FakeEngine.contexts)
    router.close()


def test_engine_notification_rides_router_trace():
    """The real engine's customer-notification record (process/fraud.py)
    carries the router's trace context, so the notify leg resumes it."""
    import numpy as np

    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import FEATURE_NAMES
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.router.router import Router

    cfg = Config()
    broker = Broker()
    sink = SpanSink(sample=1.0, registry=Registry())
    engine = build_engine(cfg, broker, Registry(), None)
    router = Router(cfg, broker,
                    lambda x: np.ones(len(x), np.float32),  # all fraud ->
                    engine, Registry(),                     # notifications
                    tracer=Tracer(Registry(), "router", sink))
    rows = [",".join("1000.0" for _ in FEATURE_NAMES).encode()]
    broker.produce_batch(cfg.kafka_topic, rows, [7])
    assert router.step() == 1
    notif_consumer = broker.consumer("t", (cfg.customer_notification_topic,))
    recs = notif_consumer.poll(10)
    assert recs and recs[0].headers, "notification record lost the trace"
    ctx = extract_context(recs[0].headers)
    sink.flush(0.0)
    spans = sink.trace(ctx.trace_id)
    assert spans is not None
    assert "router.route" in {s["name"] for s in spans}
    router.close()


def test_client_span_marks_5xx_error_and_sampler_keeps_it():
    """A 5xx reply returns normally from PooledHTTPClient but must mark
    the span errored — those traces are always tail-sampled KEEP."""
    from ccfd_tpu.utils.httpclient import PooledHTTPClient
    from ccfd_tpu.utils.httpserver import FrameworkHTTPServer

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(500)
            self.send_header("Content-Length", "0")
            self.end_headers()

    httpd = FrameworkHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        reg = Registry()
        sink = SpanSink(sample=0.0, registry=reg)  # ONLY forced keeps
        client = PooledHTTPClient(
            f"http://127.0.0.1:{httpd.server_address[1]}", 80,
            tracer=Tracer(Registry(), "c", sink), trace_edge="engine",
        )
        status, _ = client.request("GET", "/x")
        assert status == 500
        client.close()
        sink.flush(0.0)
        assert len(sink.traces()) == 1 and sink.traces()[0]["errored"]
        assert reg.counter("ccfd_traces_kept_total").value(
            {"reason": "error"}) == 1
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_remote_scorer_hop_joins_trace_with_exemplar():
    """SeldonClient injects traceparent; the PredictionServer's
    serving.predict span joins the caller's trace and the serving latency
    histogram carries the trace id as an exemplar."""
    import numpy as np

    from ccfd_tpu.config import Config
    from ccfd_tpu.serving.client import SeldonClient
    from ccfd_tpu.serving.scorer import Scorer
    from ccfd_tpu.serving.server import PredictionServer

    sink = SpanSink(sample=1.0, registry=Registry())
    server_reg = Registry()
    scorer = Scorer(model_name="logreg", batch_sizes=(16,))
    scorer.warmup()
    srv = PredictionServer(
        scorer, Config(dynamic_batching=False, native_front=False),
        server_reg, tracer=Tracer(server_reg, "seldon", sink))
    port = srv.start("127.0.0.1", 0)
    try:
        cfg = Config(seldon_url=f"http://127.0.0.1:{port}")
        client = SeldonClient(cfg, tracer=Tracer(Registry(), "router", sink))
        with Tracer(Registry(), "router", sink).span("router.score") as sp:
            proba = client.score(np.zeros((3, 30), np.float32))
        assert proba.shape == (3,)
        client.close()
        sink.flush(0.0)
        names = {s["name"] for s in sink.trace(sp.trace_id)}
        assert {"router.score", "rpc.scorer", "serving.predict"} <= names
        om = server_reg.render(openmetrics=True)
        assert f'trace_id="{sp.trace_id}"' in om
    finally:
        srv.stop()


# -- tail sampler ------------------------------------------------------------
def _span(tr, name, **attrs):
    with tr.span(name) as sp:
        sp.attrs.update(attrs)
        return sp


def test_tail_sampler_keeps_interesting_drops_boring():
    reg = Registry()
    sink = SpanSink(sample=0.0, slow_s=0.05, registry=reg)
    tr = Tracer(Registry(), sink=sink)
    _span(tr, "boring")
    _span(tr, "flagged", degraded="rules")
    sp = tr.start("slowone")
    sp._t0 -= 1.0  # synthesize a 1s span (durations are monotonic-based)
    tr.finish(sp)
    sink.flush(0.0)
    kept = {t["root"] for t in sink.traces()}
    assert kept == {"flagged", "slowone"}
    c = reg.counter("ccfd_traces_kept_total")
    assert c.value({"reason": "degraded"}) == 1
    assert c.value({"reason": "slow"}) == 1
    assert reg.counter("ccfd_traces_dropped_total").value() == 1


def test_tail_sampler_hash_is_deterministic():
    a = SpanSink(sample=0.5, registry=Registry())
    b = SpanSink(sample=0.5, registry=Registry())
    ids = [f"{i:032x}" for i in range(200)]
    decisions_a = [a._hash_keep(t) for t in ids]
    decisions_b = [b._hash_keep(t) for t in ids]
    assert decisions_a == decisions_b  # same decision on every component
    frac = sum(decisions_a) / len(decisions_a)
    assert 0.3 < frac < 0.7
    assert all(SpanSink(sample=1.0, registry=Registry())._hash_keep(t)
               for t in ids[:5])
    assert not any(SpanSink(sample=0.0, registry=Registry())._hash_keep(t)
                   for t in ids[:5])


def test_sampler_pending_overflow_finalizes_oldest():
    sink = SpanSink(sample=1.0, max_pending=4, registry=Registry())
    tr = Tracer(Registry(), sink=sink)
    for i in range(8):
        _span(tr, f"s{i}")
    # overflow finalized (kept, sample=1.0) instead of growing unbounded
    assert len(sink.traces()) >= 4


def test_retained_ring_is_bounded():
    sink = SpanSink(sample=1.0, max_retained=3, registry=Registry())
    tr = Tracer(Registry(), sink=sink)
    for i in range(10):
        _span(tr, f"s{i}")
    sink.flush(0.0)
    assert len(sink.traces()) == 3


# -- exemplars + cardinality guard -------------------------------------------
def test_exemplar_rendering_openmetrics_only():
    reg = Registry()
    h = reg.histogram("lat")
    h.observe(0.004, labels={"endpoint": "/p"},
              exemplar={"trace_id": "ff" * 16})
    plain = reg.render()
    om = reg.render(openmetrics=True)
    assert "# {" not in plain
    assert f'# {{trace_id="{"ff" * 16}"}}' in om
    assert om.rstrip().endswith("# EOF")


def test_exemplar_on_overflowed_labelset_is_spec_valid_openmetrics():
    """Exemplars attached to series that FOLD into the cardinality
    guard's overflow labelset (metrics/prom.py OVERFLOW_KEY) must render
    spec-valid OpenMetrics — the fold rewrites the series labels after
    the exemplar was recorded, which was untested (ISSUE 9 satellite).
    The reference OM parser is the judge, as in the exporter tests."""
    prom_parser = pytest.importorskip("prometheus_client.openmetrics.parser")
    reg = Registry()
    h = reg.histogram("lat_seconds", labelset_limit=2)
    for i in range(6):
        h.observe(0.004, labels={"endpoint": f"/e{i}"},
                  exemplar={"trace_id": f"{i:032x}"})
    om = reg.render(openmetrics=True)
    families = {f.name: f for f in
                prom_parser.text_string_to_metric_families(om)}
    assert "lat_seconds" in families  # parsed end-to-end without raising
    overflow_buckets = [
        s for s in families["lat_seconds"].samples
        if s.name == "lat_seconds_bucket"
        and s.labels.get("overflow") == "true"
    ]
    # the 4 folded observations landed on ONE overflow series...
    assert overflow_buckets
    assert any(s.value == 4 for s in overflow_buckets)
    # ...carrying a well-formed exemplar (one of the folded trace ids)
    folded_ids = {f"{i:032x}" for i in range(2, 6)}
    ex = [s.exemplar for s in overflow_buckets if s.exemplar is not None]
    assert ex and ex[0].labels["trace_id"] in folded_ids
    assert ex[0].value == pytest.approx(0.004)
    # admitted series keep their own exemplars untouched by the fold
    kept = [s.exemplar for s in families["lat_seconds"].samples
            if s.exemplar is not None
            and s.labels.get("endpoint") == "/e0"]
    assert kept and kept[0].labels["trace_id"] == f"{0:032x}"


def test_label_cardinality_guard_folds_and_counts():
    reg = Registry()
    c = reg.counter("edges", labelset_limit=3)
    for i in range(10):
        c.inc(labels={"edge": f"e{i}"})
    # first 3 series admitted, the rest fold into one overflow series
    assert c.value({"edge": "e0"}) == 1
    assert c.value({"edge": "e9"}) == 0
    assert c.value({"overflow": "true"}) == 7
    dropped = reg.counter("ccfd_metric_labelsets_dropped_total")
    assert dropped.value({"metric": "edges"}) == 7
    # existing series and the unlabeled series keep working past the limit
    c.inc(labels={"edge": "e0"})
    c.inc()
    assert c.value({"edge": "e0"}) == 2 and c.value() == 1


def test_cardinality_guard_on_histogram_and_gauge():
    reg = Registry()
    h = reg.histogram("h", labelset_limit=2)
    g = reg.gauge("g", labelset_limit=2)
    for i in range(5):
        h.observe(0.1, labels={"k": str(i)})
        g.set(i, labels={"k": str(i)})
    assert h.count({"overflow": "true"}) == 3
    assert g.value({"overflow": "true"}) == 4.0  # last fold wins


# -- structured logging ------------------------------------------------------
def test_slog_stamps_trace_ids_and_extras():
    from ccfd_tpu.observability import slog

    buf = io.StringIO()
    log = slog.configure("router", logger="ccfd_tpu.test_slog", stream=buf)
    tr = Tracer(Registry())
    with tr.span("work") as sp:
        log.warning("edge degraded", extra={"tier": "host"})
    log.info("outside any span")
    lines = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert lines[0]["trace_id"] == sp.trace_id
    assert lines[0]["span_id"] == sp.span_id
    assert lines[0]["component"] == "router"
    assert lines[0]["level"] == "warning"
    assert lines[0]["tier"] == "host"
    assert "trace_id" not in lines[1]
    # idempotent reconfigure: no duplicate handlers
    slog.configure("router", logger="ccfd_tpu.test_slog", stream=buf)
    assert len(log.handlers) == 1


# -- exporter contract -------------------------------------------------------
@pytest.fixture()
def exporter_with_sink():
    from ccfd_tpu.metrics.exporter import MetricsExporter

    kie, router = Registry(), Registry()
    kie.counter("kie_things_total").inc()
    router.histogram("router_lat").observe(
        0.01, exemplar={"trace_id": "ee" * 16})
    sink = SpanSink(sample=1.0, registry=Registry())
    tr = Tracer(Registry(), component="x", sink=sink)
    with tr.span("root") as sp:
        pass
    sink.flush(0.0)  # decide now: /traces lists only FINALIZED traces
    exp = MetricsExporter({"kie": kie, "router": router},
                          sink=sink).start()
    yield exp, sp
    exp.stop()


def _get(url, method="GET", accept=None):
    req = urllib.request.Request(url, method=method,
                                 headers={"Accept": accept} if accept else {})
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_exporter_path_routing_and_content_type(exporter_with_sink):
    exp, _sp = exporter_with_sink
    code, headers, body = _get(exp.endpoint + "/prometheus")
    assert code == 200
    assert headers["Content-Type"] == "text/plain; version=0.0.4"
    assert b"kie_things_total" in body and b"router_lat" in body

    code, _h, body = _get(exp.endpoint + "/prometheus/router")
    assert code == 200 and b"router_lat" in body and b"kie_things" not in body

    code, _h, body = _get(exp.endpoint + "/rest/metrics")
    assert code == 200 and b"kie_things_total" in body

    code, _h, _b = _get(exp.endpoint + "/prometheus/nope")
    assert code == 404
    code, _h, _b = _get(exp.endpoint + "/definitely/not")
    assert code == 404


def test_exporter_head_mirrors_get(exporter_with_sink):
    exp, _sp = exporter_with_sink
    code, headers, body = _get(exp.endpoint + "/prometheus", method="HEAD")
    assert code == 200 and body == b""
    assert headers["Content-Type"] == "text/plain; version=0.0.4"
    assert int(headers["Content-Length"]) > 0
    code, _h, _b = _get(exp.endpoint + "/prometheus/nope", method="HEAD")
    assert code == 404


def test_exporter_openmetrics_negotiation_carries_exemplars(exporter_with_sink):
    exp, _sp = exporter_with_sink
    code, headers, body = _get(exp.endpoint + "/prometheus",
                               accept="application/openmetrics-text")
    assert code == 200
    assert headers["Content-Type"].startswith("application/openmetrics-text")
    assert b'# {trace_id="' in body and b"# EOF" in body


def test_exporter_traces_endpoints(exporter_with_sink):
    exp, sp = exporter_with_sink
    code, headers, body = _get(exp.endpoint + "/traces")
    assert code == 200 and headers["Content-Type"] == "application/json"
    traces = json.loads(body)["traces"]
    assert any(t["trace_id"] == sp.trace_id for t in traces)

    code, _h, body = _get(exp.endpoint + f"/traces/{sp.trace_id}")
    assert code == 200
    spans = json.loads(body)["spans"]
    assert spans[0]["span_id"] == sp.span_id

    code, _h, _b = _get(exp.endpoint + "/traces/" + "0" * 32)
    assert code == 404


def test_aggregated_openmetrics_parses_with_reference_parser(exporter_with_sink):
    """The merged multi-registry OM body must satisfy a spec parser:
    counter families named without _total, one EOF, no duplicate series
    (this is what a real Prometheus negotiating OM will do to it)."""
    prom_parser = pytest.importorskip("prometheus_client.openmetrics.parser")
    exp, _sp = exporter_with_sink
    _code, _h, body = _get(exp.endpoint + "/prometheus",
                           accept="application/openmetrics-text")
    families = list(prom_parser.text_string_to_metric_families(body.decode()))
    assert families  # parsed end-to-end without raising
    names = {f.name for f in families}
    assert "kie_things" in names  # counter family stripped of _total


def test_merge_sums_duplicate_series_across_registries():
    from ccfd_tpu.metrics.exporter import MetricsExporter

    r1, r2 = Registry(), Registry()
    # same family + SAME labelset in two registries (e.g. two component
    # tracers timing the same span name)
    r1.histogram("trace_span_seconds").observe(0.01, labels={"span": "rpc.bus"})
    r2.histogram("trace_span_seconds").observe(0.02, labels={"span": "rpc.bus"})
    exp = MetricsExporter({"a": r1, "b": r2})
    body = exp.render_path("/prometheus")
    count_lines = [l for l in body.splitlines()
                   if l.startswith("trace_span_seconds_count")]
    assert count_lines == ['trace_span_seconds_count{span="rpc.bus"} 2'], (
        count_lines)
    assert body.count("# TYPE trace_span_seconds histogram") == 1


def test_exporter_without_sink_404s_traces():
    from ccfd_tpu.metrics.exporter import MetricsExporter

    exp = MetricsExporter({"kie": Registry()}).start()
    try:
        code, _h, _b = _get(exp.endpoint + "/traces")
        assert code == 404
    finally:
        exp.stop()
