"""The start-up trace (``observability/trace.py::startup``): the phases a
small history service opens while it is built, JAX's trace / lower /
compile / cache-load events billed to the executable that paid them
(``observability/profile.py``'s one hook), and the operator's use of it.
Each test stands a fresh record in the process's place."""

from __future__ import annotations

import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.observability import profile, trace
from ccfd_tpu.process.fraud import build_engine
from ccfd_tpu.router.router import Router
from ccfd_tpu.serving.history import SeqScorer
from ccfd_tpu.utils.gctune import tune_for_service

GRID = [(4, 16), (8, 16)]  # (L, B) of the small scorer's two executables
BUILD = ("startup.head", "startup.store", "startup.weights",
         "startup.executable", "startup.restore", "startup.gc",
         "startup.router")


@pytest.fixture
def record(monkeypatch):
    rec = trace.Startup()
    monkeypatch.setattr(trace, "startup", rec)
    return rec


def _scorer(registry=None, length: int = 8) -> SeqScorer:
    """Executables (4, 16) and (``length``, 16). The positions anchor at
    ``length``, so a test that has to see JAX trace and compile takes a
    length no test before it in this process has built."""
    params = seq_mod.init(jax.random.PRNGKey(5401))
    return SeqScorer(params, length=length, batch_sizes=(16,),
                     len_buckets=(4,), compute_dtype="float32",
                     registry=registry)


def _service(tracer=None):
    """Built as a deployment builds it: scorer, warm-up, restore, gc,
    router started; then 32 records through it."""
    cfg = Config(fraud_threshold=0.99)
    broker = Broker()
    scorer = _scorer(Registry())
    scorer.warmup()
    scorer.store.restore(None)
    tune_for_service()
    router = Router(cfg, broker, scorer,
                    build_engine(cfg, broker, Registry()), Registry(),
                    tracer=tracer)
    thread = router.start(poll_timeout_s=0.01)
    broker.produce_batch(cfg.kafka_topic, [
        {FEATURE_NAMES[j]: float(j % 5) for j in range(30)}
        | {"id": i % 4, "customer_id": i % 4} for i in range(32)])
    deadline = time.monotonic() + 30.0
    while len(scorer.store) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    router.stop()
    thread.join(timeout=30.0)
    assert not thread.is_alive()
    return scorer


@pytest.fixture
def built(record):
    return record, _service()


def test_the_build_gives_the_phases_as_children_of_one_root(built):
    rec, _ = built
    spans = rec.spans()
    root = rec.root
    assert root in spans and root.name == "startup"
    assert root.parent_id is None
    names = [s.name for s in spans]
    for name in BUILD + ("startup.inventory",):
        assert name in names, name
    assert names.count("startup.executable") == len(GRID)
    for s in spans:
        assert s.trace_id == root.trace_id
        assert s.name.startswith("startup")
        if s is not root:
            assert s.parent_id == root.span_id
        # name, start, end, parent, on perf_counter beside the wall clock
        assert s.duration_s >= 0.0 and s.t0 > 0.0
        assert abs((s.start - root.start) - (s.t0 - root.t0)) < 0.05
    by = {(s.attrs["l_bucket"], s.attrs["b_bucket"]): s for s in spans
          if s.name == "startup.executable"}
    assert sorted(by) == GRID
    weights = next(s for s in spans if s.name == "startup.weights")
    assert weights.attrs["leaves"] > 0 and weights.attrs["bytes"] > 0
    restore = next(s for s in spans if s.name == "startup.restore")
    assert restore.attrs == {"customers": 0, "bytes": 0}


def test_named_parts_and_what_no_phase_owns_make_the_root_exactly(built):
    rec, _ = built
    root = rec.root
    end = root.t0 + root.duration_s
    assert end == rec.ready_at
    inside = sorted((s for s in rec.spans()
                     if s is not root and s.t0 < end), key=lambda s: s.t0)
    assert inside[0].name == "startup.head" and inside[0].t0 == root.t0
    unowned, upto = 0.0, root.t0
    for s in inside:  # one thread built it: the phases follow one another
        assert s.t0 >= upto - 1e-9 and s.t0 + s.duration_s <= end + 1e-9
        unowned += s.t0 - upto
        upto = s.t0 + s.duration_s
    unowned += end - upto
    assert unowned >= 0.0
    assert sum(s.duration_s for s in inside) + unowned == pytest.approx(
        root.duration_s, abs=1e-9)
    # and the operator's sums are the same spans by name
    secs = rec.seconds()
    assert secs["total"] == root.duration_s
    assert secs["head"] == inside[0].duration_s
    assert secs["executable"] == pytest.approx(sum(
        s.duration_s for s in inside if s.name == "startup.executable"))


def test_an_executable_carries_its_own_jax_events_and_not_its_neighbours(
        record):
    prof = profile.StageProfiler(registry=Registry())
    prof.arm_compile_listener()
    before = prof.compile_counts().get("seq.warmup", 0)
    _scorer(length=12).warmup()
    execs = [s for s in record.spans() if s.name == "startup.executable"]
    assert [(s.attrs["l_bucket"], s.attrs["b_bucket"])
            for s in execs] == [(4, 16), (12, 16)]
    for s in execs:
        a = s.attrs
        # the CPU backend emits a trace, a lower and a compile event an
        # executable, and no cache event (the cache is off in tier-1)
        assert a["traces"] >= 1 and a["retraced"] == 0
        assert a["trace_s"] > 0.0 and a["lower_s"] > 0.0
        assert a["compile_s"] > 0.0 and a["compiles"] == 1
        assert a["cache_hit"] == 0 and a["cache_load_s"] == 0.0
        four = (a["trace_s"] + a["lower_s"] + a["compile_s"]
                + a["cache_load_s"])
        assert four <= s.duration_s  # the rest: the first run and its wait
    # the operator's label under it: one compile an executable, no more
    assert prof.compile_counts()["seq.warmup"] - before == len(GRID)


def test_the_inventory_is_a_phase_once_an_executable_and_bills_its_traces(
        record):
    scorer = _scorer(length=10)
    scorer.warmup()
    grid = scorer.executable_grid()["grid"]
    scorer.executable_grid()  # the memo: no second phase
    inv = [s for s in record.spans() if s.name == "startup.inventory"]
    assert sorted((s.attrs["l_bucket"], s.attrs["b_bucket"])
                  for s in inv) == [(4, 16), (10, 16)]
    for s in inv:
        held = next(g for g in grid
                    if (g["l_bucket"], g["b_bucket"])
                    == (s.attrs["l_bucket"], s.attrs["b_bucket"]))
        for key in ("attn_kernel", "expert_kernel", "gdn_kernel"):
            assert s.attrs[key] == int(held[key])
        assert s.attrs["traces"] >= 1 and s.attrs["compiles"] == 0
        # what it traced again is counted against its executable's names
        assert 0 <= s.attrs["retraced"] <= s.attrs["traces"]


def test_a_cache_hit_is_a_load_and_not_a_compile(record):
    reg = Registry()
    prof = profile.StageProfiler(registry=reg)
    prof.arm_compile_listener()
    events = reg.counter("ccfd_xla_compile_events_total")
    with profile.compile_stage("seq.warmup"), profile.billed(
            "startup.executable", l_bucket=64, b_bucket=8) as ph:
        profile._on_compile_event(
            "/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="fwd")
        profile._on_compile_event(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25,
            fun_name="jit_fwd")
        profile._on_compile_event(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        profile.record_synthetic_compile(1.5, cache_hit=True)
    a = ph.stats
    assert (a["trace_s"], a["lower_s"], a["cache_read_s"]) == (
        0.5, 0.25, 0.125)
    assert (a["cache_load_s"], a["compile_s"]) == (1.5, 0.0)
    assert (a["cache_hit"], a["compiles"], a["traces"]) == (1, 0, 1)
    assert prof.compile_counts() == {"total": 0}
    assert prof.compile_counts(cache="hit") == {"seq.warmup": 1, "total": 1}
    assert events.value({"cache": "hit"}) == 1
    assert events.value({"cache": "miss"}) == 0
    assert reg.counter("ccfd_xla_compile_seconds_total").total() == 0.0
    # the next one misses: a compile, and the hit does not stick to it
    with profile.compile_stage("seq.warmup"), profile.billed(
            "startup.executable", l_bucket=64, b_bucket=16) as ph:
        profile.record_synthetic_compile(2.0)
    assert (ph.stats["compile_s"], ph.stats["cache_hit"],
            ph.stats["compiles"]) == (2.0, 0, 1)
    assert prof.compile_counts() == {"seq.warmup": 1, "total": 1}
    assert events.value({"cache": "miss"}) == 1
    assert "compile_by_stage" in prof.snapshot()


def test_nested_traces_count_once_and_a_body_paid_twice_is_retraced(record):
    def event(secs, name):
        profile._on_compile_event(
            "/jax/core/compile/jaxpr_trace_duration", secs, fun_name=name)

    with profile.billed("startup.executable", l_bucket=64, b_bucket=8) as ph:
        time.sleep(0.02)
        event(0.004, "kernel_body")  # traced inside fwd's trace,
        event(0.006, "kernel_body")  # at two shapes
        event(0.02, "fwd")  # which reports last and spans both
    assert ph.stats["traces"] == 3 and ph.stats["retraced"] == 0
    assert ph.stats["trace_s"] == pytest.approx(0.02)
    with profile.billed("startup.inventory", l_bucket=64, b_bucket=8) as ph:
        event(0.001, "fwd")
        event(0.001, "make_jaxpr_wrapper")
    assert ph.stats["traces"] == 2 and ph.stats["retraced"] == 1
    # another (L, B) has traced nothing yet
    with profile.billed("startup.inventory", l_bucket=64, b_bucket=16) as ph:
        event(0.001, "fwd")
    assert ph.stats["retraced"] == 0
    # and outside any billed phase the hook keeps nothing
    event(0.001, "fwd")


def test_the_hook_registers_once_whatever_opens_it(monkeypatch):
    import jax.monitoring as monitoring

    calls = []
    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        lambda fn: calls.append(("duration", fn)))
    monkeypatch.setattr(monitoring, "register_event_listener",
                        lambda fn: calls.append(("event", fn)))
    monkeypatch.setattr(profile, "_HOOK_REGISTERED", False)
    rec = trace.Startup()
    monkeypatch.setattr(trace, "startup", rec)
    with rec.phase("startup.store"):  # the trace opening
        pass
    profile.StageProfiler().arm_compile_listener()
    profile.StageProfiler().arm_compile_listener()
    with trace.Startup().phase("startup.store"):  # another record
        pass
    assert calls == [("duration", profile._on_compile_event),
                     ("event", profile._on_cache_event)]


def test_the_served_path_never_parents_on_the_startup_root(record):
    sink = trace.SpanSink(sample=1.0)
    _service(tracer=trace.Tracer(Registry(), component="router", sink=sink))
    assert record.ready_at is not None
    assert {s.name.partition(".")[0] for s in record.spans()} == {"startup"}
    sink.flush(0.0)
    served = [sp for t in sink.traces()
              for sp in sink.trace(t["trace_id"])]
    assert any(sp["name"].startswith("router.") for sp in served)
    assert any(sp["name"].startswith("seq.") for sp in served)
    ours = {s.span_id for s in record.spans()}
    for sp in served:
        assert sp["trace_id"] != record.root.trace_id
        assert sp["parent_id"] not in ours
    # nothing is left set in the building thread's context
    assert trace.current_context() is None


def test_ready_and_first_verdict_stamp_once(built):
    rec, _ = built
    ready, verdict = rec.ready_at, rec.first_verdict_at
    assert ready is not None and verdict is not None and verdict >= ready
    roots = [s for s in rec.spans() if s.parent_id is None]
    rec.ready()
    rec.first_verdict()
    assert (rec.ready_at, rec.first_verdict_at) == (ready, verdict)
    assert [s for s in rec.spans() if s.parent_id is None] == roots
    assert len(roots) == 1


def test_the_record_is_bounded_and_starts_at_the_process(record):
    with record.phase("startup.store"):
        pass
    head = record.spans()[0]
    assert head.name == "startup.head" and head.t0 == record.root.t0
    # the OS's start of the process, or this module's import at the latest
    assert record.root.t0 <= trace._IMPORTED
    assert trace._process_start() <= trace._IMPORTED
    for _ in range(trace._Kept.CAP + 5):
        with record.phase("startup.restore"):
            pass
    assert len(record.spans()) == trace._Kept.CAP
    assert record._kept.dropped == 7


def test_gc_tuning_opens_no_record_of_its_own(record):
    assert tune_for_service()  # a JAX-free service: nothing to trace under
    assert record.root is None and record.spans() == []


def test_platform_up_sets_the_gauge_and_serves_the_trace(record):
    from ccfd_tpu.platform.operator import Platform, PlatformSpec

    cr = {"spec": {
        "store": {"enabled": False}, "producer": {"enabled": False},
        "investigator": {"enabled": False}, "analytics": {"enabled": False},
        "retrain": {"enabled": False}, "lifecycle": {"enabled": False},
        "engine": {"enabled": True}, "notify": {"enabled": False},
        "tracing": {"enabled": True},
    }}
    plat = Platform(PlatformSpec.from_cr(cr, cfg=Config())).up()
    try:
        assert record.ready_at is not None
        names = {s.name for s in record.spans()}
        assert {"startup", "startup.head", "startup.platform",
                "startup.executable"} <= names
        platform = next(s for s in record.spans()
                        if s.name == "startup.platform")
        for s in record.spans():  # the row scorer's ladder, inside the build
            if s.name == "startup.executable":
                assert s.parent_id == platform.span_id
                assert "b_bucket" in s.attrs and "compile_s" in s.attrs
        with urllib.request.urlopen(
                plat.exporter.endpoint + "/prometheus", timeout=10) as r:
            scrape = r.read().decode()
        for phase in ("total", "head", "platform", "executable"):
            assert f'ccfd_startup_seconds{{phase="{phase}"}}' in scrape
        gauge = plat.registries["startup"].get("ccfd_startup_seconds")
        assert gauge.value({"phase": "total"}) == record.root.duration_s
        with urllib.request.urlopen(
                plat.exporter.endpoint
                + f"/traces/{record.root.trace_id}", timeout=10) as r:
            served = json.loads(r.read().decode())
        spans = served["spans"] if isinstance(served, dict) else served
        assert {s["name"] for s in spans} == names
    finally:
        plat.down()
