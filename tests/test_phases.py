"""``trace.phase``: the served path's phases are events of any profiler
capture (router loop, score worker, scorer, store, device wait), child
spans under an active span, and the one clock read behind the scorer's two
histograms. A pipelined router's loop line is closed (``router.signals``,
``router.admit``, ``router.submit``, ``router.await``, ``router.force``
beside poll, decode, route and commit),
the hand-over to the score worker is on ``router.score``, ``seq.wait``
names the copy-out and the tap, and a batch's phases share an ordinal."""

import glob
import os
import time

import jax
import numpy as np
import pytest

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.observability.trace import SpanSink, Tracer, phase
from ccfd_tpu.process.fraud import build_engine
from ccfd_tpu.router.router import Router
from ccfd_tpu.serving.history import SeqScorer

SEQ_PHASES = ("seq.gather", "seq.pad", "seq.enqueue", "seq.wait",
              "seq.commit")


class Capture:
    """A profiler capture around a block; ``lines`` afterwards holds the
    host plane's lines that carry a phase, each a list of
    ``(name, start_ns, end_ns, stats)``."""

    def __init__(self, logdir):
        self.logdir = str(logdir)
        self.lines: list[list[tuple]] = []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(path)
        for plane in profile.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(("seq.", "router."))]
                if events:
                    self.lines.append(events)

    def named(self, name):
        return [e for line in self.lines for e in line if e[0] == name]

    def line_of(self, name):
        found = [line for line in self.lines
                 if any(e[0] == name for e in line)]
        assert len(found) == 1, f"{name} is on {len(found)} lines"
        return found[0]


def tiny_scorer(registry=None, batch_sizes=(16,)):
    return SeqScorer(seq_mod.init(jax.random.PRNGKey(0)), length=8,
                     batch_sizes=batch_sizes, compute_dtype="float32",
                     registry=registry)


def rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, 30)).astype(np.float32)


@pytest.fixture(scope="module")
def aux_family():
    """A history family whose program hands back ``aux`` beside the
    probabilities, as ``hybrid_moe`` does, at no compile to speak of."""
    import jax.numpy as jnp

    from ccfd_tpu.models import registry

    def make_apply(dtype, pos_length, config):
        @jax.jit
        def fn(params, hist):
            last = hist[:, -1, :]
            return jax.nn.sigmoid(last.sum(-1)), {
                "logits": last * 2.0,
                "row_pairs": jnp.arange(len(hist), dtype=jnp.int32)}

        return fn

    name = "phases_aux"
    registry.register_history(registry.HistorySpec(
        name=name, owns=lambda params: False, make_apply=make_apply,
        make_observer=lambda reg: lambda aux: {
            "pairs_served": int(aux["row_pairs"].sum())}))
    yield name
    del registry._HISTORY[name]


def aux_scorer(family, registry=None):
    return SeqScorer({}, length=8, batch_sizes=(16,),
                     compute_dtype="float32", registry=registry,
                     family=family)


def inside(events, span):
    return [e for e in events if span[1] <= e[1] and e[2] <= span[2]]


def run_pipelined(tmp_path, score_fn, n_records=256, max_batch=64):
    """``n_records`` keyed records through a pipelined router with no
    tracer, inside a capture."""
    cfg = Config(fraud_threshold=0.99)
    broker = Broker()
    engine = build_engine(cfg, broker, Registry())
    router = Router(cfg, broker, score_fn, engine, Registry(),
                    max_batch=max_batch)
    assert router.tracer is None
    records = [{FEATURE_NAMES[j]: float(j % 5) for j in range(30)}
               | {"id": i % 7, "customer_id": i % 7}
               for i in range(n_records)]
    with Capture(tmp_path) as cap:
        thread = router.start(poll_timeout_s=0.01, pipeline=True)
        try:
            broker.produce_batch(cfg.kafka_topic, records)
            deadline = time.monotonic() + 60.0
            consumed = router.registry.counter("transaction_incoming_total")
            routed = router.registry.counter("transaction_outgoing_total")
            while (routed.total() < len(records)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert consumed.value() == routed.total() == len(records)
        finally:
            router.stop()
            thread.join(timeout=30)
            assert not thread.is_alive()
    return cap


@pytest.fixture(scope="module")
def deferring_capture(tmp_path_factory):
    """The history scorer behind a pipelined router: it defers, so the
    stream's last batch (and any the loop finished with none newer in
    flight) is forced on the loop thread."""
    scorer = tiny_scorer(Registry(), batch_sizes=(16, 128))
    scorer.warmup()
    return run_pipelined(tmp_path_factory.mktemp("deferring"), scorer)


@pytest.mark.parametrize("explicit", [False, True])
def test_a_phase_under_an_active_span_is_its_child(explicit):
    sink = SpanSink(sample=1.0)
    tr = Tracer(Registry(), component="router", sink=sink)
    batch = tr.start("router.batch")
    # the router's stages are parented on the batch span, which hops
    # threads; the scorer's on whatever span is active on the thread
    outer_cm = (phase("router.score", tr, batch.context, rows=3) if explicit
                else tr.span("router.score", parent=batch.context))
    with outer_cm as outer:
        with phase("seq.gather", rows=3) as inner:
            inner.set(new_customers=2)
    outer = outer.span if explicit else outer
    tr.finish(batch)
    assert inner.span.parent_id == outer.span_id
    assert outer.parent_id == batch.span_id
    assert inner.span.trace_id == outer.trace_id == batch.trace_id
    assert inner.span.attrs == {"rows": 3, "new_customers": 2}
    assert inner.span.duration_s == inner.seconds > 0.0
    got = {d["name"] for d in sink.trace(batch.trace_id)}
    assert got == {"router.batch", "router.score", "seq.gather"}
    assert tr.registry.histogram("trace_span_seconds").count(
        {"span": "seq.gather"}) == 1


def test_a_phase_with_no_tracer_is_the_annotation_alone():
    sink = SpanSink(sample=1.0)
    Tracer(Registry(), sink=sink)  # wired elsewhere, not active here
    with phase("seq.gather", rows=3) as ph:
        ph.set(new_customers=1)
    assert ph.span is None and ph.seconds > 0.0
    tiny_scorer().score(rows(5), ids=list("abcde"))
    assert sink.traces() == [] and sink.registry.counter(
        "ccfd_trace_spans_total").total() == 0


def test_a_failing_phase_marks_its_span_and_restores_the_context():
    from ccfd_tpu.observability.trace import current_context

    sink = SpanSink(sample=0.0)  # an errored trace is kept whatever the rate
    tr = Tracer(Registry(), sink=sink)
    with tr.span("router.score") as sp:
        with pytest.raises(RuntimeError):
            with phase("seq.enqueue", bytes=8):
                raise RuntimeError("staging failed")
        assert current_context() == sp.context
    spans = {d["name"]: d for d in sink.trace(sp.trace_id)}
    assert spans["seq.enqueue"]["status"] == "error"


def test_a_capture_around_the_scorer_holds_its_phases(tmp_path):
    reg = Registry()
    scorer = tiny_scorer(reg)
    scorer.warmup()
    x = rows(40)
    ids = [f"c{i % 10}" for i in range(40)]  # chunks of 16, 16, 8 rows
    with Capture(tmp_path) as cap:
        scorer.score(x, ids=ids)
    dispatches = sum(g["dispatches"]
                     for g in scorer.executable_grid()["grid"])
    assert dispatches == 3
    (score,) = cap.named("seq.score")
    assert score[3]["rows"] == 40
    line = cap.line_of("seq.score")
    for name in SEQ_PHASES:
        events = cap.named(name)
        assert events, name
        for e in events:
            assert e in line
            assert score[1] <= e[1] and e[2] <= score[2]
            assert e[3]["cpu_ns"] >= 0
    assert len(cap.named("seq.enqueue")) == dispatches
    assert len(cap.named("seq.wait")) == dispatches
    assert len(cap.named("seq.commit")) == 1
    gathers = cap.named("seq.gather")
    assert [e[3]["rows"] for e in gathers] == [16, 16, 8]
    # customers c0..c9: the first chunk meets all ten and six of them twice
    assert [e[3]["new_customers"] for e in gathers] == [10, 0, 0]
    assert [e[3]["repeated_keys"] for e in gathers] == [6, 6, 0]
    for e in cap.named("seq.enqueue"):
        assert e[3]["bytes"] == 16 * 8 * 30 * 4
        assert (e[3]["b_bucket"], e[3]["l_bucket"]) == (16, 8)
        assert e[3]["attn_kernel"] == 0  # 8 records: no length it tiles
        assert e[3]["expert_kernel"] == 0  # ``seq`` has no experts
        assert e[3]["ssd_kernel"] == 0  # and no state-space mixer
        assert e[3]["kda_kernel"] == 0  # nor a KDA one
        assert e[3]["gdn_kernel"] == 0  # nor a Gated DeltaNet one
        assert e[3]["flat_wire"] == 0  # 240 values a row: no whole tile
    assert [e[3]["padded_rows"] for e in cap.named("seq.pad")] == [0, 0, 8]
    assert sum(e[3]["rows"] for e in cap.named("seq.wait")) == 40
    (commit,) = cap.named("seq.commit")
    assert (commit[3]["customers"], commit[3]["stale"]) == (10, 0)


def test_the_enqueue_phase_says_which_wire_the_batch_crossed(tmp_path):
    """512 records of 30 values are 15 whole (8, 128) tiles a row: the
    batch crosses flat, the phase carries it, and ``bytes`` is still the
    batch's own size."""
    scorer = SeqScorer(seq_mod.init(jax.random.PRNGKey(0)), length=512,
                       batch_sizes=(4,), registry=Registry())
    scorer.warmup()
    with Capture(tmp_path) as cap:
        scorer.score(rows(3), ids=["a", "b", "c"])
    (enqueue,) = cap.named("seq.enqueue")
    assert enqueue[3]["flat_wire"] == 1
    assert enqueue[3]["attn_kernel"] == 1
    assert enqueue[3]["expert_kernel"] == 0
    assert enqueue[3]["ssd_kernel"] == 0
    assert enqueue[3]["kda_kernel"] == 0
    assert enqueue[3]["gdn_kernel"] == 0
    assert enqueue[3]["bytes"] == 4 * 512 * 30 * 4
    assert (enqueue[3]["b_bucket"], enqueue[3]["l_bucket"]) == (4, 512)


LANE_WIDE_SCAN = {"hidden_size": 256, "mamba_n_heads": 8, "mamba_d_head": 64,
                  "mamba_d_state": 128, "mamba_n_groups": 1, "scan_chunk": 128}


# two KDA layers (the dense one and one with experts) at heads a lane tile
# wide; 8 records are 240 tokens: four chunks of 64 behind 16 of padding
LANE_WIDE_KDA = {"num_attention_heads": 2, "head_dim": 128,
                 "v_head_dim": 128, "layers_kept": [0, 2]}


@pytest.mark.parametrize("model,widths,experts,scan,delta", [
    # the small presets: experts of 64 x 32 (the tile loop), Mamba-2 heads
    # of 16 with a state of 16 and KDA heads of 16 (the scans through XLA)
    ("mistral4", {}, False, False, False),
    ("mistral4", {"hidden_size": 128, "moe_intermediate_size": 128}, True,
     False, False),  # no ``mamba2`` layer: no scan kernel, whatever the widths
    ("granite4h", {}, False, False, False),
    ("granite4h", LANE_WIDE_SCAN, False, True, False),
    ("ling3", {"layers_kept": [0, 2]}, False, False, False),
    ("ling3", LANE_WIDE_KDA, False, False, True),
], ids=["small", "lane_wide", "small_scan", "lane_wide_scan", "small_kda",
        "lane_wide_kda"])
def test_the_enqueue_phase_says_which_kernels_the_program_holds(
        tmp_path, model, widths, experts, scan, delta):
    """A keyed stream through ``SeqScorer`` with a ``hybrid_moe`` model:
    where hidden and expert widths fill lane tiles the program holds the
    grouped kernels (``ops/grouped_experts.py``), where the Mamba-2 heads
    fill lane tiles and the state is a lane tile wide the scan's kernel
    (``ops/ssd_scan.py``) and, its x, B and C then whole lane tiles too, the
    convolution's before it (``ops/short_conv.py``), where a KDA head's keys
    and values are a lane tile wide the delta rule's (``ops/kda_scan.py``),
    and the inventory, the ``seq.enqueue`` phase and
    ``seq_expert_kernel_dispatch_total`` / ``seq_ssd_kernel_dispatch_total``
    / ``seq_conv_kernel_dispatch_total`` / ``seq_kda_kernel_dispatch_total``
    say so of every dispatch, once a dispatch, as ``attn_kernel``'s signs
    do of the attention; the chunk stays the configuration's."""
    import json

    from benchmark.reference import hybrid_moe_f32, mla_moe_f32, ssm_moe_f32
    from ccfd_tpu.models import hybrid_moe

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tests", "benchmark",
                           model + "_small_config.json")) as f:
        config = {**json.load(f), **widths}
    if model == "mistral4":
        config.update(num_hidden_layers=2, layers_kept=[0, 1])
    ref = {"mistral4": mla_moe_f32, "granite4h": ssm_moe_f32,
           "ling3": hybrid_moe_f32}[model]
    reg = Registry()
    scorer = SeqScorer(ref.make_params(config), length=8,
                       batch_sizes=(4,), compute_dtype="float32",
                       registry=reg, family="hybrid_moe",
                       family_config=hybrid_moe.HybridConfig.from_dict(
                           config))
    scorer.warmup()
    with Capture(tmp_path) as cap:
        for lo in (0, 4):
            scorer.score(rows(4, seed=lo), ids=["a", "b", "a", "c"])
    enqueues = cap.named("seq.enqueue")
    chunk = config.get("scan_chunk")  # none where no layer scans
    assert [(e[3]["expert_kernel"], e[3]["ssd_kernel"], e[3]["conv_kernel"],
             e[3]["kda_kernel"], e[3].get("scan_chunk"))
            for e in enqueues] == [
        (int(experts), int(scan), int(scan), int(delta), chunk)] * 2
    assert [e[3]["attn_kernel"] for e in enqueues] == [0, 0]  # heads of 16
    assert [e[3]["gdn_kernel"] for e in enqueues] == [0, 0]  # no such mixer
    assert [(g["b_bucket"], g["expert_kernel"], g["ssd_kernel"],
             g["conv_kernel"], g["kda_kernel"], g["attn_kernel"],
             g.get("scan_chunk"), g["dispatches"])
            for g in scorer.executable_grid()["grid"]] == [
        (4, experts, scan, scan, delta, False, chunk, 2)]
    assert reg.counter("seq_bucket_dispatch_total").total() == 2
    assert reg.counter("seq_expert_kernel_dispatch_total").total() == (
        2 if experts else 0)
    assert reg.counter("seq_ssd_kernel_dispatch_total").total() == (
        2 if scan else 0)
    assert reg.counter("seq_conv_kernel_dispatch_total").total() == (
        2 if scan else 0)
    assert reg.counter("seq_kda_kernel_dispatch_total").total() == (
        2 if delta else 0)
    assert reg.counter("seq_gdn_kernel_dispatch_total").total() == 0


def test_a_deferred_batch_waits_and_commits_inside_the_next_call(tmp_path):
    """The deferring entry: ``seq.wait`` and ``seq.commit`` of batch k
    open inside ``seq.score`` of k+1 (after k+1's gather, pad and
    enqueue); a batch that is forced waits and commits outside every
    ``seq.score``; the stats say how many batches were open and whether
    this one was staged beside an unresolved one."""
    from ccfd_tpu.router.router import DeferrableRecords

    scorer = tiny_scorer(Registry())
    scorer.warmup()
    sizes = (5, 9, 3)
    with Capture(tmp_path) as cap:
        results = [
            scorer.score_with_ids(DeferrableRecords(
                [{"id": f"b{k}-{j}"} for j in range(n)]), rows(n, seed=k))
            for k, n in enumerate(sizes)]
        np.asarray(results[-1])
    scores = cap.named("seq.score")
    assert [e[3]["rows"] for e in scores] == list(sizes)
    assert [e[3]["open_batches"] for e in scores] == [0, 1, 1]
    assert [e[3]["overlapped"] for e in scores] == [0, 1, 1]

    assert not inside(cap.named("seq.wait"), scores[0])
    assert not inside(cap.named("seq.commit"), scores[0])
    for k in (1, 2):
        (wait,) = inside(cap.named("seq.wait"), scores[k])
        (commit,) = inside(cap.named("seq.commit"), scores[k])
        (enqueue,) = inside(cap.named("seq.enqueue"), scores[k])
        assert wait[3]["rows"] == commit[3]["customers"] == sizes[k - 1]
        assert enqueue[2] <= wait[1] <= wait[2] <= commit[1]
        # a wait inside the NEXT batch's call says whose it is
        assert wait[3]["seq_batch"] == commit[3]["seq_batch"] == k
    forced = [e for e in cap.named("seq.wait")
              if not any(s[1] <= e[1] and e[2] <= s[2] for s in scores)]
    assert [e[3]["rows"] for e in forced] == [sizes[-1]]
    assert [e[3]["seq_batch"] for e in scores] == [1, 2, 3]
    assert forced[0][3]["seq_batch"] == 3


def test_a_pipelined_router_with_no_tracer_shows_in_a_capture(
        deferring_capture):
    cap, n = deferring_capture, 256
    worker = cap.line_of("router.score")
    loop = cap.line_of("router.decode")
    assert loop is cap.line_of("router.route") is cap.line_of("router.poll")
    assert worker is not loop
    assert worker is cap.line_of("seq.score")
    for name in ("router.score", "router.decode", "router.route"):
        assert sum(e[3]["rows"] for e in cap.named(name)) == n
    assert sum(e[3]["rows"] for e in cap.named("router.poll")) == n
    for e in cap.named("seq.score"):  # the scorer's call, inside the stage
        assert any(inside([e], s) for s in cap.named("router.score"))


LOOP_PHASES = ("router.signals", "router.poll", "router.admit",
               "router.decode", "router.submit", "router.await",
               "router.force", "router.route", "router.commit")


def test_the_loops_line_is_closed_and_its_phases_never_overlap(
        deferring_capture):
    cap = deferring_capture
    loop = cap.line_of("router.decode")
    for name in ("router.signals", "router.admit", "router.submit",
                 "router.await", "router.force"):
        assert cap.line_of(name) is loop, name
    admits = cap.named("router.admit")
    assert sum(e[3]["rows"] for e in admits) == 256
    assert all(e[3]["admitted"] == e[3]["rows"] and e[3]["shed"] == 0
               for e in admits)
    awaits = cap.named("router.await")
    assert sum(e[3]["rows"] for e in awaits) == 256
    assert {e[3]["deferred"] for e in awaits} == {1}
    outer = sorted((e for e in loop if e[0] in LOOP_PHASES),
                   key=lambda e: e[1])
    assert {e[0] for e in outer} >= set(LOOP_PHASES) - {"router.commit"}
    for a, b in zip(outer, outer[1:]):
        assert a[2] <= b[1], (a[0], b[0])
    # whatever else the line holds is the scorer's, under a force
    for e in loop:
        if e[0] not in LOOP_PHASES:
            assert e[0] in ("seq.wait", "seq.commit")
            assert inside([e], next(f for f in cap.named("router.force")
                                    if f[1] <= e[1] <= f[2]))


def test_a_forced_batch_waits_and_commits_under_router_force(
        deferring_capture):
    """The stream's last batch at least: its ``seq.wait`` and
    ``seq.commit`` run on the loop thread and have a parent now."""
    cap = deferring_capture
    scores = cap.named("seq.score")
    outside = [e for e in cap.named("seq.wait") + cap.named("seq.commit")
               if not any(inside([e], s) for s in scores)]
    assert outside, "no batch was forced"
    forces = cap.named("router.force")
    loop = cap.line_of("router.decode")
    for e in outside:
        assert e in loop and any(inside([e], f) for f in forces), e[0]
    last = inside(outside, max(forces, key=lambda f: f[1]))
    assert [e[0] for e in last].count("seq.commit") == 1
    assert [e[0] for e in last].count("seq.wait") >= 1
    assert len({e[3]["seq_batch"] for e in last}) == 1


def test_the_hand_over_is_measured_on_router_score(deferring_capture):
    cap = deferring_capture
    scores = sorted(cap.named("router.score"), key=lambda e: e[1])
    assert len(scores) >= 4
    for e in scores:
        assert e[3]["handoff_ns"] >= 0 and e[3]["idle_ns"] >= 0
    assert scores[0][3]["idle_ns"] == 0  # the worker's first batch
    for prev, e in zip(scores, scores[1:]):
        # the worker's own clock reads sit just inside the gap between
        # the two annotations
        assert 0 < e[3]["idle_ns"] <= e[1] - prev[2]
    decodes = {e[3]["batch"]: e for e in cap.named("router.decode")}
    for e in scores:  # submitted after its decode, begun after its submit
        assert e[3]["handoff_ns"] <= e[1] - decodes[e[3]["batch"]][2]


def test_one_ordinal_on_all_of_a_batchs_router_phases(deferring_capture):
    cap = deferring_capture
    per_name = {name: [e[3]["batch"] for e in sorted(
        cap.named(name), key=lambda e: e[1])]
        for name in LOOP_PHASES[2:] + ("router.score",)}
    n = len(per_name["router.admit"])
    assert per_name["router.admit"] == list(range(1, n + 1))
    for name in ("router.decode", "router.submit", "router.score",
                 "router.await", "router.route"):
        assert per_name[name] == list(range(1, n + 1)), name
    assert set(per_name["router.force"]) <= set(range(1, n + 1))
    assert n in per_name["router.force"]  # the stream's last batch
    for name in LOOP_PHASES[:2]:  # once a turn of the loop, batch or none
        assert "batch" not in cap.named(name)[0][3]
    # the two lines join by containment: a batch's router.score holds one
    # seq.score, and the scorer's ordinal runs beside the router's
    for e in cap.named("router.score"):
        (call,) = inside(cap.named("seq.score"), e)
        assert call[3]["seq_batch"] == e[3]["batch"]


def test_a_scorer_that_resolves_in_its_call_is_awaited_not_forced(tmp_path):
    cap = run_pipelined(tmp_path, lambda x: np.full(len(x), 0.25,
                                                    np.float32), 128, 32)
    awaits = cap.named("router.await")
    assert awaits and {e[3]["deferred"] for e in awaits} == {0}
    assert not cap.named("router.force")
    assert cap.line_of("router.await") is cap.line_of("router.decode")
    batches = sorted(e[3]["batch"] for e in cap.named("router.score"))
    assert batches == sorted(e[3]["batch"] for e in awaits)
    assert sum(e[3]["rows"] for e in awaits) == 128


def test_seq_wait_names_the_copy_out_and_the_tap(tmp_path, aux_family):
    reg = Registry()
    scorer = aux_scorer(aux_family, reg)
    scorer.warmup()
    kept = []
    scorer.aux_tap = lambda idx, m, aux: kept.append(aux)
    with Capture(tmp_path) as cap:
        scorer.score(rows(20), ids=[f"c{i}" for i in range(20)])
    waits = cap.named("seq.wait")
    assert [e[3]["rows"] for e in waits] == [16, 4] and len(kept) == 2
    for wait, aux in zip(waits, kept):
        (fetch,) = inside(cap.named("seq.fetch"), wait)
        (tap,) = inside(cap.named("seq.tap"), wait)
        assert fetch[2] <= tap[1]
        assert fetch[3]["leaves"] == 2
        assert fetch[3]["bytes"] == sum(v.nbytes for v in aux.values()) \
            == 16 * 30 * 4 + 16 * 4
        assert tap[3]["rows"] == wait[3]["rows"]
        # what the observer returns stays on seq.wait
        assert wait[3]["pairs_served"] == sum(range(16))
        assert "pairs_served" not in tap[3]
        assert fetch[3]["seq_batch"] == tap[3]["seq_batch"] \
            == wait[3]["seq_batch"] == 1
    assert len(cap.named("seq.fetch")) == len(cap.named("seq.tap")) == 2


def test_seq_wait_of_a_family_without_aux_holds_neither(tmp_path):
    scorer = tiny_scorer(Registry())
    scorer.warmup()
    with Capture(tmp_path) as cap:
        scorer.score(rows(20), ids=[f"c{i}" for i in range(20)])
        scorer.score(rows(3), ids=["x", "y", "z"])
    assert len(cap.named("seq.wait")) == 3
    assert not cap.named("seq.fetch") and not cap.named("seq.tap")
    assert [e[3]["seq_batch"] for e in cap.named("seq.score")] == [1, 2]
    assert [e[3]["seq_batch"] for e in cap.named("seq.wait")] == [1, 1, 2]
    assert [e[3]["seq_batch"] for e in cap.named("seq.commit")] == [1, 2]


@pytest.mark.parametrize("with_aux", [False, True])
def test_the_scorers_histograms_are_fed_from_the_phases_clock_reads(
        with_aux, aux_family):
    """Also where ``seq.wait`` has children: ``seq_dispatch_seconds`` is
    enqueue + the whole wait, the copy-out and the tap inside it."""
    reg = Registry()
    scorer = aux_scorer(aux_family, reg) if with_aux else tiny_scorer(reg)
    sink = SpanSink(sample=1.0)
    tr = Tracer(Registry(), sink=sink)
    batches = [rows(40, seed=1), rows(7, seed=2), rows(16, seed=3)]
    with tr.span("router.score") as sp:
        for i, x in enumerate(batches):
            scorer.score(x, ids=[f"k{i}-{j % 9}" for j in range(len(x))])
    spans = sink.trace(sp.trace_id)

    def total(*names):
        return sum(d["duration_s"] for d in spans if d["name"] in names)

    assembly = reg.get("seq_assembly_seconds")
    dispatch = reg.get("seq_dispatch_seconds")
    assert assembly.count() == dispatch.count() == len(batches)
    assert assembly.sum() == pytest.approx(
        total("seq.gather", "seq.pad"), abs=1e-9)
    assert dispatch.sum() == pytest.approx(
        total("seq.enqueue", "seq.wait"), abs=1e-9)
    assert sum(d["name"] == "seq.score" for d in spans) == len(batches)
    assert total("seq.score") >= (assembly.sum() + dispatch.sum()
                                  + total("seq.commit"))
    by_id = {d["span_id"]: d for d in spans}
    children = [d for d in spans if d["name"] in ("seq.fetch", "seq.tap")]
    assert len(children) == (2 * 5 if with_aux else 0)  # 3 + 1 + 1 waits
    for d in children:  # each a child span of its seq.wait
        assert by_id[d["parent_id"]]["name"] == "seq.wait"
    assert total("seq.fetch", "seq.tap") <= total("seq.wait")
