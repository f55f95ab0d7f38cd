"""``reduce/host_spans.py`` and the readers ``span_mean``, ``span_offcpu``
and ``idle_under_span`` on a small capture written by hand: a device line,
the score worker's line and the router loop's line, four batches of which
the slice's edges cut the first and the last. Every expected number below
is worked out by hand from the events listed at the top of
``benchmark/reduce/fixtures/worker_and_loop.textproto`` (microseconds of a
1000 us slice, so an idle share in % is a tenth of the idle microseconds)."""

import json
import os

import pytest

from benchmark.harness import manifest
from benchmark.readers import idle_under_span, span_mean, span_offcpu
from benchmark.reduce import host_spans, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
CONFIG = {"trace": {"op_line": "XLA Ops"}}
OBS = {"capture": os.path.join(FIXTURES, "worker_and_loop.textproto"),
       "config": CONFIG}
# a capture of a program without phases: the benchmark's own span only
BARE = {"capture": os.path.join(FIXTURES, "three_dispatches.textproto"),
        "config": CONFIG}

NEW_METRICS = [
    "enqueue_ms.sat", "result_wait_ms.sat", "commit_ms.sat",
    "gather_offcpu_pct.sat", "idle_assembly_pct.sat", "idle_enqueue_pct.sat",
    "idle_wait_pct.sat", "idle_other_pct.sat", "idle_starved_pct.sat"]
IDLE = [m for m in NEW_METRICS if m.startswith("idle_")]

# by hand, per metric as its file's reader and arguments give it
WANT = {
    # batches 1 and 2 are whole; batch 3's enqueue of 40 us and batch 0's
    # wait of 50 us and commit of 8 us are left out
    "enqueue_ms.sat": (30 + 40) / 2 / 1e3,
    "result_wait_ms.sat": (70 + 80) / 2 / 1e3,
    "commit_ms.sat": (14 + 8) / 2 / 1e3,
    # gathers of 140, 120 and 170 us on 105, 60 and 150 us of CPU
    "gather_offcpu_pct.sat": 100 * (1 - 315 / 430),
    # gather + pad 110-270, 425-560, 730-915: the device ran in none of it
    "idle_assembly_pct.sat": (160 + 135 + 185) / 10,
    # enqueue 270-300, 560-600 less the operation stamped at 590, 915-955
    "idle_enqueue_pct.sat": (30 + 30 + 40) / 10,
    # wait 10-60 (busy to 40), 300-370 (busy 310-360), 600-680 (busy to 660)
    "idle_wait_pct.sat": (20 + 20 + 20) / 10,
    # router.score open (0-70 and 730-1000 shown by the cut batches' phases)
    # and no leaf phase innermost: 60-70; 100-110, 370-400; 420-425,
    # 680-700; 955-960
    "idle_other_pct.sat": (10 + 40 + 25 + 5) / 10,
    # 70-100, 400-420, 700-730
    "idle_starved_pct.sat": (30 + 20 + 30) / 10,
}


def _read(metric: str, obs: dict, **override):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        doc = json.load(f)
    return manifest.load_kind("readers", doc["reader"]).read(
        obs, dict(doc["args"], **override))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_gives_the_number_worked_out_by_hand(metric):
    # the recording's microseconds stand for a run's milliseconds: its
    # gathers are held against no clock step
    override = ({"cpu_clock_step_ms": 0.0}
                if metric == "gather_offcpu_pct.sat" else {})
    assert _read(metric, OBS, **override) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("wall_ns,cpu_ns,want", [
    # the ledger's zaya1_* reading (PR 39): 1.9 x as much CPU time as wall
    # time is charged, a raw share of -90.21: a share reads 0
    (20.0e6, 38.042e6, 0.0),
    # a slice's gathers of 1.4 ms against a clock that steps by 10 ms
    (1.4e6, 0.0, None), (1.4e6, 10.0e6, None),
    (430e6, 315e6, 100 * (1 - 315 / 430)), (20.0e6, 0.0, 100.0),
    (0.0, 0.0, None)])
def test_the_offcpu_share_is_a_share_and_needs_a_step_of_the_cpu_clock(
        wall_ns, cpu_ns, want):
    got = span_offcpu.share_pct(wall_ns, cpu_ns, 10.0e6)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_gathers_metric_states_the_clocks_step_and_its_cells():
    """With its own file's step the recording's 430 us of gathers read
    nothing; it is listed where a gather takes milliseconds a batch."""
    assert _read("gather_offcpu_pct.sat", OBS) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == "gather_offcpu_pct.sat")
    assert {"history_saturated", "history_sparse_saturated"} <= set(
        entry["workloads"])
    assert not {"ling3_window_saturated", "zaya1_window_saturated",
                "mistral4_window_saturated"} & set(entry["workloads"])


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_capture_without_the_programs_phases_gives_nothing(metric):
    """An older commit of the program under this benchmark: the metric is
    left out of its line, the run does not fail."""
    assert _read(metric, BARE) is None


def test_a_run_that_left_no_capture_gives_nothing(tmp_path):
    obs = {"capture": str(tmp_path / "none"), "config": CONFIG}
    assert all(_read(m, obs) is None for m in NEW_METRICS)


def test_the_five_idle_shares_add_up_to_the_slices_idle_share():
    planes = trace.load(OBS["capture"], "bench.score")
    summary = trace.reduce(planes, op_line="XLA Ops", kernel_patterns=[".*"])
    assert summary.idle_share_pct == pytest.approx(80.0)
    assert sum(_read(m, OBS) for m in IDLE) == pytest.approx(
        summary.idle_share_pct, rel=1e-9)
    cap = host_spans.of(OBS)
    assert cap.window_ns / 1e9 == pytest.approx(summary.window_s)


def test_a_batch_cut_by_the_slices_edge_is_left_out_of_span_mean():
    cap = host_spans.of(OBS)
    assert len(cap.named("seq.enqueue")) == 3  # one of them batch 3's
    assert len(cap.named("seq.score")) == 2
    with_the_cut_one = sum(e.dur_ns for e in cap.named("seq.enqueue")) / 3
    got = span_mean.read(OBS, {"span": "seq.enqueue"})
    assert got == pytest.approx(35e-3)
    assert got != pytest.approx(with_the_cut_one / 1e6)


def test_the_worker_is_the_line_that_holds_router_score():
    cap = host_spans.of(OBS)
    worker = cap.line_of(host_spans.WORKER_SPAN)
    loop = cap.line_of(host_spans.LOOP_SPAN)
    assert worker is not loop and len(cap.lines) == 2
    assert {e.name for e in loop} == {"router.poll", "router.decode",
                                     "router.route", "router.commit"}
    assert host_spans.worker_open(cap, worker) == [
        (0.0, 70e3), (100e3, 400e3), (420e3, 700e3), (730e3, 1000e3)]
    stats = cap.named("seq.gather")[0].stats
    assert (stats["rows"], stats["cpu_ns"]) == (1024, 105000)


def test_the_info_line_splits_the_starved_time_by_the_loops_phase(capsys):
    cap = host_spans.of(OBS)
    cap.reported = False
    idle_under_span.read(OBS, {"outside": "router.score"})
    idle_under_span.read(OBS, {"spans": ["seq.wait"]})  # printed once a run
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("INFO idle_by_phase")]
    assert lines == [
        "INFO idle_by_phase % of slice: cut 0.70 router.score 3.00 "
        "seq.commit 3.00 seq.enqueue 10.00 seq.gather 43.00 seq.pad 5.00 "
        "seq.score 1.30 seq.wait 6.00 starved 8.00 (loop thread: none 2.60 "
        "router.commit 0.30 router.decode 1.20 router.poll 0.20 "
        "router.route 3.70)"]


def test_off_cpu_share_needs_the_stat():
    assert span_offcpu.read(OBS, {"span": "seq.pad"}) is None


@pytest.mark.parametrize("line,want", [
    ([("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("b", 60, 100)],
     [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
      (40, 60, "a"), (60, 100, "b")]),
    ([("a", 0, 10), ("a", 30, 40)], [(0, 10, "a"), (30, 40, "a")]),
])
def test_innermost_names_each_stretch_by_the_deepest_open_phase(line, want):
    events = [trace.Event(n, a, b - a, {}) for n, a, b in line]
    assert host_spans.innermost(events) == want


def test_interval_arithmetic():
    u = host_spans.union([(5, 10), (8, 20), (30, 40)])
    assert u == [(5, 20), (30, 40)]
    assert host_spans.complement(u, 0, 50) == [(0, 5), (20, 30), (40, 50)]
    assert host_spans.intersect([(0, 10), (20, 30)], [(5, 25)]) == [
        (5, 10), (20, 25)]
    assert host_spans.total_ns(u) == 25


def _capture(*lines):
    return host_spans.Capture(
        lo_ns=0.0, hi_ns=1000.0, busy=[[(0.0, 10.0)]], size_bytes=0,
        others={}, lines=[[trace.Event(n, a, b - a, {}) for n, a, b in line]
                          for line in lines])


@pytest.mark.parametrize("loop,want_tail", [
    # the next batch was decoded while the last recorded one ran: the
    # worker went straight on when that one ended
    ([("router.decode", 210, 220)], (400, 1000)),
    # it was decoded only later: the worker starved until then
    ([("router.decode", 450, 470)], (470, 1000)),
    # nothing decoded since: the worker had nothing to go on with
    ([("router.decode", 150, 160)], None),
])
def test_a_batch_cut_in_its_first_phase_is_shown_by_the_loops_decode(
        loop, want_tail):
    """The capture stopped inside the gather of the batch after the last
    recorded one: no phase of that batch is in it."""
    worker = [("router.score", 200, 400), ("seq.gather", 205, 390)]
    cap = _capture(worker, loop)
    want = [(200, 400)] if want_tail is None else (
        [(200, 1000)] if want_tail[0] == 400 else [(200, 400), want_tail])
    assert host_spans.worker_open(cap, cap.lines[0]) == want
