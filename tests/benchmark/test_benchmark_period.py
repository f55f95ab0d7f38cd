"""The readers ``worker_period`` and ``phase_mean``, and ``span_mean`` /
``idle_under_span`` under the phases PR 38 added to the program
(``router.signals`` / ``admit`` / ``submit`` / ``await`` / ``force`` on the
loop thread's line, the
hand-over stats on ``router.score``, ``seq.fetch`` / ``seq.tap`` inside
``seq.wait``), on a small capture written by hand: three batches of a
deferring scorer on two host lines, a fourth cut by the slice's end, the
device's four programs beside them. Every expected number is worked out
by hand from the events listed at the top of
``benchmark/reduce/fixtures/period_three_batches.textproto`` (microseconds
of a 1000 us slice; two whole periods, 50 to 650).

The seven metrics are entries of the repo's ``BENCHMARK.json`` since PR
40, each with the list of its cells: the cells are resolved here through
it."""

import dataclasses
import json
import os

import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.readers import phase_mean, worker_period
from benchmark.reduce import host_spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
CONFIG = {"trace": {"op_line": "XLA Ops"}}
OBS = {"capture": os.path.join(FIXTURES, "period_three_batches.textproto"),
       "config": CONFIG}
# a capture of the program as it was before: PR 24's phases only
BEFORE = {"capture": os.path.join(FIXTURES, "worker_and_loop.textproto"),
          "config": CONFIG}

WANT = {
    # router.score starts at 50, 340, 650: two pairs
    "period_ms.sat": (650 - 50) / 2 / 1e3,
    # 300 -> 340 and 600 -> 650
    "worker_gap_ms.sat": (40 + 50) / 2 / 1e3,
    # 4, 5 and 6 us on the three router.score phases
    "handoff_ms.sat": (4 + 5 + 6) / 3 / 1e3,
    # await 20-302 clipped to 50-302, 336-602, 645-902 clipped to 645-650
    "loop_await_ms.sat": (252 + 266 + 5) / 2 / 1e3,
    # 302-303, 335-336, 602-603, 644-645
    "loop_unowned_ms.sat": (1 + 1 + 1 + 1) / 2 / 1e3,
    # one seq.fetch inside each of the three seq.score
    "fetch_ms.sat": (20 + 25 + 22) / 3 / 1e3,
    # idle 225-240, 520-560, 820-880 under fetch 230-250, 530-555, 835-857
    # and tap 555-570, 857-870
    "idle_fetch_pct.sat": (10 + 25 + 22 + 5 + 13) / 10,
}
NEW_METRICS = list(WANT)
NEW_READERS = NEW_METRICS[:5]  # the last two are data for readers of PR 24
IDLE = ["idle_assembly_pct.sat", "idle_enqueue_pct.sat", "idle_wait_pct.sat",
        "idle_fetch_pct.sat", "idle_other_pct.sat", "idle_starved_pct.sat"]
CELLS = ["history_saturated", "history_sparse_saturated",
         "ling3_window_saturated", "zaya1_window_saturated",
         "mistral4_window_saturated"]
# ms a batch on the loop's line inside the two periods, by phase
LOOP = {"router.signals": (1 + 1) / 2e3, "router.poll": (2 + 3) / 2e3,
        "router.admit": (1 + 1) / 2e3, "router.decode": (6 + 11) / 2e3,
        "router.submit": (1 + 1) / 2e3, "router.await": 523 / 2e3,
        "router.force": (2 + 2) / 2e3, "router.route": (17 + 20) / 2e3,
        "router.commit": (2 + 2) / 2e3}


def _doc(metric: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _read(metric: str, obs: dict):
    doc = _doc(metric)
    return manifest.load_kind("readers", doc["reader"]).read(
        obs, doc["args"])


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_metric_gives_the_number_worked_out_by_hand(metric):
    assert _read(metric, OBS) == pytest.approx(WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_capture_without_the_new_phases(metric):
    """The parent of PR 38 under these files: the new readers give
    nothing, so the metric is left out of its line; the two metrics that
    are data for ``span_mean`` and ``idle_under_span`` read no time under
    a phase that is not there."""
    got = _read(metric, BEFORE)
    assert got is None if metric in NEW_READERS else got == 0.0


def test_a_run_that_left_no_capture_gives_nothing(tmp_path):
    obs = {"capture": str(tmp_path / "none"), "config": CONFIG}
    assert all(_read(m, obs) is None for m in NEW_METRICS)


def test_the_cut_batch_is_left_out_of_the_period():
    cap = host_spans.of(OBS)
    assert len(cap.named("router.score")) == 3
    assert len(cap.named("seq.gather")) == 4  # one of them batch 9's
    assert phase_mean.periods(cap) == (50e3, 650e3, 2)
    # with the cut batch's start (940) the mean would be 296.7
    assert _read("period_ms.sat", OBS) != pytest.approx(
        (940 - 50) / 3 / 1e3)


def test_the_period_is_the_call_plus_the_gap_and_the_loops_parts_add_up():
    cap = host_spans.of(OBS)
    period = _read("period_ms.sat", OBS)
    scores = cap.named("router.score")
    call = sum(e.dur_ns for e in scores[:2]) / 2 / 1e6
    assert call == pytest.approx(0.255)
    assert call + _read("worker_gap_ms.sat", OBS) == pytest.approx(
        period, rel=1e-12)
    window = phase_mean.periods(cap)
    loop = {name: phase_mean.named_ms(cap, [name], window)
            for name in worker_period.LOOP_PHASES}
    assert loop == pytest.approx(LOOP, rel=1e-12)
    assert loop["router.await"] == _read("loop_await_ms.sat", OBS)
    assert sum(loop.values()) + _read(
        "loop_unowned_ms.sat", OBS) == pytest.approx(period, rel=1e-12)
    # the worker's gap is the loop's work between two awaits, the
    # hand-over and what no phase owns there
    assert sum(v for k, v in loop.items() if k != "router.await") + _read(
        "handoff_ms.sat", OBS) <= _read("worker_gap_ms.sat", OBS)


def test_the_programs_idle_ns_agrees_with_the_gap(capsys):
    cap = host_spans.of(OBS)
    cap.period_reported = False
    worker_period.read(OBS, {"part": "gap"})
    worker_period.read(OBS, {"part": "period"})  # printed once a run
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("INFO period")]
    assert lines == [
        "INFO period ms a batch: period 0.300 score 0.255 gap 0.045 "
        "(idle_ns 0.044) handoff 0.005 | loop: signals 0.001 poll 0.003 "
        "admit 0.001 decode 0.009 submit 0.001 await 0.262 force 0.002 "
        "route 0.018 commit 0.002 unowned 0.002 | pairs 2"]


def test_the_six_idle_shares_add_up_with_the_nested_phases():
    planes = trace.load(OBS["capture"], "bench.score")
    summary = trace.reduce(planes, op_line="XLA Ops", kernel_patterns=[".*"])
    assert summary.idle_share_pct == pytest.approx(11.5)
    shares = {m: _read(m, OBS) for m in IDLE}
    assert sum(shares.values()) == pytest.approx(summary.idle_share_pct,
                                                 rel=1e-9)
    # seq.wait innermost: 225-230, 520-530, 820-835 and, after the tap,
    # 870-875; the copy-out and the tap are counted once, under their own
    # name: "other" keeps seq.score 875-878 and seq.commit 878-880
    assert shares["idle_wait_pct.sat"] == pytest.approx(3.5)
    assert shares["idle_fetch_pct.sat"] == pytest.approx(7.5)
    assert shares["idle_other_pct.sat"] == pytest.approx(0.5)
    assert {"seq.fetch", "seq.tap"} <= set(
        _doc("idle_other_pct.sat")["args"]["except"])


def test_a_loop_line_that_is_not_closed_is_not_read():
    args = _doc("loop_unowned_ms.sat")["args"]
    assert phase_mean.read(BEFORE, args) is None
    assert phase_mean.read(OBS, dict(args, needs="router.none")) is None
    assert phase_mean.read(OBS, {"spans": ["router.none"],
                                 "per": "router.score"}) is None


@pytest.mark.parametrize("cell", CELLS)
@benchmark_manifests.manifest_level
def test_every_cell_resolves_with_the_new_metrics_where_listed(cell):
    resolved = benchmark_manifests.repo_manifest().resolve(cell)
    got = {m.name for m in resolved.per_layer}
    assert set(NEW_READERS) <= got
    copy_out = {"fetch_ms.sat", "idle_fetch_pct.sat"}
    assert got & copy_out == (set() if cell.startswith("history") else
                              copy_out)
    for name in got & set(NEW_METRICS):
        assert callable(manifest.load_kind(
            "readers", resolved.metric_docs[name]["reader"]).read)


@benchmark_manifests.manifest_level
def test_the_seven_entries_are_listed_each_with_its_cells():
    """By name, wherever in the list they stand. None leaves its cells
    open: an entry without a list would have to be reported by every later
    cell that reports ``tx_s``, whatever its program marks."""
    doc = benchmark_manifests.repo_doc()
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    layers = {m["layer"] for m in doc["per_layer"]
              if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        e = per_layer[name]
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["layer"] in layers and e["moves"] == "tx_s"
        assert e["better"] == "lower"
        listed = set(CELLS if name in NEW_READERS else CELLS[2:])
        assert listed <= set(e["workloads"])
        assert not set(CELLS) - listed & set(e["workloads"])


@pytest.mark.parametrize("cell", ["history_saturated",
                                  "zaya1_window_saturated"])
def test_the_parents_traced_run_has_nothing_to_read_for_the_new_metrics(cell):
    """``read_metrics`` as the traced run calls it, over a capture of the
    program before PR 38: the five of the new readers find nothing, which
    is an error in a cell the manifest lists (and left out of the line
    where an entry leaves its cells open); the two data metrics read 0."""
    resolved = benchmark_manifests.repo_manifest().resolve(cell)
    new = [m for m in resolved.per_layer if m.name in NEW_METRICS]
    with pytest.raises(RuntimeError, match="period_ms.sat"):
        core.read_metrics(resolved, new, BEFORE)
    anywhere = [dataclasses.replace(m, workloads=None) for m in new]
    got = core.read_metrics(resolved, anywhere, BEFORE)
    want = ({"fetch_ms.sat", "idle_fetch_pct.sat"}
            if cell.startswith("zaya1") else set())
    assert set(got) == want
    assert all(v["value"] == 0.0 for v in got.values())
    after = core.read_metrics(resolved, new, OBS)
    assert set(after) == {m.name for m in new}
    assert after["period_ms.sat"] == {"value": pytest.approx(0.3),
                                      "unit": "ms"}
