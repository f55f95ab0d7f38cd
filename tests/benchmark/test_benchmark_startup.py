"""The reader ``startup_span`` and the nine ``startup_*`` metrics, over a
start-up record written by hand (seconds on ``perf_counter``; ``T_START``
100, ``setup_s`` 50, so set-up is 100 to 150):

    root startup          99.9 ->        (the process began 0.1 s before)
    startup.head          99.9 -> 110.0
    startup.store        110.0 -> 110.5
    startup.weights      110.5 -> 112.5
    startup.executable   113.0 -> 121.0  (64, 16): trace 2.0 lower 1.0
                                          cache_load 1.5 (a hit)
    startup.executable   121.0 -> 131.0  (64, 128): trace 2.5 lower 1.5
                                          compile 4.0 (a miss)
    startup.restore      132.0 -> 135.0
    startup.gc           135.0 -> 135.5  (named by no metric)
    startup.router       140.0 -> 140.1  (nor this)
    ready_at 140.2, first_verdict_at 142.2
    startup.inventory    141.7 -> 142.7  trace 0.75 (half of it lies
                                          inside ready -> first verdict)
    startup.inventory    151.0 -> 152.0  trace 0.9 (the window's: left out)

The spans are stand-ins with the four fields the reader reads, so the
numbers below hold whatever the program's classes become."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

import benchmark_manifests
from benchmark.harness import core, manifest
from benchmark.readers import startup_span
from test_benchmark_hybrid_moe import _small_cell, service_gc  # noqa: F401

ROOT = benchmark_manifests.ROOT
T_START, SETUP_S = 100.0, 50.0


def _span(name, t0, end, **attrs):
    return SimpleNamespace(name=name, t0=t0, duration_s=end - t0,
                           attrs=attrs)


def _record():
    root = _span("startup", 99.9, 140.2)
    spans = [
        _span("startup.head", 99.9, 110.0),
        _span("startup.store", 110.0, 110.5, bytes=1 << 20),
        _span("startup.weights", 110.5, 112.5, leaves=40, bytes=1 << 30),
        _span("startup.executable", 113.0, 121.0, l_bucket=64, b_bucket=16,
              trace_s=2.0, lower_s=1.0, compile_s=0.0, cache_load_s=1.5,
              cache_read_s=0.5, cache_hit=1, compiles=0, traces=300,
              retraced=0),
        _span("startup.executable", 121.0, 131.0, l_bucket=64, b_bucket=128,
              trace_s=2.5, lower_s=1.5, compile_s=4.0, cache_load_s=0.0,
              cache_read_s=0.0, cache_hit=0, compiles=1, traces=300,
              retraced=0),
        _span("startup.restore", 132.0, 135.0, customers=8, bytes=4096),
        _span("startup.gc", 135.0, 135.5),
        _span("startup.router", 140.0, 140.1),
        root,
        _span("startup.inventory", 141.7, 142.7, l_bucket=64, b_bucket=16,
              trace_s=0.75, lower_s=0.0, compile_s=0.0, cache_load_s=0.0,
              traces=2, retraced=1),
        _span("startup.inventory", 151.0, 152.0, l_bucket=64, b_bucket=128,
              trace_s=0.9, lower_s=0.0, compile_s=0.0, cache_load_s=0.0,
              traces=2, retraced=1),
    ]
    return SimpleNamespace(root=root, spans=lambda: list(spans),
                           ready_at=140.2, first_verdict_at=142.2)


WANT = {
    "startup_head_s.sat": 110.0 - 99.9,
    "startup_weights_s.sat": 2.0,
    "startup_trace_lower_s.sat": 2.0 + 1.0 + 2.5 + 1.5 + 0.75,
    "startup_compile_s.sat": 4.0,
    "startup_cache_load_s.sat": 1.5,
    # 8.0 - 4.5 and 10.0 - 8.0
    "startup_first_run_s.sat": 3.5 + 2.0,
    "startup_store_s.sat": 0.5 + 3.0,
    "startup_to_first_verdict_s.sat": 2.0,
    # head from 100.0 on 10.0, store 0.5, weights 2.0, executables 18.0,
    # restore 3.0, ready to first verdict 2.0 and the 0.5 of the first
    # inventory past it: 36.0 of 50
    "startup_covered_pct.sat": 72.0,
}
METRICS = list(WANT)
SECONDS = METRICS[:-1]
CELLS = ["history_saturated", "history_sparse_saturated",
         "ling3_window_saturated", "zaya1_window_saturated",
         "mistral4_window_saturated", "xing4_window_saturated",
         "granite4h_window_saturated", "nemotron3n_window_saturated",
         "qwen3next_window_saturated"]
OBS = {"setup_s": SETUP_S}


@pytest.fixture
def by_hand(monkeypatch):
    rec = _record()
    monkeypatch.setattr(startup_span, "record", lambda: rec)
    monkeypatch.setattr(startup_span, "_reported", None)
    monkeypatch.setattr(sys.modules["__main__"], "T_START", T_START,
                        raising=False)
    return rec


def _doc(metric: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _read(metric: str, obs: dict = OBS):
    doc = _doc(metric)
    return manifest.load_kind("readers", doc["reader"]).read(
        obs, doc["args"])


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_gives_the_number_worked_out_by_hand(by_hand, metric):
    assert _doc(metric)["reader"] == "startup_span"
    assert _read(metric) == pytest.approx(WANT[metric], rel=1e-12)


def test_the_parts_add_up_to_setup_within_what_is_not_covered(by_hand):
    parts = sum(_read(m) for m in SECONDS)
    covered = _read("startup_covered_pct.sat") / 100.0 * SETUP_S
    # the eight name the same stretches the union is made of, less the
    # inventory's walk over the jaxpr (0.25 of its 1.0 is no trace) and
    # plus what overlaps (0.5) and the head's tenth before T_START
    assert parts == pytest.approx(covered - 0.25 + 0.5 + 0.1)
    assert parts <= SETUP_S and covered <= SETUP_S


def test_without_run_py_the_window_starts_at_the_records_root(
        by_hand, monkeypatch):
    monkeypatch.delattr(sys.modules["__main__"], "T_START")
    assert startup_span.window(OBS, by_hand) == (99.9, 149.9)
    # the head counts whole then: 36.1 of 50
    assert _read("startup_covered_pct.sat") == pytest.approx(72.2)


def test_what_a_window_opens_is_not_set_ups(by_hand):
    longer = {"setup_s": 53.0}  # the second inventory began before its end
    assert _read("startup_trace_lower_s.sat", longer) == pytest.approx(
        WANT["startup_trace_lower_s.sat"] + 0.9)
    assert _read("startup_covered_pct.sat", longer) == pytest.approx(
        100.0 * 37.0 / 53.0)


def test_a_run_prints_the_split_once(by_hand, capsys):
    for metric in METRICS:
        _read(metric)
    lines = capsys.readouterr().out.splitlines()
    head = [ln for ln in lines if ln.startswith("INFO startup s:")]
    assert len(head) == 1
    assert "head 10.100" in head[0] and "compile 4.000" in head[0]
    assert "covered 36.000 of setup_s 50.000" in head[0]
    execs = [ln for ln in lines if ln.startswith("INFO startup.executable")]
    assert len(execs) == 2 and "L 64 B 128" in execs[1]
    assert "compile_s 4.000" in execs[1] and "cache_hit 0" in execs[1]
    assert len([ln for ln in lines
                if ln.startswith("INFO startup.inventory")]) == 1


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_record_gives_none(monkeypatch, metric):
    """An older commit under this benchmark: no ``startup`` in its
    ``observability/trace.py``; and a process that built no scorer, whose
    record never opened. The reader gives None; the metric's file asks for
    0.0 in its place (the seconds such a program's spans account for),
    because it lists its cells and ``read_metrics`` raises on a None
    there."""
    from ccfd_tpu.observability import trace

    args = _doc(metric)["args"]
    monkeypatch.setattr(trace, "startup", trace.Startup())  # never opened
    assert startup_span.record() is None
    assert startup_span.read(OBS, {"part": args["part"]}) is None
    assert startup_span.read(OBS, args) == 0.0
    monkeypatch.delattr(trace, "startup")  # the parent's module
    assert startup_span.record() is None
    assert startup_span.read(OBS, {"part": args["part"]}) is None
    assert startup_span.read(OBS, args) == args["absent"] == 0.0


def test_the_programs_own_record_is_what_the_reader_finds(monkeypatch):
    from ccfd_tpu.observability import trace

    rec = trace.Startup(t0=99.9)
    monkeypatch.setattr(trace, "startup", rec)
    with rec.phase("startup.weights"):
        pass
    rec.ready()
    assert startup_span.record() is rec
    got = startup_span.parts(rec, float("inf"))
    assert sorted(got) == sorted(startup_span.PARTS)
    head = rec.spans()[0]
    assert got["head"] == head.duration_s and head.t0 == 99.9
    assert got["weights"] == rec.spans()[1].duration_s


@benchmark_manifests.manifest_level
def test_the_nine_entries_move_setup_s_from_the_layer_start_up():
    """By name, wherever in the list they stand; lists by membership."""
    doc = benchmark_manifests.repo_doc()
    per_layer = {m["name"]: m for m in doc["per_layer"]}
    for name in METRICS:
        e = per_layer[name]
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (e["layer"], e["moves"], e["source"]) == (
            "Start-up", "setup_s", "program_span")
        pct = name == "startup_covered_pct.sat"
        assert (e["unit"], e["better"]) == (
            ("%", "higher") if pct else ("s", "lower"))
        assert set(CELLS) <= set(e["workloads"])
    # no other metric claims the layer or the end-to-end metric yet
    for name, e in per_layer.items():
        if name not in METRICS and e["layer"] == "Start-up":
            assert e["moves"] == "setup_s"


@benchmark_manifests.manifest_level
@pytest.mark.parametrize("cell", CELLS)
def test_an_accepted_cell_resolves_with_the_nine(cell):
    resolved = benchmark_manifests.repo_manifest().resolve(cell)
    reported = [m.name for m in resolved.per_layer]
    for name in METRICS:
        assert reported.count(name) == 1
        assert resolved.metric_docs[name]["reader"] == "startup_span"
        assert resolved.metric_docs[name]["args"]["part"] in (
            *startup_span.PARTS, "covered_pct")
    assert "setup_s" in {m.name for m in resolved.end_to_end}


@benchmark_manifests.manifest_level
def test_a_cell_the_entries_do_not_list_resolves_without_them():
    """A later cell reports the nine once its PR appends its name."""
    doc = benchmark_manifests.repo_doc()
    others = [w["name"] for w in doc["workloads"] if w["name"] not in CELLS]
    man = benchmark_manifests.repo_manifest()
    for cell in others:
        assert not set(METRICS) & {
            m.name for m in man.resolve(cell).per_layer}


@pytest.mark.parametrize("cell", ["history_saturated",
                                  "granite4h_window_saturated"])
def test_read_metrics_prints_the_nine_in_a_listed_cell(by_hand, cell):
    """As the traced run calls it; and over a program without the record
    the line carries 0.0 and nothing raises."""
    resolved = benchmark_manifests.repo_manifest().resolve(cell)
    ours = [m for m in resolved.per_layer if m.name in METRICS]
    assert len(ours) == len(METRICS)
    got = core.read_metrics(resolved, ours, dict(OBS))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(WANT)
    assert got["startup_covered_pct.sat"]["unit"] == "%"
    assert got["startup_head_s.sat"]["unit"] == "s"


def test_the_parents_traced_run_reads_zero_and_does_not_raise(monkeypatch):
    monkeypatch.setattr(startup_span, "record", lambda: None)
    resolved = benchmark_manifests.repo_manifest().resolve(
        "qwen3next_window_saturated")
    ours = [m for m in resolved.per_layer if m.name in METRICS]
    got = core.read_metrics(resolved, ours, dict(OBS))
    assert {v["value"] for v in got.values()} == {0.0}
    assert sorted(got) == sorted(METRICS)


def test_a_whole_run_on_the_cpu_leaves_a_record_the_nine_read(
        service_gc, monkeypatch, tmp_path, capsys):  # noqa: F811
    """``run_cell`` at the small preset (as ``run.py`` calls it, but for
    the chip) under a fresh record: the deployment's calls open the phases
    themselves, the nine read the record, the parts fit inside ``setup_s``
    and the split is printed."""
    import time

    from ccfd_tpu.observability import trace

    t_start = time.perf_counter()
    rec = trace.Startup(t0=t_start)  # as if the process began with the run
    monkeypatch.setattr(trace, "startup", rec)
    monkeypatch.setattr(startup_span, "_reported", None)
    monkeypatch.setattr(sys.modules["__main__"], "T_START", t_start,
                        raising=False)
    result = core.run_cell(_small_cell(str(tmp_path)), seed=2**31 + 54,
                           seconds=3.0, trace=False, t_start=t_start,
                           root=ROOT)
    assert result["correct"] is True, capsys.readouterr().out
    names = [s.name for s in rec.spans()]
    for name in ("startup.head", "startup.store", "startup.weights",
                 "startup.executable", "startup.inventory",
                 "startup.restore", "startup.gc", "startup.router",
                 "startup"):
        assert name in names, name
    restore = next(s for s in rec.spans() if s.name == "startup.restore")
    assert restore.attrs["customers"] > 0
    assert rec.ready_at is not None and rec.first_verdict_at is not None
    obs = {"setup_s": result["metrics"]["setup_s"]["value"]}
    got = {m: _read(m, obs) for m in METRICS}
    assert all(v is not None and v >= 0.0 for v in got.values())
    assert 0.0 < got["startup_covered_pct.sat"] <= 100.0
    assert sum(got[m] for m in SECONDS) <= obs["setup_s"] * 1.001
    assert got["startup_head_s.sat"] > 0.0
    assert got["startup_trace_lower_s.sat"] > 0.0  # the CPU traces too
    assert got["startup_store_s.sat"] >= restore.duration_s
    assert "INFO startup s: head " in capsys.readouterr().out
