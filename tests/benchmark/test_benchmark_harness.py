"""The harness is driven by data: a later PR adds a cell, a mix or a metric
by adding files and manifest entries, and edits no file that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark_manifests
from benchmark.harness import manifest

ROOT = benchmark_manifests.ROOT
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def _names(key):
    return [(which, name) for which in benchmark_manifests.FILES
            for name in benchmark_manifests.names(which, key)]


@pytest.mark.parametrize("which", list(benchmark_manifests.FILES))
@benchmark_manifests.manifest_level
def test_manifest_has_the_contracts_keys_and_limits(which):
    doc = benchmark_manifests.load(which).doc
    assert set(doc) == TOP_KEYS
    assert 1 <= doc["run_seconds"] <= 51
    assert len(json.dumps(doc)) < 64 * 1024
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    for entry in doc["configs"] + doc["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in doc["configs"]:
        assert len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
        assert any(w["config"] == c["name"] for w in doc["workloads"])


@pytest.mark.parametrize("which,workload", _names("workloads"))
@benchmark_manifests.manifest_level
def test_every_cell_resolves_to_files_and_modules(which, workload):
    man = benchmark_manifests.load(which)
    cell = man.resolve(workload)
    assert manifest.load_kind("deployments", cell.deployment_kind).Deployment
    assert manifest.load_kind("generators", cell.generator_kind).Generator
    reported = {m.name for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m.moves in reported
    for m in cell.end_to_end + cell.per_layer:
        reader = manifest.load_kind("readers",
                                    cell.metric_docs[m.name]["reader"])
        assert callable(reader.read)
    # the configuration's file states what the manifest says was reduced
    entry = man.configs[cell.config_name]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert entry["source"] == cell.config["source"]


@pytest.mark.parametrize("folder,metric", sorted({
    *(("end_to_end", m) for _, m in _names("end_to_end")),
    *(("layer_metrics", m) for _, m in _names("per_layer"))}))
def test_every_metric_has_a_file_of_its_own(folder, metric):
    path = os.path.join(ROOT, "benchmark", folder, metric + ".json")
    with open(path) as f:
        doc = json.load(f)
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", doc["reader"] + ".py"))


def test_a_listed_metric_with_nothing_to_read_is_an_error():
    """``generator_late`` finds nothing where no request had a due instant.
    The manifest lists the cell for the metric, so that is an error; with
    the cells left open the metric is left out of the line instead."""
    import dataclasses
    import types

    import numpy as np

    from benchmark.harness import core

    cell = benchmark_manifests.load("mlp").resolve("rest_single_paced")
    late = next(m for m in cell.per_layer
                if m.name == "generator_late_ms.paced")
    obs = {"outcome": types.SimpleNamespace(late_ms=np.zeros(0))}
    with pytest.raises(RuntimeError, match="generator_late_ms.paced"):
        core.read_metrics(cell, [late], obs)
    anywhere = dataclasses.replace(late, workloads=None)
    assert core.read_metrics(cell, [anywhere], obs) == {}
    obs["outcome"].late_ms = np.array([1.0, 3.0])
    assert core.read_metrics(cell, [late], obs) == {
        "generator_late_ms.paced": {"value": pytest.approx(2.98),
                                    "unit": "ms"}}


def _digest(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _run(copy, *args):
    """``run.py`` in a copy that holds the benchmark alone: it resolves
    the cell, then stops for want of the program, before it imports JAX."""
    return subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=copy)


def test_a_new_cell_is_added_as_data_with_no_edit(tmp_path):
    copy = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(os.path.join(copy, "benchmark"))
    with open(benchmark_manifests.KEPT) as f:
        doc = json.load(f)
    bench = os.path.join(copy, "benchmark")
    with open(os.path.join(bench, "configs", "seldon_rest_mlp.json")) as f:
        config = json.load(f)
    config["serving"] = dict(config["serving"], model_name="mlp_q8",
                             checkpoint_dir="checkpoints_q8")
    with open(os.path.join(bench, "configs", "seldon_rest_q8.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "mixed_paced.json"), "w") as f:
        json.dump({"generator": "rest", "workers": 2, "connections": 8,
                   "rows_per_request": 8, "warm_requests_per_connection": 1,
                   "arrivals": {"kind": "poisson", "rate_per_s": 100}}, f)
    with open(os.path.join(bench, "layer_metrics", "late_p50_ms.new.json"),
              "w") as f:
        json.dump({"reader": "generator_late",
                   "args": {"quantile": 0.5}}, f)
    doc["configs"].append({
        "name": "seldon_rest_q8", "source": config["source"],
        "file": "benchmark/configs/seldon_rest_q8.json",
        "reduced": ["table_rows"], "why": "int8 wire"})
    doc["workloads"].append({
        "name": "rest_q8_mixed", "config": "seldon_rest_q8",
        "traffic": "mixed_paced", "chips": 1, "why": "a later PR's cell"})
    with open(os.path.join(bench, "end_to_end", "verdict_p75_ms.json"),
              "w") as f:
        json.dump({"reader": "latency_quantile",
                   "args": {"percent": 75}}, f)
    doc["end_to_end"][0]["workloads"].append("rest_q8_mixed")
    doc["end_to_end"].append({
        "name": "verdict_p75_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["rest_q8_mixed"]})
    doc["per_layer"].append({
        "name": "late_p50_ms.new", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "Traffic generator",
        "moves": doc["end_to_end"][0]["name"],
        "workloads": ["rest_q8_mixed"]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    cell = manifest.Manifest(copy).resolve("rest_q8_mixed")
    assert cell.config["serving"]["model_name"] == "mlp_q8"
    assert cell.traffic["rows_per_request"] == 8
    assert "late_p50_ms.new" in [m.name for m in cell.per_layer]
    assert "verdict_p75_ms" in [m.name for m in cell.end_to_end]
    assert cell.metric_docs["verdict_p75_ms"]["args"] == {"percent": 75}
    after = _digest(os.path.join(copy, "benchmark"))
    assert {k: after[k] for k in before} == before, "an existing file moved"
    assert len(after) == len(before) + 4

    found = _run(copy, "--workload", "rest_q8_mixed", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert found.returncode != 0 and found.stdout == ""
    assert "ccfd_tpu/" in found.stderr  # resolved; only the program is missing
    unknown = _run(copy, "--workload", "no_such_cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert unknown.returncode != 0 and unknown.stdout == ""
    assert "unknown workload" in unknown.stderr


@pytest.mark.parametrize("name", [
    "has space", "comma,name", "slash/name", "-leading", "", "x" * 65,
    "grüß", None])
def test_names_outside_the_allowed_set_are_refused(name):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(name, "test")


@pytest.mark.parametrize("name", ["a", "_x", "9lives", "tx_s.sat-2", "x" * 64])
def test_names_inside_the_allowed_set_pass(name):
    assert manifest.check_name(name, "test") == name


@pytest.mark.parametrize("unit", [
    "tokens per second", "µs", "", "x" * 17, "a,b", None])
def test_units_outside_the_allowed_set_are_refused(unit):
    with pytest.raises(manifest.ManifestError):
        manifest.check_unit(unit, "test")


@pytest.mark.parametrize("unit", ["ms", "tx/s", "%", "us/tx", "rows"])
def test_units_inside_the_allowed_set_pass(unit):
    assert manifest.check_unit(unit, "test") == unit


def test_a_metric_that_moves_no_end_to_end_metric_is_refused(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"][0]["moves"] = "no_such_metric"
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest(str(tmp_path))
