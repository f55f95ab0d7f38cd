"""Adding a configuration, a cell or a per-layer metric is a matter of new
files and appended entries, and one metric name serves every model that
has the layer.

The first half holds the property the contract asks of the benchmark's own
tests: to a copy of the repo's ``BENCHMARK.json`` a made-up fifth
configuration, a sixth cell of one chip and a per-layer metric listed for
it alone are appended (``benchmark_manifests.grow``), and then every
accepted cell resolves to exactly the metrics it resolved to before, and
every test that ``@benchmark_manifests.manifest_level`` marks, in every
``test_benchmark_*.py`` beside this file (a later PR's too), passes over the
copy as it passes over the repo's manifest. A test that counts ``configs``,
``workloads`` or ``per_layer``, takes their last elements or counts the
metrics a cell reports fails here, in the PR that writes it, and not in
the next ``model_config`` PR, which may not edit it.

The second half holds what the retired names (``cca_*`` / ``mistral4_*``
``_backbone_roofline.sat``, ``_expert_roofline.sat``,
``mistral4_mla_roofline.sat``) guarded by being names: that one model's
costs are never read over another model's trace. Under the shared names
``scope_roofline`` computes with the cost functions the configuration
names (``costs.kind`` -> ``reduce/costs_<kind>.py``), which a spy on the
three cost modules shows for each of the three small configurations, and a
``costs.kind`` with no module is an error."""

import glob
import importlib
import json
import os

import pytest

import benchmark_manifests
from benchmark.harness import manifest
from benchmark.readers import scope_roofline
from benchmark.reduce import trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = benchmark_manifests.ROOT
FIXTURES = os.path.join(ROOT, "benchmark", "reduce", "fixtures")
MADE_UP = benchmark_manifests.MADE_UP


# -- a manifest that grows ---------------------------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A checkout's ``BENCHMARK.json`` and ``benchmark/``, grown."""
    top = str(tmp_path_factory.mktemp("grown"))
    benchmark_manifests.grow(top)
    return top


def _accepted() -> list[str]:
    return benchmark_manifests.names("repo", "workloads")


def test_the_copy_holds_one_more_of_each_and_passes_the_manifests_checks(
        copy):
    before = benchmark_manifests.repo_doc()
    with benchmark_manifests.reading(copy):
        after = benchmark_manifests.repo_doc()
        man = benchmark_manifests.repo_manifest()  # its checks pass
    for key, name in (("configs", MADE_UP["config"]),
                      ("workloads", MADE_UP["cell"]),
                      ("per_layer", MADE_UP["metric"])):
        assert name not in [e["name"] for e in before[key]]
        assert [e for e in after[key] if e["name"] != name] == before[key]
        assert [e["name"] for e in after[key]].count(name) == 1
    cell = man.resolve(MADE_UP["cell"])
    assert cell.chips == 1 and cell.config_name == MADE_UP["config"]
    assert {m.name for m in cell.end_to_end} == {"tx_s", "setup_s"}
    assert MADE_UP["metric"] in {m.name for m in cell.per_layer}
    assert cell.metric_docs[MADE_UP["metric"]]["reader"] == "span_mean"


@pytest.mark.parametrize("cell", _accepted())
def test_an_accepted_cell_resolves_to_what_it_resolved_to_before(copy, cell):
    """Exactly the end-to-end and per-layer names, in their order, with
    the same files behind them."""
    was = benchmark_manifests.repo_manifest().resolve(cell)
    with benchmark_manifests.reading(copy):
        now = benchmark_manifests.repo_manifest().resolve(cell)
    for kind in ("end_to_end", "per_layer"):
        assert [m.name for m in getattr(now, kind)] == [
            m.name for m in getattr(was, kind)]
    assert MADE_UP["metric"] not in now.metric_docs
    assert now.metric_docs == was.metric_docs
    assert (now.config, now.traffic) == (was.config, was.traffic)


def _manifest_level_cases():
    """``(module, function, arguments)`` of every marked test of every
    test file beside this one, a parametrised one once a case."""
    cases = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_benchmark_*.py"))):
        name = os.path.basename(path)[:-3]
        if name == __name__.rpartition(".")[2]:
            continue
        module = importlib.import_module(name)
        for attr, fn in sorted(vars(module).items()):
            if getattr(fn, "manifest_level", None) is not True:
                continue  # (the module benchmark_manifests has the marker)
            marks = [m for m in getattr(fn, "pytestmark", [])
                     if m.name == "parametrize"]
            assert len(marks) <= 1, f"{name}.{attr}: one parametrize, please"
            if not marks:
                cases.append((name, attr, ()))
                continue
            argnames, values = marks[0].args[:2]
            one = isinstance(argnames, str) and "," not in argnames
            cases += [(name, attr, (v,) if one else tuple(v)) for v in values]
    return cases


CASES = _manifest_level_cases()


@pytest.mark.parametrize("module,function,args", CASES, ids=[
    f"{m[len('test_benchmark_'):]}.{f}" + "".join(f"-{a}" for a in args)
    for m, f, args in CASES])
def test_a_manifest_level_test_passes_over_the_grown_manifest(
        copy, module, function, args):
    fn = getattr(importlib.import_module(module), function)
    with benchmark_manifests.reading(copy):
        fn(*args)


def test_the_marked_tests_are_found_in_every_file_that_reads_the_manifest():
    found = {module for module, _, _ in CASES}
    assert {"test_benchmark_harness", "test_benchmark_hybrid_moe",
            "test_benchmark_cca_moe", "test_benchmark_mla_moe",
            "test_benchmark_period"} <= found


def test_a_test_that_pins_a_list_fails_over_the_grown_manifest(copy):
    """What this file is there to catch, written out: the two kinds of
    assertion PR 40 took out of these tests (a count of the cells, the
    list's last entry) pass over the repo's manifest of their day and fail
    as soon as a cell or a metric is appended. They are held to fail."""
    def pins_the_count():
        assert len(benchmark_manifests.repo_doc()["workloads"]) == len(
            _accepted())

    def pins_the_end():
        doc = benchmark_manifests.repo_doc()
        assert doc["per_layer"][-1]["name"] == benchmark_manifests.names(
            "repo", "per_layer")[-1]

    for pinned in (pins_the_count, pins_the_end):
        pinned()
        with benchmark_manifests.reading(copy), pytest.raises(
                AssertionError):
            pinned()


# -- one name a layer, each model's own costs ---------------------------------------

SMALL = {  # the three small configurations, each with a capture of its kind
    "ling3": ("ling3_small_config.json", "scoped_dispatches.textproto",
              "hybrid_moe"),
    "zaya1": ("zaya1_small_config.json", "scoped_cca_dispatches.textproto",
              "cca_moe"),
    "mistral4": ("mistral4_small_config.json",
                 "scoped_mla_dispatches.textproto", "mla_moe")}
KINDS = sorted(kind for _, _, kind in SMALL.values())
SHARED = [(model, metric) for metric, models in (
    ("backbone_roofline.sat", ("ling3", "zaya1", "mistral4")),
    ("expert_roofline.sat", ("ling3", "zaya1", "mistral4")),
    ("mla_roofline.sat", ("ling3", "mistral4"))) for model in models]


def _obs(model: str) -> dict:
    config, capture, _ = SMALL[model]
    with open(os.path.join(HERE, config)) as f:
        return {"capture": os.path.join(FIXTURES, capture),
                "config": json.load(f)}


def _doc(metric: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


@pytest.fixture()
def called(monkeypatch):
    """``[(costs kind, function, part)]`` of every call into a cost
    module; the recorded times are nobody's measurement, so the refusal of
    a share over 105% is taken away, as is the look for a device."""
    import jax

    calls = []
    for kind in KINDS:
        module = importlib.import_module(f"benchmark.reduce.costs_{kind}")

        def spy(fn, kind=kind):
            def wrapped(config, work, *name):
                calls.append((kind, fn.__name__, *name))
                return fn(config, work, *name)
            return wrapped

        monkeypatch.setattr(module, "part", spy(module.part))
        monkeypatch.setattr(module, "backbone", spy(module.backbone))
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    monkeypatch.setattr(trace, "roofline_share",
                        lambda *a, **kw: (50.0, "compute"))
    return calls


@pytest.mark.parametrize("model,metric", SHARED)
def test_a_shared_name_is_computed_with_the_configurations_own_costs(
        called, model, metric):
    doc = _doc(metric)
    assert doc["reader"] == "scope_roofline"
    assert "costs_<costs.kind>" in doc["what"]
    assert scope_roofline.read(_obs(model), doc["args"]) == 50.0
    kind, part = SMALL[model][2], doc["args"]["part"]
    assert scope_roofline.costs_of(_obs(model)["config"]).__name__ == (
        f"benchmark.reduce.costs_{kind}")
    assert called and {c[0] for c in called} == {kind}
    assert called[0][1:] == (("backbone",) if part == "backbone"
                             else ("part", part))


@pytest.mark.parametrize("kind,error", [
    ("no_such_model", manifest.ManifestError),  # no reduce/costs_<kind>.py
    ("../costs_mla_moe", manifest.ManifestError),  # not a name
    (None, manifest.ManifestError)])
def test_a_costs_kind_with_no_module_is_an_error(called, kind, error):
    obs = _obs("mistral4")
    obs["config"] = dict(obs["config"],
                         costs=dict(obs["config"]["costs"], kind=kind))
    with pytest.raises(error):
        scope_roofline.read(obs, _doc("backbone_roofline.sat")["args"])
    assert called == []  # and no other model's costs stand in


@pytest.mark.parametrize("grown", [False, True])
def test_the_shared_names_list_the_cells_whose_model_has_the_layer(
        copy, grown):
    with benchmark_manifests.reading(copy if grown else ROOT):
        doc = benchmark_manifests.repo_doc()
    per_layer = {m["name"]: set(m["workloads"]) for m in doc["per_layer"]}
    lm = {"ling3_window_saturated", "zaya1_window_saturated",
          "mistral4_window_saturated"}
    seq = {"history_saturated", "history_sparse_saturated"}
    assert lm <= per_layer["backbone_roofline.sat"]
    assert lm <= per_layer["expert_roofline.sat"]
    assert lm - {"zaya1_window_saturated"} <= per_layer["mla_roofline.sat"]
    assert "zaya1_window_saturated" not in per_layer["mla_roofline.sat"]
    for name in ("backbone_roofline.sat", "expert_roofline.sat",
                 "mla_roofline.sat"):
        assert not seq & per_layer[name]
    assert seq <= per_layer["kernel_roofline.sat"]
    assert not lm & per_layer["kernel_roofline.sat"]
    # the five names the fold retired point at nothing
    for retired in ("cca_backbone_roofline.sat", "cca_expert_roofline.sat",
                    "mistral4_backbone_roofline.sat",
                    "mistral4_mla_roofline.sat",
                    "mistral4_expert_roofline.sat"):
        assert retired not in per_layer
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", retired + ".json"))
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", "scope_roofline_cca.py"))
