"""The two manifests the tests resolve cells from: the repo's
``BENCHMARK.json``, and ``mlp_cells_manifest.json`` beside this file, which
holds the four cells of the flagship ``mlp`` scorer that PR 23 proved on
the chip and that the driver's memory floor then refused (3.3 MB on the
device). Their configurations, mixes, generators, deployments and readers
stay under ``benchmark/`` so that a later configuration that fills the chip
behind the same REST front or router is data only; these tests keep them
working.

**A test of the repo's manifest names what it guards.** A later PR appends
a configuration, a cell or a per-layer metric to ``BENCHMARK.json`` and may
edit no file here, so no test may hold the length of ``configs``,
``workloads`` or ``per_layer``, their last elements, or the count of the
metrics a cell reports: it finds its entries by name, and holds a metric
away from the accepted cells it names. A test that reads the repo's
manifest takes it from ``repo_manifest()`` / ``repo_doc()`` and is marked
``@manifest_level``: ``test_benchmark_growth.py`` runs every marked test of
every ``test_benchmark_*.py`` again over a copy of the manifest to which a
made-up configuration, cell and metric were appended (``grow``,
``reading``), and a test that pins a list fails there."""

import contextlib
import functools
import json
import os
import shutil
import tempfile

from benchmark.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
KEPT = os.path.join(HERE, "mlp_cells_manifest.json")
FILES = {"repo": os.path.join(ROOT, "BENCHMARK.json"), "mlp": KEPT}
# the checkout whose BENCHMARK.json and benchmark/ "repo" stands for:
# ``reading`` points it at a copy for the length of a test
_REPO = {"root": ROOT}


def manifest_level(fn):
    """Marks a test that reads the repo's manifest and nothing that runs:
    ``test_benchmark_growth.py`` calls it again over the grown copy."""
    fn.manifest_level = True
    return fn


def repo_root() -> str:
    return _REPO["root"]


def repo_doc() -> dict:
    with open(os.path.join(repo_root(), "BENCHMARK.json")) as f:
        return json.load(f)


def repo_manifest() -> manifest.Manifest:
    return manifest.Manifest(repo_root())


def names(which: str, key: str) -> list[str]:
    with open(FILES[which]) as f:
        return [e["name"] for e in json.load(f)[key]]


@functools.cache
def _kept(which: str) -> manifest.Manifest:
    tmp = tempfile.TemporaryDirectory(prefix="benchmark_manifests_")
    _kept.dirs.append(tmp)  # gone with the process
    return load(which, tmp.name)


_kept.dirs = []


def load(which: str, tmp_root: str | None = None) -> manifest.Manifest:
    """``which`` manifest over the repo's ``benchmark/`` directory (a kept
    one is written into ``tmp_root``, or once a process into a directory of
    its own)."""
    if which == "repo":
        return repo_manifest()
    if tmp_root is None:
        return _kept(which)
    with open(FILES[which]) as f, open(
            os.path.join(tmp_root, "BENCHMARK.json"), "w") as out:
        out.write(f.read())
    link = os.path.join(tmp_root, "benchmark")
    if not os.path.exists(link):
        os.symlink(os.path.join(ROOT, "benchmark"), link)
    return manifest.Manifest(tmp_root)


# -- the manifest, grown as a later PR grows it ----------------------------------

MADE_UP = {"config": "kafka_history_made_up", "cell": "made_up_saturated",
           "metric": "made_up_ms.sat"}


def grow(copy: str) -> dict:
    """Into the empty directory ``copy``: the repo's ``benchmark/`` and its
    ``BENCHMARK.json`` with what a ``model_config`` PR brings appended, as
    new files and new entries only: a fifth configuration (a file under
    ``tests/benchmark/``, as the small configurations are), a sixth cell of
    one chip, listed for ``tx_s``, and a per-layer metric with a file of its
    own, listed for that cell alone. Returns the grown document."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(FILES["repo"]) as f:
        doc = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kafka_history_seq.json")) as f:
        config = json.load(f)
    config["source"] = "tests only: a configuration a later PR appends"
    os.makedirs(os.path.join(copy, "tests", "benchmark"))
    with open(os.path.join(copy, "tests", "benchmark",
                           "made_up_config.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(copy, "benchmark", "layer_metrics",
                           MADE_UP["metric"] + ".json"), "w") as f:
        json.dump({"reader": "span_mean", "args": {"span": "seq.made_up"},
                   "what": "tests only"}, f)
    doc["configs"].append({
        "name": MADE_UP["config"], "source": config["source"],
        "file": "tests/benchmark/made_up_config.json",
        "reduced": config["reduced"], "why": "a later PR's configuration"})
    doc["workloads"].append({
        "name": MADE_UP["cell"], "config": MADE_UP["config"],
        "traffic": "keyed_saturated", "chips": 1, "why": "a later PR's cell"})
    tx_s = next(m for m in doc["end_to_end"] if m["name"] == "tx_s")
    tx_s["workloads"].append(MADE_UP["cell"])
    doc["per_layer"].append({
        "name": MADE_UP["metric"], "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Scorer dispatch seam",
        "moves": "tx_s", "workloads": [MADE_UP["cell"]]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return doc


@contextlib.contextmanager
def reading(copy: str):
    """Inside: ``repo_root`` / ``repo_doc`` / ``repo_manifest`` read the
    checkout ``copy`` (one that ``grow`` made)."""
    _REPO["root"] = copy
    try:
        yield
    finally:
        _REPO["root"] = ROOT
