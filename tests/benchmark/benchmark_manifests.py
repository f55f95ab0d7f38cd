"""The two manifests the tests resolve cells from: the repo's
``BENCHMARK.json``, and ``mlp_cells_manifest.json`` beside this file, which
holds the four cells of the flagship ``mlp`` scorer that PR 23 proved on
the chip and that the driver's memory floor then refused (3.3 MB on the
device). Their configurations, mixes, generators, deployments and readers
stay under ``benchmark/`` so that a later configuration that fills the chip
behind the same REST front or router is data only; these tests keep them
working."""

import json
import os

from benchmark.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "mlp_cells_manifest.json")
FILES = {"repo": os.path.join(ROOT, "BENCHMARK.json"), "mlp": KEPT}


def names(which: str, key: str) -> list[str]:
    with open(FILES[which]) as f:
        return [e["name"] for e in json.load(f)[key]]


def load(which: str, tmp_root: str) -> manifest.Manifest:
    """``which`` manifest over the repo's ``benchmark/`` directory."""
    if which == "repo":
        return manifest.Manifest(ROOT)
    with open(FILES[which]) as f, open(
            os.path.join(tmp_root, "BENCHMARK.json"), "w") as out:
        out.write(f.read())
    link = os.path.join(tmp_root, "benchmark")
    if not os.path.exists(link):
        os.symlink(os.path.join(ROOT, "benchmark"), link)
    return manifest.Manifest(tmp_root)
