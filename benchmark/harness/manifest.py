"""BENCHMARK.json and the data files it names: loading, checking, resolving.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name in the
manifest and nowhere else:

    benchmark/configs/<config>.json          -> "deployment": <kind>
    benchmark/deployments/<kind>.py
    benchmark/traffic/<traffic>.json         -> "generator": <kind>
    benchmark/generators/<kind>.py
    benchmark/end_to_end/<metric>.json       -> "reader": <kind>
    benchmark/layer_metrics/<metric>.json    -> "reader": <kind>
    benchmark/readers/<kind>.py

so a later PR adds a cell, a mix or a metric by adding files and manifest
entries and edits nothing that is here. Imports no JAX and nothing of the
program: the tests and ``run.py``'s argument handling use it before any
device is touched.
"""

from __future__ import annotations

import importlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest or a file it names breaks the benchmark's contract."""


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name is at most 64 letters, digits, '_', "
            "'.' and '-', and starts with a letter, a digit or '_'")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"{what}: unit {unit!r} is not 1 to 16 letters, digits, '_', "
            "'/', '%', '.' and '-'")
    return unit


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{path}: no such file") from None
    except ValueError as e:
        raise ManifestError(f"{path}: not JSON ({e})") from None
    if not isinstance(doc, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return doc


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None  # None: every cell that can report it
    bound: float | None = None  # end-to-end only
    layer: str | None = None  # per-layer only
    moves: str | None = None  # per-layer only

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]
    # the file of each metric this cell reports: reader kind, arguments
    metric_docs: dict[str, dict] = field(default_factory=dict)

    @property
    def deployment_kind(self) -> str:
        return check_name(self.config.get("deployment"),
                          f"configuration {self.config_name}: deployment")

    @property
    def generator_kind(self) -> str:
        return check_name(self.traffic.get("generator"),
                          f"traffic {self.traffic_name}: generator")


class Manifest:
    """The parsed ``BENCHMARK.json`` of one checkout."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.doc = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])
        self.run_seconds = int(self.doc["run_seconds"])
        self.end_to_end = [self._metric(m, True)
                           for m in self.doc["end_to_end"]]
        self.per_layer = [self._metric(m, False)
                          for m in self.doc["per_layer"]]
        names = [m.name for m in self.end_to_end + self.per_layer]
        if len(set(names)) != len(names):
            raise ManifestError("two metrics share a name")
        e2e_names = {m.name for m in self.end_to_end}
        for m in self.per_layer:
            if m.moves not in e2e_names:
                raise ManifestError(
                    f"per-layer metric {m.name}: moves {m.moves!r}, which "
                    "is no end-to-end metric")
        self.configs = {}
        for c in self.doc["configs"]:
            check_name(c.get("name"), "configuration")
            for key in c.get("reduced", []):
                check_name(key, f"configuration {c['name']}: reduced key")
            self.configs[c["name"]] = c
        self.workloads = {}
        for w in self.doc["workloads"]:
            check_name(w.get("name"), "workload")
            check_name(w.get("traffic"), f"workload {w['name']}: traffic")
            if w.get("config") not in self.configs:
                raise ManifestError(
                    f"workload {w['name']}: unknown configuration "
                    f"{w.get('config')!r}")
            if w.get("chips") not in (1, 4):
                raise ManifestError(f"workload {w['name']}: chips is 1 or 4")
            self.workloads[w["name"]] = w
        for m in self.end_to_end + self.per_layer:
            for cell in m.workloads or ():
                if cell not in self.workloads:
                    raise ManifestError(
                        f"metric {m.name}: unknown workload {cell!r}")

    @staticmethod
    def _metric(m: dict, end_to_end: bool) -> Metric:
        name = check_name(m.get("name"), "metric")
        if m.get("better") not in ("lower", "higher"):
            raise ManifestError(f"metric {name}: better is lower or higher")
        if m.get("source") not in SOURCES:
            raise ManifestError(f"metric {name}: source {m.get('source')!r}")
        cells = m.get("workloads")
        return Metric(
            name=name, unit=check_unit(m.get("unit"), f"metric {name}"),
            better=m["better"], source=m["source"],
            workloads=None if cells is None else tuple(cells),
            bound=float(m["bound"]) if end_to_end else None,
            layer=None if end_to_end else m["layer"],
            moves=None if end_to_end else check_name(
                m.get("moves"), f"metric {name}: moves"))

    def resolve(self, workload: str) -> Cell:
        """The cell ``workload`` with its configuration, its traffic mix
        and its metrics' files loaded."""
        check_name(workload, "workload")
        if workload not in self.workloads:
            raise ManifestError(
                f"unknown workload {workload!r}; BENCHMARK.json has "
                f"{sorted(self.workloads)}")
        w = self.workloads[workload]
        entry = self.configs[w["config"]]
        config = _load_json(os.path.join(self.root, entry["file"]))
        traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", w["traffic"] + ".json"))
        end_to_end = [m for m in self.end_to_end if m.reported_in(workload)]
        reported = {m.name for m in end_to_end}
        per_layer = [m for m in self.per_layer
                     if m.reported_in(workload) and m.moves in reported]
        cell = Cell(name=workload, chips=int(w["chips"]), why=w["why"],
                    config_name=w["config"], traffic_name=w["traffic"],
                    config=config, traffic=traffic,
                    end_to_end=end_to_end, per_layer=per_layer)
        for folder, metrics in (("end_to_end", end_to_end),
                                ("layer_metrics", per_layer)):
            for m in metrics:
                doc = _load_json(os.path.join(
                    self.bench_dir, folder, m.name + ".json"))
                check_name(doc.get("reader"), f"metric {m.name}: reader")
                cell.metric_docs[m.name] = doc
        return cell


def load_kind(package: str, kind: str):
    """``benchmark.<package>.<kind>``: the module a data file names."""
    check_name(kind, f"{package} kind")
    try:
        return importlib.import_module(f"benchmark.{package}.{kind}")
    except ModuleNotFoundError as e:
        if e.name == f"benchmark.{package}.{kind}":
            raise ManifestError(
                f"benchmark/{package}/{kind}.py: no such file") from None
        raise
