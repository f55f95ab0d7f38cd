"""The REST model server, built exactly as ``python -m ccfd_tpu serve``
builds it: ``cli.start_server`` (Scorer -> warm-up -> PredictionServer,
the C++ front with its take queue in front of the scorer).

From the program the benchmark takes the system under test and its
counters; the guarantees it is held to are the configuration's: every
request answered 200 with one probability per row, in order, every row
scored on the device by the fused kernel.
"""

from __future__ import annotations

import dataclasses
import importlib
import os

LATENCY = "seldon_api_executor_client_requests_seconds"
REQUESTS = "seldon_api_executor_server_requests_total"


def restore_params(serving: dict, root: str):
    """The committed checkpoint of the served family, through the
    program's own restore (what ``serve`` does at start-up), which the
    configuration's file names as ``module:function``."""
    module, _, function = serving["restore"].partition(":")
    restore = getattr(importlib.import_module(module), function)
    params = restore(os.path.join(root, serving["checkpoint_dir"]))
    if params is None:
        raise RuntimeError(f"no checkpoint under {serving['checkpoint_dir']}")
    return params


def serving_section(config: dict, control: bool) -> dict:
    serving = dict(config["serving"])
    if control:
        serving.update(config["control"])
    return serving


def scorer_counters(scorer) -> dict:
    grid = scorer.executable_grid()
    return {
        "dispatches": {int(b): int(n) for b, n in grid["dispatches"].items()},
        "fused": bool(grid["fused"]),
        "host_tier_rows": int(grid["host_tier_rows"]),
        "host_fallback_scores": int(scorer.host_fallback_scores),
        "dispatch_timeouts": int(scorer.dispatch_timeouts),
    }


def check_device_path(checks, before: dict, after: dict) -> None:
    """chip_smoke.py's conditions: nothing on the host stood in for the
    device while the window ran."""
    dispatched = sum(after["dispatches"].values()) - sum(
        before["dispatches"].values())
    checks.at_least("device_dispatches", dispatched, 1)
    checks.exactly("fused", after["fused"], True)
    checks.exactly("host_tier_rows", after["host_tier_rows"], 0)
    for key in ("host_fallback_scores", "dispatch_timeouts"):
        checks.exactly(key, after[key] - before[key], 0)


class Deployment:
    def __init__(self, config: dict, *, root: str, control: bool,
                 traced: bool, seed: int = 0):
        self.config = config
        self.root = root
        self.serving = serving_section(config, control)
        self.srv = None
        self.scorer = None

    def start(self) -> dict:
        from ccfd_tpu import cli
        from ccfd_tpu.config import Config

        s = self.serving
        cfg = dataclasses.replace(
            Config(), model_name=s["model_name"],
            compute_dtype=s["compute_dtype"],
            batch_sizes=tuple(s["batch_sizes"]),
            host_tier_rows=int(s["host_tier_rows"]))
        params = restore_params(s, self.root)
        self.srv, port = cli.start_server(cfg, params, "127.0.0.1", 0)
        self.scorer = self.srv.scorer
        return {"host": "127.0.0.1", "port": port,
                "path": self.config["endpoint"]}

    def wrap_score(self, wrap) -> None:
        """Put the benchmark's delegating wrapper around the callable the
        front's takers score with (they look it up on every take)."""
        self.scorer.score = wrap(self.scorer.score)

    def counters(self) -> dict:
        """The program's series, read from its registry by name."""
        out = scorer_counters(self.scorer)
        registry = self.srv.registry
        h = registry.get(LATENCY)
        labels = {"endpoint": self.config["endpoint"]}
        out["server_seconds_sum"] = float(h.sum(labels))
        out["server_seconds_count"] = int(h.count(labels))
        requests = registry.get(REQUESTS)
        out["responses"] = {
            code: int(requests.value(labels={"code": code}))
            for code in ("200", "429", "500", "503")}
        # the one thing the program has no public handle for: whether the
        # front scores small requests itself (chip_smoke.py reads the same
        # attribute); without the attribute the check fails, not passes
        front = getattr(self.srv, "_httpd", None)
        out["inline_model"] = getattr(front, "host_model_active", None)
        return out

    def check_guarantees(self, checks, before, after, outcome) -> None:
        check_device_path(checks, before, after)
        checks.exactly("front_scored_inline", after["inline_model"], False)
        checks.exactly("requests_failed", outcome.failed, 0)
        refused = sum(after["responses"][c] - before["responses"][c]
                      for c in ("429", "500", "503"))
        checks.exactly("responses_not_200", refused, 0)

    def stop(self) -> None:
        if self.srv is not None:
            self.srv.stop()
            self.srv = None
