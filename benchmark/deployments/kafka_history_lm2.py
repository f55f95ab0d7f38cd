"""``kafka_history_lm`` with nothing of one model in it: the same wiring
(customer-keyed records -> ``Router`` micro-batch -> ``SeqScorer`` over the
``HistoryStore`` -> threshold rule -> process start, in-process ``Broker``,
one router, every ring preloaded through ``HistoryStore.restore``, the
fp8-mantissa control), but everything that belongs to the served model is
found by the names the configuration gives:

- ``family``: the history family in the program's ``models/registry``; its
  settings are what the family's own ``config_from`` makes of the
  configuration's published keys. A checkout whose program has no such
  family, or whose family cannot read the configuration, fails here, before
  a single weight is drawn;
- ``reference.module``: the plain reference under ``benchmark/reference/``;
  it draws the one tree program and reference both take (``make_params``),
  says which rows are preloaded and sampled (``preload_rows``, ``sampled``)
  and where the sampled rows' extras go (``aux_path``);
- ``reference.row_aux``: which per-row arrays of the program's ``aux``
  (leading axis: the dispatch's rows) the tap keeps for the reference.

So the next language model behind this path is a configuration, a
reference and cost functions: data and new files only.

Guarantees held: ``kafka_history_lm``'s, and: every routed token is served
by its expert or counted as skipped, none dropped for capacity (the
program's counts over the whole run: pairs multiplied = pairs chosen, and
where every expert is held and a token has one, served + skipped = routed
tokens x expert layers, exactly).
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.deployments import kafka_history, kafka_history_lm
from benchmark.harness import manifest
from benchmark.reference import table


class ScoreTap(kafka_history.ScoreTap):
    """``kafka_history.ScoreTap`` plus the per-row arrays of the program's
    ``aux`` that the configuration names, one dict of (rows, ...) arrays
    per call."""

    def __init__(self, scorer, keys: list[str]):
        super().__init__(scorer)
        self.keys = list(keys)
        self.kept: list[dict[str, np.ndarray]] = []
        self._call: list[tuple[np.ndarray, int, dict]] = []
        scorer.aux_tap = lambda rows, m, aux: self._call.append(
            (rows, m, aux))

    def score_with_ids(self, txs, x):
        self._call = []
        proba = super().score_with_ids(txs, x)
        out = {}
        for key in self.keys:
            first = self._call[0][2][key]
            out[key] = np.zeros((len(txs), *first.shape[1:]), first.dtype)
            for rows, m, aux in self._call:
                out[key][rows] = aux[key][:m]
        self.kept.append(out)
        return proba


class Deployment(kafka_history_lm.Deployment):
    """``kafka_history_lm.Deployment`` (its counters, guarantees and
    ``stop``) with the model's own parts looked up by name."""

    def __init__(self, config: dict, *, root: str, control: bool,
                 traced: bool, seed: int = 0):
        super().__init__(config, root=root, control=control, traced=traced,
                         seed=seed)
        self.reference = manifest.load_kind(
            "reference", config["reference"]["module"])

    def start(self) -> dict:
        import dataclasses

        from ccfd_tpu.bus.broker import Broker
        from ccfd_tpu.config import Config
        from ccfd_tpu.metrics.prom import Registry
        from ccfd_tpu.models import registry as families
        from ccfd_tpu.process.fraud import build_engine
        from ccfd_tpu.serving.history import SeqScorer
        from ccfd_tpu.utils.gctune import tune_for_service

        c = self.config
        s, r = c["serving"], c["router"]
        self.family_config = families.get_history(c["family"]).config_from(c)
        params = self.reference.make_params(c)
        if self.control:
            params = kafka_history_lm.round_to_fp8_mantissa(params)
        self.cfg = dataclasses.replace(
            Config(), kafka_topic=r["topic"],
            fraud_threshold=float(r["fraud_threshold"]))
        self.broker = Broker()
        self.registry = Registry()
        self.tap = kafka_history.EngineTap(
            build_engine(self.cfg, self.broker, self.registry, None))
        self.scorer = SeqScorer(
            params, length=int(s["length"]),
            batch_sizes=tuple(s["batch_sizes"]),
            compute_dtype=s["compute_dtype"],
            max_customers=int(s["max_customers"]),
            inflight=int(s["inflight"]), registry=self.registry,
            family=c["family"], family_config=self.family_config)
        del params  # the scorer holds the one tree
        self.scorer.warmup()
        self._preload()
        tune_for_service()
        self.score_tap = ScoreTap(self.scorer, c["reference"]["row_aux"])
        if self.traced:
            from ccfd_tpu.observability.profile import StageProfiler

            self.profiler = StageProfiler()
        return {"broker": self.broker, "topic": r["topic"], "tap": self.tap,
                "start_router": self.start_router,
                "consumed": self.consumed, "stop_router": self.stop_router,
                "shed": lambda: int(self.registry.counter(
                    "router_shed_total").total()),
                "fraud_threshold": float(r["fraud_threshold"]),
                "stream": self.stream}

    def _preload(self) -> None:
        """Every customer's ring as the checkpoint left it."""
        c = self.config
        _, rows, _ = table.make_table(int(c["table_rows"]), self.seed)
        held = self.reference.preload_rows(c, self.seed)
        length = int(c["serving"]["length"])
        n, depth = held.shape
        if depth > length:
            raise ValueError("preload deeper than the store's rings")
        windows = np.zeros((n, length, rows.shape[1]), np.float32)
        windows[:, length - depth:] = rows[held]
        self.scorer.store.restore({
            "version": 1, "length": length, "num_features": rows.shape[1],
            "customers": [[c_id, windows[c_id], depth]
                          for c_id in range(n)]})

    def stream(self) -> dict:
        """``kafka_history``'s stream; the sampled rows' kept arrays go to
        the run's work directory for the reference."""
        tap = self.score_tap
        out = tap.stream()
        which = self.reference.sampled(
            out["customer"], self.seed,
            int(self.config["reference"]["sample_records"]))
        # the call that served each sampled row, and the row inside it
        sizes = np.array([len(c[0]) for c in tap.calls], np.int64)
        ends = np.cumsum(sizes)
        call = np.searchsorted(ends, which, side="right")
        inside = which - (ends[call] - sizes[call])
        path = self.reference.aux_path(self.root)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        np.savez(path, which=which, **{
            key: np.stack([tap.kept[i][key][j]
                           for i, j in zip(call, inside)])
            for key in tap.keys if len(which)})
        return out

    def counters(self) -> dict:
        out = super().counters()
        out["moe_skipped_tokens_total"] = float(self.registry.counter(
            "moe_skipped_tokens_total").total())
        return out

    def check_guarantees(self, checks, before, after, outcome) -> None:
        super().check_guarantees(checks, before, after, outcome)
        c = self.config
        if int(c["num_experts_per_tok"]) == 1 and int(
                c["experts_held"]["count"]) + 1 >= int(
                    c["num_experts_routed_over"]):
            # one expert a token and every expert here: what was not
            # served was skipped by the router's own choice
            checks.exactly(
                "served_plus_skipped_minus_routed",
                after["moe_pairs_served_total"]
                + after["moe_skipped_tokens_total"]
                - after["moe_routed_token_layers"], 0)
