"""The history pipeline with a language-model backbone in the scorer's
place: ``kafka_history``'s wiring (customer-keyed records -> ``Router``
micro-batch -> ``SeqScorer`` over the ``HistoryStore`` -> threshold rule ->
process start, in-process ``Broker``, one router), the family found by the
name the configuration gives (``family``: ``hybrid_moe``) in the program's
``models/registry``, its settings the configuration's own published keys.

This module imports the program's model file before it draws a single
weight, so a checkout whose program lacks the family fails at once.

The weights are the benchmark's, one draw served in every run
(``reference/hybrid_moe_f32.make_params``, made on the device), handed to
the program as its tree; the control rounds the same matrices to
float8_e4m3's 3 mantissa bits first (what weight-only fp8 serving would
compute under ideal scales: ``round_to_fp8_mantissa``).

**Preload.** Before the window every customer of ``preload`` holds its
``records`` seeded records (``reference.preload_rows``: table rows drawn
from ``--seed``), put there through the program's own
``HistoryStore.restore``: a deployment restarts from its checkpoint with
the histories it had, so every verdict of the window reads a full window
and the reference knows every history from the seed.

Taps as ``kafka_history``'s, and one more: the scorer's ``aux_tap`` hands
over what the program returns beside the probabilities, per dispatch; the
tap keeps each served row's slice logits and pair count in consumption
order, and when the run is over writes those of the rows the reference
will sample to ``reference.aux_path`` (the generator's stream carries
probabilities only).

Guarantees held: ``kafka_history``'s (with the preloaded customers in the
store's count), and: every pair of a routed token and a held expert is
computed, none dropped for capacity (the program's count of pairs its
expert loop multiplied against the count its routing chose, over the whole
run, exact).
"""

from __future__ import annotations

import os

import numpy as np

import ccfd_tpu.models.hybrid_moe as hybrid_moe  # fails at once where absent
from benchmark.deployments import kafka_history
from benchmark.reference import hybrid_moe_f32, table


class ScoreTap(kafka_history.ScoreTap):
    """``kafka_history.ScoreTap`` plus the program's per-row extras."""

    def __init__(self, scorer):
        super().__init__(scorer)
        self.logits: list[np.ndarray] = []  # one (rows, vocab) per call
        self.row_pairs: list[np.ndarray] = []
        self._call: list[tuple[np.ndarray, int, dict]] = []
        scorer.aux_tap = self._on_aux

    def _on_aux(self, rows: np.ndarray, m: int, aux: dict) -> None:
        self._call.append((rows, m, aux))

    def score_with_ids(self, txs, x):
        self._call = []
        proba = super().score_with_ids(txs, x)
        vocab = self._call[0][2]["logits"].shape[1]
        logits = np.zeros((len(txs), vocab), np.float32)
        pairs = np.zeros((len(txs),), np.int64)
        for rows, m, aux in self._call:
            logits[rows] = aux["logits"][:m]
            pairs[rows] = aux["row_pairs"][:m]
        self.logits.append(logits)
        self.row_pairs.append(pairs)
        return proba


def round_to_fp8_mantissa(params):
    """The control: every bfloat16 matrix rounded (to nearest, ties to
    even) to float8_e4m3's 3 mantissa bits, the exponent kept: what
    weight-only fp8 serving computes under ideal per-value scales, the
    least error such a scheme can have. On the value's bits, because a
    convert to float8 and back is dropped by the compiler (excess precision
    is allowed by default); one leaf at a time in the leaf's own memory,
    because a second tree does not fit the device beside the first."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        bits = jax.lax.bitcast_convert_type(w, jnp.uint16)
        # bfloat16 keeps 7 mantissa bits: drop 4, half of the dropped step
        # less one plus the kept step's low bit rounds ties to even
        bits = (bits + jnp.uint16(7) + ((bits >> 4) & jnp.uint16(1))) \
            & jnp.uint16(0xFFF0)
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)

    in_place = jax.jit(rounded, donate_argnums=0)
    return jax.tree.map(
        lambda w: in_place(w) if w.dtype == jnp.bfloat16 else w, params)


class Deployment(kafka_history.Deployment):
    def __init__(self, config: dict, *, root: str, control: bool,
                 traced: bool, seed: int = 0):
        super().__init__(config, root=root, control=control, traced=traced,
                         seed=seed)
        self.seed = int(seed)

    def start(self) -> dict:
        import dataclasses

        from ccfd_tpu.bus.broker import Broker
        from ccfd_tpu.config import Config
        from ccfd_tpu.metrics.prom import Registry
        from ccfd_tpu.process.fraud import build_engine
        from ccfd_tpu.serving.history import SeqScorer
        from ccfd_tpu.utils.gctune import tune_for_service

        c = self.config
        s, r = c["serving"], c["router"]
        self.family_config = hybrid_moe.HybridConfig.from_dict(c)
        params = hybrid_moe_f32.make_params(c)
        if self.control:
            params = round_to_fp8_mantissa(params)
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
        self.score_tap = ScoreTap(self.scorer)
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
        held = hybrid_moe_f32.preload_rows(c, self.seed)
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
        """``kafka_history``'s stream; the sampled rows' logits and pair
        counts go to the run's work directory for the reference."""
        tap = self.score_tap
        out = tap.stream()
        which = hybrid_moe_f32.sampled(
            out["customer"], self.seed,
            int(self.config["reference"]["sample_records"]))
        # the call that served each sampled row, and the row inside it
        ends = np.cumsum([len(p) for p in tap.row_pairs])
        call = np.searchsorted(ends, which, side="right")
        inside = which - (ends[call] - [len(tap.row_pairs[i]) for i in call])
        path = hybrid_moe_f32.aux_path(self.root)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        np.savez(
            path, which=which,
            logits=np.stack([tap.logits[i][j] for i, j in zip(call, inside)])
            if len(which) else np.zeros(
                (0, int(self.config["vocab_size"])), np.float32),
            row_pairs=np.array([tap.row_pairs[i][j]
                                for i, j in zip(call, inside)], np.int64))
        return out

    def counters(self) -> dict:
        reg = self.registry
        out = super().counters()
        for key in ("moe_pairs_served_total", "moe_pairs_routed_total",
                    "moe_routed_tokens_total", "lm_tokens_total",
                    "moe_expert_load_ratio_total",
                    "moe_layer_dispatches_total"):
            out[key] = float(reg.counter(key).total())
        out["moe_routed_token_layers"] = (out["moe_routed_tokens_total"]
                                          * self.family_config.moe_layers)
        out["swap_refused"] = int(reg.counter(
            "seq_swap_refused_total").total())
        return out

    def check_guarantees(self, checks, before, after, outcome) -> None:
        s = self.config["serving"]
        top = max(int(b) for b in s["batch_sizes"])
        dispatched = sum(after["dispatches"].values()) - sum(
            before["dispatches"].values())
        checks.at_least("device_dispatches", dispatched, 1)
        checks.at_least("top_bucket_dispatches", after["dispatches"].get(
            top, 0) - before["dispatches"].get(top, 0), 1)
        checks.exactly("served_model", after["model"], self.config["family"])
        checks.exactly("router_degraded_total", after["degraded"], 0)
        checks.exactly("consumed_minus_routed_shed_errors",
                       after["consumed"] - after["routed"] - after["shed"]
                       - after["start_errors"], 0)
        checks.exactly("records_missed", outcome.failed, 0)
        for key in ("produced_minus_consumed", "records_lost",
                    "records_doubled", "route_mismatches",
                    "records_out_of_order"):
            checks.exactly(key, outcome.extra[key], 0)
        for key in ("anonymous_rows", "stale_commits", "contended_skips"):
            checks.exactly(key, after[key], 0)
        checks.exactly("customers_in_store_minus_preloaded",
                       after["customers_in_store"]
                       - int(self.config["preload"]["customers"]), 0)
        checks.at_least("pairs_served", after["moe_pairs_served_total"], 1)
        checks.exactly("pairs_routed_minus_served",
                       after["moe_pairs_routed_total"]
                       - after["moe_pairs_served_total"], 0)

    def stop(self) -> None:
        super().stop()
        scorer = getattr(self, "scorer", None)
        if scorer is not None:
            # the reference draws the same tree again after the window: the
            # served one has to have left the device by then
            scorer.params = None
            import jax

            jax.clear_caches()
