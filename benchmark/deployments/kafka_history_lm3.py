"""``kafka_history_lm2`` for a model whose chip holds a share of the
experts and may give a token several: the same wiring, names and taps, and
the count of what the share leaves to the other chips.

The program counts every chosen (token, expert) pair either as served (its
expert is held here and the tile loop multiplied it) or as absent (its
expert is another chip's; the carried router's *skip* is an expert nobody
holds). This deployment reads the second counter too
(``moe_pairs_absent_total``) and holds the sum:

Guarantees held: ``kafka_history_lm2``'s, and: every chosen pair is served
here or counted as another chip's, none lost between (served + absent =
``num_experts_per_tok`` x routed tokens x expert layers over the whole run,
exactly).
"""

from __future__ import annotations

from benchmark.deployments import kafka_history_lm2


class Deployment(kafka_history_lm2.Deployment):
    def counters(self) -> dict:
        out = super().counters()
        out["moe_pairs_absent_total"] = float(self.registry.counter(
            "moe_pairs_absent_total").total())
        return out

    def check_guarantees(self, checks, before, after, outcome) -> None:
        super().check_guarantees(checks, before, after, outcome)
        checks.exactly(
            "served_plus_absent_minus_chosen",
            after["moe_pairs_served_total"] + after["moe_pairs_absent_total"]
            - int(self.config["num_experts_per_tok"])
            * after["moe_routed_token_layers"], 0)
