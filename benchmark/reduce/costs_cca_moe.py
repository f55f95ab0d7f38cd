"""What the ``hybrid_moe`` family's ``zaya`` model needs of the chip,
computed from shapes: the operations and the bytes the algorithm cannot do
without, by part (``cca``, ``experts``, everything else) and for the whole
program. Kept with the benchmark so that a roofline share means the same in
every PR; from the program it takes nothing. The settings are read from the
configuration's published keys (``configs/kafka_history_zaya1.json``).

``work`` as ``costs_hybrid_moe``: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, expert) pairs served, summed over the layers (a skipped token
serves none), ``tokens_per_row`` the window's length in tokens.

Operations, two per multiply-add, per token unless said otherwise:
- CCA: the projections of q (hidden x H D), k and v (hidden x G D each)
  and the output (H D x hidden), the grouped convolution (``cca_time1``
  blocks of D x D a head over the H + G heads of q and k), the depthwise
  convolution (``cca_time0`` taps over (H + G) D channels), and causal
  grouped-query attention per row: H x T (T + 1) / 2 pairs x 2 x 2 D.
- Experts: 6 x hidden x moe_intermediate_size per served pair.
- Else: the router (hidden x R, two R x R, R x routed outputs) and the tied
  head 2 x hidden x vocabulary per ROW (one position is read).
Norms, the q-k mean, L2 norms, softmax, activations, rotary, the residual
scaling and the tokeniser are left out: the count is a floor.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch, all held experts among them (with some 960 pairs an expert every
one is used); each token's residual row read and written once per sublayer
(float32); the window read once as it is staged (``in_bytes_per_value``),
the embedding row of each token, the embedding once more a dispatch as the
head, the logits written once a row.
"""

from __future__ import annotations

PARTS = ("cca", "experts")


def _dims(c: dict) -> dict:
    return {
        "d": int(c["hidden_size"]), "h": int(c["num_attention_heads"]),
        "g": int(c["num_key_value_heads"]), "hd": int(c["head_dim"]),
        "taps0": int(c["cca_time0"]), "taps1": int(c["cca_time1"]),
        "r": int(c["router_hidden_size"]),
        "routed": int(c["num_experts_routed_over"]),
        "expert": int(c["moe_intermediate_size"]),
        "held": int(c["experts_held"]["count"]),
        "vocab": int(c["vocab_size"]), "layers": len(c["layers_kept"]),
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def cca_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one CCA mixer."""
    m = _dims(c)
    wide = (m["h"] + m["g"]) * m["hd"]
    weights = (m["d"] * (m["h"] + 2 * m["g"]) * m["hd"]
               + m["h"] * m["hd"] * m["d"]
               + m["taps1"] * (m["h"] + m["g"]) * m["hd"] * m["hd"])
    t = int(work["tokens_per_row"])
    attention = m["h"] * (t * (t + 1) / 2.0) * 2.0 * 2 * m["hd"]
    flop = (work["tokens"] * (2.0 * weights + 2.0 * m["taps0"] * wide)
            + work["rows"] * attention)
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def experts_all_layers(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' part of every layer:
    ``pairs`` is already the sum over the layers."""
    m = _dims(c)
    per_expert = 3 * m["d"] * m["expert"]
    flop = work["pairs"] * 2.0 * per_expert
    moved = m["layers"] * (
        work["dispatches"] * m["held"] * per_expert * m["wb"]
        + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def rest(c: dict, work: dict) -> tuple[float, float]:
    """Every layer's router, the embedding, the tied head, and the window
    as it is staged."""
    m = _dims(c)
    router = m["d"] * m["r"] + 2 * m["r"] * m["r"] + m["r"] * m["routed"]
    flop = (m["layers"] * work["tokens"] * 2.0 * router
            + work["rows"] * 2.0 * m["d"] * m["vocab"])
    moved = (m["layers"] * work["dispatches"] * router * m["wb"]
             + work["dispatches"] * m["d"] * m["vocab"] * m["wb"]
             + work["tokens"] * (m["ib"] + m["d"] * m["wb"])
             + work["rows"] * m["vocab"] * 4.0)
    return flop, moved


def part(c: dict, work: dict, name: str) -> tuple[float, float]:
    """One of ``PARTS``, over all its layers."""
    if name == "experts":
        return experts_all_layers(c, work)
    flop, moved = {"cca": cca_layer}[name](c, work)
    n = _dims(c)["layers"]
    return n * flop, n * moved


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
