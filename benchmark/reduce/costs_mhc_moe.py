"""What the ``hybrid_moe`` family's ``xing4_0`` model needs of the chip,
computed from shapes: the operations and the bytes the algorithm cannot do
without, by part (``mla``, ``experts``, ``hc``, everything else) and for
the whole program. Kept with the benchmark so that a roofline share means
the same in every PR; from the program it takes nothing. The settings are
read from the configuration's published keys
(``configs/kafka_history_xing4.json``).

``work`` as ``costs_hybrid_moe``: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, held expert) pairs served, summed over the expert layers,
``tokens_per_row`` the window's length in tokens.

The kept layers are ``layers_kept``: those below ``first_k_dense_replace``
have a dense feed-forward, the others the expert layer; every layer mixes
by MLA and has two sublayers under the residual rule.

Operations, two per multiply-add, per token unless said otherwise:
- MLA: ``costs_mla_moe.mla_layer`` (the query's two low-rank projections,
  the key-value down- and up-projection, the output, and causal attention
  per row at the causal count of pairs: H x T (T + 1) / 2 x 2 x (nope +
  rope + v), whatever computes the scores: kernel or plain path).
- Experts: 6 x hidden x moe_intermediate_size per served pair.
- ``hc``, per sublayer: the maps' product, 2 x (n hidden) x (2 n + n^2)
  with n = ``hc_mult`` streams; the sublayer's input, 2 x n x hidden; the
  streams' update, 2 x (n^2 + n) x hidden.
- Else: the router (hidden x routed outputs) and the shared expert (3 x
  hidden x moe_intermediate_size x ``n_shared_experts``) of every expert
  layer, the dense feed-forward (3 x hidden x ``intermediate_size``) of
  every dense layer, and the untied head 2 x hidden x vocabulary per ROW
  (one position is read).
Norms, sigmoids, Sinkhorn's steps on n x n values a token, softmax,
activations, rotary and the tokeniser are left out: the count is a floor.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch, all held experts among them; a token's sublayer input read and
its output written once per sublayer (float32; counted with ``mla``, the
experts or the dense feed-forward as the other models' residual row is);
**``hc``: the streams (n x hidden float32 a token) at the least number of
passes any implementation needs, three a sublayer: read once for the
norm, the maps and the sublayer's input, read and written once for the
update**, and the maps' ``phi`` once a dispatch; the window read once as
it is staged (``in_bytes_per_value``), the embedding row of each token,
the head once a dispatch, the logits written once a row.
"""

from __future__ import annotations

from benchmark.reduce.costs_mla_moe import mla_layer

PARTS = ("mla", "experts", "hc")


def _dims(c: dict) -> dict:
    dense = sum(1 for i in c["layers_kept"]
                if i < int(c["first_k_dense_replace"]))
    return {
        "d": int(c["hidden_size"]), "streams": int(c["hc_mult"]),
        "routed": int(c["num_experts_routed_over"]),
        "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["n_shared_experts"]),
        "dense_width": int(c["intermediate_size"]),
        "held": int(c["experts_held"]["count"]),
        "vocab": int(c["vocab_size"]), "layers": len(c["layers_kept"]),
        "dense_layers": dense, "moe_layers": len(c["layers_kept"]) - dense,
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def experts_all_layers(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' part of every expert
    layer: ``pairs`` is already the sum over them."""
    m = _dims(c)
    per_expert = 3 * m["d"] * m["expert"]
    flop = work["pairs"] * 2.0 * per_expert
    moved = m["moe_layers"] * (
        work["dispatches"] * m["held"] * per_expert * m["wb"]
        + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def hc_sublayer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the residual rule around one sublayer."""
    m = _dims(c)
    n, d = m["streams"], m["d"]
    outs = 2 * n + n * n
    flop = work["tokens"] * 2.0 * (n * d * outs + n * d + (n * n + n) * d)
    moved = (work["dispatches"] * n * d * outs * m["wb"]
             + work["tokens"] * 3 * n * d * 4.0)
    return flop, moved


def rest(c: dict, work: dict) -> tuple[float, float]:
    """Every expert layer's router and shared expert, every dense layer's
    feed-forward, the embedding, the untied head, and the window as it is
    staged."""
    m = _dims(c)
    sparse = m["d"] * m["routed"] + 3 * m["d"] * m["expert"] * m["shared"]
    plain = 3 * m["d"] * m["dense_width"]
    layers = m["moe_layers"] * sparse + m["dense_layers"] * plain
    flop = (work["tokens"] * 2.0 * layers
            + work["rows"] * 2.0 * m["d"] * m["vocab"])
    moved = (work["dispatches"] * layers * m["wb"]
             + m["dense_layers"] * work["tokens"] * m["d"] * 8.0
             + work["dispatches"] * m["d"] * m["vocab"] * m["wb"]
             + work["tokens"] * (m["ib"] + m["d"] * m["wb"])
             + work["rows"] * m["vocab"] * 4.0)
    return flop, moved


def part(c: dict, work: dict, name: str) -> tuple[float, float]:
    """One of ``PARTS``, over all its layers."""
    if name == "experts":
        return experts_all_layers(c, work)
    m = _dims(c)
    flop, moved = {"mla": mla_layer, "hc": hc_sublayer}[name](c, work)
    n = m["layers"] * (2 if name == "hc" else 1)
    return n * flop, n * moved


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
