"""What the ``hybrid_moe`` family's ``mistral4`` model needs of the chip,
computed from shapes: the operations and the bytes the algorithm cannot do
without, by part (``mla``, ``experts``, everything else) and for the whole
program. Kept with the benchmark so that a roofline share means the same in
every PR; from the program it takes nothing. The settings are read from the
configuration's published keys (``configs/kafka_history_mistral4.json``).

``work`` as ``costs_hybrid_moe``: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, held expert) pairs served, summed over the layers,
``tokens_per_row`` the window's length in tokens.

Operations, two per multiply-add, per token unless said otherwise:
- MLA: the query's two low-rank projections (hidden x ``q_lora_rank`` and
  ``q_lora_rank`` x H (nope + rope)), the key-value down-projection (hidden
  x (``kv_lora_rank`` + rope)) and up-projection (``kv_lora_rank`` x H
  (nope + v)), the output (H v x hidden), and causal attention per row: H x
  T (T + 1) / 2 pairs x 2 x (nope + rope + v).
- Experts: 6 x hidden x moe_intermediate_size per served pair.
- Else: the router (hidden x routed outputs) and the shared expert (3 x
  hidden x moe_intermediate_size x ``n_shared_experts``) of every layer,
  and the untied head 2 x hidden x vocabulary per ROW (one position is
  read).
Norms, softmax, activations, rotary and the tokeniser are left out: the
count is a floor.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch, all held experts among them (with some 480 pairs an expert every
one is used); each token's residual row read and written once per sublayer
(float32); the window read once as it is staged (``in_bytes_per_value``),
the embedding row of each token, the head once a dispatch, the logits
written once a row.
"""

from __future__ import annotations

PARTS = ("mla", "experts")


def _dims(c: dict) -> dict:
    return {
        "d": int(c["hidden_size"]), "h": int(c["num_attention_heads"]),
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]),
        "v": int(c["v_head_dim"]), "q_rank": int(c["q_lora_rank"]),
        "kv_rank": int(c["kv_lora_rank"]),
        "routed": int(c["num_experts_routed_over"]),
        "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["n_shared_experts"]),
        "held": int(c["experts_held"]["count"]),
        "vocab": int(c["vocab_size"]), "layers": len(c["layers_kept"]),
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def mla_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one MLA mixer."""
    m = _dims(c)
    qk = m["nope"] + m["rope"]
    weights = (m["d"] * m["q_rank"] + m["q_rank"] * m["h"] * qk
               + m["d"] * (m["kv_rank"] + m["rope"])
               + m["kv_rank"] * m["h"] * (m["nope"] + m["v"])
               + m["h"] * m["v"] * m["d"])
    t = int(work["tokens_per_row"])
    attention = m["h"] * (t * (t + 1) / 2.0) * 2.0 * (qk + m["v"])
    flop = work["tokens"] * 2.0 * weights + work["rows"] * attention
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
    """Every layer's router and shared expert, the embedding, the untied
    head, and the window as it is staged."""
    m = _dims(c)
    layer = m["d"] * m["routed"] + 3 * m["d"] * m["expert"] * m["shared"]
    flop = (m["layers"] * work["tokens"] * 2.0 * layer
            + work["rows"] * 2.0 * m["d"] * m["vocab"])
    moved = (m["layers"] * work["dispatches"] * layer * m["wb"]
             + work["dispatches"] * m["d"] * m["vocab"] * m["wb"]
             + work["tokens"] * (m["ib"] + m["d"] * m["wb"])
             + work["rows"] * m["vocab"] * 4.0)
    return flop, moved


def part(c: dict, work: dict, name: str) -> tuple[float, float]:
    """One of ``PARTS``, over all its layers."""
    if name == "experts":
        return experts_all_layers(c, work)
    flop, moved = {"mla": mla_layer}[name](c, work)
    n = _dims(c)["layers"]
    return n * flop, n * moved


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
