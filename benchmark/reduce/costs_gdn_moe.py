"""What the ``hybrid_moe`` family's ``qwen3_next`` model needs of the chip,
computed from shapes: the operations and the bytes the algorithm cannot do
without, by part (``gdn``, ``gqa``, ``experts``, everything else) and for
the whole program. Kept with the benchmark so that a roofline share means
the same in every PR; from the program it takes nothing. The settings are
read from the configuration's published keys
(``configs/kafka_history_qwen3next.json``).

Every kept layer has a mixer and the expert layer; the mixer is attention
where (i + 1) % ``full_attention_interval`` == 0 and Gated DeltaNet
elsewhere, and a mixer's part is counted over the layers that have it.

``work`` as ``costs_hybrid_moe``: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, held expert) pairs served, summed over the expert layers,
``tokens_per_row`` the window's length in tokens.

Operations, two per multiply-add, per token unless said otherwise:
- Gated DeltaNet, with K = ``linear_num_key_heads`` x
  ``linear_key_head_dim``, V = ``linear_num_value_heads`` x
  ``linear_value_head_dim``: the two in-projections (hidden x (2 K + 2 V)
  and hidden x 2 ``linear_num_value_heads``) and the out-projection (V x
  hidden), the convolution's ``linear_conv_kernel_dim`` taps on 2 K + V
  channels, and the delta rule at the plain recurrence's count: per value
  head the decay of S (dk dv), S^T k (2 dk dv), the rank-one update beta k
  (v - S^T k)^T (2 dk dv) and S^T q (2 dk dv): 7 dk dv. A chunked scan that
  spends more or less reads its share against this count, whatever chunk
  it serves and however many value heads share a key head.
- Gated attention: q with its gate (hidden x heads x 2 ``head_dim``), the
  output (heads x ``head_dim`` x hidden), k and v (hidden x key-value heads
  x ``head_dim`` each), and causal attention per row: heads x T (T + 1) / 2
  pairs x 2 x 2 ``head_dim``.
- Experts: 6 x hidden x ``moe_intermediate_size`` per served pair (three
  matrices an expert), whichever body of the program runs.
- Else: the router (hidden x routed outputs), the shared expert (3 x hidden
  x ``shared_expert_intermediate_size``) and its gate (hidden) of every
  layer, and the untied head 2 x hidden x vocabulary per ROW (one position
  is read).
Norms, gates, the L2 norms, softplus, softmax, the rotary turn and the
tokeniser are left out: the count is a floor that does not depend on chunk
or implementation.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch, all held experts among them; each token's residual row read and
written once per sublayer (float32); the window read once as it is staged
(``in_bytes_per_value``), the embedding row of each token, the head once a
dispatch, the logits written once a row.
"""

from __future__ import annotations

PARTS = ("gdn", "gqa", "experts")


def _dims(c: dict) -> dict:
    hk, hv = int(c["linear_num_key_heads"]), int(c["linear_num_value_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    return {
        "d": int(c["hidden_size"]), "hv": hv, "dk": dk, "dv": dv,
        "keys": hk * dk, "values": hv * dv,
        "taps": int(c["linear_conv_kernel_dim"]),
        "heads": int(c["num_attention_heads"]),
        "kv": int(c["num_key_value_heads"]), "hd": int(c["head_dim"]),
        "routed": int(c["num_experts_routed_over"]),
        "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["shared_expert_intermediate_size"]),
        "held": int(c["experts_held"]["count"]),
        "vocab": int(c["vocab_size"]),
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def layer_kinds(c: dict) -> list[str]:
    """``gdn`` or ``gqa``: the mixer of every layer the cut keeps."""
    period = int(c["full_attention_interval"])
    return ["gqa" if (i + 1) % period == 0 else "gdn"
            for i in c["layers_kept"]]


def gdn_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one Gated DeltaNet mixer."""
    m = _dims(c)
    weights = (m["d"] * (2 * m["keys"] + 2 * m["values"] + 2 * m["hv"])
               + m["values"] * m["d"])
    per_token = (2.0 * weights
                 + 2.0 * m["taps"] * (2 * m["keys"] + m["values"])
                 + m["hv"] * 7.0 * m["dk"] * m["dv"])
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return work["tokens"] * per_token, moved


def gqa_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one gated attention mixer."""
    m = _dims(c)
    weights = 3 * m["d"] * m["heads"] * m["hd"] + 2 * m["d"] * m["kv"] * m[
        "hd"]
    t = int(work["tokens_per_row"])
    attention = m["heads"] * (t * (t + 1) / 2.0) * 2.0 * 2 * m["hd"]
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
    moved = len(c["layers_kept"]) * (
        work["dispatches"] * m["held"] * per_expert * m["wb"]
        + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def rest(c: dict, work: dict) -> tuple[float, float]:
    """Every layer's router, shared expert and its gate, the embedding,
    the untied head, and the window as it is staged."""
    m = _dims(c)
    layers = len(c["layers_kept"])
    layer = m["d"] * m["routed"] + 3 * m["d"] * m["shared"] + m["d"]
    flop = (layers * work["tokens"] * 2.0 * layer
            + work["rows"] * 2.0 * m["d"] * m["vocab"])
    moved = (layers * work["dispatches"] * layer * m["wb"]
             + work["dispatches"] * m["d"] * m["vocab"] * m["wb"]
             + work["tokens"] * (m["ib"] + m["d"] * m["wb"])
             + work["rows"] * m["vocab"] * 4.0)
    return flop, moved


def part(c: dict, work: dict, name: str) -> tuple[float, float]:
    """One of ``PARTS``, over all its layers."""
    if name == "experts":
        return experts_all_layers(c, work)
    flop, moved = {"gdn": gdn_layer, "gqa": gqa_layer}[name](c, work)
    n = layer_kinds(c).count(name)
    return n * flop, n * moved


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
