"""What the ``hybrid_moe`` family's ``nemotron_h`` model needs of the chip,
computed from shapes: the operations and the bytes the algorithm cannot do
without, by part (``mamba``, ``gqa``, ``experts``, everything else) and for
the whole program. Kept with the benchmark so that a roofline share means
the same in every PR; from the program it takes nothing. The settings are
read from the configuration's published keys
(``configs/kafka_history_nemotron3n.json``).

The stack is a list of single sublayers: by ``hybrid_override_pattern``
each kept layer is one Mamba-2 mixer (``M``), one attention mixer (``*``)
or one expert layer (``E``), and a part is counted over the layers of its
letter alone.

``work`` as ``costs_hybrid_moe``: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, held expert) pairs served, summed over the expert layers,
``tokens_per_row`` the window's length in tokens.

Operations, two per multiply-add, per token unless said otherwise:
- Mamba-2 (``M``), with I = ``mamba_num_heads`` x ``mamba_head_dim`` = H P
  (as given: not ``expand`` x hidden), W = I + 2 G N the convolution's
  channels: the in-projection (hidden x (I + W + H)) and the out-projection
  (I x hidden), the convolution's ``conv_kernel`` taps on W channels, and
  the selective recurrence at the plain recurrence's count, as
  ``costs_ssm_moe`` counts it: per head the decay of S (P N), the rank-one
  update dt x B^T (2 P N) and S C (2 P N): 5 P N. A chunked scan that
  spends more or less reads its share against this count, whatever chunk
  it serves and however many groups share B and C.
- Attention (``*``): q and the output (hidden x heads x ``head_dim``
  each), k and v (hidden x key-value heads x ``head_dim`` each), and
  causal attention per row: heads x T (T + 1) / 2 pairs x 2 x 2
  ``head_dim``.
- Experts: 4 x hidden x ``moe_intermediate_size`` per served pair (two
  matrices an expert, no gate), at the published width whatever the
  storage pads and whichever body of the program runs.
- Else: the router (hidden x routed outputs) and the shared expert (2 x
  hidden x ``moe_shared_expert_intermediate_size``) of every expert layer,
  and the untied head 2 x hidden x vocabulary per ROW (one position is
  read).
Norms, gates, softplus, softmax, activations and the tokeniser are left
out: the count is a floor that does not depend on chunk or implementation.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch, all held experts among them at their published width; each
token's residual row read and written once per sublayer (float32); the
window read once as it is staged (``in_bytes_per_value``), the embedding
row of each token, the head once a dispatch, the logits written once a row.
"""

from __future__ import annotations

PARTS = ("mamba", "gqa", "experts")
KINDS = {"M": "mamba", "*": "gqa", "E": "experts"}


def _dims(c: dict) -> dict:
    h, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    return {
        "d": int(c["hidden_size"]), "h": h, "p": p,
        "n": int(c["ssm_state_size"]), "inner": h * p,
        "wide": h * p + 2 * int(c["n_groups"]) * int(c["ssm_state_size"]),
        "taps": int(c["conv_kernel"]),
        "heads": int(c["num_attention_heads"]),
        "kv": int(c["num_key_value_heads"]), "hd": int(c["head_dim"]),
        "routed": int(c["num_experts_routed_over"]),
        "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["moe_shared_expert_intermediate_size"]),
        "held": int(c["experts_held"]["count"]),
        "vocab": int(c["vocab_size"]),
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def layer_kinds(c: dict) -> list[str]:
    """``mamba``, ``gqa`` or ``experts``: the one sublayer of every layer
    the cut keeps."""
    pattern = c["hybrid_override_pattern"]
    return [KINDS[pattern[i]] for i in c["layers_kept"]]


def mamba_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one Mamba-2 mixer."""
    m = _dims(c)
    weights = m["d"] * (m["inner"] + m["wide"] + m["h"]) + m["inner"] * m["d"]
    per_token = (2.0 * weights + 2.0 * m["taps"] * m["wide"]
                 + m["h"] * 5.0 * m["p"] * m["n"])
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return work["tokens"] * per_token, moved


def gqa_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one grouped-query attention mixer."""
    m = _dims(c)
    weights = 2 * m["d"] * m["heads"] * m["hd"] + 2 * m["d"] * m["kv"] * m[
        "hd"]
    t = int(work["tokens_per_row"])
    attention = m["heads"] * (t * (t + 1) / 2.0) * 2.0 * 2 * m["hd"]
    flop = work["tokens"] * 2.0 * weights + work["rows"] * attention
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def experts_all_layers(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' part of every expert
    layer: ``pairs`` is already the sum over the layers."""
    m = _dims(c)
    per_expert = 2 * m["d"] * m["expert"]
    flop = work["pairs"] * 2.0 * per_expert
    moved = layer_kinds(c).count("experts") * (
        work["dispatches"] * m["held"] * per_expert * m["wb"]
        + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def rest(c: dict, work: dict) -> tuple[float, float]:
    """Every expert layer's router and shared expert, the embedding, the
    untied head, and the window as it is staged."""
    m = _dims(c)
    layers = layer_kinds(c).count("experts")
    layer = m["d"] * m["routed"] + 2 * m["d"] * m["shared"]
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
    flop, moved = {"mamba": mamba_layer, "gqa": gqa_layer}[name](c, work)
    n = layer_kinds(c).count(name)
    return n * flop, n * moved


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
