"""What the ``hybrid_moe`` backbone needs of the chip, computed from
shapes: the operations and the bytes the algorithm cannot do without, by
part (``kda``, ``mla``, ``experts``, everything else) and for the whole
program. Kept with the benchmark so that a roofline share means the same
in every PR; from the program it takes nothing. The settings are read from
the configuration's published keys (``configs/kafka_history_ling3.json``).

``work`` is what was dispatched: ``dispatches`` device calls, ``rows``
windows, ``tokens`` real tokens (every one passes every layer), ``pairs``
(token, held expert) pairs served, summed over the expert layers (from the
program's counters, not the average), ``tokens_per_row`` the window's
length in tokens.

Operations, two per multiply-add, per token unless said otherwise:
- KDA: the four projections to heads x head_dim, beta and the head-wise
  gate to heads, the output projection, the convolution's taps on q, k and
  v, and the gated delta rule at the plain recurrence's count: per head the
  decay of S (d_k d_v), k^T S, the rank-one update and S^T q (2 d_k d_v
  each): 7 d_k d_v. A chunked scan that spends more (the triangular solve,
  the products inside a chunk) reads a lower share.
- MLA: q, the latent down- and up-projections, the output projection, and
  causal attention per row: heads x T (T + 1) / 2 pairs x 2 (nope + rope +
  v_dim).
- Experts: 6 x hidden x moe_intermediate_size per served pair.
- Else: router 2 x hidden x experts routed over, the shared expert and the
  leading dense layer's SwiGLU (6 x hidden x width), and the head 2 x
  hidden x vocabulary per ROW (one position is read).
Norms, gates, softmax, activations, rotary and the tokeniser are left out:
the count is a floor.

Bytes: each layer's weights (in ``weight_bytes_per_value``) read once a
dispatch; of the held experts' weights, all of them once a layer a
dispatch (with some 480 pairs an expert every expert is used); each token's
residual row read and written once per mixer and once per feed-forward
(float32); the window read once as it is staged (``in_bytes_per_value``),
the embedding row of each token, the head once a dispatch, the slice logits
written once a row.
"""

from __future__ import annotations

PARTS = ("kda", "mla", "experts")


def _dims(c: dict) -> dict:
    return {
        "d": int(c["hidden_size"]), "h": int(c["num_attention_heads"]),
        "hd": int(c["head_dim"]), "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]), "vd": int(c["v_head_dim"]),
        "rank": int(c["kv_lora_rank"]),
        "taps": int(c["short_conv_kernel_size"]),
        "dense": int(c["intermediate_size"]),
        "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["moe_shared_expert_intermediate_size"]),
        "routed": int(c["num_experts_routed_over"]),
        "held": int(c["experts_held"]["count"]), "vocab": int(c["vocab_size"]),
        "wb": int(c["costs"]["weight_bytes_per_value"]),
        "ib": int(c["costs"]["in_bytes_per_value"]),
    }


def layer_kinds(c: dict) -> list[tuple[str, str]]:
    period, dense = int(c["layer_group_size"]), int(
        c["first_k_dense_replace"])
    return [("mla" if (i + 1) % period == 0 else "kda",
             "dense" if i < dense else "moe") for i in c["layers_kept"]]


def kda_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one KDA mixer."""
    m = _dims(c)
    wide = m["h"] * m["hd"]
    weights = 4 * m["d"] * wide + 2 * m["d"] * m["h"] + wide * m["d"]
    per_token = (2.0 * weights + 3 * 2.0 * m["taps"] * wide
                 + m["h"] * 7.0 * m["hd"] * m["hd"])
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return work["tokens"] * per_token, moved


def mla_layer(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one MLA mixer."""
    m = _dims(c)
    weights = (m["d"] * m["h"] * (m["nope"] + m["rope"])
               + m["d"] * (m["rank"] + m["rope"])
               + m["rank"] * m["h"] * (m["nope"] + m["vd"])
               + m["h"] * m["vd"] * m["d"])
    t = int(work["tokens_per_row"])
    attention = m["h"] * (t * (t + 1) / 2.0) * 2.0 * (
        m["nope"] + m["rope"] + m["vd"])
    flop = work["tokens"] * 2.0 * weights + work["rows"] * attention
    moved = (work["dispatches"] * weights * m["wb"]
             + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def experts_all_layers(c: dict, work: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the held experts' part of every expert
    layer: ``pairs`` is already the sum over the layers."""
    m = _dims(c)
    layers = sum(1 for _, ffn in layer_kinds(c) if ffn == "moe")
    per_expert = 3 * m["d"] * m["expert"]
    flop = work["pairs"] * 2.0 * per_expert
    moved = layers * (work["dispatches"] * m["held"] * per_expert * m["wb"]
                      + work["tokens"] * m["d"] * 8.0)
    return flop, moved


def rest(c: dict, work: dict) -> tuple[float, float]:
    """Router, shared expert, the leading dense layers' feed-forward,
    embedding, head, and the window as it is staged."""
    m = _dims(c)
    flop = moved = 0.0
    for _, ffn in layer_kinds(c):
        if ffn == "dense":
            w = 3 * m["d"] * m["dense"]
        else:
            w = m["d"] * m["routed"] + 3 * m["d"] * m["shared"]
        flop += work["tokens"] * 2.0 * w
        moved += work["dispatches"] * w * m["wb"]
    flop += work["rows"] * 2.0 * m["d"] * m["vocab"]
    moved += (work["dispatches"] * m["d"] * m["vocab"] * m["wb"]
              + work["tokens"] * (m["ib"] + m["d"] * m["wb"])
              + work["rows"] * m["vocab"] * 4.0)
    return flop, moved


def part(c: dict, work: dict, name: str) -> tuple[float, float]:
    """One of ``PARTS``, over all its layers."""
    kinds = layer_kinds(c)
    if name == "experts":
        return experts_all_layers(c, work)
    one = {"kda": kda_layer, "mla": mla_layer}[name](c, work)
    n = sum(1 for mixer, _ in kinds if mixer == name)
    return n * one[0], n * one[1]


def backbone(c: dict, work: dict) -> tuple[float, float]:
    """The whole program."""
    parts = [part(c, work, name) for name in PARTS] + [rest(c, work)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
