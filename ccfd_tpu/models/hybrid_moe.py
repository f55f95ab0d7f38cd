"""Hybrid linear-attention / state-space / latent-attention / sparse-expert
backbone over a customer's tokenised transaction window: the third history
family.

A decoder-only language model of several layer kinds in one stack, driven
by the published ``config.json`` keys of the model it serves. Nothing here
is one model's numbers: a model is a tuple of ``(mixer, feed-forward)``
kinds (either half may be absent: a layer of one sublayer, a mixer alone or
the experts alone, runs one norm and one pass of the residual rule), each
mixer and each router an entry of a table (``MIXERS``,
``ROUTERS``: name -> function) with settings of its own, read from the
published keys by one small reader a ``model_type`` (``READERS``); what no
layer of a model uses is not in that model's settings. Mixers: **KDA**
(gated delta-rule linear attention with a short causal convolution, served
as a chunked scan), **MLA** (latent keys and values with a decoupled rotary
part; queries at full rank or through a normed low-rank latent; the four
parts RMS-normed or not; rotary pairs by halves or interleaved, plain or
YaRN frequencies with its softmax scale) and **CCA** (grouped-query softmax
attention inside a compressed latent: queries, keys and values projected
down, two causal convolutions over q and k together, a q-k mean added back
across the grouping, half the value heads read from the previous token,
L2-normed q and k with a learned temperature, partial rotary), **Mamba-2**
(``mamba2``: the selective state-space mixer: one projection to a gate, the
convolved x, B and C and a step dt a head; a causal depthwise convolution
with a bias; per head S_t = e^(dt_t A) S_(t-1) + dt_t x_t B_t^T, y_t = S_t
C_t + D x_t with B and C shared by the heads of a group; the gate, then an
RMS norm over all inner values or inside each of the model's groups of
them; served as a chunked scan whose chunk is the deployment's
``scan_chunk``), **GQA** (``gqa``: grouped-query causal softmax
attention, head width and scale the model's own; plain and without
positions unless the model sets an RMS norm on every query and key head, a
rotary turn of a head's leading dims, or a sigmoid gate a head that the
query projection carries) and **Gated DeltaNet** (``gdn``: a delta rule
whose decay is one scalar a value head, unbounded; fewer key heads than
value heads, each key head read by several value heads; one causal
convolution without a bias over q, k and v together; L2-normed q and k;
S_t = e^(g_t) S_(t-1) + beta_t k_t (v_t - (e^(g_t) S_(t-1))^T k_t)^T, o_t =
S_t^T q_t; an RMS norm over each head's values times SiLU of a gate;
served as a chunked scan whose chunk is the deployment's ``gdn_chunk``:
``ops/gdn_scan.py``'s kernel where it fits, else the loop over
``_gdn_chunk`` through XLA).
Routers of the **sparse expert layer**: ``top_k`` (sigmoid or softmax
scores over all
routed experts, an optional expert bias for the choice, group-limited or
not, weights renormalised over the chosen) with or without a shared
expert, which a model may put behind a sigmoid gate of its own (a leaf
``shared_gate``: one scalar a token); or ``carried_mlp``, a small MLP on a
down-projection whose hidden state is handed from one layer's router to
the next, softmax, top 1, and a last output that means *no expert* (the
token skips the layer). **An
expert's body** (routed and shared alike) is a setting of the model
(``EXPERT_BODIES``): SwiGLU of three matrices, or relu squared between two
with no gate.
**The residual path** is a table too (``RESIDUALS``): ``plain`` (x + f(x)),
``multiplied`` (x + m f(x), m one constant of the model), ``scaled`` (a
learned scale and bias on the stream and on the sublayer's output) or
``mhc``, manifold-constrained hyper-connections: a token's state
between sublayers is ``streams`` rows of the hidden width, and every
sublayer reads one mix of them and writes to all of them through three
maps computed from the token's own streams, the stream-to-stream one made
doubly stochastic by Sinkhorn-Knopp steps (:func:`_mhc`).
A stack whose layers are alike may arrive as one tree with the layers on
the leading axis of every leaf and is then scanned (``lax.scan``: one layer
is compiled); a stack that arrives as a list is unrolled, and an entry of
the list may itself be such a tree of alike layers (leading dense layers
listed, the expert layers behind them scanned; or two stacks of alike
layers around a single one of another kind). A model may multiply its
embedding and divide its logits by constants (``embed_scale``,
``logit_divisor``), and may store its norm weights zero-centred
(``norm_offset`` 1: the layers', the final and ``gqa``'s head norms
multiply by 1 + w). Causal throughout. The window (B, L, F) of the
``HistoryStore`` is
tokenised on the device (TabFormer-style: column j of a record is token
j * bins + its quantile bin), so a verdict is one L * F token pass read
out at the newest record's last token.

**The share.** The expert layer is told which experts it holds
(``HybridConfig.held_first`` / ``held_count``), routes over all of the
published experts and computes its own experts' part of the result: what
expert parallelism asks of the program. A token's pairs with absent
experts are left out (and counted: ``pairs_absent``) and the partial sum
goes on; on one chip the layer runs without its exchange and nothing
stands in for the absent chips.
**Dropless**: the (token, held expert) pairs are sorted by expert and each
expert's group is cut into row tiles, so a tile belongs to one expert. The
tiles are multiplied by the grouped-matmul kernels of
``ops/grouped_experts.py``, ``MOE_CHUNK`` rows a call, or by the plain
loop, whose trip count is the number of tiles the batch really has
(``held_experts`` chooses while the program is traced). No capacity
factor, no pair dropped, work in proportion to the pairs served.

**Padding.** ``filled`` (B,) is each row's count of real records
(right-aligned, as ``StagingBatch`` stages them). Positions count from a
row's first real token; padding keys are masked in MLA; a padding token
has beta = 0, alpha = 1 and sends zeros into the convolution, so the KDA
state passes it unchanged (under ``mamba2``: dt = 0, so its decay is 1 and
it puts nothing in; zeros into the convolution; a masked key under
``gqa``; under ``gdn`` beta = 0, g = 0 and zeros into the convolution); it
routes to no expert. A row's verdict is
therefore the same at every window length that holds its history.

The equations, with the key each symbol is read from, are in the plain
references ``benchmark/reference/hybrid_moe_f32.py``, ``cca_moe_f32.py``,
``mla_moe_f32.py``, ``mhc_moe_f32.py``, ``ssm_moe_f32.py``,
``ssm_relu2_moe_f32.py`` and ``gdn_moe_f32.py`` (which import nothing from
here); the parameter
tree is the one their ``make_params`` draw (a layer's tree holds ``norm1``
and ``mixer`` where it has a mixer, ``norm2`` and ``ffn`` where it has a
feed-forward part).

Precision: matrices bfloat16, products accumulated in float32, the
residual stream, norms, gates, softmax and the router in float32 (the
router and its carried state at ``highest``: a token near a tie must
choose as the model does; the hyper-connections' maps too: an error in the
stream-to-stream map is every later sublayer's), the KDA state and
everything inside a chunk, CCA's convolution sums and L2 norms in float32;
Mamba-2's convolution, steps, decays, state and everything inside a chunk
too (a decay is always the exponential of a difference of running sums of
log-decays that is <= 0, never a quotient of two exponentials), and so
Gated DeltaNet's (its pairwise decays one (C, C) matrix a value head, masked
before the exponential; the triangular inverse at ``highest``).

Device scopes (``jax.named_scope``, so a capture's operations carry them):
``lm.embed``, ``kda``, ``mla`` (inside it ``mla.project``: every
projection, the norms and the rotary, and ``mla.attend``), ``cca`` (inside
it ``cca.conv`` and ``cca.attend``; both ``attend`` scopes hold
:func:`_causal_attention`), ``mamba`` (the ``mamba2`` mixer; inside it ``mamba.project``: the
in- and out-projections, ``mamba.conv``, ``mamba.scan``: steps, decays and
the chunked scan, ``mamba.gate``: the gate and its norm), ``gqa`` (inside it
``gqa.project`` and ``gqa.attend``, which holds :func:`_causal_attention`
too), ``gdn`` (inside it ``gdn.project``: the two in-projections and the
out-projection, ``gdn.conv``: the convolution and SiLU, ``gdn.scan``:
beta, the decays, the L2 norms of q and k (inside the kernel where the scan
is ``ops/gdn_scan.py``'s) and the chunked scan, ``gdn.gate``: the per-head
norm and SiLU(z)), ``dense_ffn``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``lm.head``, and ``hc`` around everything
the ``mhc`` rule adds (inside it ``hc.maps``: the flattened norm, the
product, the sigmoids and Sinkhorn; ``hc.mix``: the sublayer's input from
the streams and the streams' update), never around the sublayer itself.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import (causal_attention, cca_conv, gdn_scan,
                          grouped_experts, kda_scan, short_conv, ssd_scan)

Params = Mapping[str, Any]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# inside a KDA or a Mamba-2 chunk: what touches the carried state multiplies
# float32 operands as three bfloat16 passes; what stays inside the chunk (the
# pairwise matrices and their products with u) as one; the triangular
# inverse at ``highest`` (few operations, and the solve's error is every
# later token's)
KDA_PRECISION = jax.lax.Precision.HIGH
KDA_INSIDE = jax.lax.Precision.DEFAULT
L2_EPS = 1e-6
MASKED = causal_attention.MASKED  # a key that is padding or after the query
MOE_TILE = 256  # rows of one expert's group a trip of the plain loop multiplies
MOE_CHUNK = 4096  # rows the expert kernels are handed at a time
KDA_SUB = 16  # kda_lower_bound * KDA_SUB must stay inside float32's exponent
PLAIN_QUERY_BLOCKS = 4  # query blocks a row on the plain causal-attention path


# -- settings: one small class a kind, read from the published keys ------------

@dataclasses.dataclass(frozen=True)
class Kda:
    heads: int
    head_dim: int
    lower_bound: float
    chunk: int

    @classmethod
    def read(cls, m: Mapping[str, Any]) -> "Kda":
        if int(m["v_head_dim"]) != int(m["head_dim"]):
            raise ValueError("KDA heads are head_dim wide in keys and values")
        chunk = int(m.get("kda_chunk", 64))
        if chunk % KDA_SUB or abs(float(m["kda_lower_bound"])) * KDA_SUB > 85:
            raise ValueError("kda_chunk / kda_lower_bound outside what the "
                             "chunked scan holds in float32")
        return cls(int(m["num_attention_heads"]), int(m["head_dim"]),
                   float(m["kda_lower_bound"]), chunk)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """``rope_parameters`` of type ``yarn``: the frequencies' stretch, the
    softmax scale it brings, and the llama-4 query scale past
    ``original`` positions."""

    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    query_beta: float

    @staticmethod
    def m(factor: float, scale: float) -> float:
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class Mla:
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    q_rank: int | None  # None: q = x W_q at full rank
    part_norms: bool  # q_n, q_r, k_n, k_r each RMS-normed over its width
    interleaved: bool  # rotary pairs (2i, 2i + 1); else the two halves
    theta: float
    yarn: Yarn | None = None

    @property
    def scale(self) -> float:
        """What the scores are multiplied by before the softmax."""
        plain = 1.0 / math.sqrt(self.nope + self.rope)
        if self.yarn is None or not self.yarn.mscale_all_dim:
            return plain
        return plain * Yarn.m(self.yarn.factor, self.yarn.mscale_all_dim) ** 2

    @property
    def turn_scale(self) -> float:
        """What cos and sin are multiplied by."""
        y = self.yarn
        if y is None:
            return 1.0
        if y.mscale and y.mscale_all_dim:
            return Yarn.m(y.factor, y.mscale) / Yarn.m(y.factor,
                                                        y.mscale_all_dim)
        return Yarn.m(y.factor, 1.0)

    @classmethod
    def read(cls, m: Mapping[str, Any]) -> "Mla":
        rope = m.get("rope_parameters") or {}
        yarn = None
        if rope.get("rope_type", rope.get("type")) == "yarn":
            yarn = Yarn(float(rope["factor"]),
                        int(rope["original_max_position_embeddings"]),
                        float(rope.get("beta_fast", 32)),
                        float(rope.get("beta_slow", 1)),
                        float(rope.get("mscale", 0)),
                        float(rope.get("mscale_all_dim", 0)),
                        float(rope.get("llama_4_scaling_beta", 0)))
        rank = m.get("q_lora_rank")
        return cls(int(m["num_attention_heads"]), int(m["qk_nope_head_dim"]),
                   int(m["qk_rope_head_dim"]), int(m["v_head_dim"]),
                   int(m["kv_lora_rank"]), None if rank is None else int(rank),
                   bool(m.get("use_qk_norm", False)),
                   bool(m.get("rope_interleave", False)),
                   float(rope["rope_theta"] if "rope_theta" in rope
                         else m["rope_theta"]), yarn)


@dataclasses.dataclass(frozen=True)
class Cca:
    heads: int
    kv_heads: int  # key-value heads under ``heads`` query heads
    head_dim: int
    rotary_dim: int  # the leading dims of a head that are rotated
    theta: float

    @classmethod
    def read(cls, m: Mapping[str, Any]) -> "Cca":
        rope = m["rope_parameters"]["hybrid"]
        heads, hd = int(m["num_attention_heads"]), int(m["head_dim"])
        if heads % int(m["num_key_value_heads"]):
            raise ValueError("CCA: query heads a multiple of the key-value "
                             "heads")
        return cls(heads, int(m["num_key_value_heads"]), hd,
                   int(hd * float(rope["partial_rotary_factor"])),
                   float(rope["rope_theta"]))


# what each family that has the mixer calls the mixer's numbers (``block``:
# the published kernel's chunk; the two biases: the convolution's and the
# projections')
MAMBA_KEYS = {
    "granitemoehybrid": dict(
        heads="mamba_n_heads", head_dim="mamba_d_head",
        state="mamba_d_state", groups="mamba_n_groups", conv="mamba_d_conv",
        block="mamba_chunk_size", conv_bias="mamba_conv_bias",
        proj_bias="mamba_proj_bias"),
    "nemotron_h": dict(
        heads="mamba_num_heads", head_dim="mamba_head_dim",
        state="ssm_state_size", groups="n_groups", conv="conv_kernel",
        block="chunk_size", conv_bias="use_conv_bias",
        proj_bias="mamba_proj_bias"),
}


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """The selective state-space mixer: ``heads`` heads of ``head_dim``
    (the inner width is their product, as given) with a state of
    ``head_dim`` x ``state`` each, B and C shared by the heads of a group,
    a causal depthwise convolution of ``conv`` taps; the deployment's
    ``chunk``, the tokens a step of the served scan takes at once (no part
    of the result); and ``norm_groups``, the groups of inner values the
    gated norm norms apart (1: all of them together)."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int
    chunk: int
    norm_groups: int = 1

    @classmethod
    def read(cls, m: Mapping[str, Any], norm_groups: int = 1) -> "Mamba2":
        """By the key names of ``m``'s own family (``MAMBA_KEYS``)."""
        key = MAMBA_KEYS[m["model_type"]]
        heads, groups = int(m[key["heads"]]), int(m[key["groups"]])
        if heads % groups or heads % norm_groups or m[key["proj_bias"]] \
                or not m[key["conv_bias"]]:
            raise ValueError(
                f"mamba2: {key['heads']} a multiple of {key['groups']} (and "
                f"of the gated norm's groups), {key['proj_bias']} false, "
                f"{key['conv_bias']} true")
        chunk = int(m.get("scan_chunk", m[key["block"]]))
        if chunk < 1:
            raise ValueError("scan_chunk is a count of tokens")
        return cls(heads, int(m[key["head_dim"]]), int(m[key["state"]]),
                   groups, int(m[key["conv"]]), chunk, norm_groups)

    def chunk_for(self, tokens: int) -> int:
        """The chunk of a window of ``tokens``: a window shorter than the
        chunk is one chunk; a longer one is padded on the left to whole
        chunks."""
        return min(self.chunk, tokens)


@dataclasses.dataclass(frozen=True)
class Gqa:
    """Grouped-query softmax attention; plain and without positions
    unless a setting says otherwise: ``rotary_dim`` leading dims of every
    query and key head turned by position (0: none) at ``theta``,
    ``qk_norm``: an RMS norm over every query and key head before the
    turn, ``gated``: the query projection carries a gate beside each
    head's query, and the head's output is multiplied by its sigmoid."""

    heads: int
    kv_heads: int
    head_dim: int
    scale: float  # what the scores are multiplied by before the softmax
    rotary_dim: int = 0
    theta: float = 0.0
    qk_norm: bool = False
    gated: bool = False

    @classmethod
    def read(cls, m: Mapping[str, Any], head_dim: int, scale: float,
             **more) -> "Gqa":
        """The head's width, the softmax scale and what is not plain
        (``more``) are the reader's: each family says them its own way."""
        heads, kv = int(m["num_attention_heads"]), int(
            m["num_key_value_heads"])
        if heads % kv or m.get("attention_bias", False):
            raise ValueError("gqa: num_attention_heads a multiple of "
                             "num_key_value_heads, attention_bias false")
        return cls(heads, kv, int(head_dim), float(scale), **more)


@dataclasses.dataclass(frozen=True)
class Gdn:
    """Gated DeltaNet: a delta rule whose decay is one scalar a value
    head: ``key_heads`` heads of ``key_dim`` feed ``value_heads`` heads of
    ``value_dim`` (value head j reads key head j // (value_heads /
    key_heads)), a causal depthwise convolution of ``conv`` taps over q, k
    and v together; the deployment's ``chunk``, the tokens a step of the
    served scan takes at once (no part of the result)."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int
    chunk: int

    @classmethod
    def read(cls, m: Mapping[str, Any]) -> "Gdn":
        keys, values = int(m["linear_num_key_heads"]), int(
            m["linear_num_value_heads"])
        chunk = int(m.get("gdn_chunk", 64))
        if values % keys or chunk < 8 or chunk & (chunk - 1):
            raise ValueError(
                "gdn: linear_num_value_heads a multiple of "
                "linear_num_key_heads, gdn_chunk a power of two from 8 (the "
                "triangular inverse joins blocks of 8 pair by pair)")
        return cls(keys, values, int(m["linear_key_head_dim"]),
                   int(m["linear_value_head_dim"]),
                   int(m["linear_conv_kernel_dim"]), chunk)


@dataclasses.dataclass(frozen=True)
class TopK:
    """The router that scores every routed expert and keeps the largest
    ``per_token`` (of ``HybridConfig``), inside the best groups where the
    model limits them."""

    score: str  # "sigmoid" | "softmax"
    bias: bool  # an expert bias added for the choice, not the weight
    groups: int
    groups_kept: int
    scale: float


@dataclasses.dataclass(frozen=True)
class Mhc:
    """The ``mhc`` residual rule: how many streams a token's state has, the
    Sinkhorn-Knopp steps that make the stream-to-stream map doubly
    stochastic, the eps under their sums, and what its logits are clamped
    to before the exponential."""

    streams: int
    sinkhorn_iters: int
    eps: float
    clamp: tuple[float, float]

    @classmethod
    def read(cls, m: Mapping[str, Any]) -> "Mhc":
        keys = ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
        if any(key not in m for key in keys):
            raise ValueError(f"the mhc residual rule reads {keys}")
        return cls(int(m["hc_mult"]), int(m["hc_sinkhorn_iters"]),
                   float(m["hc_eps"]), (float(m["mhc_h_res_clamp_min"]),
                                        float(m["mhc_h_res_clamp_max"])))


@dataclasses.dataclass(frozen=True)
class Multiplied:
    """The ``multiplied`` residual rule: the one constant on every
    sublayer's output."""

    multiplier: float


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The model's settings, hashable so that a jit takes them as static:
    the stack, each of its kinds' own settings, and what all models share."""

    eps: float
    # (mixer, feed-forward) per layer; None where the layer has no such
    # half (a layer of one sublayer: a mixer alone, or the experts alone)
    layers: tuple[tuple[str | None, str | None], ...]
    mixers: tuple[tuple[str, Any], ...]  # (name in MIXERS, its settings)
    router: str  # name in ROUTERS
    routing: Any  # that router's settings (None: it has none of its own)
    routed: int  # outputs the router scores: the published count
    held_first: int
    held_count: int
    per_token: int
    bins: int
    fraud_id: int
    legit_id: int
    shift: float
    residual: str = "plain"  # name in RESIDUALS
    residual_settings: Any = None  # that rule's settings (None: it has none)
    tied_head: bool = False  # the head is the embedding
    embed_scale: float = 1.0  # on every token's embedding
    logit_divisor: float = 1.0  # under the logits
    expert_body: str = "swiglu"  # name in EXPERT_BODIES: routed and shared
    # added to the weight of the layers', the final and gqa's head norms (1:
    # a family that stores them zero-centred and multiplies by 1 + w)
    norm_offset: float = 0.0

    @classmethod
    def from_dict(cls, m: Mapping[str, Any]) -> "HybridConfig":
        """From a configuration under the published key names (plus the
        cut: ``layers_kept``, ``experts_held``, ``num_experts_routed_over``,
        and the deployment's ``bins``, ``kda_chunk`` / ``scan_chunk`` and
        ``readout``)."""
        kind = m.get("model_type", "ling")
        if kind not in READERS:
            raise ValueError(f"hybrid_moe reads model_type {sorted(READERS)}"
                             f", not {kind!r}")
        held = m["experts_held"]
        settings = READERS[kind](m)
        if "eps" not in settings:  # the key most families have
            settings["eps"] = float(m["rms_norm_eps"])
        return cls(
            routed=int(m["num_experts_routed_over"]),
            held_first=int(held["first"]), held_count=int(held["count"]),
            per_token=int(m["num_experts_per_tok"]), bins=int(m["bins"]),
            fraud_id=int(m["readout"]["fraud_id"]),
            legit_id=int(m["readout"]["legit_id"]),
            shift=float(m["readout"]["shift"]), **settings)

    def mixer(self, name: str) -> Any:
        """The settings of the mixer kind ``name``."""
        return dict(self.mixers)[name]

    @property
    def moe_layers(self) -> int:
        return sum(1 for _, ffn in self.layers if ffn == "moe")


def _held_all_of(m: Mapping[str, Any], key: str) -> None:
    if int(m["experts_held"]["count"]) != int(m[key]):
        raise ValueError(f"experts_held.count is not {key}")


def _mixers_of(layers, read: Mapping[str, Any]) -> tuple:
    """``(name, settings)`` of every mixer kind ``layers`` names, each read
    by ``read[name]()``: what no kept layer uses is not read."""
    return tuple((name, read[name]())
                 for name in sorted({mixer for mixer, _ in layers if mixer}))


def _read_ling(m: Mapping[str, Any]) -> dict:
    """Ling-3.0: KDA with every ``layer_group_size``-th layer MLA, leading
    dense layers, sigmoid scores with a bias inside the best groups."""
    _held_all_of(m, "num_experts")
    period, dense = int(m["layer_group_size"]), int(
        m["first_k_dense_replace"])
    layers = tuple(("mla" if (i + 1) % period == 0 else "kda",
                    "dense" if i < dense else "moe")
                   for i in m["layers_kept"])
    return dict(
        layers=layers, mixers=(("kda", Kda.read(m)), ("mla", Mla.read(m))),
        router="top_k", routing=TopK(
            "sigmoid", True, int(m["n_group"]), int(m["topk_group"]),
            float(m["routed_scaling_factor"])))


def _read_zaya(m: Mapping[str, Any]) -> dict:
    """ZAYA1: CCA and the carried router in every layer, top 1, scaled
    residuals."""
    _held_all_of(m, "num_experts")
    kinds = {m["layer_types"][i] for i in m["layers_kept"]}
    if kinds != {"hybrid"} or int(m["num_experts_per_tok"]) != 1:
        raise ValueError("zaya: layers of type hybrid, one expert a token")
    return dict(
        layers=(("cca", "moe"),) * len(m["layers_kept"]),
        mixers=(("cca", Cca.read(m)),), router="carried_mlp", routing=None,
        residual="scaled", tied_head=bool(m["tie_word_embeddings"]))


def _read_mistral4(m: Mapping[str, Any]) -> dict:
    """Mistral-Small-4: MLA with low-rank queries and YaRN in every layer,
    softmax scores over all routed experts, no group limit, no bias."""
    _held_all_of(m, "n_routed_experts")
    if int(m["first_k_dense_replace"]) or int(m["n_group"]) != 1 \
            or int(m["n_shared_experts"]) != 1 or not m["norm_topk_prob"]:
        raise ValueError("mistral4: no leading dense layer, one group, one "
                         "shared expert, weights renormalised")
    return dict(
        layers=(("mla", "moe"),) * len(m["layers_kept"]),
        mixers=(("mla", Mla.read(m)),), router="top_k", routing=TopK(
            "softmax", False, 1, 1, float(m["routed_scaling_factor"])),
        tied_head=bool(m["tie_word_embeddings"]))


def _read_xing4(m: Mapping[str, Any]) -> dict:
    """Xing4.0: MLA with low-rank queries and YaRN in every layer (the
    source gives YaRN as ``rope_scaling`` beside a top-level ``rope_theta``
    and has no ``rope_interleave``: pairs (2i, 2i + 1), as the key family
    lays them out), leading dense layers, sigmoid scores with a bias for
    the choice over all routed experts, one shared expert, the ``mhc``
    residual path."""
    _held_all_of(m, "n_routed_experts")
    if int(m["n_group"]) != 1 or int(m["topk_group"]) != 1 \
            or int(m["n_shared_experts"]) != 1 or not m["norm_topk_prob"] \
            or m["scoring_func"] != "sigmoid" \
            or m["topk_method"] != "noaux_tc":
        raise ValueError("xing4_0: one group, one shared expert, sigmoid "
                         "scores with the noaux_tc bias, weights "
                         "renormalised")
    dense = int(m["first_k_dense_replace"])
    rope = dict(m["rope_scaling"], rope_theta=m["rope_theta"])
    return dict(
        layers=tuple(("mla", "dense" if i < dense else "moe")
                     for i in m["layers_kept"]),
        mixers=(("mla", Mla.read(dict(
            m, rope_parameters=rope,
            rope_interleave=m.get("rope_interleave", True)))),),
        router="top_k", routing=TopK(
            "sigmoid", True, 1, 1, float(m["routed_scaling_factor"])),
        residual="mhc", residual_settings=Mhc.read(m),
        tied_head=bool(m["tie_word_embeddings"]))


def _read_granite(m: Mapping[str, Any]) -> dict:
    """granite-4.0-h: by ``layer_types`` a Mamba-2 mixer or, in every
    tenth layer or so, grouped-query attention without positions; top-k
    by logit with a softmax over the chosen logits (``TopK`` softmax over
    all, renormalised over the chosen, is that softmax), one shared
    expert, a tied head, and four constants: on the embedding, on every
    sublayer's output, under the logits and as the softmax scale."""
    _held_all_of(m, "num_local_experts")
    if m["normalization_function"] != "rmsnorm" or m["hidden_act"] != "silu":
        raise ValueError("granitemoehybrid: RMS norms, SiLU gates")
    kinds = {"mamba": "mamba2", "attention": "gqa"}
    layers = tuple((kinds[m["layer_types"][i]], "moe")
                   for i in m["layers_kept"])
    hidden, heads = int(m["hidden_size"]), int(m["num_attention_heads"])
    if int(m["mamba_n_heads"]) * int(m["mamba_d_head"]) != int(
            m["mamba_expand"]) * hidden:
        raise ValueError("granitemoehybrid: mamba2's mamba_n_heads x "
                         "mamba_d_head = mamba_expand x hidden_size")
    if hidden % heads or m["position_embedding_type"] != "nope":
        raise ValueError("granitemoehybrid: gqa heads of hidden_size / "
                         "num_attention_heads, position_embedding_type nope")
    return dict(
        layers=layers, mixers=_mixers_of(layers, {
            "mamba2": lambda: Mamba2.read(m),
            "gqa": lambda: Gqa.read(m, hidden // heads,
                                    float(m["attention_multiplier"]))}),
        router="top_k", routing=TopK("softmax", False, 1, 1, 1.0),
        residual="multiplied",
        residual_settings=Multiplied(float(m["residual_multiplier"])),
        tied_head=bool(m["tie_word_embeddings"]),
        embed_scale=float(m["embedding_multiplier"]),
        logit_divisor=float(m["logits_scaling"]))


def _read_nemotron(m: Mapping[str, Any]) -> dict:
    """Nemotron-H with experts: a layer is ONE sublayer, by the letter of
    ``hybrid_override_pattern``: a Mamba-2 mixer whose gated norm norms
    inside each of ``n_groups`` groups (M), grouped-query attention
    ``head_dim`` wide without positions (*), or the expert layer (E):
    sigmoid scores with a bias for the choice over all routed experts,
    weights renormalised and scaled, experts of two matrices with relu
    squared and no gate, one shared expert; an untied head, the one eps
    under two names."""
    _held_all_of(m, "n_routed_experts")
    if int(m["n_group"]) != 1 or int(m["topk_group"]) != 1 \
            or int(m["n_shared_experts"]) != 1 or not m["norm_topk_prob"] \
            or m["mlp_hidden_act"] != "relu2" \
            or m["mamba_hidden_act"] != "silu" or m["mlp_bias"] \
            or m["use_bias"] or m["norm_eps"] != m["layer_norm_epsilon"]:
        raise ValueError(
            "nemotron_h: n_group 1, topk_group 1, n_shared_experts 1, "
            "norm_topk_prob true, mlp_hidden_act relu2, mamba_hidden_act "
            "silu, mlp_bias and use_bias false, norm_eps = "
            "layer_norm_epsilon")
    kinds = {"M": ("mamba2", None), "*": ("gqa", None), "E": (None, "moe")}
    pattern = m["hybrid_override_pattern"]
    for i in m["layers_kept"]:
        if pattern[i] not in kinds:
            raise ValueError(
                f"nemotron_h: layer {i} of hybrid_override_pattern is "
                f"{pattern[i]!r}; {' '.join(kinds)} are served")
    layers = tuple(kinds[pattern[i]] for i in m["layers_kept"])
    head_dim = int(m["head_dim"])
    return dict(
        eps=float(m["norm_eps"]), layers=layers, mixers=_mixers_of(layers, {
            "mamba2": lambda: Mamba2.read(m, norm_groups=int(m["n_groups"])),
            "gqa": lambda: Gqa.read(m, head_dim, head_dim ** -0.5)}),
        router="top_k", routing=TopK(
            "sigmoid", True, 1, 1, float(m["routed_scaling_factor"])),
        tied_head=bool(m["tie_word_embeddings"]), expert_body="relu2")


def _read_qwen3_next(m: Mapping[str, Any]) -> dict:
    """Qwen3-Next: Gated DeltaNet with every ``full_attention_interval``-th
    layer gated grouped-query attention ``head_dim`` wide (an RMS norm on
    every query and key head, a rotary turn of the leading
    ``partial_rotary_factor`` of a head, a sigmoid gate the query
    projection carries), the expert layer in every layer: softmax scores
    over all routed experts renormalised over the chosen, one shared
    expert behind a gate of its own; norms that multiply by 1 + w; an
    untied head."""
    _held_all_of(m, "num_experts")
    if int(m["decoder_sparse_step"]) != 1 or m["mlp_only_layers"] \
            or m.get("rope_scaling") or m.get("use_sliding_window") \
            or not m["norm_topk_prob"] or m["hidden_act"] != "silu":
        raise ValueError(
            "qwen3_next: decoder_sparse_step 1, mlp_only_layers empty, no "
            "rope_scaling, use_sliding_window false, norm_topk_prob true, "
            "hidden_act silu")
    period, hd = int(m["full_attention_interval"]), int(m["head_dim"])
    layers = tuple(("gqa" if (i + 1) % period == 0 else "gdn", "moe")
                   for i in m["layers_kept"])
    return dict(
        layers=layers, mixers=_mixers_of(layers, {
            "gdn": lambda: Gdn.read(m),
            "gqa": lambda: Gqa.read(
                m, hd, hd ** -0.5, qk_norm=True, gated=True,
                rotary_dim=int(hd * float(m["partial_rotary_factor"])),
                theta=float(m["rope_theta"]))}),
        router="top_k", routing=TopK("softmax", False, 1, 1, 1.0),
        tied_head=bool(m["tie_word_embeddings"]), norm_offset=1.0)


READERS = {"ling": _read_ling, "zaya": _read_zaya,
           "mistral4": _read_mistral4, "xing4_0": _read_xing4,
           "granitemoehybrid": _read_granite, "nemotron_h": _read_nemotron,
           "qwen3_next": _read_qwen3_next}


def owns(params: Any) -> bool:
    """Whether a parameter tree is this family's (the scorer asks once,
    where a tree arrives without a name)."""
    return isinstance(params, Mapping) and "edges" in params \
        and "layers" in params


# -- small pieces ---------------------------------------------------------------

def _rms(x, weight, eps, offset: float = 0.0):
    """x / rms(x) times ``weight`` (plus ``offset`` where the family
    stores its weights zero-centred: ``HybridConfig.norm_offset``)."""
    x = x.astype(F32)
    if offset:
        weight = weight + offset
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _mm(x, w, dtype):
    """x @ w in ``dtype`` with float32 accumulation."""
    return jnp.einsum("...i,io->...o", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=F32)


def _swiglu(p, x, dtype):
    h = jax.nn.silu(_mm(x, p["gate"], dtype)) * _mm(x, p["up"], dtype)
    return _mm(h, p["down"], dtype)


def _relu2(p, x, dtype):
    h = jnp.square(jax.nn.relu(_mm(x, p["up"], dtype)))
    return _mm(h, p["down"], dtype)


# an expert's body (routed and shared alike), name -> (its matrices in the
# order they are cut out of the stack, f(p, x, dtype)): SwiGLU of three
# matrices, or relu squared between two with no gate
EXPERT_BODIES = {"swiglu": (("gate", "up", "down"), _swiglu),
                 "relu2": (("up", "down"), _relu2)}


def tokenise(edges, hist, bins: int):
    """(B, L, F) records -> (B, L * F) token ids: a value's bin is the
    number of its column's edges below it."""
    b, length, cols = hist.shape
    bin_ = jnp.sum(hist[..., None] > edges, axis=-1, dtype=jnp.int32)
    return (bin_ + jnp.arange(cols, dtype=jnp.int32) * bins).reshape(
        b, length * cols)


# -- KDA ------------------------------------------------------------------------

def _short_conv(u, taps):
    """Causal depthwise convolution along axis 1 of ``u`` (B, T, H, d);
    ``taps`` (K, H, d), the last on the current token. T is a major axis
    of this layout, so a shift by a token moves whole tiles."""
    k, length = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
    out = padded[:, :length] * taps[0]
    for j in range(1, k):
        out = out + padded[:, j:j + length] * taps[j]
    return out


def _unit_lower_inverse(a, base: int = 8):
    """(I + a)^-1 for strictly lower-triangular ``a`` (..., C, C), block
    by block. The ``base`` x ``base`` diagonal blocks are inverted by the
    nilpotent series (with n = -a, (I + a)^-1 = (I + n)(I + n^2)(I + n^4):
    at 8 rows no power grows past a few tens even where every entry of a is
    near 1, as it is between identical tokens under a slow gate); then
    neighbouring blocks are joined, size by size: the inverse of [[A, 0],
    [C, B]] is [[A^-1, 0], [-B^-1 C A^-1, B^-1]]. Squaring the whole
    matrix instead meets powers of 1e5 and more at C = 64 and cancels them
    in float32: wrong by far."""
    c = a.shape[-1]
    lead = a.shape[:-2]

    def diagonal(x, size, offset=0):  # (..., c // step, size, size)
        step = size * (2 if offset else 1)
        return jnp.stack([
            x[..., lo + offset:lo + offset + size, lo:lo + size]
            for lo in range(0, c, step)], axis=-3)

    n = -diagonal(a, base)
    eye = jnp.eye(base, dtype=a.dtype)
    inv = eye + n
    power = 1
    while power * 2 < base:
        n = jnp.matmul(n, n, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + n, precision=HIGHEST)
        power *= 2
    size = base
    while size < c:
        top, bottom = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        below = diagonal(a, size, offset=size)  # C of each pair of blocks
        corner = -jnp.matmul(jnp.matmul(bottom, below, precision=HIGHEST),
                             top, precision=HIGHEST)
        zeros = jnp.zeros_like(top)
        inv = jnp.concatenate([jnp.concatenate([top, zeros], -1),
                               jnp.concatenate([corner, bottom], -1)], -2)
        size *= 2
    return inv.reshape(*lead, c, c)


def _kda_chunk(state, chunk, sub: int):
    """One chunk of the gated delta rule for every (row, head) at once.

    ``state`` (B, H, dk, dv) is S at the chunk's start; ``chunk`` holds q,
    k (B, C, H, dk), v (B, C, H, dv), the log-decays g (B, C, H, dk) <= 0
    and beta (B, C, H), laid out as the projections leave them. With G_t
    the running sum of g inside the chunk, u_t = beta_t (v_t - S_(t-1)^T
    Diag(alpha_t) k_t) solves (I + A) U = beta (V - (K e^G) S), A_ti =
    beta_t sum_c k_tc k_ic e^(G_tc - G_ic) for i < t, and o_t = (q_t
    e^G_t)^T S + sum_(i<=t) P_ti u_i with P like A on q. e^(G_t - G_i) is
    formed per ``sub`` rows against the running sum at their first row, so
    that neither factor leaves float32 (|g| <= 5 a token: at most e^80
    inside 16 rows). Returns the new state and o (B, C, H, dv)."""
    q, k, v, g, beta = chunk
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    c = q.shape[1]
    run = jnp.cumsum(g, axis=1)  # G_t, inclusive
    a_rows, p_rows = [], []
    for lo in range(0, c, sub):
        hi = lo + sub
        ref = run[:, lo - 1:lo] if lo else jnp.zeros_like(run[:, :1])
        rows = jnp.exp(run[:, lo:hi] - ref)
        cols = k[:, :hi] * jnp.exp(ref - run[:, :hi])
        pad = ((0, 0), (0, 0), (0, 0), (0, c - hi))
        a_rows.append(jnp.pad(jnp.einsum(
            "bthc,bihc->bhti", k[:, lo:hi] * rows, cols,
            precision=KDA_INSIDE), pad))
        p_rows.append(jnp.pad(jnp.einsum(
            "bthc,bihc->bhti", q[:, lo:hi] * rows, cols,
            precision=KDA_INSIDE), pad))
    at = jnp.arange(c)
    a = jnp.where(at[:, None] > at[None, :], jnp.concatenate(a_rows, 2), 0.0)
    p = jnp.where(at[:, None] >= at[None, :], jnp.concatenate(p_rows, 2), 0.0)
    beta_h = jnp.swapaxes(beta, 1, 2)  # (B, H, C)
    solve = _unit_lower_inverse(a * beta_h[..., None]) * beta_h[..., None, :]
    decay = jnp.exp(run)
    seen = jnp.einsum("bthc,bhcv->bthv", k * decay, state,  # (e^G_t k_t)^T S
                      precision=KDA_PRECISION)
    u = jnp.einsum("bhti,bihv->bthv", solve, v - seen,
                   precision=KDA_INSIDE)
    out = (jnp.einsum("bthc,bhcv->bthv", q * decay, state,
                      precision=KDA_PRECISION)
           + jnp.einsum("bhti,bihv->bthv", p, u, precision=KDA_INSIDE))
    last = run[:, -1:]
    k_out = k * jnp.exp(last - run)  # e^(G_C - G_i) k_i
    state = (state * jnp.exp(last)[:, 0, :, :, None]
             + jnp.einsum("bihc,bihv->bhcv", k_out, u,
                          precision=KDA_PRECISION))
    return state, out


def _chunk_loop(xs: tuple, state, one_chunk, c: int, rest: tuple):
    """``one_chunk(state, chunk) -> (state, o)`` over a window ``c`` tokens
    at a time: ``xs`` arrays (B, T, ...), ``chunk`` their (B, C, ...)
    slices, ``o`` (B, C, *rest) -> every chunk's ``o`` as (B, T, *rest)
    float32. A window is padded on the left to whole chunks with zeros,
    which every caller's recurrence passes its state through unchanged."""
    b, t = xs[0].shape[:2]
    lead = -t % c
    n = (t + lead) // c

    def chunks(x):  # (B, T, ...) -> (B, N, C, ...): no data moves
        x = jnp.pad(x, ((0, 0), (lead, 0)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(b, n, c, *x.shape[2:])

    xs = tuple(chunks(x) for x in xs)

    def step(i, carry):
        state, out = carry
        state, o = one_chunk(state, tuple(
            jax.lax.dynamic_index_in_dim(x, i, 1, keepdims=False)
            for x in xs))
        return state, jax.lax.dynamic_update_index_in_dim(
            out, o.astype(out.dtype), i, 1)

    _, o = jax.lax.fori_loop(0, n, step, (
        state, jnp.zeros((b, n, c, *rest), F32)))
    return o.reshape(b, t + lead, *rest)[:, lead:]


def _delta_scan(q, k, v, g, beta, c: int):
    """The gated delta rule over a window from a zero state: ``q``, ``k``,
    ``v`` (B, T, H, d) in the compute dtype, the log-decays ``g`` (B, T, H,
    d) <= 0 and ``beta`` (B, T, H) float32 -> o (B, T, H, d) float32, the
    state moving ``c`` tokens at a time. Two paths, one recurrence at one
    precision, chosen while the program is traced
    (``ops/kda_scan.py::kernel_fits`` says where): the Pallas kernel, or
    the loop over :func:`_kda_chunk` through XLA, the definition the tests
    hold the kernel against. A window is padded on the left to whole
    chunks: a padding token has g = beta = 0 and passes the state
    unchanged. The kernel has no derivative; nothing differentiates this
    family (it is served only)."""
    if kda_scan.kernel_fits(q, v, c, KDA_SUB):
        return kda_scan.kda_scan(q, k, v, g, beta, chunk=c, sub=KDA_SUB)
    b, _, h, dk = q.shape
    return _chunk_loop(
        (q, k, v, g, beta), jnp.zeros((b, h, dk, dk), F32),
        lambda state, chunk: _kda_chunk(state, chunk, KDA_SUB), c, (h, dk))


def kda(p, z, real, cfg: HybridConfig, dtype):
    """(B, T, hidden) normed input -> (B, T, hidden) mixer output. The
    projections leave their result as (B, T, H, d) and everything up to
    the output projection stays in that layout: a chunk is then a slice
    of a major axis, and so is the convolution's shift."""
    s = cfg.mixer("kda")
    h, dk = s.heads, s.head_dim
    keep = real[:, :, None, None].astype(F32)
    zc = z.astype(dtype)

    def heads(w, out):  # (hidden, H * d) -> (B, T, H, d), f32 accumulation
        with jax.named_scope("kda.project"):
            return jnp.einsum("bti,ihd->bthd", zc,
                              w.astype(dtype).reshape(-1, h, dk),
                              preferred_element_type=out)

    def branch(w, taps):  # the product leaves the matmul in ``dtype``
        u = heads(w, dtype)
        with jax.named_scope("kda.conv"):
            return jax.nn.silu(_short_conv(
                u.astype(F32) * keep, taps.reshape(-1, h, dk)))

    def unit(x):
        with jax.named_scope("kda.conv"):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q = (unit(branch(p["wq"], p["conv_q"])) * dk ** -0.5).astype(dtype)
    k = unit(branch(p["wk"], p["conv_k"])).astype(dtype)
    v = branch(p["wv"], p["conv_v"]).astype(dtype)
    raw = heads(p["wg"], F32)
    with jax.named_scope("kda.project"):
        to_beta, to_gate = _mm(z, p["wb"], dtype), _mm(z, p["wog"], dtype)
    with jax.named_scope("kda.scan"):
        g = s.lower_bound * jax.nn.sigmoid(
            jnp.exp(p["a_log"])[:, None]
            * (raw + p["dt_bias"].reshape(h, dk))) * keep
        beta = jax.nn.sigmoid(to_beta) * keep[..., 0]
        o = _delta_scan(q, k, v, g, beta, s.chunk)
    with jax.named_scope("kda.gate"):
        o = _rms(o, p["o_norm"], cfg.eps)
        o = o * jax.nn.sigmoid(to_gate)[..., None]
    with jax.named_scope("kda.project"):
        return jnp.einsum("bthd,hdo->bto", o.astype(dtype),
                          p["wo"].astype(dtype).reshape(h, dk, -1),
                          preferred_element_type=F32)


# -- MLA ------------------------------------------------------------------------

def _frequencies(theta: float, width: int, yarn: Yarn | None = None):
    """(width / 2,) rotary frequencies theta^(-2i / width); under YaRN the
    slow ones divided by ``factor``, the fast ones kept, a linear ramp
    between the dims that turn ``beta_fast`` and ``beta_slow`` times inside
    the ``original`` positions."""
    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    if yarn is None:
        return freq

    def dim_of(turns: float) -> float:
        return width * math.log(yarn.original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = min(max(math.floor(dim_of(yarn.beta_fast)), 0), half - 1)
    high = min(max(math.ceil(dim_of(yarn.beta_slow)), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return ramp * freq / yarn.factor + (1.0 - ramp) * freq


def _rotary(x, position, freq, interleaved: bool = False,
            scale: float = 1.0):
    """``x`` (B, T, [heads,] width) turned by its ``position`` (B, T). The
    pairs are the two halves of the width, or neighbours (2i, 2i + 1); the
    result is laid out by halves either way (q and k alike, so their
    product is the same)."""
    half = x.shape[-1] // 2
    angle = position.astype(F32)[..., None] * freq
    if x.ndim == 4:
        angle = angle[:, :, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _causal_attention(q, k, v, real, scale: float, dtype):
    """Causal softmax attention over real keys: ``q`` (B, T, H, D) against
    ``k`` (B, T, H, D) and ``v`` (B, T, H, Dv), or grouped queries
    (B, T, G, per, D) against (B, T, G, D) and (B, T, G, Dv); ``real``
    (B, T); the result in ``q``'s layout, Dv wide, in ``dtype``. Two paths,
    one mathematics at one precision, chosen while the program is traced
    (``ops/causal_attention.py::kernel_fits`` says where): the Pallas
    kernel (operands handed over by head, (B, H, T, D): a transposing copy
    each unless XLA folds it into the fusion beside it), or
    :func:`_plain_causal_attention`, the plain definition the tests compare
    against. The kernel has no derivative; nothing differentiates this
    family (it is served only)."""
    b, t = q.shape[:2]

    def by_head_shape(x):  # (B, T, heads.., d) -> (B, heads, T, d)
        return b, math.prod(x.shape[2:-1]), t, x.shape[-1]

    def by_head(x):
        return x.reshape(b, t, -1, x.shape[-1]).transpose(0, 2, 1, 3)

    if not causal_attention.kernel_fits(
            by_head_shape(q), by_head_shape(k), by_head_shape(v), q.dtype):
        return _plain_causal_attention(q, k, v, real, scale, dtype)
    o = causal_attention.fused_causal_attention(
        by_head(q), by_head(k), by_head(v), real, scale=scale,
        dtype=jnp.dtype(dtype))
    return o.transpose(0, 2, 1, 3).reshape(q.shape[:-1] + v.shape[-1:])


def _plain_causal_attention(q, k, v, real, scale: float, dtype):
    """The plain path of :func:`_causal_attention`, through XLA: a row at
    a time in query blocks (a block's keys end where it ends, so the scores
    of a row are never whole in memory)."""
    t = q.shape[1]
    # a row's q and k to scores that end in ``qk``; the weights and v
    scores, mix = (("qgpd,kgd->gpqk", "gpqk,kgd->qgpd") if q.ndim == 5
                   else ("qhd,khd->hqk", "hqk,khd->qhd"))
    blocks = (PLAIN_QUERY_BLOCKS
              if t % PLAIN_QUERY_BLOCKS == 0 and t >= 512 else 1)
    step = t // blocks

    def one_row(row):
        q1, k1, v1, real1 = row  # (T, heads.., d) x 3, (T,)
        outs = []
        for lo in range(0, t, step):
            hi = lo + step
            s = jnp.einsum(scores, q1[lo:hi], k1[:hi],
                           preferred_element_type=F32) * scale
            w = jax.nn.softmax(jnp.where(
                _attendable(real1[:hi], lo, hi), s, MASKED), axis=-1)
            outs.append(jnp.einsum(mix, w.astype(dtype), v1[:hi],
                                   preferred_element_type=F32))
        return jnp.concatenate(outs, 0).astype(dtype)

    return jax.lax.map(one_row, (q, k, v, real))


def _attendable(real_keys, lo: int, hi: int):
    """(hi - lo, hi) bool: query lo + i may read key j: a real token at or
    before it."""
    return real_keys[None, :] & (jnp.arange(hi)[None, :]
                                 <= jnp.arange(lo, hi)[:, None])


def mla(p, z, real, position, cfg: HybridConfig, dtype):
    """(B, T, hidden) normed input -> (B, T, hidden) mixer output; the
    query path, the norms, the rotary and the softmax scale are the
    settings' (``Mla``)."""
    b, t, _ = z.shape
    s = cfg.mixer("mla")
    h, nope, rope, vd = s.heads, s.nope, s.rope, s.v_dim
    with jax.named_scope("mla.project"):
        if s.q_rank is None:
            q = _mm(z, p["wq"], dtype)
        else:
            q = _mm(_rms(_mm(z, p["wdq"], dtype), p["q_norm"], cfg.eps),
                    p["wuq"], dtype)
        q = q.reshape(b, t, h, nope + rope)
        down = _mm(z, p["wdkv"], dtype)
        c = _rms(down[..., :s.kv_rank], p["c_norm"], cfg.eps)
        up = _mm(c, p["wukv"], dtype).reshape(b, t, h, nope + vd)
        parts = {"qn": q[..., :nope], "qr": q[..., nope:],
                 "kn": up[..., :nope], "kr": down[..., s.kv_rank:]}
        if s.part_norms:
            parts = {name: _rms(x, p[name + "_norm"], cfg.eps)
                     for name, x in parts.items()}
        freq = _frequencies(s.theta, rope, s.yarn)

        def turned(x):
            return _rotary(x, position, freq, s.interleaved, s.turn_scale)

        # the rotary part rides beside the rest as extra width of q and k:
        # one product gives q_n k_n^T + q_r k_r^T
        q = jnp.concatenate([parts["qn"], turned(parts["qr"])], -1)
        if s.yarn is not None and s.yarn.query_beta and t > s.yarn.original:
            # 1 at every position below ``original``: a window that short
            # is traced without it
            q = q * (1.0 + s.yarn.query_beta * jnp.log1p(jnp.floor(
                position.astype(F32) / s.yarn.original)))[:, :, None, None]
        k = jnp.concatenate([
            parts["kn"], jnp.broadcast_to(
                turned(parts["kr"])[:, :, None, :], (b, t, h, rope))], -1)
        q, k = q.astype(dtype), k.astype(dtype)
        v = up[..., nope:].astype(dtype)
    with jax.named_scope("mla.attend"):
        o = _causal_attention(q, k, v, real, s.scale, dtype)
    with jax.named_scope("mla.project"):
        return _mm(o.reshape(b, t, h * vd), p["wo"], dtype)


# -- CCA ------------------------------------------------------------------------

def _back(x, keep):
    """x_(t-1) along axis 1 of ``x`` (B, T, ...); zeros before a row's
    first real token: ``keep`` (B, T, 1...) is 0 on padding."""
    x = x * keep
    return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]


def _causal_taps(u, keep, taps, tap):
    """sum over lags of tap(u_(t - lag), taps[-1 - lag]): the last tap on
    the current token."""
    out = tap(u, taps[-1])
    for lag in range(1, taps.shape[0]):
        u = _back(u, keep)
        out = out + tap(u, taps[-1 - lag])
    return out


def _cca_latent(p, z, real, position, s: Cca, dtype):
    """``cca``'s q (B, T, G, per, D), k and v (B, T, G, D) in ``dtype``
    through XLA: the plain chain, the only path for every shape
    ``ops/cca_conv.py::kernel_fits`` refuses and the definition the tests
    hold the kernel against."""
    b, t, _ = z.shape
    h, g, hd = s.heads, s.kv_heads, s.head_dim
    per, rot = h // g, s.rotary_dim
    freq = _frequencies(s.theta, rot)
    keep = real[:, :, None, None].astype(F32)
    zc = z.astype(dtype)

    def heads(w):  # (hidden, n * D) -> (B, T, n, D), float32 accumulation
        return jnp.einsum("bti,ihd->bthd", zc,
                          w.astype(dtype).reshape(w.shape[0], -1, hd),
                          preferred_element_type=F32)

    q_lat, k_lat, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    # the later half of the value heads read the previous token: no
    # bias, so the shifted product is the product of the shifted input
    now = g - g // 2
    v = jnp.concatenate([v[:, :, :now], _back(v[:, :, now:], keep)], 2)
    u = jnp.concatenate([q_lat, k_lat], 2)  # (B, T, H + G, D)
    c0 = _causal_taps(
        u, keep, p["conv0"].reshape(-1, h + g, hd),
        lambda x, w: x * w) + p["conv0_b"].reshape(h + g, hd)

    def per_head(x, w):  # heads as the leading batch axis of the product
        x = jnp.moveaxis(x, 2, 0).reshape(h + g, b * t, hd)
        return jnp.moveaxis(jnp.einsum(
            "hnd,hde->hne", x, w, preferred_element_type=F32).reshape(
                h + g, b, t, hd), 0, 2)

    c1 = _causal_taps(
        c0.astype(dtype), keep.astype(dtype), p["conv1"].astype(dtype),
        per_head) + p["conv1_b"].reshape(h + g, hd)
    q_heads = q_lat.reshape(b, t, g, per, hd)
    q = c1[:, :, :h].reshape(b, t, g, per, hd) + (
        q_heads + k_lat[:, :, :, None]) * 0.5
    k = c1[:, :, h:] + (q_heads.mean(3) + k_lat) * 0.5

    def unit(x):
        return x * (math.sqrt(hd) * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) + L2_EPS))

    def turned(x):  # (B, T, n, D): the leading ``rot`` dims rotated
        return jnp.concatenate([_rotary(
            x[..., :rot], position, freq), x[..., rot:]], -1)

    q = turned(unit(q).reshape(b, t, h, hd)).reshape(
        b, t, g, per, hd).astype(dtype)
    k = turned(unit(k) * p["tau"][:, None]).astype(dtype)
    return q, k, v.astype(dtype)


def _cca_latent_kernel(p, z, real, position, s: Cca, dtype):
    """:func:`_cca_latent` with everything after the three projections in
    ``ops/cca_conv.py``'s kernel: the projections token-major as their
    products leave them, the turn's cosines and sines by ``_rotary``'s own
    lines, laid along a head's lanes as the kernel reads them."""
    b, t, _ = z.shape
    h, g, hd, rot = s.heads, s.kv_heads, s.head_dim, s.rotary_dim
    angle = position.astype(F32)[..., None] * _frequencies(s.theta, rot)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    rest = (b, t, hd - rot)
    q, k, v = cca_conv.cca_conv(
        _mm(z, p["wq"], dtype), _mm(z, p["wk"], dtype), _mm(z, p["wv"], dtype),
        real[:, :, None].astype(F32),
        jnp.concatenate([cos, cos, jnp.ones(rest, F32)], -1),
        jnp.concatenate([-sin, sin, jnp.zeros(rest, F32)], -1),
        p["conv0"], p["conv0_b"], p["conv1"], p["conv1_b"], p["tau"],
        rot=rot, dtype=jnp.dtype(dtype), eps=L2_EPS)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    return q.reshape(b, t, g, h // g, hd), k, v


def cca(p, z, real, position, cfg: HybridConfig, dtype):
    """(B, T, hidden) normed input -> (B, T, hidden) mixer output. Between
    the three projections and the attention two paths, one chain at one
    precision, chosen while the program is traced
    (``ops/cca_conv.py::kernel_fits`` says where): the Pallas kernel on the
    projections token-major, or :func:`_cca_latent` through XLA, which
    keeps them as (B, T, heads, D) as ``kda`` does. The kernel has no
    derivative; nothing differentiates this family (it is served only)."""
    b, t, _ = z.shape
    s = cfg.mixer("cca")
    latent = (_cca_latent_kernel if cca_conv.kernel_fits(
        z, p["wq"], p["wk"], p["conv0"], p["conv1"], dtype) else _cca_latent)
    with jax.named_scope("cca.conv"):
        q, k, v = latent(p, z, real, position, s, dtype)
    with jax.named_scope("cca.attend"):
        o = _causal_attention(q, k, v, real, 1.0 / math.sqrt(s.head_dim),
                              dtype)
    return _mm(o.reshape(b, t, s.heads * s.head_dim), p["wo"], dtype)


# -- Mamba-2 and plain grouped-query attention -------------------------------------

def _ssd(x, bm, cm, dt, a, c: int):
    """The selective state-space recurrence S_t = e^(a_t) S_(t-1) + dt_t
    x_t B_t^T, y_t = S_t C_t, for every (row, head) at once, a chunk of
    ``c`` tokens at a time: ``(y (B, T, H, P), the most negative running
    sum of a inside a chunk)``. ``x`` (B, T, H, P), ``bm`` and ``cm`` (B, T, G,
    N), ``dt`` and the log-decays ``a`` <= 0 (B, T, H), all float32; the H
    heads are G groups of H / G that share B and C. With R_t the running
    sum of a inside the chunk: y_t = sum_(s<=t) (C_t . B_s) e^(R_t - R_s)
    dt_s x_s + e^(R_t) S_0 C_t, S_0 the state the chunks before left,
    which moves on by S_0 <- e^(R_last) S_0 + sum_s e^(R_last - R_s) dt_s
    x_s B_s^T. A decay is always the exponential of a difference of
    running sums that is <= 0 (masked before the exponential where s > t),
    never a quotient of two exponentials. A window is padded on the left
    to whole chunks: a padding token has dt = a = 0 and passes the state
    unchanged."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    k = h // g
    lead = -t % c
    nc = (t + lead) // c

    def chunks(v, *shape):  # (B, T, ...) -> (B, NC, C, *shape)
        v = jnp.pad(v, ((0, 0), (lead, 0)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(b, nc, c, *shape)

    u = chunks(x * dt[..., None], g, k, p)  # dt_s x_s
    bm, cm = chunks(bm, g, n), chunks(cm, g, n)
    run = jnp.cumsum(chunks(a, g, k), axis=2)  # R_t, inclusive
    at = jnp.arange(c)
    by_head = jnp.moveaxis(run, 2, -1)  # (B, NC, G, K, C)
    decay = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = jnp.einsum("bctgn,bcsgn->bcgts", cm, bm, precision=KDA_INSIDE)
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", scores[:, :, :, None] * decay, u,
                   precision=KDA_INSIDE)
    last = run[:, :, -1]  # (B, NC, G, K)
    own = jnp.einsum("bcsgkp,bcsgn->bcgkpn",
                     u * jnp.exp(last[:, :, None] - run)[..., None], bm,
                     precision=KDA_PRECISION)

    def carry_on(state, chunk):
        own_c, whole = chunk
        return state * whole[..., None, None] + own_c, state

    _, start = jax.lax.scan(
        carry_on, jnp.zeros((b, g, k, p, n), F32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)))
    y = y + jnp.einsum("bctgn,cbgkpn->bctgkp", cm, start,
                       precision=KDA_PRECISION) * jnp.exp(run)[..., None]
    return y.reshape(b, t + lead, h, p)[:, lead:], run.min()


def _state_scan(x, bm, cm, dt, a, d, c: int):
    """:func:`_ssd` and the skip: ``(y + d x, the most negative running
    log-decay inside a chunk)``. Two paths, one recurrence at one
    precision, chosen while the program is traced
    (``ops/ssd_scan.py::kernel_fits`` says where): the Pallas kernel, which
    takes x and gives y in ``mamba2``'s own lane-dense layout, or
    :func:`_ssd` through XLA, the definition the tests hold the kernel
    against. The kernel has no derivative; nothing differentiates this
    family (it is served only)."""
    if ssd_scan.kernel_fits(x, bm, c):
        return ssd_scan.ssd_scan(x, bm, cm, dt, a, d, chunk=c)
    y, low = _ssd(x, bm, cm, dt, a, c)
    return y + d[:, None] * x, low


def _gated_norm(y, gate, weight, eps, groups: int = 1):
    """RMSNorm(y * SiLU(gate)) w: the gate first, the norm after it,
    inside each of ``groups`` equal groups of the values (1: over all of
    them together)."""
    v = y * jax.nn.silu(gate)
    if groups == 1:
        return _rms(v, weight, eps)
    apart = v.reshape(*v.shape[:-1], groups, -1)
    return _rms(apart, weight.reshape(groups, -1), eps).reshape(v.shape)


def _conv_silu(proj, taps, bias, keep, at: int, widths: tuple):
    """SiLU(causal depthwise convolution + bias) of ``proj``'s (B, T, W)
    columns from ``at``, masked by ``keep`` (B, T, 1) first, cut into
    ``widths``: float32 arrays (B, T, width). Two paths, one convolution
    at one precision, chosen while the program is traced
    (``ops/short_conv.py::kernel_fits`` says where): the Pallas kernel,
    which reads the columns where they lie and writes each width
    token-major, as ``ssd_scan`` takes them, or :func:`_short_conv`
    through XLA on (B, T, tiles, 128) (a shift by a token then moves whole
    tiles), the definition the tests hold the kernel against."""
    if short_conv.kernel_fits(proj, taps, at, widths):
        return short_conv.short_conv(proj, taps, bias, keep, at=at,
                                     widths=widths)
    b, t, _ = proj.shape
    k, wide = taps.shape
    lane = 128 if wide % 128 == 0 else wide
    out = jax.nn.silu(_short_conv(
        (proj[..., at:at + wide] * keep).reshape(b, t, -1, lane),
        taps.reshape(k, -1, lane)) + bias.reshape(-1, lane)).reshape(
        b, t, wide)
    ends = [sum(widths[:i]) for i in range(1, len(widths))]
    return tuple(jnp.split(out, ends, axis=-1))


def mamba2(p, z, real, cfg: HybridConfig, dtype):
    """(B, T, hidden) normed input -> ``((B, T, hidden) mixer output, the
    most negative running log-decay inside a chunk)``: one projection to
    [gate | x B C | dt], the causal depthwise convolution with its bias
    and SiLU over x, B and C together, the chunked scan (:func:`_ssd`) in
    float32, the skip D x, the gate and after it the RMS norm over the
    inner values (all together, or inside each of the settings'
    ``norm_groups``), the output projection."""
    b, t, _ = z.shape
    s = cfg.mixer("mamba2")
    h, hd, n, g = s.heads, s.head_dim, s.state, s.groups
    inner = h * hd
    wide = inner + 2 * g * n
    keep = real[:, :, None].astype(F32)
    with jax.named_scope("mamba.project"):
        proj = _mm(z, p["w_in"], dtype)
        gate, dt = proj[..., :inner], proj[..., inner + wide:]
    with jax.named_scope("mamba.conv"):
        x, bm, cm = _conv_silu(proj, p["conv"], p["conv_b"], keep, inner,
                               (inner, g * n, g * n))
    with jax.named_scope("mamba.scan"):
        dt = jax.nn.softplus(dt + p["dt_bias"]) * keep
        y, low = _state_scan(
            x.reshape(b, t, h, hd), bm.reshape(b, t, g, n),
            cm.reshape(b, t, g, n), dt, -jnp.exp(p["a_log"]) * dt, p["d"],
            s.chunk_for(t))
    with jax.named_scope("mamba.gate"):
        y = _gated_norm(y.reshape(b, t, inner), gate, p["norm"], cfg.eps,
                        s.norm_groups)
    with jax.named_scope("mamba.project"):
        return _mm(y, p["w_out"], dtype), low


def gqa(p, z, real, cfg: HybridConfig, dtype, position=None):
    """(B, T, hidden) normed input -> (B, T, hidden) mixer output: causal
    softmax attention of ``heads`` query heads over ``kv_heads`` key-value
    heads at the model's own scale; by the settings (``Gqa``) an RMS norm
    on every query and key head, a turn of their leading ``rotary_dim``
    dims by ``position`` (B, T) (the two halves of those dims are the
    pairs; read by nothing where the model has no positions) and a
    sigmoid gate a head that ``wq`` carries beside the head's query."""
    b, t, _ = z.shape
    s = cfg.mixer("gqa")
    h, g, hd = s.heads, s.kv_heads, s.head_dim

    def heads(x, n: int, norm: str):
        """(B, T, n * hd) as the product leaves it, each head normed and
        turned where the model does either."""
        if not (s.qk_norm or s.rotary_dim):
            return x
        x = x.reshape(b, t, n, hd)
        if s.qk_norm:
            x = _rms(x, p[norm], cfg.eps, cfg.norm_offset)
        if s.rotary_dim:
            x = jnp.concatenate([
                _rotary(x[..., :s.rotary_dim], position,
                        _frequencies(s.theta, s.rotary_dim)),
                x[..., s.rotary_dim:]], -1)
        return x

    with jax.named_scope("gqa.project"):
        q, gate = _mm(z, p["wq"], dtype), None
        if s.gated:  # a head's query and its gate side by side
            q, gate = jnp.split(q.reshape(b, t, h, 2 * hd), 2, -1)
        q = heads(q, h, "q_norm").reshape(b, t, g, h // g, hd).astype(dtype)
        k = heads(_mm(z, p["wk"], dtype), g, "k_norm").reshape(
            b, t, g, hd).astype(dtype)
        v = _mm(z, p["wv"], dtype).reshape(b, t, g, hd).astype(dtype)
    with jax.named_scope("gqa.attend"):
        o = _causal_attention(q, k, v, real, s.scale, dtype)
    with jax.named_scope("gqa.project"):
        if s.gated:
            o = o.reshape(b, t, h, hd).astype(F32) * jax.nn.sigmoid(gate)
        return _mm(o.reshape(b, t, h * hd), p["wo"], dtype)


# -- Gated DeltaNet -----------------------------------------------------------------

def _gdn_chunk(state, chunk):
    """One chunk of the scalar-decay delta rule for every (row, head) at
    once: :func:`_kda_chunk` with one log-decay a value head and token
    and ``per`` value heads on each key head.

    ``state`` (B, Hk, per, dk, dv) is S at the chunk's start; ``chunk``
    holds q, k (B, C, Hk, dk), v (B, C, Hk, per, dv), the log-decays g <= 0
    and beta (B, C, Hk, per). With G_t the running sum of g inside the
    chunk, u_t = beta_t (v_t - e^(G_t) S_0^T k_t - sum_(i<t) e^(G_t - G_i)
    (k_i . k_t) u_i) solves (I + A) U = beta (V - e^G K S_0), A_ti = beta_t
    (k_t . k_i) e^(G_t - G_i) for i < t, and o_t = e^(G_t) S_0^T q_t +
    sum_(i<=t) (q_t . k_i) e^(G_t - G_i) u_i. The pairwise factor is one
    (C, C) matrix a value head, the exponential of a difference masked to
    i <= t first, so that it is <= 1 however fast a head forgets (no bound
    on g), never a quotient of two exponentials; K K^T and Q K^T are
    formed once a key head and used by its value heads. Returns the new
    state and o (B, C, Hk, per, dv)."""
    q, k, v, g, beta = chunk
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    c = q.shape[1]
    run = jnp.cumsum(g, axis=1)  # G_t, inclusive
    at = jnp.arange(c)
    by_head = jnp.moveaxis(run, 1, -1)  # (B, Hk, per, C)
    decay = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    kk = jnp.einsum("bthc,bihc->bhti", k, k, precision=KDA_INSIDE)
    qk = jnp.einsum("bthc,bihc->bhti", q, k, precision=KDA_INSIDE)
    beta_h = jnp.moveaxis(beta, 1, -1)[..., None]  # (B, Hk, per, C, 1)
    a = jnp.where(at[:, None] > at[None, :], kk[:, :, None] * decay, 0.0)
    solve = _unit_lower_inverse(a * beta_h) * jnp.swapaxes(beta_h, -1, -2)
    whole = jnp.exp(run)[..., None]  # e^(G_t)
    seen = jnp.einsum("bthc,bhrcv->bthrv", k, state,
                      precision=KDA_PRECISION) * whole
    u = jnp.einsum("bhrti,bihrv->bthrv", solve, v - seen,
                   precision=KDA_INSIDE)
    out = (jnp.einsum("bthc,bhrcv->bthrv", q, state,
                      precision=KDA_PRECISION) * whole
           + jnp.einsum("bhrti,bihrv->bthrv", qk[:, :, None] * decay, u,
                        precision=KDA_INSIDE))
    last = run[:, -1]  # G_C (B, Hk, per)
    left = jnp.exp(last[:, None] - run)[..., None]  # e^(G_C - G_i)
    state = (state * jnp.exp(last)[..., None, None]
             + jnp.einsum("bihc,bihrv->bhrcv", k, u * left,
                          precision=KDA_PRECISION))
    return state, out


def _scalar_delta_scan(q, k, v, g, beta, c: int, l2=None, gate=None):
    """The delta rule with one decay a value head over a window from a
    zero state: ``q``, ``k`` (B, T, Hk, dk) and ``v`` (B, T, Hv, dv) in the
    compute dtype, the log-decays ``g`` <= 0 and ``beta`` (B, T, Hv)
    float32 -> o (B, T, Hv, dv) float32, the state moving ``c`` tokens at a
    time (scope ``gdn.scan``). What ``gdn`` does around the scan rides
    along, so that the kernel can take it in: with ``l2`` = (epsilon,
    compute dtype), q, k and v arrive as the convolution leaves them: q
    and k are L2-normed by head first (q times dk^-0.5) and all three
    rounded to that dtype; with ``gate`` = (proj, column, weight, epsilon),
    o leaves RMS-normed by head times weight, times SiLU of ``proj``'s Hv x
    dv columns from ``column``, in the compute dtype (scope ``gdn.gate``
    where XLA does it). Two paths, one recurrence at one precision, chosen
    while the program is traced (``ops/gdn_scan.py::kernel_fits`` says
    where): the Pallas kernel, or the loop over :func:`_gdn_chunk` through
    XLA, :func:`_delta_scan`'s sibling and the definition the tests hold
    the kernel against. A padding token has g = beta = 0 and passes the
    state unchanged. The kernel has no derivative; nothing differentiates
    this family (it is served only)."""
    eps, dtype = l2 or (None, q.dtype)
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    proj, at, weight, gate_eps = gate or (None, 0, None, None)
    with jax.named_scope("gdn.scan"):
        if gdn_scan.kernel_fits(q, v, c, dtype):
            return gdn_scan.gdn_scan(
                q, k, v, g, beta, chunk=c, unit=eps, dtype=dtype, z=proj,
                norm=weight, at=at, eps=gate_eps)
        per = hv // hk
        if l2 is not None:
            def unit(x):
                return x * jax.lax.rsqrt(
                    jnp.sum(x * x, -1, keepdims=True) + eps)

            q = (unit(q) * dk ** -0.5).astype(dtype)
            k, v = unit(k).astype(dtype), v.astype(dtype)
        o = _chunk_loop(
            (q, k, v.reshape(b, t, hk, per, dv), g.reshape(b, t, hk, per),
             beta.reshape(b, t, hk, per)),
            jnp.zeros((b, hk, per, dk, dv), F32), _gdn_chunk, c,
            (hk, per, dv)).reshape(b, t, hv, dv)
    if gate is None:
        return o
    with jax.named_scope("gdn.gate"):
        return (_rms(o, weight, gate_eps) * jax.nn.silu(
            proj[..., at:at + hv * dv].reshape(b, t, hv, dv))).astype(dtype)


def gdn(p, z, real, cfg: HybridConfig, dtype):
    """(B, T, hidden) normed input -> ``((B, T, hidden) mixer output, the
    most negative log-decay of a token)``: one projection to [q | k | v |
    z] and one to [b | a], the causal depthwise convolution and SiLU over
    q, k and v together (no bias), q and k L2-normed by head, beta =
    sigmoid(b) and g = -exp(A_log) softplus(a + dt_bias) a value head, the
    chunked scan (:func:`_scalar_delta_scan`) in float32, an RMS norm over each
    head's values (times w, whatever ``norm_offset``) times SiLU(z), the
    output projection."""
    b, t, _ = z.shape
    s = cfg.mixer("gdn")
    hk, hv, dk, dv = s.key_heads, s.value_heads, s.key_dim, s.value_dim
    keys, values = hk * dk, hv * dv
    keep = real[:, :, None].astype(F32)
    with jax.named_scope("gdn.project"):
        proj = _mm(z, p["w_qkvz"], dtype)
        ba = _mm(z, p["w_ba"], dtype)
    with jax.named_scope("gdn.conv"):
        q, k, v = _conv_silu(proj, p["conv"], jnp.zeros((
            2 * keys + values,), F32), keep, 0, (keys, keys, values))
    with jax.named_scope("gdn.scan"):
        beta = jax.nn.sigmoid(ba[..., :hv]) * keep
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"]) * keep
    o = _scalar_delta_scan(
        q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk),
        v.reshape(b, t, hv, dv), g, beta, s.chunk, (L2_EPS, dtype),
        (proj, 2 * keys + values, p["norm"], cfg.eps))
    with jax.named_scope("gdn.project"):
        return _mm(o.reshape(b, t, values), p["w_out"], dtype), g.min()


# -- the expert layer -------------------------------------------------------------

def route(p, z, real, cfg: HybridConfig):
    """``(experts (N, k) int32, weights (N, k) float32)`` over all routed
    experts for tokens ``z`` (N, hidden), by the ``TopK`` settings; a
    padding token gets expert -1 and weight 0."""
    rule = cfg.routing
    logits = jnp.matmul(z.astype(F32), p["router"].astype(F32),
                        precision=HIGHEST)
    s = jax.nn.sigmoid(logits) if rule.score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choice = s + p["bias"] if rule.bias else s
    if rule.groups > 1:
        choice = _inside_the_best_groups(choice, rule, cfg.routed)
    _, chosen = jax.lax.top_k(choice, cfg.per_token)
    # s at the chosen, as a masked sum: a gather of (N, k) from (N, routed)
    # costs more on this device than the compare over routed
    w = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(cfg.routed),
                          s[:, None, :], 0.0), axis=-1)
    w = w / w.sum(-1, keepdims=True) * rule.scale
    return (jnp.where(real[:, None], chosen, -1).astype(jnp.int32),
            jnp.where(real[:, None], w, 0.0))


def _inside_the_best_groups(choice, rule: TopK, routed: int):
    """``choice`` (N, routed) with -inf outside the ``groups_kept`` groups
    whose two largest add up to most."""
    per = routed // rule.groups
    by_group = choice.reshape(-1, rule.groups, per)
    best = by_group.max(-1, keepdims=True)
    at_best = jnp.argmax(by_group, -1)[..., None] == jnp.arange(per)
    score = best[..., 0] + jnp.where(at_best, -jnp.inf, by_group).max(-1)
    # a group is kept if fewer than ``groups_kept`` groups beat it (an
    # equal score beats it from a lower index, as top_k orders ties)
    at = jnp.arange(rule.groups)
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (at[None, :] < at[:, None]))
    open_ = ahead.sum(-1) < rule.groups_kept
    return jnp.where(jnp.repeat(open_, per, axis=1), choice, -jnp.inf)


def route_carried(p, z, r, real, cfg: HybridConfig):
    """The router that hands its state on: ``(expert (N, 1) int32, weight
    (N, 1) float32, r (N, R))`` for tokens ``z`` (N, hidden) and the state
    ``r`` the layer before handed over (zeros at the first layer). An MLP
    on a down-projection plus ``gamma * r``, softmax over the routed
    outputs, the largest of probability plus balancing bias; the last
    output is *skip*, an expert nobody holds. A padding token gets expert
    -1, weight 0, and hands ``r`` on as it came. Float32 at ``highest``
    throughout."""
    def mm(x, w):
        return jnp.matmul(x, w.astype(F32), precision=HIGHEST)

    m = p["router"]
    state = mm(z.astype(F32), m["down"]) + m["down_b"] + m["gamma"] * r
    a = _rms(state, m["norm"], cfg.eps)
    a = jax.nn.gelu(mm(a, m["w1"]) + m["b1"], approximate=False)
    a = jax.nn.gelu(mm(a, m["w2"]) + m["b2"], approximate=False)
    prob = jax.nn.softmax(mm(a, m["w3"]), axis=-1)
    chosen = jnp.argmax(prob + p["bias"], axis=-1)
    w = jnp.sum(jnp.where(chosen[:, None] == jnp.arange(cfg.routed), prob,
                          0.0), axis=-1)
    return (jnp.where(real, chosen, -1).astype(jnp.int32)[:, None],
            jnp.where(real, w, 0.0)[:, None],
            jnp.where(real[:, None], state, r))


def held_experts(ex, z, chosen, w, cfg: HybridConfig, dtype,
                 tile: int | None = None):
    """The held experts' part of the layer for tokens ``z`` (N, hidden):
    ``(y (N, hidden) float32, pairs (held,) int32, served int32)``.

    Every (token, slot) whose expert is held is a pair; pairs are sorted
    by expert (stable, so a group keeps token order) and each expert's
    group is cut into tiles of ``tile`` rows, the last one part empty; the
    tiles' rows stand in a buffer laid out tile after tile. Two bodies fill
    it, one mathematics at one precision, chosen while the program is
    traced (``ops/grouped_experts.py::kernel_fits`` says where):

    - the Pallas kernels of ``ops/grouped_experts.py``, a grouped matmul
      over the sorted rows that addresses the stacked matrices in place
      and keeps an expert's block on the chip across its tiles. The rows
      are gathered and multiplied ``MOE_CHUNK`` at a time (a loop
      whose trip count is the number of chunks that hold a pair), so the
      gathered tokens and the gated products take a chunk's memory and not
      the buffer's; ``tile`` comes from the pairs an expert expects
      (``row_tile``);
    - the plain loop (every other shape, and a mesh), which runs once per
      tile that exists, gathers the tile's tokens, cuts the expert's
      matrices out of the stack and writes the expert's body of the rows
      (``cfg.expert_body``, an entry of ``EXPERT_BODIES``: SwiGLU of three
      matrices, or relu squared between two; ``tile`` = ``MOE_TILE``). It
      is the definition the tests hold the kernels against. Columns of
      zeros that pad ``up`` with the same rows of zeros in ``down`` change
      nothing under either body.

    After either every token adds up its own slots' rows, scaled by the
    routing weights (a gather: on this device a row-wise scatter-add into
    the result costs several times as much). ``served`` counts the pairs
    of the tiles that were multiplied; it equals ``pairs.sum()`` because
    no pair is dropped."""
    n, k = chosen.shape
    held = cfg.held_count
    names, body = EXPERT_BODIES[cfg.expert_body]
    kernel = grouped_experts.kernel_fits(ex["up"], dtype, len(names) - 1)
    if tile is None:
        tile = grouped_experts.row_tile(n * k / cfg.routed) if kernel \
            else MOE_TILE
    local = chosen - cfg.held_first
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).reshape(-1)  # ``held``: not here
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    pairs = jnp.sum(group[:, None] == jnp.arange(held, dtype=group.dtype),
                    axis=0, dtype=jnp.int32)
    tiles = (pairs + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    first_tile = tile_end - tiles
    first_pair = jnp.cumsum(pairs) - pairs
    # by tile: its expert, and where its pairs start in ``order``
    chunk = max(MOE_CHUNK // tile, 1) if kernel else 1  # tiles a trip
    most = -(-((n * k + tile - 1) // tile + held) // chunk) * chunk
    expert_of = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(most), side="right"), held - 1).astype(jnp.int32)
    inside = (jnp.arange(most, dtype=jnp.int32) - first_tile[expert_of]) * tile
    start_of = first_pair[expert_of] + inside
    live_of = jnp.clip(pairs[expert_of] - inside, 0, tile)
    order_padded = jnp.pad(order, (0, tile))
    zc = z.astype(dtype)

    def one_tile(j, rows):
        slot = jax.lax.dynamic_slice(order_padded, (start_of[j],), (tile,))
        part = body({name: jax.lax.dynamic_index_in_dim(
            ex[name], expert_of[j], 0, keepdims=False)
            for name in names}, zc[slot // k], dtype)
        return jax.lax.dynamic_update_slice(rows, part.astype(dtype),
                                            (j * tile, 0))

    def one_chunk(c, rows):
        first = c * chunk
        slot = jax.vmap(lambda start: jax.lax.dynamic_slice(
            order_padded, (start,), (tile,)))(jax.lax.dynamic_slice(
                start_of, (first,), (chunk,))).reshape(-1)
        return grouped_experts.GROUPED[cfg.expert_body](
            zc[slot // k], *(ex[name] for name in names),
            jax.lax.dynamic_slice(expert_of, (first,), (chunk,)),
            jax.lax.dynamic_slice(live_of, (first,), (chunk,)),
            jnp.minimum(tile_end[-1] - first, chunk), rows, first, tile=tile)

    shape = (most * tile, z.shape[-1])
    if kernel:  # only rows a kernel wrote are read: no buffer of zeros
        rows = jax.lax.fori_loop(
            0, (tile_end[-1] + chunk - 1) // chunk, one_chunk,
            grouped_experts.uninitialised(shape, dtype))
    else:
        rows = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                                 jnp.zeros(shape, dtype))
    served = jnp.sum(jnp.where(jnp.arange(most) < tile_end[-1], live_of, 0),
                     dtype=jnp.int32)
    # where each (token, slot) stands in ``rows``: its rank among the
    # sorted pairs, moved by the empty ends of the groups before it
    rank = jnp.argsort(order).astype(jnp.int32)  # the inverse permutation
    own = group % held
    at = jnp.where(mine.reshape(-1), rank - first_pair[own]
                   + first_tile[own] * tile, 0).reshape(n, k)
    scale = jnp.where(mine, w, 0.0)
    y = jnp.zeros((n, z.shape[-1]), F32)
    for slot in range(k):  # one slot at a time: a gather of N rows each
        y = y + jnp.where(mine[:, slot:slot + 1],
                          rows[at[:, slot]].astype(F32), 0.0) \
            * scale[:, slot:slot + 1]
    return y, pairs, served


def moe(p, z, r, real, cfg: HybridConfig, dtype):
    """``(y, r, counts)`` of one expert layer: the held experts' part (and
    the shared expert's, where the layer has one), the router's state for
    the next layer (``r`` as it came where the router carries none), and
    ``pairs`` (held,), ``served``, ``absent`` (chosen pairs whose expert
    another chip holds, the skip among them), ``row_pairs`` (B,),
    ``skipped`` (real tokens whose choice was *skip*) and ``row_choice``
    (B, routed): each row's chosen pairs by routed output."""
    b, t, d = z.shape
    flat = z.reshape(b * t, d)
    with jax.named_scope("moe.route"):
        chosen, w, r = ROUTERS[cfg.router](p, flat, r, real.reshape(-1), cfg)
    with jax.named_scope("moe.experts"):
        y, pairs, served = held_experts(p["experts"], flat, chosen, w, cfg,
                                        dtype)
    if "shared" in p:
        _, body = EXPERT_BODIES[cfg.expert_body]
        with jax.named_scope("moe.shared"):
            shared = body(p["shared"], flat, dtype)
            if "shared_gate" in p:  # one scalar a token
                shared = shared * jax.nn.sigmoid(
                    _mm(flat, p["shared_gate"], dtype))
            y = y + shared
    local = chosen - cfg.held_first
    mine = (local >= 0) & (local < cfg.held_count)
    counts = {
        "pairs": pairs, "served": served,
        "absent": jnp.sum((chosen >= 0) & ~mine, dtype=jnp.int32),
        "row_pairs": jnp.sum(mine.reshape(b, -1), axis=1, dtype=jnp.int32),
        "skipped": jnp.sum(chosen == cfg.routed - 1, dtype=jnp.int32)
        if cfg.router == "carried_mlp" else jnp.zeros((), jnp.int32),
        "row_choice": jnp.sum(
            chosen.reshape(b, -1, 1) == jnp.arange(cfg.routed), axis=1,
            dtype=jnp.int32)}
    return y.reshape(b, t, d), r, counts


ROUTERS = {  # name -> f(p, z, r, real, cfg): (chosen, weights, r)
    "top_k": lambda p, z, r, real, cfg: (*route(p, z, real, cfg), r),
    "carried_mlp": route_carried,
}
MIXERS = {  # name -> f(p, z, real, position, cfg, dtype): (y, its report)
    "kda": lambda p, z, real, position, cfg, dtype: (
        kda(p, z, real, cfg, dtype), None),
    "mla": lambda *args: (mla(*args), None),
    "cca": lambda *args: (cca(*args), None),
    "mamba2": lambda p, z, real, position, cfg, dtype: mamba2(
        p, z, real, cfg, dtype),
    "gqa": lambda p, z, real, position, cfg, dtype: (
        gqa(p, z, real, cfg, dtype, position), None),
    "gdn": lambda p, z, real, position, cfg, dtype: gdn(
        p, z, real, cfg, dtype),
}
# a mixer's device scope, where it is not the mixer's name
MIXER_SCOPES = {"mamba2": "mamba"}
# a mixer's report: the leaf of ``aux`` it is the lowest of, over that
# mixer's layers, and what the gauge ``lm_`` + leaf says of it
MIXER_REPORTS = {
    "mamba2": ("ssm_log_decay_min",
               "most negative running sum of log-decays inside a chunk of "
               "the state-space scan over real tokens, heads, layers and "
               "dispatches"),
    "gdn": ("gdn_log_decay_min",
            "most negative log-decay of one token in a Gated DeltaNet layer "
            "over real tokens, value heads, layers and dispatches"),
}


# -- the model ----------------------------------------------------------------------

def _plain(p, x, sublayer, real, cfg):
    """x + f(x). Every rule: ``(x, what the sublayer hands on beside its
    output, the rule's defect or None)`` for the stream ``x``, the rule's
    own parameters ``p`` of this sublayer (``res1`` / ``res2`` of a layer;
    None where it has none) and ``sublayer(z) -> (y, extra)``, which norms
    its input itself."""
    y, extra = sublayer(x)
    return x + y, extra, None


def _multiplied(p, x, sublayer, real, cfg):
    """x + m f(x), m one constant of the model (its settings)."""
    y, extra = sublayer(x)
    return x + cfg.residual_settings.multiplier * y, extra, None


def _scaled(p, x, sublayer, real, cfg):
    """A learned scale and bias on the stream and on the sublayer's
    output."""
    y, extra = sublayer(x)
    return p["s_r"] * (x + p["b_r"]) + p["s_o"] * (y + p["b_o"]), extra, None


def _sinkhorn(logits, s: Mhc):
    """(..., n, n) logits -> a matrix whose rows and columns each sum to 1
    up to the last steps' residue: exp of the clamped logits, then
    ``sinkhorn_iters`` times the rows over their sums, the columns over
    theirs."""
    m = jnp.exp(jnp.clip(logits, *s.clamp))
    for _ in range(s.sinkhorn_iters):
        m = m / (m.sum(-1, keepdims=True) + s.eps)
        m = m / (m.sum(-2, keepdims=True) + s.eps)
    return m


def mhc_maps(p, x, s: Mhc, eps: float):
    """The three maps of one sublayer from the streams ``x`` (B, T, n, C):
    ``(h_pre (B, T, n), h_post (B, T, n), h_res (B, T, n, n))``. With u the
    RMS-normed flattened streams (no weight: one would fold into ``phi``'s
    rows), [pre, post, res] = alpha * (u phi) + b by thirds; h_pre =
    sigmoid, h_post = 2 sigmoid, h_res = Sinkhorn-Knopp. Float32 at
    ``highest``. The streams are never flattened (on the chip n is not
    beside C in memory, and a flat view would be a copy of them): the
    product is the sum of each stream's with its rows of ``phi``, and the
    norm's scale is applied to the product's 2n + n^2 values."""
    b, t, n, c = x.shape
    scale = jax.lax.rsqrt(jnp.mean(x * x, (-2, -1))[..., None] + eps)
    m = jnp.einsum("btnc,nco->bto", x, p["phi"].astype(F32).reshape(
        n, c, -1), precision=HIGHEST) * scale
    alpha, bias = p["alpha"], p["b"]
    pre = alpha[0] * m[..., :n] + bias[:n]
    post = alpha[1] * m[..., n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), _sinkhorn(res, s)


def _mhc(p, x, sublayer, real, cfg):
    """Manifold-constrained hyper-connections around one sublayer: the
    streams ``x`` (B, T, n, C) -> z = h_pre x (C wide) -> y = f(z) -> x' =
    h_res x + h_post^T y (stream j gets h_post[j] y). z and h_res x are
    one product of the streams with the (n + 1, n) matrix [h_res; h_pre]:
    one pass over them, before the sublayer. The defect is the largest
    abs(row or column sum of h_res - 1) over the real tokens: what a
    Sinkhorn cut short, or a clamp that bites, leaves."""
    n = x.shape[2]
    with jax.named_scope("hc"):
        with jax.named_scope("hc.maps"):
            h_pre, h_post, h_res = mhc_maps(p, x, cfg.residual_settings,
                                            cfg.eps)
            off = jnp.maximum(jnp.abs(h_res.sum(-1) - 1.0),
                              jnp.abs(h_res.sum(-2) - 1.0)).max(-1)
            defect = jnp.max(jnp.where(real, off, 0.0))
        with jax.named_scope("hc.mix"):
            both = jnp.concatenate([h_res, h_pre[:, :, None, :]], axis=2)
            mixed = jnp.sum(both[..., None] * x[:, :, None], axis=3)
    y, extra = sublayer(mixed[:, :, n])
    with jax.named_scope("hc"), jax.named_scope("hc.mix"):
        x = mixed[:, :, :n] + h_post[..., None] * y[:, :, None, :]
    return x, extra, defect


RESIDUALS = {  # name -> f(p, x, sublayer, real, cfg): (x, extra, defect)
    "plain": _plain,
    "multiplied": _multiplied,
    "scaled": _scaled,
    "mhc": _mhc,
}


def _layer(p, x, r, kind, real, position, cfg: HybridConfig, dtype):
    """One layer of kind ``(mixer, feed-forward)``, either of them None
    in a layer of one sublayer (one norm, one pass of the residual rule):
    ``(x, r, counts, defect, report)``, ``counts`` None where the layer
    has no experts, ``defect`` None where the residual rule has none,
    ``report`` None where the mixer reports nothing."""
    mixer, ffn = kind
    rule = RESIDUALS[cfg.residual]

    def mix(x):
        z = _rms(x, p["norm1"], cfg.eps, cfg.norm_offset)
        with jax.named_scope(MIXER_SCOPES.get(mixer, mixer)):
            return MIXERS[mixer](p["mixer"], z, real, position, cfg, dtype)

    def feed(x):
        z = _rms(x, p["norm2"], cfg.eps, cfg.norm_offset)
        if ffn == "dense":
            with jax.named_scope("dense_ffn"):
                return _swiglu(p["ffn"], z, dtype), (r, None)
        y, state, counts = moe(p["ffn"], z, r, real, cfg, dtype)
        return y, (state, counts)

    report = counts = defect = None
    if mixer is not None:
        x, report, defect = rule(p.get("res1"), x, mix, real, cfg)
    if ffn is not None:
        x, (r, counts), second = rule(p.get("res2"), x, feed, real, cfg)
        defect = second if defect is None else jnp.maximum(defect, second)
    return x, r, counts, defect, report


def _stacked(p) -> int | None:
    """How many alike layers the tree ``p`` carries on every leaf's
    leading axis; None for one layer's tree (which holds ``norm1`` where
    it has a mixer and ``norm2`` where it has a feed-forward part)."""
    norm = p["norm1"] if "norm1" in p else p["norm2"]
    return norm.shape[0] if norm.ndim == 2 else None


def hidden_states(params: Params, hist, filled, cfg: HybridConfig,
                  dtype=jnp.bfloat16):
    """``(x before the final norm, aux)``: x (B, T, hidden) float32, or
    the residual rule's streams (B, T, n, hidden) (``slice_logits`` sums
    them). What passes from layer to layer is the stream ``x`` and the
    router's state ``r`` (None where the router carries none)."""
    b, length, cols = hist.shape
    t = length * cols
    filled = filled.astype(jnp.int32)
    at = jnp.arange(t, dtype=jnp.int32)
    real = (at // cols)[None, :] >= (length - filled)[:, None]
    position = jnp.maximum(at[None, :] - ((length - filled) * cols)[:, None],
                           0)
    with jax.named_scope("lm.embed"):
        ids = tokenise(params["edges"], hist.astype(F32), cfg.bins)
        x = params["embed"][ids].astype(F32)
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale
        if cfg.residual == "mhc":  # the embedding in every stream
            x = jnp.broadcast_to(x[:, :, None, :], (
                b, t, cfg.residual_settings.streams, x.shape[-1]))
    layers = params["layers"]
    r = None
    if cfg.router == "carried_mlp":
        r = jnp.zeros((b * t, _router_width(layers)), F32)
    # a tree of alike layers, stacked, is scanned (one is compiled); a
    # list is unrolled, and an entry of it may be such a stack
    # a layer's or a stack's (counts, defect, report), and: stacked?
    each, first = [], 0
    for p in [layers] if isinstance(layers, Mapping) else layers:
        n = _stacked(p)
        kind = cfg.layers[first]
        if n is None:
            x, r, *out = _layer(p, x, r, kind, real, position, cfg, dtype)
        elif any(k != kind for k in cfg.layers[first:first + n]):
            raise ValueError("layers stacked in one tree are of one kind")
        else:
            def step(carry, p):
                x, r, *out = _layer(p, *carry, kind, real, position, cfg,
                                    dtype)
                return (x, r), out

            (x, r), out = jax.lax.scan(step, (x, r), p)
        each.append((out, n is not None, kind[0]))
        first += n or 1

    def over_layers(which: int, mixer: str | None = None):
        """Every layer's ``out[which]`` with the layers leading (of the
        layers that mix by ``mixer``, where one is named)."""
        parts = [(out[which], stacked) for out, stacked, mixed in each
                 if out[which] is not None and mixer in (None, mixed)]
        if not parts:
            return None
        trees, stacked = zip(*parts)
        return jax.tree.map(lambda *leaves: jnp.concatenate([
            leaf if whole else jnp.expand_dims(leaf, 0)
            for leaf, whole in zip(leaves, stacked)]), *trees)

    counts, defect = over_layers(0), over_layers(1)
    reports = {leaf: over_layers(2, mixer)  # over that mixer's layers
               for mixer, (leaf, _) in MIXER_REPORTS.items()}
    if counts is None:
        none = jnp.zeros((0,), jnp.int32)
        counts = {"pairs": jnp.zeros((0, cfg.held_count), jnp.int32),
                  "served": none, "absent": none, "skipped": none,
                  "row_pairs": jnp.zeros((0, b), jnp.int32),
                  "row_choice": jnp.zeros((0, b, cfg.routed), jnp.int32)}
    aux = {"pairs": counts["pairs"],  # (expert layers, held)
           "pairs_served": counts["served"].sum(dtype=jnp.int32),
           "pairs_absent": counts["absent"].sum(dtype=jnp.int32),
           "routed_tokens": jnp.sum(real, dtype=jnp.int32),
           "skipped_tokens": counts["skipped"].sum(dtype=jnp.int32),
           "row_pairs": counts["row_pairs"].sum(0, dtype=jnp.int32),
           "row_choice": jnp.swapaxes(counts["row_choice"], 0, 1)}
    if defect is not None:  # the rule's, over all sublayers
        aux["hc_defect"] = defect.max()
    for leaf, report in reports.items():
        if report is not None:
            aux[leaf] = report.min()
    return x, aux


def _router_width(layers) -> int:
    one = layers if isinstance(layers, Mapping) else layers[0]
    return one["ffn"]["router"]["gamma"].shape[-1]


def slice_logits(params: Params, x, cfg: HybridConfig, dtype=jnp.bfloat16):
    """Final norm and the head over the vocabulary slice: untied, or the
    embedding itself; of the sum of the streams where the residual rule
    keeps several (``x`` (..., n, hidden))."""
    with jax.named_scope("lm.head"):
        if cfg.residual == "mhc":
            x = x.sum(-2)
        z = _rms(x, params["final_norm"], cfg.eps, cfg.norm_offset)
        if cfg.tied_head:
            logits = jnp.einsum("...i,vi->...v", z.astype(dtype),
                                params["embed"].astype(dtype),
                                preferred_element_type=F32)
        else:
            logits = _mm(z, params["head"], dtype)
        if cfg.logit_divisor != 1.0:
            logits = logits / cfg.logit_divisor
        return logits


@partial(jax.jit, static_argnames=("cfg", "compute_dtype"))
def logits_everywhere(params: Params, hist, filled, cfg: HybridConfig,
                      compute_dtype=jnp.bfloat16):
    """Slice logits at every position (B, T, vocab): tests and offline
    use; the served path reads one position."""
    x, aux = hidden_states(params, hist, filled, cfg, compute_dtype)
    return slice_logits(params, x, cfg, compute_dtype), aux


@partial(jax.jit, static_argnames=("cfg", "compute_dtype"))
def apply_serving(params: Params, hist, filled, cfg: HybridConfig,
                  compute_dtype=jnp.bfloat16):
    """What :class:`~ccfd_tpu.serving.history.SeqScorer` dispatches:
    ``(proba (B,), aux)``. ``proba`` = sigmoid(z_fraud - z_legit + shift)
    at the newest record's last token. ``aux``: ``logits`` (B, vocab) at
    that token, ``pairs`` (expert layers, held) pairs served per held
    expert, ``pairs_served`` (what the tile loop multiplied),
    ``pairs_absent`` (chosen pairs whose expert is held elsewhere),
    ``routed_tokens`` (the batch's real tokens), ``skipped_tokens``
    (token-layers whose choice was *skip*), ``row_pairs`` (B,) and
    ``row_choice`` (B, expert layers, routed); under the ``mhc`` residual
    rule also ``hc_defect``, the largest abs(row or column sum of a
    stream-to-stream map - 1) over the real tokens and the sublayers;
    where a layer mixes by ``mamba2`` also ``ssm_log_decay_min``, the most
    negative running sum of log-decays inside a chunk of the scan over
    tokens, heads and layers (how far e^(R_t) is from float32's smallest:
    below -87 a chunk's late tokens no longer see the state it began
    with, as in the recurrence itself); where a layer mixes by ``gdn``
    also ``gdn_log_decay_min``, the most negative log-decay of one token
    over tokens, value heads and layers (what a kernel that wanted a bound
    on it would have to hold)."""
    x, aux = hidden_states(params, hist, filled, cfg, compute_dtype)
    z = slice_logits(params, x[:, -1], cfg, compute_dtype)
    aux["logits"] = z
    verdict = z[:, cfg.fraud_id] - z[:, cfg.legit_id] + cfg.shift
    return jax.nn.sigmoid(verdict), aux


def make_observer(registry: Any):
    """``observe(aux) -> stats`` for one resolved dispatch: the family's
    counters (``moe_pairs_served_total``, ``moe_pairs_routed_total``,
    ``moe_pairs_absent_total``, ``moe_routed_tokens_total``,
    ``moe_skipped_tokens_total``, ``lm_tokens_total``, the gauge
    ``moe_expert_pairs_max`` per expert layer, and the sum and count of the
    busiest-over-mean expert load per dispatch and layer), and what
    ``seq.wait`` carries: ``pairs_served``, ``pairs_absent``,
    ``skipped_tokens``, ``routed_tokens``, ``max_expert_pairs``; where the
    program hands back ``hc_defect`` (the ``mhc`` residual rule), that too,
    and the gauge ``lm_hc_defect_max``, the largest since the process
    began; where it hands back ``ssm_log_decay_min`` (a ``mamba2`` layer)
    or ``gdn_log_decay_min`` (a ``gdn`` layer), that and the gauge ``lm_`` +
    its name, the lowest since the process began."""
    served = registry.counter(
        "moe_pairs_served_total",
        "(token, held expert) pairs the expert layers multiplied")
    routed_pairs = registry.counter(
        "moe_pairs_routed_total",
        "(token, held expert) pairs the routing chose; equals the served "
        "count because no pair is dropped")
    absent = registry.counter(
        "moe_pairs_absent_total",
        "chosen (token, expert) pairs whose expert another chip holds (the "
        "skip output among them); served + absent = experts per token x "
        "routed tokens x expert layers")
    routed = registry.counter(
        "moe_routed_tokens_total", "real tokens routed (per dispatch, not "
        "per expert layer)")
    skipped = registry.counter(
        "moe_skipped_tokens_total",
        "token-layers whose routing chose no expert (the skip output); with "
        "every expert held, served + skipped = routed tokens x expert layers")
    tokens = registry.counter(
        "lm_tokens_total", "real tokens through the backbone")
    busiest = registry.gauge(
        "moe_expert_pairs_max",
        "pairs of the busiest held expert in the last dispatch, by layer")
    skew = registry.counter(
        "moe_expert_load_ratio_total",
        "busiest held expert's pairs over the held experts' mean, summed "
        "over dispatches and expert layers")
    layer_dispatches = registry.counter(
        "moe_layer_dispatches_total",
        "expert layers run, over all dispatches (those that served a pair)")
    defect = registry.gauge(
        "lm_hc_defect_max",
        "largest abs(row or column sum - 1) of a hyper-connection's "
        "stream-to-stream map over real tokens, sublayers and dispatches")
    lowest = {leaf: registry.gauge("lm_" + leaf, text)  # since start
              for leaf, text in MIXER_REPORTS.values()}

    def observe(aux: dict) -> dict:
        pairs = aux["pairs"]  # (expert layers, held)
        n_tokens = int(aux["routed_tokens"])
        total = int(pairs.sum())
        served.inc(int(aux["pairs_served"]))
        routed_pairs.inc(total)
        absent.inc(int(aux["pairs_absent"]))
        routed.inc(n_tokens)
        skipped.inc(int(aux["skipped_tokens"]))
        tokens.inc(n_tokens)
        top = pairs.max(axis=1) if pairs.size else pairs.sum(axis=1)
        per_layer = pairs.sum(axis=1)
        for layer, most in enumerate(top):
            busiest.set(float(most), labels={"layer": str(layer)})
        live = per_layer > 0
        if live.any():
            skew.inc(float((top[live] * pairs.shape[1]
                            / per_layer[live]).sum()))
            layer_dispatches.inc(int(live.sum()))
        stats = {"pairs_served": int(aux["pairs_served"]),
                 "pairs_absent": int(aux["pairs_absent"]),
                 "skipped_tokens": int(aux["skipped_tokens"]),
                 "routed_tokens": n_tokens,
                 "max_expert_pairs": int(top.max()) if pairs.size else 0}
        if "hc_defect" in aux:
            stats["hc_defect"] = float(aux["hc_defect"])
            defect.set(max(defect.value(), stats["hc_defect"]))
        for leaf, gauge in lowest.items():
            if leaf in aux:
                stats[leaf] = float(aux[leaf])
                gauge.set(min(gauge.value(), stats[leaf]))
        return stats

    return observe


def register() -> None:
    """``hybrid_moe`` in the zoo's history families. Not swappable: a
    second tree of the served size does not fit beside the first, so
    ``swap_params`` refuses it by name (counted) instead of running the
    device out of memory."""
    from ccfd_tpu.models.registry import HistorySpec, register_history

    def scan_chunk(cfg: HybridConfig, tokens: int) -> int | None:
        settings = dict(cfg.mixers).get("mamba2")
        return None if settings is None else settings.chunk_for(tokens)

    def make_apply(dtype, _pos_length, cfg: HybridConfig):
        if cfg is None:
            raise ValueError("hybrid_moe needs its configuration "
                             "(SeqScorer(family_config=...))")
        return lambda p, xs, filled: apply_serving(p, xs, filled, cfg, dtype)

    register_history(HistorySpec(
        "hybrid_moe", owns=owns, make_apply=make_apply, reads_filled=True,
        make_observer=make_observer,
        describe=lambda cfg: {
            "experts_held": [cfg.held_first, cfg.held_first + cfg.held_count],
            "experts_routed_over": cfg.routed,
            "router": cfg.router,
            "residual": cfg.residual,
            "layers": [[half for half in kind if half is not None]
                       for kind in cfg.layers],
            "expert_body": cfg.expert_body,
            "kinds": {name: dataclasses.asdict(settings) for name, settings
                      in (*cfg.mixers, (cfg.router, cfg.routing),
                          (cfg.residual, cfg.residual_settings))
                      if settings is not None}},
        config_from=HybridConfig.from_dict, scan_chunk=scan_chunk,
        swappable=False))
