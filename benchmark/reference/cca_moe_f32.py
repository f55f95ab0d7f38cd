"""The plain reference of the ``hybrid_moe`` family's second model (the
language model of ZAYA1-8B, ``model_type`` ``zaya``, as stage 0 of a
two-stage pipeline holds it), its weights, and the comparison that decides
``correct`` for every cell that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the convolutions as explicit shifted sums, attention as a full masked
softmax a row at a time with every key head repeated to its query heads,
the experts one after another on the tokens that chose each, the skip as a
seventeenth choice that adds nothing; no blocking of queries, no tile loop,
no scan over layers. Nothing here is imported from the program. The model's
settings are read from the configuration's own keys (the published
``config.json`` names); ``config.json`` names only ``cca_time0``,
``cca_time1`` and ``router_hidden_size`` for the new parts, so what it does
not pin follows ISSUE 30's record of the family's description (the CCA
paper, arXiv 2510.04476, and the ZAYA1 report, arXiv 2511.17127), is listed
in the configuration file under ``assumed`` and marked (assumed) below.

d = ``hidden_size``, H = ``num_attention_heads``, G =
``num_key_value_heads``, D = ``head_dim``, E = ``num_experts``, R =
``router_hidden_size``, eps = ``rms_norm_eps``. The residual stream x is
float32, z = RMSNorm(x) with a weight of its own before each sublayer. A
layer (``layer_types[i]`` = ``hybrid``; ``layers_kept`` names the published
layers this cut holds) is a CCA sublayer, then an expert sublayer.

*Tokens*: as ``hybrid_moe_f32`` (column j of a record is token j * ``bins``
+ its quantile bin; ``filled`` records of a window are real, the ones left
of them padding; positions count from a row's first real token).

*Residual scaling* (assumed): each sublayer f has four learned vectors of
width d: x <- s_r * (x + b_r) + s_o * (f(RMSNorm(x)) + b_o).

*CCA*, a_(t-1) below being zero at a row's first real token (never a
padding token's value):
1. q~ = z W_q (H D wide), k~ = z W_k (G D wide). Value shift (assumed):
   the first half of the key-value heads read the current token, the second
   half the previous one: v_t = [z_t W_v0, z_(t-1) W_v1] (the columns of
   ``wv`` in that order).
2. u = [q~, k~]. Depthwise, ``cca_time0`` taps (``conv0``, the last tap on
   the current token): c0_t = w0_0 * u_t + w0_1 * u_(t-1) + b0. Grouped over
   the H + G heads, ``cca_time1`` taps (``conv1``, one D x D block a head and
   tap: grouping assumed): c1_t = c0_t W1_0 + c0_(t-1) W1_1 + b1.
3. q-k mean (assumed): query head h belongs to key head h // (H / G); m_q
   = (q~ + its key head of k~) / 2, m_k = (mean of its H / G query heads of
   q~ + k~) / 2; q = c1_q + m_q, k = c1_k + m_k.
4. Per head q <- sqrt(D) q / sqrt(sum q^2 + 1e-6), k <- sqrt(D) tau_g k /
   sqrt(sum k^2 + 1e-6), tau_g one learned scalar a key head (assumed).
5. Rotary on the first D * ``partial_rotary_factor`` dims of a head,
   rotate-half pairing, theta ``rope_parameters.hybrid.rope_theta``.
6. softmax(q k^T / sqrt(D)) v over real keys at or before the query, query
   head h on key-value head h // (H / G); y = o W_o.

*Router and experts* (widths from the config; the rest assumed): h = z W_d
+ b_d (d -> R); from the second kept layer on h <- h + gamma * r_prev; r = h
goes to the next layer (a padding token passes r_prev on unchanged). p =
softmax(MLP(RMSNorm(h))), MLP = R -> R, GELU (erf), R -> R, GELU, R ->
``num_experts_routed_over`` (the first two with bias, the last without);
outputs 0 .. E - 1 are the experts, the last output is *skip*. e = argmax(p
+ beta) (``bias``: balancing biases, for the choice only), w = p[e]. Skip:
the sublayer's f is 0 for that token. Else f = w * W_down,e (SiLU(z
W_gate,e) * (z W_up,e)) of width ``moe_intermediate_size``. No shared
expert. **The share**: this chip holds the experts ``experts_held``; a
token whose expert is elsewhere gets f = 0 here. A padding token routes
nowhere.

*Readout*: final RMSNorm, the head tied to the embedding
(``tie_word_embeddings``) over all ``vocab_size`` ids; the verdict is
sigmoid(z_fraud - z_legit + c) at the newest record's last token
(``readout``, assumed).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import hybrid_moe_f32 as shared
from benchmark.reference import table
from benchmark.reference.hybrid_moe_f32 import (  # noqa: F401 - the
    # deployment and the harness find these on the module the
    # configuration names
    aux_path, histories, miss_controls, preload_rows, sampled,
    verdict_logit)
from benchmark.reference.mlp_f32 import sigmoid

F32 = jnp.float32
BF16 = jnp.bfloat16
L2_EPS = 1e-6
MASKED = -1e30
ROW_BLOCK = 11  # histories per block on the chip: activations beside 9.4 GB
VOCAB_BLOCK = 32768  # head columns multiplied at a time


def dims(model: dict) -> dict:
    rope = model["rope_parameters"]["hybrid"]
    hd = int(model["head_dim"])
    return {"heads": int(model["num_attention_heads"]),
            "groups": int(model["num_key_value_heads"]), "hd": hd,
            "rot": int(hd * float(rope["partial_rotary_factor"])),
            "theta": float(rope["rope_theta"]),
            "eps": float(model["rms_norm_eps"])}


# -- weights -------------------------------------------------------------------

def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes; every leaf
    under ``layers`` carries the kept layers on its leading axis (the layers
    are alike, so the program may scan them). Matrices are normal with
    variance 1/fan-in, stored bfloat16 (their values exact in it, so the
    program and the reference read the same numbers); the embedding has
    variance 1/``hidden_size`` because the tied head reads it; the grouped
    convolution's blocks are 1/sqrt(D) n with the identity added to the
    newest tap, the depthwise taps 0.3 n with 1 added to the newest;
    vectors are float32 and none is zero, so that a term left out shows."""
    d, m = int(model["hidden_size"]), dims(model)
    heads, groups, hd = m["heads"], m["groups"], m["hd"]
    wide = (heads + groups) * hd
    r = int(model["router_hidden_size"])
    routed = int(model["num_experts_routed_over"])
    held = int(model["experts_held"]["count"])
    width = int(model["moe_intermediate_size"])
    taps0, taps1 = int(model["cca_time0"]), int(model["cca_time1"])
    n = len(model["layers_kept"])
    root = jax.random.key(int(model["weights_seed"]) % (2 ** 31), impl="rbg")
    counter = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(root, next(counter))

    def dense(fan_in: int, *shape: int):
        return _stacked_normal_bf16(key(), n, shape, 1.0 / math.sqrt(fan_in))

    def vec(mean: float, spread: float, *shape: int):
        return mean + spread * jax.random.normal(key(), (n, *shape), F32)

    def scaling():
        return {"s_r": vec(1.0, 0.1, d), "b_r": vec(0.0, 0.02, d),
                "s_o": vec(1.0, 0.1, d), "b_o": vec(0.0, 0.02, d)}

    blocks = vec(0.0, 1.0 / math.sqrt(hd), taps1, heads + groups, hd, hd)
    mixer = {"wq": dense(d, d, heads * hd), "wk": dense(d, d, groups * hd),
             "wv": dense(d, d, groups * hd),
             "wo": dense(heads * hd, heads * hd, d),
             "conv0": vec(0.0, 0.3, taps0, wide).at[:, -1].add(1.0),
             "conv0_b": vec(0.0, 0.02, wide),
             "conv1": blocks.at[:, -1].add(jnp.eye(hd, dtype=F32)).astype(
                 BF16),
             "conv1_b": vec(0.0, 0.02, wide),
             "tau": vec(1.0, 0.1, groups)}
    router = {"down": dense(d, d, r), "down_b": vec(0.0, 0.02, r),
              "gamma": vec(0.5, 0.1, r), "norm": vec(1.0, 0.1, r),
              "w1": dense(r, r, r), "b1": vec(0.0, 0.02, r),
              "w2": dense(r, r, r), "b2": vec(0.0, 0.02, r),
              "w3": dense(r, r, routed)}
    ffn = {"router": router,
           "bias": vec(0.0, shared.ROUTER_BIAS_SCALE, routed),
           "experts": {"gate": dense(d, held, d, width),
                       "up": dense(d, held, d, width),
                       "down": dense(width, held, width, d)}}
    layers = {"norm1": vec(1.0, 0.1, d), "res1": scaling(), "mixer": mixer,
              "norm2": vec(1.0, 0.1, d), "res2": scaling(), "ffn": ffn}
    return {"edges": jnp.asarray(shared.quantile_edges(model)),
            "embed": _stacked_normal_bf16(
                key(), 1, (int(model["vocab_size"]), d),
                1.0 / math.sqrt(d))[0],
            "layers": layers,
            "final_norm": 1.0 + 0.1 * jax.random.normal(key(), (d,), F32)}


@functools.partial(jax.jit, static_argnames=("layers", "shape", "scale"))
def _stacked_normal_bf16(key, layers: int, shape: tuple, scale: float):
    """(layers, *shape), a layer at a time: the bit generator's temporaries
    are then one layer's (the experts of all kept layers are 1.3e9 values a
    matrix)."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, BF16)
                   * jnp.asarray(scale, BF16)).astype(BF16),
        jax.random.split(key, layers))


def layer_of(params: dict, i: int) -> dict:
    """Kept layer ``i`` of the stacked tree."""
    return jax.tree.map(lambda a: a[i], params["layers"])


# -- pieces, each on float32 ------------------------------------------------------

_f32 = shared._f32
rms_norm = shared.rms_norm


def scaled_residual(s: dict, x, y):
    return s["s_r"] * (x + s["b_r"]) + s["s_o"] * (y + s["b_o"])


def cca(p: dict, z, real, position, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d)."""
    return _cca(p, z, real, position, **dims(model))


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "hd", "rot", "theta", "eps"))
def _cca(p: dict, z, real, position, *, heads: int, groups: int, hd: int,
         rot: int, theta: float, eps: float):
    del eps
    n, t, _ = z.shape
    per = heads // groups
    keep = real[..., None].astype(F32)

    def back(a):  # a_(t-1); zeros before a row's first real token
        return jnp.pad(a * keep, ((0, 0), (1, 0), (0, 0)))[:, :-1]

    q_lat, k_lat = z @ _f32(p["wq"]), z @ _f32(p["wk"])
    wv = _f32(p["wv"])
    now = (groups - groups // 2) * hd
    v = jnp.concatenate([z @ wv[:, :now], back(z) @ wv[:, now:]], -1)
    u = jnp.concatenate([q_lat, k_lat], -1)
    taps = p["conv0"]
    c0 = taps[-1] * u + p["conv0_b"]
    for lag in range(1, taps.shape[0]):
        u = back(u)
        c0 = c0 + taps[-1 - lag] * u
    blocks = _f32(p["conv1"])  # (taps, H + G, D, D)

    def grouped(a, w):
        return jnp.einsum("nthd,hde->nthe", a.reshape(
            n, t, heads + groups, hd), w).reshape(n, t, -1)

    c1 = grouped(c0, blocks[-1]) + p["conv1_b"]
    for lag in range(1, blocks.shape[0]):
        c0 = back(c0)
        c1 = c1 + grouped(c0, blocks[-1 - lag])
    q_heads = q_lat.reshape(n, t, groups, per, hd)
    k_heads = k_lat.reshape(n, t, groups, hd)
    q = c1[..., :heads * hd].reshape(q_heads.shape) + (
        q_heads + k_heads[:, :, :, None]) / 2.0
    k = c1[..., heads * hd:].reshape(k_heads.shape) + (
        q_heads.mean(3) + k_heads) / 2.0

    def unit(a):
        return math.sqrt(hd) * a * jax.lax.rsqrt(
            jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    def turned(a):  # (n, T, heads, D): the first ``rot`` dims rotated
        return jnp.concatenate([
            shared.rotary(a[..., :rot], position, theta), a[..., rot:]], -1)

    q = turned(unit(q).reshape(n, t, heads, hd))
    k = turned(unit(k) * p["tau"][:, None])
    v = v.reshape(n, t, groups, hd)
    at = jnp.arange(t)

    def one_row(row):  # a row at a time: heads x T^2 scores each
        q1, k1, v1, real1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, jnp.repeat(
            k1, per, axis=1)) / math.sqrt(hd)
        allowed = real1[None, None, :] & (at[None, :] <= at[:, None])[None]
        weights = jax.nn.softmax(jnp.where(allowed, scores, MASKED), axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights,
                          jnp.repeat(v1, per, axis=1))

    o = jax.lax.map(one_row, (q, k, v, real))
    return o.reshape(n, t, heads * hd) @ _f32(p["wo"])


def route(p: dict, z, real, r_prev, model: dict):
    """``(expert (tokens,), weight (tokens,), r, p)`` for tokens ``z``
    (tokens, d): the choice over all routed outputs (the last is *skip*;
    -1 for a padding token), the softmax weight of the choice, the state
    handed to the next layer, and the probabilities. ``r_prev`` None: the
    first kept layer."""
    return _route(p["router"], p["bias"], z, real, r_prev,
                  eps=float(model["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _route(router: dict, bias, z, real, r_prev, *, eps: float):
    h = z @ _f32(router["down"]) + router["down_b"]
    if r_prev is not None:
        h = h + router["gamma"] * r_prev
        h = jnp.where(real[:, None], h, r_prev)
    a = rms_norm(h, router["norm"], eps)
    a = jax.nn.gelu(a @ _f32(router["w1"]) + router["b1"], approximate=False)
    a = jax.nn.gelu(a @ _f32(router["w2"]) + router["b2"], approximate=False)
    prob = jax.nn.softmax(a @ _f32(router["w3"]), axis=-1)
    chosen = jnp.argmax(prob + bias, axis=-1)
    w = jnp.take_along_axis(prob, chosen[:, None], axis=-1)[:, 0]
    return (jnp.where(real, chosen, -1), jnp.where(real, w, 0.0),
            jnp.where(real[:, None], h, 0.0) if r_prev is None else h, prob)


def experts(p: dict, z, real, r_prev, model: dict):
    """``(f, r, choice)``: the held experts' part of the sublayer (n, T,
    d), the router's state for the next layer, and how many of each row's
    tokens chose each routed output (n, routed)."""
    first, held = shared.held_range(model)
    routed = int(model["num_experts_routed_over"])
    shape = z.shape
    flat = z.reshape(-1, shape[-1])
    chosen, w, r, _ = route(p, flat, real.reshape(-1), r_prev, model)
    picked = np.asarray(chosen)
    here = (picked >= first) & (picked < first + held)
    counts = np.bincount(picked[here] - first, minlength=held)
    room = min(len(flat), 1 << max(8, (max(int(counts.max()), 1)
                                        - 1).bit_length()))
    f = shared._held_experts(
        p["experts"], flat, chosen[:, None], w[:, None],
        jnp.arange(held), room=room, first=first)
    choice = np.stack([np.bincount(row[row >= 0], minlength=routed)
                       for row in picked.reshape(shape[0], -1)])
    return f.reshape(shape), r, choice


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, norm, *, eps: float):
    return rms_norm(x, norm, eps)


@jax.jit
def _head_block(x, rows):
    return x @ _f32(rows).T


def tied_head(params: dict, x, eps: float):
    """RMSNorm(x) E^T over the whole vocabulary, some columns at a time."""
    x = _final(x, params["final_norm"], eps=eps)
    embed = params["embed"]
    return jnp.concatenate([
        _head_block(x, embed[lo:lo + VOCAB_BLOCK])
        for lo in range(0, embed.shape[0], VOCAB_BLOCK)], -1)


_normed = shared._normed
_scaled = jax.jit(scaled_residual)


def forward(params: dict, model: dict, hist, filled, *,
            every_position: bool = False):
    """``(logits, choice)``: the logits at the newest record's last token
    (n, vocab), or at every position (n, tokens, vocab), and each row's
    count of tokens by routed output in every kept layer (n, layers,
    routed; the last output is the skip)."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["rms_norm_eps"])
        x, real, position = shared._embed(
            params["edges"], params["embed"], jnp.asarray(hist, F32),
            jnp.asarray(filled, jnp.int32), bins=int(model["bins"]))
        choices, r = [], None
        for i in range(len(model["layers_kept"])):
            p = layer_of(params, i)
            z = _normed(x, p["norm1"], eps=eps)
            x = _scaled(p["res1"], x, cca(p["mixer"], z, real, position,
                                          model))
            z = _normed(x, p["norm2"], eps=eps)
            f, r, choice = experts(p["ffn"], z, real, r, model)
            x = _scaled(p["res2"], x, f)
            choices.append(choice)
        if not every_position:
            x = x[:, -1]
        return tied_head(params, x, eps), np.stack(choices, 1).astype(
            np.int64)


# -- what a run served against what it should have ---------------------------------

def served_and_expected(config: dict, outcome, *, seed: int, root: str):
    """The sampled verdicts the run served and, for each, the reference's
    logits and routing on the window that customer must have had."""
    stream = outcome.stream
    length = int(config["serving"]["length"])
    _, rows, _ = table.make_table(int(config["table_rows"]), seed)
    customer, row_of = stream["customer"], stream["row"]
    which = sampled(customer, seed, int(config["reference"][
        "sample_records"]))
    with np.load(aux_path(root)) as kept:
        if not np.array_equal(kept["which"], which):
            raise ValueError("the deployment kept other rows' logits than "
                             "the reference samples")
        served = Served(logits=kept["logits"], proba=stream["proba"][which],
                        choice=kept["row_choice"].astype(np.int64),
                        model=config)
    hist, filled = histories(customer, row_of, rows, which, length,
                             preload_rows(config, seed))
    t_params = time.perf_counter()
    params = make_params(config)
    jax.block_until_ready(params)
    t_forward = time.perf_counter()
    logits, choice = [], []
    for lo in range(0, len(which), ROW_BLOCK):
        block, routing = forward(params, config, hist[lo:lo + ROW_BLOCK],
                                 filled[lo:lo + ROW_BLOCK])
        logits.append(np.asarray(block))
        choice.append(routing)
    layers, routed = len(config["layers_kept"]), int(
        config["num_experts_routed_over"])
    expect = {
        "logits": (np.concatenate(logits) if logits else np.zeros(
            (0, int(config["vocab_size"])), np.float32)),
        "choice": (np.concatenate(choice) if choice else np.zeros(
            (0, layers, routed), np.int64))}
    note = (f"{len(which)} of {len(customer)} served verdicts, window "
            f"records min {filled.min() if len(which) else 0} max "
            f"{filled.max() if len(which) else 0} of {length}, token-layers "
            f"skipped {int(served.choice[..., -1].sum())} reference "
            f"{int(expect['choice'][..., -1].sum())} of "
            f"{int(expect['choice'].sum())}, weights "
            f"{t_forward - t_params:.1f}s forward "
            f"{time.perf_counter() - t_forward:.1f}s")
    return served, expect, note


@dataclasses.dataclass
class Served:
    """What the run served for the sampled records; its length is the
    number of verdicts compared."""

    logits: np.ndarray  # (n, vocab) logits at the verdict position
    proba: np.ndarray  # (n,) the served probability
    choice: np.ndarray  # (n, layers, routed) tokens by routed output
    model: dict

    def __len__(self) -> int:
        return len(self.proba)


def compare(served: Served, expect: dict) -> dict:
    """Against the reference: ``mean_abs_dlogit`` and ``max_abs_dp`` of the
    verdict (the served probability against the reference's),
    ``max_abs_dlogit_slice`` over every logit at the verdict position,
    ``max_row_rms_dlogit_slice`` and ``mean_row_rms_dlogit_slice`` (a
    row's root mean square difference over those logits: the widest row,
    which keeps one wrong row in and one outlying logit out, and the mean
    over rows), and ``choice_rel_diff``: how far the served rows' routing
    is from the reference's routing of the same rows, as the least share
    of token-layers that chose otherwise (half the summed absolute
    difference of the counts by row, layer and routed output, the skip
    among them, over the token-layers). Not exact: the two hidden states
    differ by the served precision, so a token near a tie chooses otherwise
    (PERF.md has the readings). Against the run itself ``max_abs_dp_own``:
    the served probability, which the router acted on, against the
    sigmoid of the verdict logit of the logits kept for the same row,
    which came by the tap; rounding, unless the verdict is another
    row's."""
    model = served.model
    z_ref = np.asarray(verdict_logit(expect["logits"], model), np.float64)
    z = np.asarray(verdict_logit(served.logits, model), np.float64)
    p = np.asarray(served.proba, np.float64)
    d = np.asarray(served.logits, np.float64) - expect["logits"]
    row_rms = np.sqrt(np.mean(d * d, axis=-1))
    return {
        "mean_abs_dlogit": float(np.mean(np.abs(z - z_ref))),
        "max_abs_dp": float(np.max(np.abs(p - sigmoid(z_ref)))),
        "max_abs_dp_own": float(np.max(np.abs(p - sigmoid(z)))),
        "max_abs_dlogit_slice": float(np.max(np.abs(d))),
        "max_row_rms_dlogit_slice": float(np.max(row_rms)),
        "mean_row_rms_dlogit_slice": float(np.mean(row_rms)),
        "choice_rel_diff": float(
            np.abs(served.choice - expect["choice"]).sum()
            / max(1, 2 * int(expect["choice"].sum()))),
    }
