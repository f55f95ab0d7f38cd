"""The plain reference of the ``hybrid_moe`` history family (the language
model of Ling-3.0-flash-VL as one chip of an expert-parallel group holds
it), its weights, and the comparison that decides ``correct`` for every
cell that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the gated delta rule token by token, attention as a full masked softmax,
the experts one after another on the tokens routed to each; no chunking,
no kernels, no cache. Nothing here is imported from the program. The
model's settings are read from the configuration's own keys (the published
``config.json`` names); what the config does not pin is listed in the
configuration file under ``assumed`` and marked (assumed) below.

Per token, x in R^hidden, pre-norm RMSNorm (``rms_norm_eps``) before each
mixer and each feed-forward, residual adds, causal throughout. Layer i of
the published stack is MLA where (i + 1) % ``layer_group_size`` == 0 and
KDA otherwise; layers below ``first_k_dense_replace`` have a dense SwiGLU
of ``intermediate_size``, the others the expert layer (``layers_kept``
names the published layers this cut holds).

*Tokens.* Column j of a record is token id j * ``bins`` + (number of that
column's ``bins`` - 1 quantile edges below the value): 30 tokens a record,
``history_length`` records a window; ``filled`` records of a window are
real, the ones left of them padding. Positions count from a row's first
real token.

*KDA* (``num_attention_heads`` heads of ``head_dim``; d_k = d_v): q, k, v =
SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v)) (``linear_silu``)
with a causal depthwise convolution of ``short_conv_kernel_size`` taps, the
last tap on the current token (assumed), no bias (assumed); q and k divided
by their L2 norm per head (``use_qk_norm``), q times d_k^-0.5; g =
``kda_lower_bound`` * sigmoid(exp(A_log) * (x W_g + dt_bias)) per channel
(``kda_safe_gate``; W_g full rank: ``no_kda_lora``; A_log one per head,
dt_bias one per channel: assumed), alpha = exp(g); beta = sigmoid(x W_b)
per head. Per head, S in R^(d_k x d_v):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

out = W_o [RMSNorm_head(o_t) * sigmoid(x W_og)], one gate scalar per head
(``gated_attention_proj_granularity_type`` head_wise; ``group_norm_size``
1: the norm is per head, its weight shared by the heads: assumed). A
padding token has beta = 0, alpha = 1 and sends zeros into the
convolution, so the state passes it unchanged.

*MLA*: q = x W_q -> heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim``)
(``q_lora_rank`` null); [c, k_r] = x W_dkv -> ``kv_lora_rank`` +
``qk_rope_head_dim``; c = RMSNorm(c); [k_n, v] = c W_ukv -> heads x
(``qk_nope_head_dim`` + ``v_head_dim``); q_n, q_r, k_n and k_r each
RMS-normalised over their own width (``use_qk_norm``; where the norms sit:
assumed, chosen so that k_r stays one vector for all heads); rotary
(``rope_theta``, halves rotated: assumed) on q_r and k_r; softmax((q_n
k_n^T + q_r k_r^T) / sqrt(nope + rope)) v over real keys at or before the
query; W_o.

*Experts*: s = sigmoid(x W_r) over all ``num_experts_routed_over``
experts (``score_function``); chosen on s + b
(``moe_router_enable_expert_bias``): ``n_group`` groups, a group's score
the sum of its two largest, ``topk_group`` groups kept (the others take
-inf: assumed), the ``num_experts_per_tok`` largest among them; weights =
s of the chosen, summing to 1 (``norm_topk_prob``), times
``routed_scaling_factor``; expert e gives W_down,e (SiLU(x W_gate,e) * (x
W_up,e)) of width ``moe_intermediate_size``; one shared expert of
``moe_shared_expert_intermediate_size`` for every token. **The share**:
this chip holds the experts ``experts_held`` (``first`` .. ``first`` +
``count``); a token's pairs with other experts are left out and the
partial sum goes on. A padding token routes nowhere.

*Readout*: final RMSNorm, untied head over the vocabulary slice; the
verdict is sigmoid(z_fraud - z_legit + c) at the newest record's last
token (``readout``: the two answer ids and the constant c, assumed).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import table
from benchmark.reference.mlp_f32 import sigmoid

F32 = jnp.float32
BF16 = jnp.bfloat16
L2_EPS = 1e-6
MASKED = -1e30
ROUTER_BIAS_SCALE = 0.02
ROW_BLOCK = 11  # histories per block on the chip: activations beside 10 GB


# -- the stack ---------------------------------------------------------------

def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of every layer this cut keeps."""
    period, dense = int(model["layer_group_size"]), int(
        model["first_k_dense_replace"])
    return [("mla" if (i + 1) % period == 0 else "kda",
             "dense" if i < dense else "moe")
            for i in model["layers_kept"]]


def held_range(model: dict) -> tuple[int, int]:
    held = model["experts_held"]
    return int(held["first"]), int(held["count"])


# -- weights -------------------------------------------------------------------

def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes. Matrices
    are normal with variance 1/fan-in and stored bfloat16 (so their values
    are exact in it and the program and the reference read the same
    numbers); norm weights, A_log, dt_bias, the router's bias and the
    quantile edges are small float32 vectors, none of them zero, so that a
    term left out shows."""
    d = int(model["hidden_size"])
    heads, hd = int(model["num_attention_heads"]), int(model["head_dim"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    vd, rank = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    taps = int(model["short_conv_kernel_size"])
    vocab = int(model["vocab_size"])
    _, held = held_range(model)
    routed = int(model["num_experts_routed_over"])
    # the device's own bit generator: five billion values from the default
    # one take most of a minute on the chip; the draw is the same for the
    # program and the reference of one run, which is all that is asked of it
    root = jax.random.key(int(model["weights_seed"]) % (2 ** 31), impl="rbg")
    counter = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(root, next(counter))

    def dense(fan_in: int, *shape: int):
        return _normal_bf16(key(), shape, 1.0 / math.sqrt(fan_in))

    def vec(n: int, mean: float, spread: float):
        return mean + spread * jax.random.normal(key(), (n,), F32)

    def swiglu(width: int, *lead: int):
        return {"gate": dense(d, *lead, d, width),
                "up": dense(d, *lead, d, width),
                "down": dense(width, *lead, width, d)}

    layers = []
    for mixer, ffn in layer_kinds(model):
        if mixer == "kda":
            mix = {"wq": dense(d, d, heads * hd), "wk": dense(d, d, heads * hd),
                   "wv": dense(d, d, heads * hd), "wg": dense(d, d, heads * hd),
                   "wb": dense(d, d, heads), "wog": dense(d, d, heads),
                   "wo": dense(heads * hd, heads * hd, d),
                   "conv_q": _conv_taps(key(), taps, heads * hd),
                   "conv_k": _conv_taps(key(), taps, heads * hd),
                   "conv_v": _conv_taps(key(), taps, heads * hd),
                   "a_log": vec(heads, 0.0, 0.5),
                   # a slow gate, as a trained one is: g near -5 sigmoid(-4)
                   # = -0.09 a token, channels from a few tokens' memory
                   # to a few hundred
                   "dt_bias": vec(heads * hd, -4.0, 1.0),
                   "o_norm": vec(hd, 1.0, 0.1)}
        else:
            mix = {"wq": dense(d, d, heads * (nope + rope)),
                   "wdkv": dense(d, d, rank + rope),
                   "wukv": dense(rank, rank, heads * (nope + vd)),
                   "wo": dense(heads * vd, heads * vd, d),
                   "c_norm": vec(rank, 1.0, 0.1),
                   "qn_norm": vec(nope, 1.0, 0.1), "qr_norm": vec(rope, 1.0, 0.1),
                   "kn_norm": vec(nope, 1.0, 0.1), "kr_norm": vec(rope, 1.0, 0.1)}
        if ffn == "dense":
            ff = swiglu(int(model["intermediate_size"]))
        else:
            ff = {"router": dense(d, d, routed),
                  "bias": vec(routed, 0.0, ROUTER_BIAS_SCALE),
                  "experts": swiglu(int(model["moe_intermediate_size"]), held),
                  "shared": swiglu(
                      int(model["moe_shared_expert_intermediate_size"]))}
        layers.append({"norm1": vec(d, 1.0, 0.1), "mixer": mix,
                       "norm2": vec(d, 1.0, 0.1), "ffn": ff})
    return {"edges": jnp.asarray(quantile_edges(model)),
            "embed": dense(1, vocab, d), "layers": layers,
            "final_norm": vec(d, 1.0, 0.1), "head": dense(d, d, vocab)}


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _normal_bf16(key, shape: tuple, scale: float):
    return (jax.random.normal(key, shape, BF16)
            * jnp.asarray(scale, BF16)).astype(BF16)


def _conv_taps(key, taps: int, channels: int):
    """The newest tap near 1 and the older ones smaller, as a trained
    short convolution looks; float32."""
    w = 0.3 * jax.random.normal(key, (taps, channels), F32)
    return w.at[-1].add(1.0)


def quantile_edges(model: dict) -> np.ndarray:
    """(columns, bins - 1) float32: each column's quantile edges over a
    seeded table of the ``weights_seed`` (the checkpoint's own tokeniser,
    as the ``seq`` family's standardiser is its checkpoint's)."""
    rows, _ = table.surrogate_rows(8192, int(model["weights_seed"]))
    bins = int(model["bins"])
    q = np.arange(1, bins, dtype=np.float64) / bins
    return np.quantile(rows.astype(np.float64), q, axis=0).T.astype(
        np.float32)


# -- pieces, each on float32 ------------------------------------------------------

def _f32(w):
    return jnp.asarray(w).astype(F32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def tokenise(edges, hist, bins: int):
    """(n, L, F) records -> (n, L * F) token ids."""
    n, length, cols = hist.shape
    find = jax.vmap(lambda e, v: jnp.searchsorted(e, v, side="left"),
                    in_axes=(0, 2), out_axes=2)
    ids = find(edges, hist) + jnp.arange(cols) * bins
    return ids.reshape(n, length * cols).astype(jnp.int32)


def real_tokens(filled, length: int, cols: int):
    """(n, L * F) bool: the tokens of a row's ``filled`` newest records."""
    record = jnp.arange(length * cols) // cols
    return record[None, :] >= (length - filled)[:, None]


def swiglu(p: dict, x):
    return (jax.nn.silu(x @ _f32(p["gate"])) * (x @ _f32(p["up"]))) @ _f32(
        p["down"])


def short_conv(u, taps):
    """Causal depthwise convolution along axis 1: y_t = sum_j taps[j] *
    u_(t - (K - 1) + j), zeros before the sequence's start."""
    k = taps.shape[0]
    length = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + length] * taps[j] for j in range(k))


def kda(p: dict, x, real, model: dict):
    """The gated delta rule, one token at a time."""
    return _kda(p, x, real, heads=int(model["num_attention_heads"]),
                hd=int(model["head_dim"]),
                bound=float(model["kda_lower_bound"]),
                eps=float(model["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("heads", "hd", "bound", "eps"))
def _kda(p: dict, x, real, *, heads: int, hd: int, bound: float, eps: float):
    n, length, _ = x.shape
    keep = real[..., None].astype(F32)

    def branch(w, taps):
        y = jax.nn.silu(short_conv((x @ _f32(w)) * keep, taps))
        return y.reshape(n, length, heads, hd)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    q = unit(branch(p["wq"], p["conv_q"])) * hd ** -0.5
    k = unit(branch(p["wk"], p["conv_k"]))
    v = branch(p["wv"], p["conv_v"])
    raw = (x @ _f32(p["wg"]) + p["dt_bias"]).reshape(n, length, heads, hd)
    g = bound * jax.nn.sigmoid(jnp.exp(p["a_log"])[:, None] * raw)
    g = g * keep[..., None]
    beta = jax.nn.sigmoid(x @ _f32(p["wb"])) * keep

    def step(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("nhk,nhkv->nhv", k_t, state)
        state = state + (b_t[..., None, None] * k_t[..., None]
                         * (v_t - seen)[..., None, :])
        return state, jnp.einsum("nhk,nhkv->nhv", q_t, state)

    first = jnp.zeros((n, heads, hd, hd), F32)
    _, o = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)  # (n, length, heads, hd)
    o = rms_norm(o, p["o_norm"], eps)
    o = o * jax.nn.sigmoid(x @ _f32(p["wog"]))[..., None]
    return o.reshape(n, length, heads * hd) @ _f32(p["wo"])


def rotary(t, position, theta: float):
    """``t`` (..., length, [heads,] width) turned by its ``position``
    (n, length): the two halves of the width are the pairs."""
    half = t.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = position.astype(F32)[..., None] * freq
    if t.ndim == 4:
        angle = angle[:, :, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def mla(p: dict, x, real, position, model: dict):
    return _mla(p, x, real, position,
                heads=int(model["num_attention_heads"]),
                nope=int(model["qk_nope_head_dim"]),
                rope=int(model["qk_rope_head_dim"]),
                vd=int(model["v_head_dim"]), rank=int(model["kv_lora_rank"]),
                eps=float(model["rms_norm_eps"]),
                theta=float(model["rope_theta"]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "rank", "eps", "theta"))
def _mla(p: dict, x, real, position, *, heads: int, nope: int,
         rope: int, vd: int, rank: int, eps: float, theta: float):
    n, length, _ = x.shape
    q = (x @ _f32(p["wq"])).reshape(n, length, heads, nope + rope)
    down = x @ _f32(p["wdkv"])
    c = rms_norm(down[..., :rank], p["c_norm"], eps)
    up = (c @ _f32(p["wukv"])).reshape(n, length, heads, nope + vd)
    q_n = rms_norm(q[..., :nope], p["qn_norm"], eps)
    q_r = rotary(rms_norm(q[..., nope:], p["qr_norm"], eps), position, theta)
    k_n = rms_norm(up[..., :nope], p["kn_norm"], eps)
    k_r = rotary(rms_norm(down[..., rank:], p["kr_norm"], eps), position,
                 theta)
    v = up[..., nope:]
    at = jnp.arange(length)

    def one_row(row):  # a row at a time: heads x length^2 scores each
        q_n1, q_r1, k_n1, k_r1, v1, real1 = row
        scores = (jnp.einsum("qhd,khd->hqk", q_n1, k_n1)
                  + jnp.einsum("qhd,kd->hqk", q_r1, k_r1)) / math.sqrt(
                      nope + rope)
        allowed = real1[None, None, :] & (at[None, :] <= at[:, None])[None]
        weights = jax.nn.softmax(jnp.where(allowed, scores, MASKED), axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v1)

    o = jax.lax.map(one_row, (q_n, q_r, k_n, k_r, v, real))
    return o.reshape(n, length, heads * vd) @ _f32(p["wo"])


def route(p: dict, x, real, model: dict):
    """``(experts (tokens, k), weights (tokens, k))`` over all the
    published experts; a padding token's weights are zero and its experts
    -1."""
    return _route(p["router"], p["bias"], x, real,
                  routed=int(model["num_experts_routed_over"]),
                  groups=int(model["n_group"]), kept=int(model["topk_group"]),
                  per_token=int(model["num_experts_per_tok"]),
                  scale=float(model["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=(
    "routed", "groups", "kept", "per_token", "scale"))
def _route(router, bias, x, real, *, routed: int, groups: int, kept: int,
           per_token: int, scale: float):
    p = {"router": router, "bias": bias}
    s = jax.nn.sigmoid(x @ _f32(p["router"]))
    choice = s + p["bias"]
    by_group = choice.reshape(-1, groups, routed // groups)
    best_two = -jnp.sort(-by_group, axis=-1)[..., :2]
    group_rank = jnp.argsort(jnp.argsort(-best_two.sum(-1), axis=-1), axis=-1)
    open_ = jnp.repeat(group_rank < kept, routed // groups, axis=-1)
    chosen = jnp.argsort(-jnp.where(open_, choice, -jnp.inf),
                         axis=-1)[:, :per_token]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scale
    return (jnp.where(real[:, None], chosen, -1),
            jnp.where(real[:, None], w, 0.0))


@functools.partial(jax.jit, static_argnames=("room", "first"))
def _held_experts(experts: dict, x, chosen, w, which, *, room: int,
                  first: int):
    """The experts ``which`` (held ids; -1: none) one after another, each
    on the token rows that chose it (``room`` rows: the expert's own
    first, rows of weight 0 behind them), its result added back at those
    rows."""
    def one(i, y):
        e = which[i]

        def run(y):
            weight = jnp.where(chosen == first + e, w, 0.0).sum(-1)
            rows = jnp.argsort(weight == 0.0, stable=True)[:room]
            part = swiglu({k: v[e] for k, v in experts.items()}, x[rows])
            return y.at[rows].add(part * weight[rows][:, None])

        return jax.lax.cond(e >= 0, run, lambda y: y, y)

    return jax.lax.fori_loop(0, len(which), one, jnp.zeros_like(x))


BUSY_EXPERT = 1024  # pairs; an expert above it is run with more room


def moe(p: dict, x, real, model: dict):
    """``(y, pairs)``: the shared expert plus this share's experts' part,
    and how many (token, held expert) pairs that was. Routing with random
    weights is uneven (a busiest expert at 15 times the mean), so the few
    busy experts are run apart from the rest, with the room they need."""
    first, held = held_range(model)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, x, real.reshape(-1), model)
    here = (chosen >= first) & (chosen < first + held)
    counts = np.bincount(np.asarray(chosen[here]) - first, minlength=held)
    y = _dense(p["shared"], x)
    ids = np.arange(held)
    for busy in (False, True):
        mine = (counts > BUSY_EXPERT) == busy
        if not mine.any():
            continue
        most = int(counts[mine].max())
        room = min(len(x), 1 << max(8, (max(most, 1) - 1).bit_length()))
        y = y + _held_experts(
            p["experts"], x, chosen, w, jnp.asarray(np.where(mine, ids, -1)),
            room=room, first=first)
    return y.reshape(shape), int(counts.sum())


@functools.partial(jax.jit, static_argnames=("bins",))
def _embed(edges, embed, hist, filled, *, bins: int):
    """``(x, real, position)`` of the windows' tokens."""
    n, length, cols = hist.shape
    ids = tokenise(edges, hist, bins)
    real = real_tokens(filled, length, cols)
    position = jnp.maximum(
        jnp.arange(length * cols)[None, :]
        - ((length - filled) * cols)[:, None], 0)
    return _f32(embed[ids]), real, position


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, *, eps: float):
    return rms_norm(x, weight, eps)


_dense = jax.jit(swiglu)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps: float):
    return rms_norm(x, norm, eps) @ _f32(head)


def forward(params: dict, model: dict, hist, filled, *,
            every_position: bool = False):
    """``(logits, pairs)``: the slice logits at the newest record's last
    token (n, vocab), or at every position (n, tokens, vocab), and the
    (token, held expert) pairs of every expert layer (layers,)."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["rms_norm_eps"])
        x, real, position = _embed(
            params["edges"], params["embed"], jnp.asarray(hist, F32),
            jnp.asarray(filled, jnp.int32), bins=int(model["bins"]))
        pairs = []
        for (mixer, ffn), p in zip(layer_kinds(model), params["layers"]):
            z = _normed(x, p["norm1"], eps=eps)
            if mixer == "kda":
                x = x + kda(p["mixer"], z, real, model)
            else:
                x = x + mla(p["mixer"], z, real, position, model)
            z = _normed(x, p["norm2"], eps=eps)
            if ffn == "dense":
                x = x + _dense(p["ffn"], z)
            else:
                y, served = moe(p["ffn"], z, real, model)
                x = x + y
                pairs.append(served)
        if not every_position:
            x = x[:, -1]
        return (_head(x, params["final_norm"], params["head"], eps=eps),
                np.asarray(pairs, np.int64))


def verdict_logit(logits, model: dict):
    """z_fraud - z_legit + c from slice logits (..., vocab)."""
    r = model["readout"]
    return (logits[..., int(r["fraud_id"])] - logits[..., int(r["legit_id"])]
            + float(r["shift"]))


# -- what a run served against what it should have ---------------------------------

def histories(customer: np.ndarray, row_of: np.ndarray, rows: np.ndarray,
              which: np.ndarray, length: int, preload: np.ndarray
              ) -> tuple[np.ndarray, np.ndarray]:
    """The windows of the served records ``which`` (positions in
    consumption order) and how many of each window's records are real:
    customer c started with the table rows ``preload[c]`` (oldest first;
    -1 where it held fewer) and every consumed record of c was appended."""
    out = np.zeros((len(which), length, rows.shape[1]), np.float32)
    filled = np.zeros(len(which), np.int32)
    for j, i in enumerate(which):
        c = customer[i]
        mine = np.flatnonzero(customer[:i + 1] == c)
        before = preload[c][preload[c] >= 0]
        seq = np.r_[before, row_of[mine]][-length:]
        out[j, length - len(seq):] = rows[seq]
        filled[j] = len(seq)
    return out, filled


def preload_rows(config: dict, seed: int) -> np.ndarray:
    """(customers, length) table rows every customer's ring holds before
    the first record, oldest first: a function of ``--seed`` alone."""
    pre = config["preload"]
    rng = np.random.default_rng([int(seed), 0x9E10AD])
    return rng.integers(0, int(config["table_rows"]), size=(
        int(pre["customers"]), int(pre["records"])), dtype=np.int64)


AUX_FILE = "served_aux.npz"


def aux_path(root: str) -> str:
    """Where a run's deployment leaves the sampled rows' slice logits and
    pair counts (the generator's stream carries probabilities only): in
    the run's work directory, by this process's id, as the capture is."""
    return os.path.join(root, ".benchwork", f"run_{os.getpid()}", AUX_FILE)


def sampled(customer: np.ndarray, seed: int, take: int) -> np.ndarray:
    """Which served verdicts are compared (positions in consumption
    order): ``take`` drawn from the seed, and the newest verdict of the
    customer with the most records."""
    n = len(customer)
    if n == 0:
        return np.zeros(0, np.int64)
    rng = np.random.default_rng([int(seed), 0x5A3])
    which = rng.choice(n, size=min(int(take), n), replace=False)
    busiest = np.flatnonzero(customer == np.bincount(customer).argmax())
    return np.unique(np.r_[which, busiest[-1]])


def served_and_expected(config: dict, outcome, *, seed: int, root: str):
    """The sampled verdicts the run served and, for each, the reference's
    slice logits on the window that customer must have had."""
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
                        pairs=int(kept["row_pairs"].sum()), model=config)
    hist, filled = histories(customer, row_of, rows, which, length,
                             preload_rows(config, seed))
    t_params = time.perf_counter()
    params = make_params(config)
    jax.block_until_ready(params)
    t_forward = time.perf_counter()
    logits, pairs = [], 0
    for lo in range(0, len(which), ROW_BLOCK):
        block, layer_pairs = forward(params, config, hist[lo:lo + ROW_BLOCK],
                                     filled[lo:lo + ROW_BLOCK])
        logits.append(np.asarray(block))
        pairs += int(layer_pairs.sum())
    expect = {"logits": (np.concatenate(logits) if logits else np.zeros(
        (0, int(config["vocab_size"])), np.float32)), "pairs": pairs}
    note = (f"{len(which)} of {len(customer)} served verdicts, window "
            f"records min {filled.min() if len(which) else 0} max "
            f"{filled.max() if len(which) else 0} of {length}, pairs served "
            f"{served.pairs} reference {expect['pairs']}, weights "
            f"{t_forward - t_params:.1f}s forward "
            f"{time.perf_counter() - t_forward:.1f}s")
    return served, expect, note


@dataclasses.dataclass
class Served:
    """What the run served for the sampled records; its length is the
    number of verdicts compared."""

    logits: np.ndarray  # (n, vocab) slice logits at the verdict position
    proba: np.ndarray  # (n,) the served probability
    pairs: int  # (token, held expert) pairs the program served for them
    model: dict

    def __len__(self) -> int:
        return len(self.proba)


def miss_controls(served, expect: dict) -> dict:
    """The comparison's own controls, ``{label: (served, expect)}``, for
    ``check_outputs`` to run ``compare`` on and print beside the run's
    numbers (they decide nothing): ``rolled`` holds served row i against
    the expectation for row i + 1, what a misordered answer is, and
    ``proba_rolled`` hands row i the verdict served for row i + 1 over
    its own logits, an altered answer. Whatever the expectation holds as
    one number for all rows stays as it is."""
    rolled = {k: np.roll(v, -1, axis=0) if isinstance(v, np.ndarray) else v
              for k, v in expect.items()}
    return {"rolled": (served, rolled),
            "proba_rolled": (dataclasses.replace(
                served, proba=np.roll(served.proba, -1)), expect)}


def compare(served: Served, expect: dict) -> dict:
    """``mean_abs_dlogit`` and ``max_abs_dp`` of the verdict (the served
    probability against the reference's), ``max_abs_dlogit_slice`` over
    every slice logit at the verdict position, and how far the served
    rows' count of (token, held expert) pairs is from the reference's
    routing of the same rows, as a share of it (the two hidden states
    differ by the served precision, so a token near a tie chooses
    otherwise: not exact, PERF.md has the readings)."""
    model = served.model
    z_ref = np.asarray(verdict_logit(expect["logits"], model), np.float64)
    z = np.asarray(verdict_logit(served.logits, model), np.float64)
    p = np.asarray(served.proba, np.float64)
    return {
        "mean_abs_dlogit": float(np.mean(np.abs(z - z_ref))),
        "max_abs_dp": float(np.max(np.abs(p - sigmoid(z_ref)))),
        "max_abs_dlogit_slice": float(np.max(np.abs(
            np.asarray(served.logits, np.float64) - expect["logits"]))),
        "pairs_rel_diff": abs(served.pairs - expect["pairs"]) / max(
            1, expect["pairs"]),
    }
