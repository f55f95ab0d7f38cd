"""The plain reference of the ``hybrid_moe`` family's third model (the
language model of Mistral-Small-4-119B-2603, ``model_type`` ``mistral4``,
as one chip of a four-chip expert-parallel group holds it), its weights,
and the comparison that decides ``correct`` for every cell that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
attention as a full (T, T) masked softmax a row at a time, the rotary on
neighbouring pairs where they stand, the experts one after another on the
tokens that chose each, the head in vocabulary blocks; no blocking of
queries, no tile loop, no scan over layers. Nothing here is imported from
the program. Every symbol is read from the configuration's own keys (the
published ``config.json`` names); what the config does not pin is listed
in the configuration file under ``assumed`` and marked (assumed) below.

Per token, x in R^``hidden_size``, pre-norm RMSNorm (``rms_norm_eps``),
plain residual adds, causal, all layers alike (``first_k_dense_replace``
0; ``layers_kept`` names the published layers this cut holds):
x <- x + MLA(RMSNorm(x)); x <- x + Experts(RMSNorm(x)).

*Tokens*: as ``hybrid_moe_f32`` (column j of a record is token j * ``bins``
+ its quantile bin; ``filled`` records of a window are real, the ones left
of them padding; positions count from a row's first real token).

*MLA.* c_q = RMSNorm(x W_dq) in R^``q_lora_rank``; q = c_q W_uq -> heads x
(``qk_nope_head_dim`` + ``qk_rope_head_dim``). [c, k_r] = x W_dkv in
R^(``kv_lora_rank`` + ``qk_rope_head_dim``); c <- RMSNorm(c); [k_n, v] = c
W_ukv -> heads x (``qk_nope_head_dim`` + ``v_head_dim``); k_r is one vector
for all heads. No norm on q_n, q_r, k_n, k_r (the config has no such key).
Rotary on q_r and k_r with interleaved pairs (dims 2i, 2i + 1:
``rope_interleave``), frequencies by YaRN (``rope_parameters``: theta
``rope_theta``, d = ``qk_rope_head_dim``, ``factor``, L0 =
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``): f_i =
theta^(-2i/d); low = floor(d ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
high = ceil(d ln(L0 / (beta_slow 2 pi)) / (2 ln theta)), both clipped to
[0, d/2 - 1]; ramp_i = clip((i - low) / (high - low), 0, 1); f'_i = ramp_i
f_i / factor + (1 - ramp_i) f_i. Cos and sin are scaled by m(``mscale``) /
m(``mscale_all_dim``), m(s) = 0.1 s ln(factor) + 1. Scores: softmax(sigma
(q_n k_n^T + q_r k_r^T)) over real keys at or before the query, sigma =
(nope + rope)^-0.5 x m(``mscale_all_dim``)^2 (the DeepSeek-V3 convention
for this key family: assumed). ``llama_4_scaling_beta``: q_t <- q_t (1 +
beta ln(1 + floor(t / L0))), which is 1 below position L0; computed here
at every length. out = [softmax v] W_o. No bias (``attention_bias``).

*Experts.* s = softmax(x W_r) over all ``num_experts_routed_over`` routed
experts (no ``scoring_func`` in the config: softmax assumed; with
``norm_topk_prob`` the same as a softmax over the chosen logits);
``n_group`` 1: no group limit; no expert bias (assumed); the
``num_experts_per_tok`` largest, an equal score to the lower index;
weights = s of the chosen over their sum, x ``routed_scaling_factor``.
Expert e: W_down,e (SiLU(x W_gate,e) * (x W_up,e)) of width
``moe_intermediate_size``; one shared expert of ``moe_intermediate_size``
x ``n_shared_experts`` for every token. **The share**: this chip holds the
experts ``experts_held``; a token's pairs with the others are left out and
the partial sum goes on. A padding token routes nowhere.

*Readout*: final RMSNorm, untied head (``tie_word_embeddings`` false) over
the vocabulary slice; the verdict is sigmoid(z_fraud - z_legit + c) at the
newest record's last token (``readout``, assumed). The vision tower is
left out.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import hybrid_moe_f32 as shared
from benchmark.reference import table
from benchmark.reference.cca_moe_f32 import (  # noqa: F401 - ``Served``
    # and ``compare`` are the comparison of every model that reports its
    # routing by row; the harness finds them on the module the
    # configuration names
    Served, _stacked_normal_bf16, compare)
from benchmark.reference.hybrid_moe_f32 import (  # noqa: F401 - the
    # deployment and the harness find these on the module the
    # configuration names
    aux_path, histories, miss_controls, preload_rows, sampled,
    verdict_logit)

F32 = jnp.float32
MASKED = -1e30
ROW_BLOCK = 11  # histories per block on the chip: activations beside 10.9 GB
VOCAB_BLOCK = 8192  # head columns multiplied at a time


def dims(model: dict) -> dict:
    """What ``_mla`` takes as static: widths, and the rotary's numbers."""
    rope = model["rope_parameters"]
    factor = float(rope["factor"])

    def m(scale: float) -> float:
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    nope, turned = int(model["qk_nope_head_dim"]), int(
        model["qk_rope_head_dim"])
    return {
        "heads": int(model["num_attention_heads"]), "nope": nope,
        "rope": turned, "vd": int(model["v_head_dim"]),
        "rank": int(model["kv_lora_rank"]),
        "eps": float(model["rms_norm_eps"]),
        "freq": yarn_frequencies(rope, turned),
        "turn_scale": m(float(rope["mscale"])) / m(float(
            rope["mscale_all_dim"])),
        "sigma": (nope + turned) ** -0.5 * m(float(
            rope["mscale_all_dim"])) ** 2,
        "l0": int(rope["original_max_position_embeddings"]),
        "beta": float(rope["llama_4_scaling_beta"]),
    }


def yarn_frequencies(rope: dict, d: int) -> tuple[float, ...]:
    """f'_i of the docstring, i < d / 2, in float64."""
    theta, l0 = float(rope["rope_theta"]), float(
        rope["original_max_position_embeddings"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)

    def dim_of(turns: float) -> float:
        return d * math.log(l0 / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = min(max(math.floor(dim_of(float(rope["beta_fast"]))), 0),
              d // 2 - 1)
    high = min(max(math.ceil(dim_of(float(rope["beta_slow"]))), 0),
               d // 2 - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return tuple(ramp * f / float(rope["factor"]) + (1.0 - ramp) * f)


# -- weights -------------------------------------------------------------------

def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes. Matrices
    are normal with variance 1/fan-in, stored bfloat16 (their values exact
    in it, so the program and the reference read the same numbers); the
    embedding has variance 1, the untied head 1/``hidden_size``; norm
    weights are float32, 1 + 0.1 n, none of them zero, so that a term left
    out shows. ``layer_stack`` ``scanned``: every leaf under ``layers``
    carries the kept layers on its leading axis; ``listed``: a list of one
    tree a layer, the same values."""
    d = int(model["hidden_size"])
    m = dims(model)
    heads, nope, rope, vd = m["heads"], m["nope"], m["rope"], m["vd"]
    q_rank, rank = int(model["q_lora_rank"]), m["rank"]
    routed = int(model["num_experts_routed_over"])
    held = int(model["experts_held"]["count"])
    width = int(model["moe_intermediate_size"])
    vocab = int(model["vocab_size"])
    n = len(model["layers_kept"])
    stacked = model["layer_stack"] == "scanned"
    root = jax.random.key(int(model["weights_seed"]) % (2 ** 31), impl="rbg")
    counter = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(root, next(counter))

    def dense(fan_in: int, *shape: int):
        scale = 1.0 / math.sqrt(fan_in)
        if stacked:
            return _stacked_normal_bf16(key(), n, shape, scale)
        return [shared._normal_bf16(k, shape, scale)
                for k in jax.random.split(key(), n)]

    def vec(*shape: int):
        out = 1.0 + 0.1 * jax.random.normal(key(), (n, *shape), F32)
        return out if stacked else list(out)

    def swiglu(wide: int, *lead: int):
        return {"gate": dense(d, *lead, d, wide),
                "up": dense(d, *lead, d, wide),
                "down": dense(wide, *lead, wide, d)}

    mixer = {"wdq": dense(d, d, q_rank), "q_norm": vec(q_rank),
             "wuq": dense(q_rank, q_rank, heads * (nope + rope)),
             "wdkv": dense(d, d, rank + rope), "c_norm": vec(rank),
             "wukv": dense(rank, rank, heads * (nope + vd)),
             "wo": dense(heads * vd, heads * vd, d)}
    ffn = {"router": dense(d, d, routed),
           "experts": swiglu(width, held),
           "shared": swiglu(width * int(model["n_shared_experts"]))}
    layers = {"norm1": vec(d), "mixer": mixer, "norm2": vec(d), "ffn": ffn}
    if not stacked:
        layers = [jax.tree.map(lambda leaf: leaf[i], layers,
                               is_leaf=lambda x: isinstance(x, list))
                  for i in range(n)]
    return {"edges": jnp.asarray(shared.quantile_edges(model)),
            "embed": shared._normal_bf16(key(), (vocab, d), 1.0),
            "layers": layers,
            "final_norm": 1.0 + 0.1 * jax.random.normal(key(), (d,), F32),
            "head": shared._normal_bf16(key(), (d, vocab),
                                        1.0 / math.sqrt(d))}


def layer_of(params: dict, i: int) -> dict:
    """Kept layer ``i``, of a stacked tree or of a list."""
    layers = params["layers"]
    if isinstance(layers, list):
        return layers[i]
    return jax.tree.map(lambda a: a[i], layers)


# -- pieces, each on float32 ------------------------------------------------------

_f32 = shared._f32
rms_norm = shared.rms_norm


def rotary_interleaved(t, position, freq, scale: float):
    """``t`` (n, T, [heads,] width) turned by its ``position`` (n, T):
    dims 2i and 2i + 1 are pair i, and stay where they are."""
    angle = position.astype(F32)[..., None] * jnp.asarray(freq, F32)
    if t.ndim == 4:
        angle = angle[:, :, None, :]
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    a, b = t[..., 0::2], t[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        t.shape)


def mla(p: dict, x, real, position, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d)."""
    return _mla(p, x, real, position, **dims(model))


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "rank", "eps", "freq", "turn_scale",
    "sigma", "l0", "beta"))
def _mla(p: dict, x, real, position, *, heads: int, nope: int, rope: int,
         vd: int, rank: int, eps: float, freq: tuple, turn_scale: float,
         sigma: float, l0: int, beta: float):
    n, length, _ = x.shape
    c_q = rms_norm(x @ _f32(p["wdq"]), p["q_norm"], eps)
    q = (c_q @ _f32(p["wuq"])).reshape(n, length, heads, nope + rope)
    down = x @ _f32(p["wdkv"])
    c = rms_norm(down[..., :rank], p["c_norm"], eps)
    up = (c @ _f32(p["wukv"])).reshape(n, length, heads, nope + vd)
    grow = 1.0 + beta * jnp.log1p(jnp.floor(position.astype(F32) / l0))
    q_n = q[..., :nope] * grow[:, :, None, None]
    q_r = rotary_interleaved(q[..., nope:], position, freq,
                             turn_scale) * grow[:, :, None, None]
    k_n, v = up[..., :nope], up[..., nope:]
    k_r = rotary_interleaved(down[..., rank:], position, freq, turn_scale)
    at = jnp.arange(length)

    def one_row(row):  # a row at a time: heads x T^2 scores each
        q_n1, q_r1, k_n1, k_r1, v1, real1 = row
        scores = (jnp.einsum("qhd,khd->hqk", q_n1, k_n1)
                  + jnp.einsum("qhd,kd->hqk", q_r1, k_r1)) * sigma
        allowed = real1[None, None, :] & (at[None, :] <= at[:, None])[None]
        weights = jax.nn.softmax(jnp.where(allowed, scores, MASKED), axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v1)

    o = jax.lax.map(one_row, (q_n, q_r, k_n, k_r, v, real))
    return o.reshape(n, length, heads * vd) @ _f32(p["wo"])


def route(p: dict, x, real, model: dict):
    """``(experts (tokens, k), weights (tokens, k), s (tokens, routed))``
    over all the published experts; a padding token's weights are zero and
    its experts -1."""
    return _route(p["router"], x, real,
                  per_token=int(model["num_experts_per_tok"]),
                  scale=float(model["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("per_token", "scale"))
def _route(router, x, real, *, per_token: int, scale: float):
    s = jax.nn.softmax(x @ _f32(router), axis=-1)
    chosen = jnp.argsort(-s, axis=-1, stable=True)[:, :per_token]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scale
    return (jnp.where(real[:, None], chosen, -1),
            jnp.where(real[:, None], w, 0.0), s)


def experts(p: dict, z, real, model: dict):
    """``(f, choice)``: the shared expert plus the held experts' part of
    the sublayer (n, T, d), and how many of each row's (token, slot) pairs
    chose each routed expert (n, routed)."""
    first, held = shared.held_range(model)
    routed = int(model["num_experts_routed_over"])
    shape = z.shape
    flat = z.reshape(-1, shape[-1])
    chosen, w, _ = route(p, flat, real.reshape(-1), model)
    picked = np.asarray(chosen)
    here = (picked >= first) & (picked < first + held)
    counts = np.bincount(picked[here] - first, minlength=held)
    room = min(len(flat), 1 << max(8, (max(int(counts.max()), 1)
                                        - 1).bit_length()))
    f = shared._dense(p["shared"], flat) + shared._held_experts(
        p["experts"], flat, chosen, w, jnp.arange(held), room=room,
        first=first)
    per_row = picked.reshape(shape[0], -1)
    choice = np.stack([np.bincount(row[row >= 0], minlength=routed)
                       for row in per_row])
    return f.reshape(shape), choice


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, norm, *, eps: float):
    return rms_norm(x, norm, eps)


@jax.jit
def _head_block(x, columns):
    return x @ _f32(columns)


def head(params: dict, x, eps: float):
    """RMSNorm(x) W_head over the vocabulary slice, some columns at a
    time."""
    x = _final(x, params["final_norm"], eps=eps)
    w = params["head"]
    return jnp.concatenate([
        _head_block(x, w[:, lo:lo + VOCAB_BLOCK])
        for lo in range(0, w.shape[1], VOCAB_BLOCK)], -1)


_normed = shared._normed


def forward(params: dict, model: dict, hist, filled, *,
            every_position: bool = False):
    """``(logits, choice)``: the slice logits at the newest record's last
    token (n, vocab), or at every position (n, tokens, vocab), and each
    row's count of chosen pairs by routed expert in every kept layer (n,
    layers, routed)."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["rms_norm_eps"])
        x, real, position = shared._embed(
            params["edges"], params["embed"], jnp.asarray(hist, F32),
            jnp.asarray(filled, jnp.int32), bins=int(model["bins"]))
        choices = []
        for i in range(len(model["layers_kept"])):
            p = layer_of(params, i)
            z = _normed(x, p["norm1"], eps=eps)
            x = x + mla(p["mixer"], z, real, position, model)
            z = _normed(x, p["norm2"], eps=eps)
            f, choice = experts(p["ffn"], z, real, model)
            x = x + f
            choices.append(choice)
        if not every_position:
            x = x[:, -1]
        return head(params, x, eps), np.stack(choices, 1).astype(np.int64)


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
    first, held = shared.held_range(config)
    note = (f"{len(which)} of {len(customer)} served verdicts, window "
            f"records min {filled.min() if len(which) else 0} max "
            f"{filled.max() if len(which) else 0} of {length}, pairs held "
            f"{int(served.choice[..., first:first + held].sum())} reference "
            f"{int(expect['choice'][..., first:first + held].sum())} of "
            f"{int(expect['choice'].sum())} chosen, weights "
            f"{t_forward - t_params:.1f}s forward "
            f"{time.perf_counter() - t_forward:.1f}s")
    return served, expect, note
