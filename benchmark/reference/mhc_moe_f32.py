"""The plain reference of the ``hybrid_moe`` family's fourth model (the
language model of Xing4.0-29B-A4B, ``model_type`` ``xing4_0``, every layer
whole on its chip: ``ep_size`` 1), its weights, and the comparison that
decides ``correct`` for every cell that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the residual streams as they are written down below, attention as a full
(T, T) masked softmax a row at a time, the experts one after another on
the tokens that chose each, the head in vocabulary blocks; no kernels, no
tiles, no scan over layers. Nothing here is imported from the program.
Every symbol is read from the configuration's own keys (the published
``config.json`` names); what the config does not pin is listed in the
configuration file under ``assumed`` and marked (assumed) below.

*Tokens*: as ``hybrid_moe_f32`` (column j of a record is token j * ``bins``
+ its quantile bin; ``filled`` records of a window are real, the ones left
of them padding; positions count from a row's first real token).

*The residual path* (manifold-constrained hyper-connections; n =
``hc_mult``, C = ``hidden_size``). A token's state between sublayers is X
in R^(n x C); X_0 is the embedding in all n streams (assumed). A layer is
two sublayers F: the mixer with its pre-norm, then the feed-forward with
its pre-norm (RMSNorm, ``rms_norm_eps``, a weight each: ``norm1``,
``norm2``), each with maps of its own (``res1``, ``res2``: ``phi`` (n C x
(2 n + n^2)), ``alpha`` (3,), ``b`` (2 n + n^2,)):

    u       = vec(X) / sqrt(mean(vec(X)^2) + ``rms_norm_eps``)   (no weight:
              one would fold into phi's rows; which eps: assumed)
    [p, q, R] = u phi, split n | n | n^2, R read row by row (n x n)
    h_pre   = sigmoid(alpha_0 p + b_pre)             in R^n
    h_post  = 2 sigmoid(alpha_1 q + b_post)          in R^n
    H_res   = Sinkhorn-Knopp(alpha_2 R + B_res)      in R^(n x n):
              M = exp(clip(., ``mhc_h_res_clamp_min``, ``_max``)), then
              ``hc_sinkhorn_iters`` times M <- M / (row sums + ``hc_eps``),
              M <- M / (column sums + ``hc_eps``) (rows first, eps beside
              the sum: assumed)
    z       = h_pre X                                in R^C
    y       = F(z)
    X'      = H_res X + h_post^T y     (stream j gets h_post[j] y)

The stack's output is the sum of the n streams (assumed), then the final
RMSNorm and the untied head.

*MLA* in every layer, as ``mla_moe_f32`` (c_q = RMSNorm(x W_dq) in
R^``q_lora_rank``; q = c_q W_uq -> heads x (``qk_nope_head_dim`` +
``qk_rope_head_dim``); [c, k_r] = x W_dkv; c <- RMSNorm(c); [k_n, v] = c
W_ukv; no norm on the four parts), with YaRN read from ``rope_scaling``
beside the top-level ``rope_theta`` (``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``mscale``, ``mscale_all_dim``), rotary on interleaved pairs (the config
has no ``rope_interleave``: the key family's layout, assumed), sigma =
(nope + rope)^-0.5 x m(``mscale_all_dim``)^2 (assumed, as
``mla_moe_f32``), no query scale past the original positions (the config
has no such key).

*Feed-forward.* Published layers below ``first_k_dense_replace``: a dense
SwiGLU of ``intermediate_size``. The others: s = sigmoid(x W_r)
(``scoring_func``) over all ``num_experts_routed_over`` experts; chosen on
s + bias (``topk_method`` ``noaux_tc``: the bias moves the choice, not the
weight), ``n_group`` = ``topk_group`` = 1: no group limit; the
``num_experts_per_tok`` largest; weights = s of the chosen over their sum
(``norm_topk_prob``) x ``routed_scaling_factor``; expert e: W_down,e
(SiLU(x W_gate,e) * (x W_up,e)) of width ``moe_intermediate_size``; one
shared expert of ``moe_intermediate_size`` x ``n_shared_experts`` for every
token. Every expert is held here (``experts_held``). A padding token
routes nowhere.

*Readout*: sigmoid(z_fraud - z_legit + c) at the newest record's last
token (``readout``, assumed). The next-token block
(``num_nextn_predict_layers``) is a training objective and a draft head:
left out.
"""

from __future__ import annotations

import functools
import math
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import hybrid_moe_f32 as shared
from benchmark.reference import mla_moe_f32 as latent
from benchmark.reference import table
from benchmark.reference.cca_moe_f32 import (  # noqa: F401 - ``Served``
    # and ``compare`` are the comparison of every model that reports its
    # routing by row; the harness finds them on the module the
    # configuration names
    Served, compare)
from benchmark.reference.hybrid_moe_f32 import (  # noqa: F401 - the
    # deployment and the harness find these on the module the
    # configuration names
    aux_path, histories, miss_controls, preload_rows, sampled,
    verdict_logit)

F32 = jnp.float32
BF16 = jnp.bfloat16
ROW_BLOCK = 3  # histories per block on the chip: 4 streams beside 11.1 GB


def mla_dims(model: dict) -> dict:
    """What ``mla_moe_f32._mla`` takes as static, from this source's keys:
    YaRN under ``rope_scaling``, theta at the top level."""
    rope = dict(model["rope_scaling"], rope_theta=model["rope_theta"])
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
        "freq": latent.yarn_frequencies(rope, turned),
        "turn_scale": m(float(rope["mscale"])) / m(float(
            rope["mscale_all_dim"])),
        "sigma": (nope + turned) ** -0.5 * m(float(
            rope["mscale_all_dim"])) ** 2,
        "l0": int(rope["original_max_position_embeddings"]),
        "beta": 0.0,  # no query scale past the original positions
    }


def layer_kinds(model: dict) -> list[str]:
    """``dense`` or ``moe`` for every layer this cut keeps."""
    dense = int(model["first_k_dense_replace"])
    return ["dense" if i < dense else "moe" for i in model["layers_kept"]]


# -- weights -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _normal_layers(keys, shape: tuple, scale: float):
    """(len(keys), *shape) bfloat16, a layer at a time (the bit generator's
    temporaries are then one layer's)."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, BF16)
                   * jnp.asarray(scale, BF16)).astype(BF16), keys)


def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes. Matrices
    are normal with variance 1/fan-in, stored bfloat16 (their values exact
    in it, so the program and the reference read the same numbers); the
    embedding has variance 1, the untied head 1/``hidden_size``; norm
    weights are float32, 1 + 0.1 n; the router's bias 0.02 n. **The maps**:
    ``phi`` normal with variance 1/(n C) (bfloat16 values), so that u phi
    is of variance 1; ``alpha`` 1 + 0.1 n; ``b`` normal of variance 1 with
    1 added on the diagonal of B_res: the dynamic terms alpha (u phi) are
    of the size of the static b and not the near-zero a trained checkpoint
    starts from, so a program that drops them, or stops Sinkhorn early,
    fails the comparison. A leaf's values depend on its name and its
    layer alone. ``layer_stack`` ``listed``: ``layers`` is a list of one
    tree a kept layer; ``scanned``: the leading dense layers listed and
    the expert layers behind them one tree with the layers on every leaf's
    leading axis; the same values either way."""
    d = int(model["hidden_size"])
    m = mla_dims(model)
    heads, nope, rope, vd = m["heads"], m["nope"], m["rope"], m["vd"]
    q_rank, rank = int(model["q_lora_rank"]), m["rank"]
    routed = int(model["num_experts_routed_over"])
    held = int(model["experts_held"]["count"])
    width = int(model["moe_intermediate_size"])
    streams = int(model["hc_mult"])
    outs = 2 * streams + streams * streams
    vocab = int(model["vocab_size"])
    kinds = layer_kinds(model)
    root = jax.random.key(int(model["weights_seed"]) % (2 ** 31), impl="rbg")

    def key(name: str, layer: int = 0):
        return jax.random.fold_in(jax.random.fold_in(
            root, zlib.crc32(name.encode()) & 0x7FFFFFFF), layer)

    def tree(at: list[int], kind: str, stacked: bool):
        """The layers ``at`` (alike): stacked, or the one layer's tree."""
        def dense(name: str, fan_in: int, *shape: int):
            scale = 1.0 / math.sqrt(fan_in)
            if stacked:
                return _normal_layers(jnp.stack([key(name, i) for i in at]),
                                      shape, scale)
            return shared._normal_bf16(key(name, at[0]), shape, scale)

        def vec(name: str, mean, spread: float, *shape: int):
            out = jnp.stack([mean + spread * jax.random.normal(
                key(name, i), shape, F32) for i in at])
            return out if stacked else out[0]

        def swiglu(name: str, wide: int, *lead: int):
            return {"gate": dense(name + "/gate", d, *lead, d, wide),
                    "up": dense(name + "/up", d, *lead, d, wide),
                    "down": dense(name + "/down", wide, *lead, wide, d)}

        def maps(name: str):
            eye = jnp.concatenate([jnp.zeros((2 * streams,), F32),
                                   jnp.eye(streams, dtype=F32).reshape(-1)])
            return {"phi": dense(name + "/phi", streams * d, streams * d,
                                 outs),
                    "alpha": vec(name + "/alpha", 1.0, 0.1, 3),
                    "b": vec(name + "/b", eye, 1.0, outs)}

        mixer = {"wdq": dense("wdq", d, d, q_rank),
                 "q_norm": vec("q_norm", 1.0, 0.1, q_rank),
                 "wuq": dense("wuq", q_rank, q_rank, heads * (nope + rope)),
                 "wdkv": dense("wdkv", d, d, rank + rope),
                 "c_norm": vec("c_norm", 1.0, 0.1, rank),
                 "wukv": dense("wukv", rank, rank, heads * (nope + vd)),
                 "wo": dense("wo", heads * vd, heads * vd, d)}
        if kind == "dense":
            ffn = swiglu("ffn", int(model["intermediate_size"]))
        else:
            ffn = {"router": dense("router", d, d, routed),
                   "bias": vec("bias", 0.0, shared.ROUTER_BIAS_SCALE,
                               routed),
                   "experts": swiglu("experts", width, held),
                   "shared": swiglu("shared", width * int(
                       model["n_shared_experts"]))}
        return {"norm1": vec("norm1", 1.0, 0.1, d), "res1": maps("res1"),
                "mixer": mixer, "norm2": vec("norm2", 1.0, 0.1, d),
                "res2": maps("res2"), "ffn": ffn}

    sparse = [i for i, kind in enumerate(kinds) if kind == "moe"]
    if model["layer_stack"] == "scanned":
        if sparse != list(range(len(kinds) - len(sparse), len(kinds))):
            raise ValueError("the dense layers lead the stack")
        layers = [tree([i], "dense", False)
                  for i in range(len(kinds) - len(sparse))]
        layers.append(tree(sparse, "moe", True))
    else:
        layers = [tree([i], kind, False) for i, kind in enumerate(kinds)]
    return {"edges": jnp.asarray(shared.quantile_edges(model)),
            "embed": shared._normal_bf16(key("embed"), (vocab, d), 1.0),
            "layers": layers,
            "final_norm": 1.0 + 0.1 * jax.random.normal(
                key("final_norm"), (d,), F32),
            "head": shared._normal_bf16(key("head"), (d, vocab),
                                        1.0 / math.sqrt(d))}


def layer_of(params: dict, i: int) -> dict:
    """Kept layer ``i``: an entry of the list, or a row of the stacked
    tree the list ends in."""
    at = 0
    for p in params["layers"]:
        n = p["norm1"].shape[0] if p["norm1"].ndim == 2 else None
        if n is None and at == i:
            return p
        if n is not None and at <= i < at + n:
            return jax.tree.map(lambda a: a[i - at], p)
        at += n or 1
    raise IndexError(i)


# -- pieces, each on float32 ------------------------------------------------------

_f32 = shared._f32
rms_norm = shared.rms_norm


def sinkhorn(logits, *, iters: int, eps: float, low: float, high: float):
    """(..., n, n) -> exp of the clamped logits made doubly stochastic:
    ``iters`` times rows over their sums, then columns over theirs."""
    m = jnp.exp(jnp.clip(logits, low, high))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def maps(p: dict, streams, model: dict):
    """``(h_pre (N, T, n), h_post (N, T, n), H_res (N, T, n, n))`` of one
    sublayer for the streams (N, T, n, C)."""
    return _maps(p, streams, norm_eps=float(model["rms_norm_eps"]),
                 iters=int(model["hc_sinkhorn_iters"]),
                 eps=float(model["hc_eps"]),
                 low=float(model["mhc_h_res_clamp_min"]),
                 high=float(model["mhc_h_res_clamp_max"]))


@functools.partial(jax.jit, static_argnames=(
    "norm_eps", "iters", "eps", "low", "high"))
def _maps(p: dict, streams, *, norm_eps: float, iters: int, eps: float,
          low: float, high: float):
    rows, t, n, c = streams.shape
    flat = streams.reshape(rows, t, n * c)
    u = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + norm_eps)
    out = u @ _f32(p["phi"])
    alpha, b = p["alpha"], p["b"]
    pre = alpha[0] * out[..., :n] + b[:n]
    post = alpha[1] * out[..., n:2 * n] + b[n:2 * n]
    res = (alpha[2] * out[..., 2 * n:] + b[2 * n:]).reshape(rows, t, n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, iters=iters, eps=eps, low=low, high=high))


@jax.jit
def _read(h_pre, streams):
    """z = h_pre X."""
    return jnp.einsum("rtn,rtnc->rtc", h_pre, streams)


@jax.jit
def _write(h_res, h_post, streams, y):
    """X' = H_res X + h_post^T y."""
    return (jnp.einsum("rtij,rtjc->rtic", h_res, streams)
            + h_post[..., None] * y[:, :, None, :])


def defect_of(h_res, real) -> float:
    """Largest abs(row or column sum - 1) over the real tokens."""
    off = jnp.maximum(jnp.abs(h_res.sum(-1) - 1.0),
                      jnp.abs(h_res.sum(-2) - 1.0)).max(-1)
    return float(jnp.max(jnp.where(real, off, 0.0)))


def experts(p: dict, z, real, model: dict):
    """``(f, choice)``: the shared expert plus the held experts' part of
    the sublayer (N, T, d), and how many of each row's (token, slot) pairs
    chose each routed expert (N, routed)."""
    first, held = shared.held_range(model)
    routed = int(model["num_experts_routed_over"])
    shape = z.shape
    flat = z.reshape(-1, shape[-1])
    chosen, w = shared._route(
        p["router"], p["bias"], flat, real.reshape(-1), routed=routed,
        groups=int(model["n_group"]), kept=int(model["topk_group"]),
        per_token=int(model["num_experts_per_tok"]),
        scale=float(model["routed_scaling_factor"]))
    picked = np.asarray(chosen)
    here = (picked >= first) & (picked < first + held)
    counts = np.bincount(picked[here] - first, minlength=held)
    room = min(len(flat), 1 << max(8, (max(int(counts.max()), 1)
                                        - 1).bit_length()))
    f = shared._dense(p["shared"], flat) + shared._held_experts(
        p["experts"], flat, chosen, w, jnp.arange(held), room=room,
        first=first)
    choice = np.stack([np.bincount(row[row >= 0], minlength=routed)
                       for row in picked.reshape(shape[0], -1)])
    return f.reshape(shape), choice


def forward(params: dict, model: dict, hist, filled, *,
            every_position: bool = False, with_defect: bool = False):
    """``(logits, choice)``: the logits at the newest record's last token
    (N, vocab), or at every position (N, tokens, vocab), and each row's
    count of chosen pairs by routed expert in every kept expert layer (N,
    expert layers, routed); with ``with_defect`` also the largest defect
    of an H_res over the real tokens and the sublayers."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["rms_norm_eps"])
        dims = mla_dims(model)
        x, real, position = shared._embed(
            params["edges"], params["embed"], jnp.asarray(hist, F32),
            jnp.asarray(filled, jnp.int32), bins=int(model["bins"]))
        n = int(model["hc_mult"])
        streams = jnp.broadcast_to(x[:, :, None, :], (
            *x.shape[:2], n, x.shape[-1]))
        choices, defects = [], [0.0]
        routed = int(model["num_experts_routed_over"])

        def mapped(p):  # a sublayer's maps, and their defect where asked
            h_pre, h_post, h_res = maps(p, streams, model)
            if with_defect:
                defects.append(defect_of(h_res, real))
            return h_pre, h_post, h_res

        for i, kind in enumerate(layer_kinds(model)):
            p = layer_of(params, i)
            h_pre, h_post, h_res = mapped(p["res1"])
            z = shared._normed(_read(h_pre, streams), p["norm1"], eps=eps)
            streams = _write(h_res, h_post, streams, latent._mla(
                p["mixer"], z, real, position, **dims))
            h_pre, h_post, h_res = mapped(p["res2"])
            z = shared._normed(_read(h_pre, streams), p["norm2"], eps=eps)
            if kind == "dense":
                f = shared._dense(p["ffn"], z)
            else:
                f, choice = experts(p["ffn"], z, real, model)
                choices.append(choice)
            streams = _write(h_res, h_post, streams, f)
        x = streams.sum(2)
        if not every_position:
            x = x[:, -1]
        choice = np.stack(choices, 1).astype(np.int64) if choices else \
            np.zeros((len(x), 0, routed), np.int64)
        out = (latent.head(params, x, eps), choice)
        return (*out, max(defects)) if with_defect else out


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
    layers = layer_kinds(config).count("moe")
    routed = int(config["num_experts_routed_over"])
    expect = {
        "logits": (np.concatenate(logits) if logits else np.zeros(
            (0, int(config["vocab_size"])), np.float32)),
        "choice": (np.concatenate(choice) if choice else np.zeros(
            (0, layers, routed), np.int64))}
    note = (f"{len(which)} of {len(customer)} served verdicts, window "
            f"records min {filled.min() if len(which) else 0} max "
            f"{filled.max() if len(which) else 0} of {length}, pairs served "
            f"{int(served.choice.sum())} reference "
            f"{int(expect['choice'].sum())}, weights "
            f"{t_forward - t_params:.1f}s forward "
            f"{time.perf_counter() - t_forward:.1f}s")
    return served, expect, note
