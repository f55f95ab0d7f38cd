"""The plain reference of the ``hybrid_moe`` family's seventh model (the
language model of Qwen3-Next-80B-A3B-Instruct, ``model_type``
``qwen3_next``, as one chip of a four-chip expert-parallel group holds it),
its weights, and the comparison that decides ``correct`` for every cell
that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the Gated DeltaNet layer as the delta rule itself, a token at a time
(``lax.scan`` over the tokens: no chunks, no running sums of decays, no
triangular inverse), attention as a full (T, T) masked softmax a row at a
time, the experts one after another on the tokens that chose each, the
untied head in vocabulary blocks; no scan over layers. Nothing here is
imported from the program. Every symbol is read from the configuration's
own keys (the published ``config.json`` names); what the config does not
pin is listed in the configuration file under ``assumed`` and marked
(assumed) below.

Per token, x in R^``hidden_size``; x_0 = E[id]. Layer i of
``layers_kept``: x <- x + Mix_i(N(x)); x <- x + Experts(N(x)), N(x) = x /
sqrt(mean x^2 + ``rms_norm_eps``) x (1 + w): **this family stores its norm
weights zero-centred** (assumed: the layers', the final and the query / key
norms; the gated norm inside Gated DeltaNet multiplies by w). Mix_i is
attention iff (i + 1) % ``full_attention_interval`` == 0, else Gated
DeltaNet. Every layer has the expert layer (``decoder_sparse_step`` 1,
``mlp_only_layers`` empty). Causal.

*Tokens*: as ``hybrid_moe_f32`` (column j of a record is token j * ``bins``
+ its quantile bin; ``filled`` records of a window are real, the ones left
of them padding; positions count from a row's first real token).

*Gated DeltaNet*. Hk = ``linear_num_key_heads`` heads of dk =
``linear_key_head_dim``, Hv = ``linear_num_value_heads`` heads of dv =
``linear_value_head_dim``, K = ``linear_conv_kernel_dim``. [q | k | v | z]
= u W_qkvz by widths Hk dk, Hk dk, Hv dv, Hv dv and [b | a] = u W_ba by
widths Hv, Hv, heads major (assumed: the published checkpoint interleaves
these columns by key head, a permutation a loader would undo). [q | k | v]
<- SiLU(conv_K ([q | k | v])): causal, depthwise, the newest tap last, no
bias, zeros before a row's first real token. By head q and k divided by
sqrt(sum of squares + 1e-6) (assumed eps), q times dk^-0.5. Value head j
reads key head j // (Hv / Hk). beta_t = sigmoid(b_t), g_t = -exp(A_log)
softplus(a_t + dt_bias) (no clamp: assumed). S_t = e^(g_t) S_(t-1) + beta_t
k_t (v_t - (e^(g_t) S_(t-1))^T k_t)^T, S_0 = 0 (dk x dv); o_t = S_t^T q_t.
o <- o / sqrt(mean o^2 over the head's dv + eps) x w_norm x SiLU(z); out =
o W_out. A padding token has beta = 0 and g = 0 and sends zeros into the
convolution: the state passes it unchanged.

*Gated attention*. u W_q is ``num_attention_heads`` x (2 ``head_dim``): a
head's query and beside it its gate. q = N_q(query), k = N_k(u W_k) over
each head's ``head_dim`` with one (1 + w) weight; v = u W_v
(``num_key_value_heads`` heads); the leading ``partial_rotary_factor`` x
``head_dim`` dims of q and k turned by position at ``rope_theta`` (the two
halves of those dims are the pairs: assumed; no ``rope_scaling``); softmax
(q k^T / sqrt(``head_dim``)) over the real keys at or before the query,
query head h with key head h // (heads / kv heads); o <- o x
sigmoid(gate); W_o.

*Experts*. p = softmax(u W_r) over all ``num_experts_routed_over``; the
``num_experts_per_tok`` largest (an equal score to the lower index);
weights p / sum of the chosen p (``norm_topk_prob``), no further scale.
Expert e: SwiGLU of width ``moe_intermediate_size``; one shared expert of
``shared_expert_intermediate_size`` for every token, times
sigmoid(u . w_sg), **one scalar a token**. **The share**: this chip holds
the experts ``experts_held``; a token's pairs with the others are left out
and the partial sum goes on. A padding token routes nowhere.

*Left out*: the multi-token-prediction block (a verdict reads one
position's logits once), the exchange across the chips.

*Readout*: final norm, the untied head (``tie_word_embeddings`` false)
over the vocabulary slice; the verdict is sigmoid(z_fraud - z_legit + c) at
the newest record's last token (``readout``, assumed).
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
from benchmark.reference.mhc_moe_f32 import _normal_layers

F32 = jnp.float32
MASKED = -1e30
L2_EPS = 1e-6
ROW_BLOCK = 3  # histories per block on the chip: float32 beside 10.9 GB
A_FLOOR = 0.01  # under A = exp(A_log): a head that never forgets is no test


def layer_kinds(model: dict) -> list[str]:
    """``gdn`` or ``gqa``: the mixer of every layer this cut keeps."""
    period = int(model["full_attention_interval"])
    return ["gqa" if (i + 1) % period == 0 else "gdn"
            for i in model["layers_kept"]]


def gdn_dims(model: dict) -> dict:
    """What ``_gdn`` takes as static."""
    return {"hk": int(model["linear_num_key_heads"]),
            "hv": int(model["linear_num_value_heads"]),
            "dk": int(model["linear_key_head_dim"]),
            "dv": int(model["linear_value_head_dim"]),
            "eps": float(model["rms_norm_eps"])}


def gqa_dims(model: dict) -> dict:
    hd = int(model["head_dim"])
    return {"heads": int(model["num_attention_heads"]),
            "groups": int(model["num_key_value_heads"]), "hd": hd,
            "rot": int(hd * float(model["partial_rotary_factor"])),
            "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"])}


# -- weights -------------------------------------------------------------------

def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes. Matrices
    are normal with variance 1/fan-in, stored bfloat16 (their values exact
    in it, so the program and the reference read the same numbers), the
    two narrow ones among them (``w_ba``, ``shared_gate``: so that beta,
    the decays and the shared expert's gate are not saturated); the
    embedding has variance 1, the untied head 1/``hidden_size``; vectors
    are float32 and none is zero, so that a term left out shows: the
    zero-centred norm weights (the layers', the final, the query / key
    norms) 0.1 n, the gated norm's 1 + 0.1 n. **Gated DeltaNet** so that
    its decays are neither 0 nor 1: A_log = log of uniform [0, 16) held
    above ``A_FLOOR``; dt_bias the inverse softplus of a log-uniform
    [0.001, 0.1] step; the convolution's taps normal with variance 1 /
    ``linear_conv_kernel_dim``. A leaf's values depend on its name and its
    layer alone. ``layer_stack`` ``listed``: ``layers`` is a list of one
    tree a kept layer; ``scanned``: every run of alike neighbours is one
    tree with the layers on every leaf's leading axis (three ``gdn``, the
    ``gqa`` layer alone, ...); the same values either way."""
    d = int(model["hidden_size"])
    m, a = gdn_dims(model), gqa_dims(model)
    keys, values = m["hk"] * m["dk"], m["hv"] * m["dv"]
    taps = int(model["linear_conv_kernel_dim"])
    routed = int(model["num_experts_routed_over"])
    held = int(model["experts_held"]["count"])
    width = int(model["moe_intermediate_size"])
    shared_width = int(model["shared_expert_intermediate_size"])
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

        def drawn(name: str, draw, *shape: int):
            out = jnp.stack([draw(key(name, i), shape) for i in at])
            return out if stacked else out[0]

        def vec(name: str, mean: float, spread: float, *shape: int):
            return drawn(name, lambda k, s: mean + spread * jax.random.normal(
                k, s, F32), *shape)

        def swiglu(name: str, wide_: int, *lead: int):
            return {"gate": dense(name + "/gate", d, *lead, d, wide_),
                    "up": dense(name + "/up", d, *lead, d, wide_),
                    "down": dense(name + "/down", wide_, *lead, wide_, d)}

        def step_bias(k, s):  # softplus^-1 of a log-uniform step
            dt = jnp.exp(jax.random.uniform(
                k, s, F32, math.log(0.001), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))

        if kind == "gdn":
            mixer = {
                "w_qkvz": dense("w_qkvz", d, d, 2 * keys + 2 * values),
                "w_ba": dense("w_ba", d, d, 2 * m["hv"]),
                "conv": vec("conv", 0.0, 1.0 / math.sqrt(taps), taps,
                            2 * keys + values),
                "dt_bias": drawn("dt_bias", step_bias, m["hv"]),
                "a_log": drawn("a_log", lambda k, s: jnp.log(jnp.maximum(
                    jax.random.uniform(k, s, F32, 0.0, 16.0), A_FLOOR)),
                    m["hv"]),
                "norm": vec("norm", 1.0, 0.1, m["dv"]),
                "w_out": dense("w_out", values, values, d)}
        else:
            q_wide, kv_wide = a["heads"] * a["hd"], a["groups"] * a["hd"]
            mixer = {"wq": dense("wq", d, d, 2 * q_wide),
                     "wk": dense("wk", d, d, kv_wide),
                     "wv": dense("wv", d, d, kv_wide),
                     "q_norm": vec("q_norm", 0.0, 0.1, a["hd"]),
                     "k_norm": vec("k_norm", 0.0, 0.1, a["hd"]),
                     "wo": dense("wo", q_wide, q_wide, d)}
        ffn = {"router": dense("router", d, d, routed),
               "experts": swiglu("experts", width, held),
               "shared": swiglu("shared", shared_width),
               "shared_gate": dense("shared_gate", d, d, 1)}
        return {"norm1": vec("norm1", 0.0, 0.1, d), "mixer": mixer,
                "norm2": vec("norm2", 0.0, 0.1, d), "ffn": ffn}

    if model["layer_stack"] == "scanned":
        runs: list[list[int]] = []
        for i, kind in enumerate(kinds):
            if runs and kinds[runs[-1][0]] == kind:
                runs[-1].append(i)
            else:
                runs.append([i])
        layers = [tree(run, kinds[run[0]], len(run) > 1) for run in runs]
    else:
        layers = [tree([i], kind, False) for i, kind in enumerate(kinds)]
    return {"edges": jnp.asarray(shared.quantile_edges(model)),
            "embed": shared._normal_bf16(key("embed"), (vocab, d), 1.0),
            "layers": layers,
            "final_norm": 0.1 * jax.random.normal(
                key("final_norm"), (d,), F32),
            "head": shared._normal_bf16(key("head"), (d, vocab),
                                        1.0 / math.sqrt(d))}


def layer_of(params: dict, i: int) -> dict:
    """Kept layer ``i``: an entry of the list, or a row of a stacked
    tree in it."""
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


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, *, eps: float):
    """N(x): the zero-centred weight enters as 1 + w."""
    return rms_norm(x, 1.0 + weight, eps)


def gdn(p: dict, u, real, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d)."""
    return _gdn(p, u, real, **gdn_dims(model))


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps"))
def _gdn(p: dict, u, real, *, hk: int, hv: int, dk: int, dv: int,
         eps: float):
    n, length, _ = u.shape
    keys, values = hk * dk, hv * dv
    per = hv // hk  # value heads that read one key head
    keep = real[..., None].astype(F32)
    proj = u @ _f32(p["w_qkvz"])
    ba = u @ _f32(p["w_ba"])
    qkv = jax.nn.silu(shared.short_conv(
        proj[..., :2 * keys + values] * keep, p["conv"]))

    def unit(t):
        t = t.reshape(n, length, hk, dk)
        t = t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
        return jnp.repeat(t, per, axis=2)  # by value head

    q = unit(qkv[..., :keys]) * dk ** -0.5
    k = unit(qkv[..., keys:2 * keys])
    v = qkv[..., 2 * keys:].reshape(n, length, hv, dv)
    z = proj[..., 2 * keys + values:].reshape(n, length, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv]) * keep  # 0 on padding
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"]) * keep  # 0 on padding: decay 1

    def one_token(state, token):  # state (n, Hv, dk, dv)
        q_t, k_t, v_t, g_t, b_t = token
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("nhk,nhkv->nhv", k_t, state)
        state = state + (b_t[..., None, None] * k_t[..., None]
                         * (v_t - seen)[..., None, :])
        return state, jnp.einsum("nhk,nhkv->nhv", q_t, state)

    _, o = jax.lax.scan(
        one_token, jnp.zeros((n, hv, dk, dv), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), p["norm"], eps) * jax.nn.silu(z)
    return o.reshape(n, length, values) @ _f32(p["w_out"])


def attention(p: dict, u, real, position, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d)."""
    return _attention(p, u, real, position, **gqa_dims(model))


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "hd", "rot", "theta", "eps"))
def _attention(p: dict, u, real, position, *, heads: int, groups: int,
               hd: int, rot: int, theta: float, eps: float):
    n, length, _ = u.shape
    per = heads // groups
    both = (u @ _f32(p["wq"])).reshape(n, length, heads, 2 * hd)
    q, gate = both[..., :hd], both[..., hd:]
    k = (u @ _f32(p["wk"])).reshape(n, length, groups, hd)
    v = (u @ _f32(p["wv"])).reshape(n, length, groups, hd)

    def turned(t):  # the leading ``rot`` dims rotated, the rest left
        return jnp.concatenate([
            shared.rotary(t[..., :rot], position, theta), t[..., rot:]], -1)

    q = turned(rms_norm(q, 1.0 + p["q_norm"], eps))
    k = jnp.repeat(turned(rms_norm(k, 1.0 + p["k_norm"], eps)), per, axis=2)
    v = jnp.repeat(v, per, axis=2)
    at = jnp.arange(length)

    def one_row(row):  # a row at a time: heads x T^2 scores each
        q1, k1, v1, real1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, k1) / math.sqrt(hd)
        allowed = real1[None, None, :] & (at[None, :] <= at[:, None])[None]
        weights = jax.nn.softmax(jnp.where(allowed, scores, MASKED), axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v1)

    o = jax.lax.map(one_row, (q, k, v, real)) * jax.nn.sigmoid(gate)
    return o.reshape(n, length, heads * hd) @ _f32(p["wo"])


def route(p: dict, x, real, model: dict):
    """``(experts (tokens, k), weights (tokens, k))`` over all the
    published experts (``mla_moe_f32``'s softmax over all of them, the
    largest kept, renormalised over the chosen: ``norm_topk_prob``; no
    further scale); a padding token's weights are zero and its experts
    -1."""
    chosen, w, _ = latent._route(
        p["router"], x, real, per_token=int(model["num_experts_per_tok"]),
        scale=1.0)
    return chosen, w


@jax.jit
def _shared_expert(p: dict, x):
    """sigmoid(x . w_sg) SwiGLU_shared(x): the gate one scalar a token."""
    return jax.nn.sigmoid(x @ _f32(p["shared_gate"])) * shared.swiglu(
        p["shared"], x)


def experts(p: dict, z, real, model: dict):
    """``(f, choice)``: the gated shared expert plus the held experts'
    part of the sublayer (n, T, d), and how many of each row's (token,
    slot) pairs chose each routed expert (n, routed)."""
    first, held = shared.held_range(model)
    routed = int(model["num_experts_routed_over"])
    shape = z.shape
    flat = z.reshape(-1, shape[-1])
    chosen, w = route(p, flat, real.reshape(-1), model)
    picked = np.asarray(chosen)
    here = (picked >= first) & (picked < first + held)
    counts = np.bincount(picked[here] - first, minlength=held)
    room = min(len(flat), 1 << max(8, (max(int(counts.max()), 1)
                                        - 1).bit_length()))
    f = _shared_expert(p, flat) + shared._held_experts(
        p["experts"], flat, chosen, w, jnp.arange(held), room=room,
        first=first)
    choice = np.stack([np.bincount(row[row >= 0], minlength=routed)
                       for row in picked.reshape(shape[0], -1)])
    return f.reshape(shape), choice


@jax.jit
def _add(x, y):
    return x + y


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
        for i, kind in enumerate(layer_kinds(model)):
            p = layer_of(params, i)
            z = _normed(x, p["norm1"], eps=eps)
            if kind == "gdn":
                f = gdn(p["mixer"], z, real, model)
            else:
                f = attention(p["mixer"], z, real, position, model)
            x = _add(x, f)
            f, choice = experts(p["ffn"], _normed(x, p["norm2"], eps=eps),
                                real, model)
            x = _add(x, f)
            choices.append(choice)
        if not every_position:
            x = x[:, -1]
        logits = latent.head(
            dict(params, final_norm=1.0 + params["final_norm"]), x, eps)
        return logits, np.stack(choices, 1).astype(np.int64)


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
        # every block ROW_BLOCK rows, the last one filled up with its own
        # last row: one set of shapes, so one set of programs (a sample of
        # 32 verdicts, where the busiest customer's newest is among the
        # drawn ones, left a block of 2 rows whose programs the chip did
        # not finish: PERF.md section 6, PR 52)
        rows = np.minimum(np.arange(lo, lo + ROW_BLOCK), len(which) - 1)
        block, routing = forward(params, config, hist[rows], filled[rows])
        keep = len(which) - lo
        logits.append(np.asarray(block)[:keep])
        choice.append(routing[:keep])
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
