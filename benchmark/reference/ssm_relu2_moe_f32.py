"""The plain reference of the ``hybrid_moe`` family's sixth model (the
language model of NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type``
``nemotron_h``, as one chip of a two-chip expert-parallel group holds it),
its weights, and the comparison that decides ``correct`` for every cell
that serves it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the state-space layer as the recurrence itself, a token at a time
(``lax.scan`` over the tokens: no chunks, no running sums of decays),
attention as a full (T, T) masked softmax a row at a time, the experts one
after another on the tokens that chose each, the untied head in vocabulary
blocks; no scan over layers. Nothing here is imported from the program.
Every symbol is read from the configuration's own keys (the published
``config.json`` names); what the config does not pin is listed in the
configuration file under ``assumed`` and marked (assumed) below.

Per token, x in R^``hidden_size``; x_0 = E[id]. **A layer is one
sublayer** (``layers_kept`` names the published layers this cut holds,
``hybrid_override_pattern`` their letters), with RMSNorm of ``norm_eps``
(= ``layer_norm_epsilon``): x <- x + f(RMSNorm(x)), f a Mamba-2 mixer
(``M``), attention (``*``) or the expert layer (``E``). Causal. No
multipliers.

*Tokens*: as ``hybrid_moe_f32`` (column j of a record is token j * ``bins``
+ its quantile bin; ``filled`` records of a window are real, the ones left
of them padding). The model has no positions (assumed: the family applies
no rotary; ``rope_theta`` and ``partial_rotary_factor`` are read by
nothing).

*Mamba-2* (``M``). I = H P with H = ``mamba_num_heads``, P =
``mamba_head_dim`` (not ``expand`` x hidden); N = ``ssm_state_size``, G =
``n_groups``, K = ``conv_kernel``. [z | xBC | dt] = u W_in by widths I, I +
2 G N, H (no bias: ``mamba_proj_bias``). xBC <- SiLU(conv_K (xBC) +
b_conv): causal, depthwise, the newest tap last, zeros before a row's first
real token (``use_conv_bias``). [x | B | C] = xBC by widths I, G N, G N;
head h reads B and C of group h // (H / G). dt_t = softplus(dt_t + dt_bias)
per head, no clamp (assumed); A = -exp(A_log). Per head, x_t in R^P, B_t,
C_t in R^N: S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T; y_t = S_t C_t + D
x_t. v = y * SiLU(z); **the RMS norm inside each of the G groups of I / G
values**, times the weight w (I values). out = v W_out. A padding token has
dt = 0: the state passes it unchanged. ``chunk_size`` is the published
kernel's block and no part of the result.

*Attention* (``*``). q = u W_q (``num_attention_heads`` heads of
``head_dim``, which is not hidden / heads), k, v = u W_k, u W_v
(``num_key_value_heads`` heads), no bias (``attention_bias``), no rotary,
no norm; softmax(q k^T / sqrt(``head_dim``)) over the real keys at or
before the query, query head h with key head h // (heads / kv heads); W_o.

*Experts* (``E``). s = sigmoid(u W_r) over all
``num_experts_routed_over`` routed experts; the ``num_experts_per_tok``
largest of s + b (``n_group`` 1, ``topk_group`` 1: no group limit);
weights = s / sum of the chosen s x ``routed_scaling_factor``
(``norm_topk_prob``). Expert e: W_down,e (relu(u W_up,e))^2 of width
``moe_intermediate_size``: **two matrices and no gate**
(``mlp_hidden_act`` ``relu2``); one shared expert of
``moe_shared_expert_intermediate_size`` for every token, unweighted, the
same body. **The share**: this chip holds the experts ``experts_held``; a
token's pairs with the others are left out and the partial sum goes on. A
padding token routes nowhere. Where the configuration names an
``expert_storage_width``, ``make_params`` hands the held experts' ``up``
and ``down`` over with that many columns / rows, the ones past the
published width zeros (relu(0)^2 = 0 and a zero row of ``down`` adds
nothing: the same numbers); everything here multiplies the published
width.

*Readout*: final RMSNorm, the untied head (``tie_word_embeddings`` false)
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
from benchmark.reference.ssm_moe_f32 import _attention

F32 = jnp.float32
ROW_BLOCK = 3  # histories per block on the chip: float32 beside 9.4 GB
KINDS = {"M": "mamba2", "*": "gqa", "E": "moe"}


def layer_kinds(model: dict) -> list[str]:
    """``mamba2``, ``gqa`` or ``moe``: the one sublayer of every layer
    this cut keeps."""
    pattern = model["hybrid_override_pattern"]
    return [KINDS[pattern[i]] for i in model["layers_kept"]]


def mamba_dims(model: dict) -> dict:
    """What ``_mamba`` takes as static."""
    return {"heads": int(model["mamba_num_heads"]),
            "hd": int(model["mamba_head_dim"]),
            "state": int(model["ssm_state_size"]),
            "groups": int(model["n_groups"]),
            "eps": float(model["norm_eps"])}


def gqa_dims(model: dict) -> dict:
    hd = int(model["head_dim"])
    return {"heads": int(model["num_attention_heads"]),
            "groups": int(model["num_key_value_heads"]), "hd": hd,
            "scale": hd ** -0.5}


# -- weights -------------------------------------------------------------------

def make_params(model: dict) -> dict:
    """One draw from ``weights_seed``, made where JAX computes. Matrices
    are normal with variance 1/fan-in, stored bfloat16 (their values exact
    in it, so the program and the reference read the same numbers); the
    embedding has variance 1, the untied head 1/``hidden_size``; vectors
    are float32 and none is zero, so that a term left out shows: norm
    weights 1 + 0.1 n, the router's choice bias 0.02 n (small, and it moves
    choices). **The state-space mixer** as Mamba-2 initialises it, so that
    its decays are neither 0 nor 1: A_log = log of uniform [1, 16]; dt_bias
    the inverse softplus of a log-uniform [``time_step_min``,
    ``time_step_max``] held above ``time_step_floor``; D = 1 + 0.1 n; the
    convolution's taps normal with variance 1 / ``conv_kernel``, its bias
    0.1 n. A leaf's values depend on its name and its layer alone. **A
    layer's tree holds the one sublayer it has**: ``norm1`` and ``mixer``
    (M, *) or ``norm2`` and ``ffn`` (E). The held experts' ``up`` and
    ``down`` are drawn at ``moe_intermediate_size`` and stored with
    ``expert_storage_width`` columns / rows where the configuration names
    one, the further ones zeros. ``layer_stack`` ``listed``: ``layers`` is
    a list of one tree a kept layer; ``scanned``: every run of alike
    neighbours is one tree with the layers on every leaf's leading axis;
    the same values either way."""
    d = int(model["hidden_size"])
    m, a = mamba_dims(model), gqa_dims(model)
    heads, hd, state, groups = m["heads"], m["hd"], m["state"], m["groups"]
    inner, taps = heads * hd, int(model["conv_kernel"])
    wide = inner + 2 * groups * state
    routed = int(model["num_experts_routed_over"])
    held = int(model["experts_held"]["count"])
    width = int(model["moe_intermediate_size"])
    stored = int(model.get("expert_storage_width", width))
    shared_width = int(model["moe_shared_expert_intermediate_size"])
    vocab = int(model["vocab_size"])
    low, high, floor = (float(model[k]) for k in (
        "time_step_min", "time_step_max", "time_step_floor"))
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

        def relu2(name: str, wide_: int, *lead: int, store: int = 0):
            up = dense(name + "/up", d, *lead, d, wide_)
            down = dense(name + "/down", wide_, *lead, wide_, d)
            if store > wide_:  # zeros past the published width
                none = ((0, 0),) * (up.ndim - 2)
                up = jnp.pad(up, (*none, (0, 0), (0, store - wide_)))
                down = jnp.pad(down, (*none, (0, store - wide_), (0, 0)))
            return {"up": up, "down": down}

        def step_bias(k, s):  # softplus^-1 of a log-uniform step
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, s, F32, math.log(low), math.log(high))), floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        if kind == "moe":
            return {"norm2": vec("norm2", 1.0, 0.1, d), "ffn": {
                "router": dense("router", d, d, routed),
                "bias": vec("bias", 0.0, shared.ROUTER_BIAS_SCALE, routed),
                "experts": relu2("experts", width, held, store=stored),
                "shared": relu2("shared", shared_width)}}
        if kind == "mamba2":
            mixer = {
                "w_in": dense("w_in", d, d, inner + wide + heads),
                "conv": vec("conv", 0.0, 1.0 / math.sqrt(taps), taps, wide),
                "conv_b": vec("conv_b", 0.0, 0.1, wide),
                "dt_bias": drawn("dt_bias", step_bias, heads),
                "a_log": drawn("a_log", lambda k, s: jnp.log(
                    jax.random.uniform(k, s, F32, 1.0, 16.0)), heads),
                "d": vec("d", 1.0, 0.1, heads),
                "norm": vec("norm", 1.0, 0.1, inner),
                "w_out": dense("w_out", inner, inner, d)}
        else:
            q_wide, kv_wide = a["heads"] * a["hd"], a["groups"] * a["hd"]
            mixer = {"wq": dense("wq", d, d, q_wide),
                     "wk": dense("wk", d, d, kv_wide),
                     "wv": dense("wv", d, d, kv_wide),
                     "wo": dense("wo", q_wide, q_wide, d)}
        return {"norm1": vec("norm1", 1.0, 0.1, d), "mixer": mixer}

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
            "final_norm": 1.0 + 0.1 * jax.random.normal(
                key("final_norm"), (d,), F32),
            "head": shared._normal_bf16(key("head"), (d, vocab),
                                        1.0 / math.sqrt(d))}


def _norm_of(p: dict):
    return p["norm1"] if "norm1" in p else p["norm2"]


def layer_of(params: dict, i: int) -> dict:
    """Kept layer ``i``: an entry of the list, or a row of a stacked
    tree in it."""
    at = 0
    for p in params["layers"]:
        norm = _norm_of(p)
        n = norm.shape[0] if norm.ndim == 2 else None
        if n is None and at == i:
            return p
        if n is not None and at <= i < at + n:
            return jax.tree.map(lambda a: a[i - at], p)
        at += n or 1
    raise IndexError(i)


# -- pieces, each on float32 ------------------------------------------------------

_f32 = shared._f32
rms_norm = shared.rms_norm


def mamba(p: dict, u, real, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d)."""
    return _mamba(p, u, real, **mamba_dims(model))


@functools.partial(jax.jit, static_argnames=(
    "heads", "hd", "state", "groups", "eps"))
def _mamba(p: dict, u, real, *, heads: int, hd: int, state: int,
           groups: int, eps: float):
    n, length, _ = u.shape
    inner = heads * hd
    wide = inner + 2 * groups * state
    keep = real[..., None].astype(F32)
    proj = u @ _f32(p["w_in"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + wide],
                  proj[..., inner + wide:])
    xbc = jax.nn.silu(shared.short_conv(xbc * keep, p["conv"]) + p["conv_b"])
    x = xbc[..., :inner].reshape(n, length, heads, hd)
    per = heads // groups  # heads that share one B and C
    b_ = xbc[..., inner:inner + groups * state].reshape(
        n, length, groups, state)
    c_ = xbc[..., inner + groups * state:].reshape(n, length, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"]) * keep  # 0 on padding
    decay = jnp.exp(-jnp.exp(p["a_log"]) * dt)

    def one_token(s, token):  # s (n, H, P, N)
        x_t, b_t, c_t, dt_t, decay_t = token
        b_t, c_t = jnp.repeat(b_t, per, axis=1), jnp.repeat(c_t, per, axis=1)
        s = decay_t[..., None, None] * s + (
            dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(
        one_token, jnp.zeros((n, heads, hd, state), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, b_, c_, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + p["d"][:, None] * x
    v = y.reshape(n, length, inner) * jax.nn.silu(z)
    # the norm inside each group of inner / groups values
    by_group = [rms_norm(part, weight, eps) for part, weight in zip(
        jnp.split(v, groups, axis=-1), jnp.split(p["norm"], groups))]
    return jnp.concatenate(by_group, axis=-1) @ _f32(p["w_out"])


def attention(p: dict, u, real, model: dict):
    """(n, T, d) normed input -> the mixer's output (n, T, d):
    ``ssm_moe_f32``'s full masked softmax at this model's head width and
    scale."""
    return _attention(p, u, real, **gqa_dims(model))


def relu2(p: dict, x, width: int):
    """W_down (relu(x W_up))^2 at the published ``width`` (what is stored
    past it is zeros)."""
    h = jnp.square(jax.nn.relu(x @ _f32(p["up"][..., :width])))
    return h @ _f32(p["down"][..., :width, :])


_shared_expert = jax.jit(relu2, static_argnames=("width",))


def route(p: dict, x, real, model: dict):
    """``(experts (tokens, k), weights (tokens, k))`` over all the
    published experts (``hybrid_moe_f32``'s sigmoid scores with a bias for
    the choice, here in one group); a padding token's weights are zero and
    its experts -1."""
    return shared._route(
        p["router"], p["bias"], x, real,
        routed=int(model["num_experts_routed_over"]),
        groups=int(model["n_group"]), kept=int(model["topk_group"]),
        per_token=int(model["num_experts_per_tok"]),
        scale=float(model["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("room", "first", "width"))
def _held_experts(experts: dict, x, chosen, w, *, room: int, first: int,
                  width: int):
    """The held experts one after another, each on the token rows that
    chose it (``room`` rows: the expert's own first, rows of weight 0
    behind them), its result added back at those rows."""
    def one(e, y):
        weight = jnp.where(chosen == first + e, w, 0.0).sum(-1)
        rows = jnp.argsort(weight == 0.0, stable=True)[:room]
        part = relu2({k: v[e] for k, v in experts.items()}, x[rows], width)
        return y.at[rows].add(part * weight[rows][:, None])

    return jax.lax.fori_loop(0, experts["up"].shape[0], one,
                             jnp.zeros_like(x))


def experts(p: dict, z, real, model: dict):
    """``(f, choice)``: the shared expert plus the held experts' part of
    the sublayer (n, T, d), and how many of each row's (token, slot) pairs
    chose each routed expert (n, routed)."""
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
    f = _shared_expert(p["shared"], flat, width=int(
        model["moe_shared_expert_intermediate_size"])) + _held_experts(
            p["experts"], flat, chosen, w, room=room, first=first,
            width=int(model["moe_intermediate_size"]))
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
    row's count of chosen pairs by routed expert in every kept expert
    layer (n, expert layers, routed)."""
    with jax.default_matmul_precision("highest"):
        eps = float(model["norm_eps"])
        x, real, _ = shared._embed(
            params["edges"], params["embed"], jnp.asarray(hist, F32),
            jnp.asarray(filled, jnp.int32), bins=int(model["bins"]))
        choices = []
        for i, kind in enumerate(layer_kinds(model)):
            p = layer_of(params, i)
            z = shared._normed(x, _norm_of(p), eps=eps)
            if kind == "moe":
                f, choice = experts(p["ffn"], z, real, model)
                choices.append(choice)
            else:
                mixer = mamba if kind == "mamba2" else attention
                f = mixer(p["mixer"], z, real, model)
            x = _add(x, f)
        if not every_position:
            x = x[:, -1]
        choice = np.stack(choices, 1).astype(np.int64) if choices else \
            np.zeros((len(x), 0, int(model["num_experts_routed_over"])),
                     np.int64)
        return latent.head(params, x, eps), choice


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
