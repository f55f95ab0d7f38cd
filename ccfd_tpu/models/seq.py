"""Sequence fraud scorer: a transformer over per-customer transaction history.

A new model family beyond the reference's single-row classifiers: each
scoring decision sees the customer's recent transaction *history*
(B, L, 30) and predicts fraud for the latest transaction. This is the
long-context member of the model zoo — histories shard over the mesh's
sequence axis and attention runs as ring attention
(ccfd_tpu/ops/ring_attention.py) when L exceeds one chip's comfort.

TPU-first choices: d_model/heads sized to 128-lane multiples, bf16 matmuls
with f32 accumulation, pre-norm blocks, sinusoidal positions (no trainable
position table to shard), last-token readout (streaming scoring semantics:
"given the history, how suspicious is the newest transaction?").
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES
from ccfd_tpu.ops.ring_attention import reference_attention
from ccfd_tpu.ops.seq_attention import attention as serving_attention

Params = Mapping[str, Any]

D_MODEL = 128
N_HEADS = 4
N_BLOCKS = 2
MLP_MULT = 4


def init(
    key: jax.Array,
    num_features: int = NUM_FEATURES,
    d_model: int = D_MODEL,
    n_blocks: int = N_BLOCKS,
) -> Params:
    keys = jax.random.split(key, 2 + 4 * n_blocks)
    k = iter(range(len(keys)))

    def dense(kk, fan_in, shape):
        return jax.random.normal(keys[kk], shape, jnp.float32) * jnp.sqrt(1.0 / fan_in)

    blocks = []
    for _ in range(n_blocks):
        blocks.append(
            {
                "ln1": {"scale": jnp.ones((d_model,)), "bias": jnp.zeros((d_model,))},
                "qkv": {"w": dense(next(k), d_model, (d_model, 3 * d_model)),
                        "b": jnp.zeros((3 * d_model,))},
                "proj": {"w": dense(next(k), d_model, (d_model, d_model)),
                         "b": jnp.zeros((d_model,))},
                "ln2": {"scale": jnp.ones((d_model,)), "bias": jnp.zeros((d_model,))},
                "mlp_in": {"w": dense(next(k), d_model, (d_model, MLP_MULT * d_model)),
                           "b": jnp.zeros((MLP_MULT * d_model,))},
                "mlp_out": {"w": dense(next(k), MLP_MULT * d_model,
                                       (MLP_MULT * d_model, d_model)),
                            "b": jnp.zeros((d_model,))},
            }
        )
    return {
        "norm": {
            "mu": jnp.zeros((num_features,), jnp.float32),
            "sigma": jnp.ones((num_features,), jnp.float32),
        },
        "embed": {"w": dense(next(k), num_features, (num_features, d_model)),
                  "b": jnp.zeros((d_model,))},
        "blocks": blocks,
        "head": {
            "ln": {"scale": jnp.ones((d_model,)), "bias": jnp.zeros((d_model,))},
            "w": dense(next(k), d_model, (d_model, 1)),
            "b": jnp.zeros((1,)),
        },
    }


def set_normalizer(params: Params, mean: np.ndarray, std: np.ndarray) -> Params:
    sigma = np.where(np.asarray(std) == 0.0, 1.0, np.asarray(std))
    out = dict(params)
    out["norm"] = {
        "mu": jnp.asarray(mean, jnp.float32),
        "sigma": jnp.asarray(sigma, jnp.float32),
    }
    return out


def _layer_norm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias).astype(x.dtype)


def _positions(length: int, d_model: int) -> jax.Array:
    pos = jnp.arange(length)[:, None].astype(jnp.float32)
    dim = jnp.arange(d_model // 2)[None, :].astype(jnp.float32)
    freq = jnp.exp(-jnp.log(10000.0) * 2.0 * dim / d_model)
    angles = pos * freq
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def logits(
    params: Params,
    x: jax.Array,
    compute_dtype=jnp.bfloat16,
    attention_fn: Callable[..., jax.Array] | None = None,
    n_heads: int = N_HEADS,
) -> jax.Array:
    """(B, L, F) -> (B,) fraud logit for the last transaction in each history."""
    attn = attention_fn or reference_attention
    mu = jax.lax.stop_gradient(params["norm"]["mu"])
    sigma = jax.lax.stop_gradient(params["norm"]["sigma"])
    h = ((x - mu) / sigma).astype(compute_dtype)
    h = jnp.einsum("blf,fd->bld", h, params["embed"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    h = (h + params["embed"]["b"]).astype(compute_dtype)
    batch, length, d_model = h.shape
    h = h + _positions(length, d_model).astype(compute_dtype)[None]

    head_dim = d_model // n_heads
    for blk in params["blocks"]:
        z = _layer_norm(h, blk["ln1"]["scale"], blk["ln1"]["bias"])
        qkv = jnp.einsum("bld,de->ble", z, blk["qkv"]["w"].astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        qkv = (qkv + blk["qkv"]["b"]).astype(compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(batch, length, n_heads, head_dim).transpose(0, 2, 1, 3)

        a = attn(heads(q), heads(k), heads(v))  # (B, H, L, Dh)
        a = a.transpose(0, 2, 1, 3).reshape(batch, length, d_model)
        a = jnp.einsum("bld,de->ble", a.astype(compute_dtype),
                       blk["proj"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        h = h + (a + blk["proj"]["b"]).astype(compute_dtype)

        z = _layer_norm(h, blk["ln2"]["scale"], blk["ln2"]["bias"])
        m = jnp.einsum("bld,de->ble", z, blk["mlp_in"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        m = jax.nn.gelu((m + blk["mlp_in"]["b"]).astype(jnp.float32)).astype(compute_dtype)
        m = jnp.einsum("ble,ed->bld", m, blk["mlp_out"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        h = h + (m + blk["mlp_out"]["b"]).astype(compute_dtype)

    last = h[:, -1, :]
    last = _layer_norm(last, params["head"]["ln"]["scale"], params["head"]["ln"]["bias"])
    z = jnp.einsum("bd,do->bo", last.astype(compute_dtype),
                   params["head"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    return (z + params["head"]["b"]).reshape(batch)


@partial(jax.jit, static_argnames=("compute_dtype",))
def apply(params: Params, x: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    """(B, L, F) -> (B,) proba_1 for the newest transaction."""
    return jax.nn.sigmoid(logits(params, x, compute_dtype))


def logits_readout(
    params: Params,
    x: jax.Array,
    compute_dtype=jnp.bfloat16,
    attention_fn: Callable[..., jax.Array] | None = None,
    n_heads: int = N_HEADS,
    pos_length: int | None = None,
) -> jax.Array:
    """Serving-path ``logits``: the LAST block computes only the readout
    token's output.

    Only position L-1 survives past the final block (``logits`` takes
    ``h[:, -1, :]``), so the last block's q-projection, attention scores,
    proj and MLP are needed for ONE position — its K/V (and every earlier
    block, whose outputs all feed the last block's attention) still run
    over the full sequence. Same params, same math, same numbers modulo
    float reassociation (parity asserted in tests/test_seq.py); the
    saving is the last block's O(L) proj+MLP work, the dominant per-token
    cost at serving time (~1.6x at n_blocks=2).

    ``pos_length``: anchor positional encodings as the LAST ``L`` rows of
    a ``pos_length``-long table. The serving L-bucket ladder dispatches a
    short window ``hist[:, -lb:]`` of a length-``pos_length`` history;
    under the full-L path (zero left-pad) the real tokens sit at
    positions ``pos_length-f .. pos_length-1``, so the short executable
    must give them the SAME encodings — without this, a customer's
    tokens would shift position at every ladder crossover. ``None``
    (default) anchors at ``x``'s own length — identical to ``logits``.

    Left to itself it attends with ``ops/seq_attention.py::attention``:
    the full blocks through the kernel that keeps the scores on the chip
    where their shape admits it, ``reference_attention`` elsewhere (the
    readout block's single query always). ``logits`` keeps
    ``reference_attention``: it is what training differentiates.
    """
    attn = attention_fn or serving_attention
    mu = jax.lax.stop_gradient(params["norm"]["mu"])
    sigma = jax.lax.stop_gradient(params["norm"]["sigma"])
    h = ((x - mu) / sigma).astype(compute_dtype)
    h = jnp.einsum("blf,fd->bld", h, params["embed"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    h = (h + params["embed"]["b"]).astype(compute_dtype)
    batch, length, d_model = h.shape
    pos = _positions(pos_length or length, d_model)[-length:]
    h = h + pos.astype(compute_dtype)[None]
    head_dim = d_model // n_heads

    def heads(t, lq):
        return t.reshape(batch, lq, n_heads, head_dim).transpose(0, 2, 1, 3)

    blocks = params["blocks"]
    for blk in blocks[:-1]:
        z = _layer_norm(h, blk["ln1"]["scale"], blk["ln1"]["bias"])
        qkv = jnp.einsum("bld,de->ble", z, blk["qkv"]["w"].astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        qkv = (qkv + blk["qkv"]["b"]).astype(compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        a = attn(heads(q, length), heads(k, length), heads(v, length))
        a = a.transpose(0, 2, 1, 3).reshape(batch, length, d_model)
        a = jnp.einsum("bld,de->ble", a.astype(compute_dtype),
                       blk["proj"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        h = h + (a + blk["proj"]["b"]).astype(compute_dtype)
        z = _layer_norm(h, blk["ln2"]["scale"], blk["ln2"]["bias"])
        m = jnp.einsum("bld,de->ble", z, blk["mlp_in"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        m = jax.nn.gelu((m + blk["mlp_in"]["b"]).astype(jnp.float32)).astype(compute_dtype)
        m = jnp.einsum("ble,ed->bld", m, blk["mlp_out"]["w"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)
        h = h + (m + blk["mlp_out"]["b"]).astype(compute_dtype)

    # last block: K/V over the full sequence, everything else readout-only
    blk = blocks[-1]
    z = _layer_norm(h, blk["ln1"]["scale"], blk["ln1"]["bias"])
    w_qkv = blk["qkv"]["w"].astype(compute_dtype)
    b_qkv = blk["qkv"]["b"]
    kv = jnp.einsum("bld,de->ble", z, w_qkv[:, d_model:],
                    preferred_element_type=jnp.float32)
    kv = (kv + b_qkv[d_model:]).astype(compute_dtype)
    k, v = jnp.split(kv, 2, axis=-1)
    q = jnp.einsum("bld,de->ble", z[:, -1:, :], w_qkv[:, :d_model],
                   preferred_element_type=jnp.float32)
    q = (q + b_qkv[:d_model]).astype(compute_dtype)
    a = attn(heads(q, 1), heads(k, length), heads(v, length))  # (B, H, 1, Dh)
    a = a.transpose(0, 2, 1, 3).reshape(batch, 1, d_model)
    a = jnp.einsum("bld,de->ble", a.astype(compute_dtype),
                   blk["proj"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    hl = h[:, -1:, :] + (a + blk["proj"]["b"]).astype(compute_dtype)
    z = _layer_norm(hl, blk["ln2"]["scale"], blk["ln2"]["bias"])
    m = jnp.einsum("bld,de->ble", z, blk["mlp_in"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    m = jax.nn.gelu((m + blk["mlp_in"]["b"]).astype(jnp.float32)).astype(compute_dtype)
    m = jnp.einsum("ble,ed->bld", m, blk["mlp_out"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    hl = hl + (m + blk["mlp_out"]["b"]).astype(compute_dtype)

    last = hl[:, 0, :]
    last = _layer_norm(last, params["head"]["ln"]["scale"], params["head"]["ln"]["bias"])
    z = jnp.einsum("bd,do->bo", last.astype(compute_dtype),
                   params["head"]["w"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    return (z + params["head"]["b"]).reshape(batch)


@partial(jax.jit, static_argnames=("compute_dtype", "pos_length"))
def apply_serving(params: Params, x: jax.Array,
                  compute_dtype=jnp.bfloat16,
                  pos_length: int | None = None) -> jax.Array:
    """Serving twin of :func:`apply` built on :func:`logits_readout` —
    what :class:`~ccfd_tpu.serving.history.SeqScorer` dispatches
    (``pos_length`` = the store's full L, so short L-bucket windows keep
    full-path positional encodings)."""
    return jax.nn.sigmoid(
        logits_readout(params, x, compute_dtype, pos_length=pos_length))


def loss_fn(params: Params, x: jax.Array, y: jax.Array,
            pos_weight: float = 8.0, compute_dtype=jnp.bfloat16,
            attention_fn=None) -> jax.Array:
    from ccfd_tpu.models.losses import weighted_bce_from_logits

    z = logits(params, x, compute_dtype, attention_fn=attention_fn)
    return weighted_bce_from_logits(z, y, pos_weight)
