"""Int8 quantized sequence scorer: the seq transformer's serving variant.

The long-context sibling of :mod:`ccfd_tpu.ops.quant` (the ``mlp_q8``
graph), with the SAME quantization conventions so the zoo's two quantized
members share one accuracy story:

- **Weights**: symmetric per-output-channel int8 at quantization time
  (``quantize_seq``): scale_o = max|W[:, o]| / 127, for every dense weight
  in the transformer (embed, per-block qkv/proj/mlp_in/mlp_out, head).
- **Activations**: symmetric per-row dynamic int8 at run time — for the
  (B, L, D) streams each of the B*L token rows quantizes independently,
  exactly the per-row rule ``quant._quantize_rows`` applies to (B, F).
- **Accumulation**: int32 via ``preferred_element_type``; dequant + bias
  stay f32. Layer norms, softmax attention, GELU and the sinusoidal
  positions run in the compute dtype (bf16/f32) — they are O(L*D) against
  the matmuls' O(L*D^2) and carry the numerics the int8 grid would wreck.

On a TPU the MXU runs int8 x int8 -> int32 at up to twice the bf16 rate
and the weights ship/reside at a quarter of f32 — the same hardware
argument as ``mlp_q8``, here applied to the seq path, whose pace the
device program sets (PERF.md section 5). As with ``mlp_q8`` the claim
made on CPU captures is accuracy preservation, not speed.

Registered in the model zoo as ``seq_q8``; it reaches serving ONLY through
the lifecycle shadow lane (AUC/PSI guardrails against the bf16 champion —
tests/test_seq_lifecycle.py exercises both the promote and the reject
path), never by a blind swap.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.models.seq import N_HEADS, _layer_norm, _positions
from ccfd_tpu.ops.seq_attention import attention as serving_attention

Params = Mapping[str, Any]

_EPS = 1e-8


def _q_weight(w: Any) -> dict[str, jax.Array]:
    """(in, out) f32 weight -> {"wq" int8, "scale" f32 (out,)} — the
    per-output-channel rule of :func:`ccfd_tpu.ops.quant.quantize_mlp`."""
    w = np.asarray(w, np.float32)
    scale = np.maximum(np.abs(w).max(axis=0) / 127.0, _EPS)
    wq = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return {"wq": jnp.asarray(wq), "scale": jnp.asarray(scale, jnp.float32)}


def _q_dense_params(layer: Mapping[str, Any]) -> dict[str, jax.Array]:
    out = _q_weight(layer["w"])
    out["b"] = jnp.asarray(np.asarray(layer["b"], np.float32))
    return out


def quantize_seq(params: Params) -> Params:
    """f32/bf16 seq params (models/seq.py layout) -> int8 inference params.

    Layer norms, biases and the normalizer stay f32; every dense weight
    becomes {"wq", "scale", "b"}."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(np.asarray(a, np.float32)), dict(t))
    blocks = []
    for blk in params["blocks"]:
        blocks.append({
            "ln1": f32(blk["ln1"]),
            "qkv": _q_dense_params(blk["qkv"]),
            "proj": _q_dense_params(blk["proj"]),
            "ln2": f32(blk["ln2"]),
            "mlp_in": _q_dense_params(blk["mlp_in"]),
            "mlp_out": _q_dense_params(blk["mlp_out"]),
        })
    return {
        "norm": f32(params["norm"]),
        "embed": _q_dense_params(params["embed"]),
        "blocks": blocks,
        "head": {
            "ln": f32(params["head"]["ln"]),
            **_q_weight(params["head"]["w"]),
            "b": jnp.asarray(np.asarray(params["head"]["b"], np.float32)),
        },
    }


def is_quantized(params: Params) -> bool:
    """Structural sniff the serving layer keys variant dispatch on: a
    quantized seq tree carries int8 "wq" leaves where the bf16 tree has
    "w" (SeqScorer.swap_params re-binds its jitted apply off this)."""
    try:
        return "wq" in params["embed"] and "blocks" in params
    except (TypeError, KeyError):
        return False


def _rowquant_tokens(h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-token symmetric int8: (..., D) -> ((..., D) int8, (..., 1) f32)."""
    amax = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, _EPS)
    q = jnp.clip(jnp.rint(h.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s


def _q_dense(h: jax.Array, layer: Mapping[str, Any],
             compute_dtype) -> jax.Array:
    """One quantized dense over the token axis: (..., D_in) -> (..., D_out),
    int8 x int8 -> int32 inside, f32 dequant + bias, cast to compute dtype."""
    q, s = _rowquant_tokens(h)
    acc = jax.lax.dot_general(
        q, layer["wq"], (((q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * s * layer["scale"] + layer["b"]
    return out.astype(compute_dtype)


def logits(
    params: Params,
    x: jax.Array,
    compute_dtype=jnp.bfloat16,
    attention_fn: Callable[..., jax.Array] | None = None,
    n_heads: int = N_HEADS,
    pos_length: int | None = None,
) -> jax.Array:
    """(B, L, F) -> (B,) fraud logit; the seq.logits graph with every
    dense matmul int8-quantized. The last block computes readout-only,
    like :func:`ccfd_tpu.models.seq.logits_readout` (the serving shape —
    this variant exists for the serving path), and ``pos_length``
    right-anchors positional encodings the same way (short L-bucket
    windows keep the full-L path's token positions), and it attends as
    ``logits_readout`` does (``ops/seq_attention.py::attention``)."""
    attn = attention_fn or serving_attention
    mu = jax.lax.stop_gradient(params["norm"]["mu"])
    sigma = jax.lax.stop_gradient(params["norm"]["sigma"])
    h = ((x.astype(jnp.float32) - mu) / sigma)
    h = _q_dense(h, params["embed"], compute_dtype)
    batch, length, d_model = h.shape
    pos = _positions(pos_length or length, d_model)[-length:]
    h = h + pos.astype(compute_dtype)[None]
    head_dim = d_model // n_heads

    def heads(t, lq):
        return t.reshape(batch, lq, n_heads, head_dim).transpose(0, 2, 1, 3)

    blocks = params["blocks"]
    for blk in blocks[:-1]:
        z = _layer_norm(h, blk["ln1"]["scale"], blk["ln1"]["bias"])
        qkv = _q_dense(z, blk["qkv"], compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        a = attn(heads(q, length), heads(k, length), heads(v, length))
        a = a.transpose(0, 2, 1, 3).reshape(batch, length, d_model)
        h = h + _q_dense(a, blk["proj"], compute_dtype)
        z = _layer_norm(h, blk["ln2"]["scale"], blk["ln2"]["bias"])
        m = _q_dense(z, blk["mlp_in"], compute_dtype)
        m = jax.nn.gelu(m.astype(jnp.float32)).astype(compute_dtype)
        h = h + _q_dense(m, blk["mlp_out"], compute_dtype)

    # last block: K/V full, q (and everything after the attention) for
    # the readout token only — per-token row quantization is independent
    # across tokens, so projecting q from z[:, -1:] with the sliced
    # weight columns is numerically identical to slicing a full qkv
    blk = blocks[-1]
    z = _layer_norm(h, blk["ln1"]["scale"], blk["ln1"]["bias"])
    w_qkv = blk["qkv"]
    kv = _q_dense(z, {"wq": w_qkv["wq"][:, d_model:],
                      "scale": w_qkv["scale"][d_model:],
                      "b": w_qkv["b"][d_model:]}, compute_dtype)
    k, v = jnp.split(kv, 2, axis=-1)
    q = _q_dense(z[:, -1:, :], {"wq": w_qkv["wq"][:, :d_model],
                                "scale": w_qkv["scale"][:d_model],
                                "b": w_qkv["b"][:d_model]}, compute_dtype)
    a = attn(heads(q, 1), heads(k, length), heads(v, length))
    a = a.transpose(0, 2, 1, 3).reshape(batch, 1, d_model)
    hl = h[:, -1:, :] + _q_dense(a, blk["proj"], compute_dtype)
    z = _layer_norm(hl, blk["ln2"]["scale"], blk["ln2"]["bias"])
    m = _q_dense(z, blk["mlp_in"], compute_dtype)
    m = jax.nn.gelu(m.astype(jnp.float32)).astype(compute_dtype)
    hl = hl + _q_dense(m, blk["mlp_out"], compute_dtype)

    last = _layer_norm(hl[:, 0, :], params["head"]["ln"]["scale"],
                       params["head"]["ln"]["bias"])
    q, s = _rowquant_tokens(last)
    acc = jax.lax.dot_general(q, params["head"]["wq"],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    z = acc.astype(jnp.float32) * s * params["head"]["scale"] + params["head"]["b"]
    return z.reshape(batch)


@partial(jax.jit, static_argnames=("compute_dtype", "pos_length"))
def apply(params: Params, x: jax.Array, compute_dtype=jnp.bfloat16,
          pos_length: int | None = None) -> jax.Array:
    """(B, L, F) -> (B,) proba_1, int8 matmuls on the MXU."""
    return jax.nn.sigmoid(
        logits(params, x, compute_dtype, pos_length=pos_length))


# serving entry point: logits are already readout-optimized
apply_serving = apply


def register() -> None:
    """Register the seq family in the model zoo: ``seq`` (the bf16/f32
    champion graph) and ``seq_q8`` (this variant) resolve by name wherever
    models do — mirrors quant.register()'s ``mlp_q8``. Neither is
    trainable (the online trainer's step is the MLP's) and neither has a
    host-tier numpy forward; both apply over (B, L, F) histories, so the
    ROW Scorer cannot serve them — :class:`ccfd_tpu.serving.history.
    SeqScorer` is their serving layer (the operator special-cases
    ``model: seq``/``seq_q8`` accordingly)."""
    from ccfd_tpu.models import seq as seq_mod
    from ccfd_tpu.models.registry import (HistorySpec, ModelSpec,
                                          register_history, register_model)

    register_model(
        ModelSpec("seq", seq_mod.init, seq_mod.apply, seq_mod.logits,
                  trainable=False)
    )
    # the served programs: neither reads ``filled`` (no padding mask,
    # ROADMAP B0), so the seam does not hand it to them
    register_history(HistorySpec(
        "seq_q8", owns=is_quantized,
        make_apply=lambda dtype, plen, _cfg: (
            lambda p, xs: apply_serving(p, xs, dtype, pos_length=plen)),
        mesh_logits=logits))
    register_history(HistorySpec(
        "seq", owns=lambda p: "blocks" in p and not is_quantized(p),
        make_apply=lambda dtype, plen, _cfg: (
            lambda p, xs: seq_mod.apply_serving(p, xs, dtype,
                                                pos_length=plen)),
        mesh_logits=seq_mod.logits_readout))

    def init_q8(key=None, **kw):
        return quantize_seq(
            seq_mod.init(key if key is not None else jax.random.PRNGKey(0),
                         **kw))

    register_model(
        ModelSpec("seq_q8", init_q8, apply, logits, trainable=False)
    )
