"""Int8 quantized MLP serving for fraud-scorer accuracy at reduced precision.

Architectural rationale: TPU MXUs execute int8 x int8 -> int32 matmuls at
up to twice the bf16 rate, and int8 weights/activations halve the HBM and
H2D bytes again over bf16 — on a wire-bound attachment that is the larger
win. NOTE these are the hardware's numbers, not this model's: ``mlp_q8``
has no recorded on-TPU throughput yet (no cell of the benchmark serves
it: ROADMAP C4; accuracy IS measured — see below and BASELINE.md "Model
variants"). Until a cell lands, the claim this module makes is accuracy
preservation, not speed. This module quantizes the flagship MLP
(models/mlp.py) for inference:

- **Weights**: symmetric per-output-channel int8 at quantization time
  (``quantize_mlp``): scale_o = max|W[:, o]| / 127. Per-channel keeps the
  widest layer's dynamic range without per-group bookkeeping.
- **Activations**: symmetric per-row dynamic int8 at run time: one amax
  per row, computed fused into the surrounding elementwise ops by XLA.
  Dynamic beats static calibration here because transaction feature rows
  vary wildly in magnitude (Amount spans cents to thousands).
- **Accumulation**: int32 via ``preferred_element_type`` — exact; the only
  rounding is the two quantizations. Dequant + bias + relu stay f32.

The int8 graph registers as model ``mlp_q8`` so the whole serving stack
(Scorer bucketing/warmup/swap, REST server, router) picks it up by name;
``apply_numpy`` implements the SAME quantized math for the host tier —
host and device disagree only in float rounding, not quantization.

Accuracy contract (asserted in tests/test_quant.py): AUC within 2e-3 of
the f32 forward and probabilities within ~0.03 — fraud routing decides
against FRAUD_THRESHOLD=0.5 (reference deploy/router.yaml:69-70), far
coarser than int8 noise.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

Params = Mapping[str, Any]

_EPS = 1e-8
# finite ceiling for the host-tier per-layer activations: far above any
# healthy model's range, far below float32 overflow (see apply_numpy)
_H_CLAMP = 1e30


def quantize_mlp(params: Params) -> Params:
    """f32 MLP params (models/mlp.py layout) -> int8 inference params.

    Returns ``{"norm": {...f32...}, "layers": [{"wq": int8 (in, out),
    "scale": f32 (out,), "b": f32 (out,)}, ...]}``.
    """
    out_layers = []
    for layer in params["layers"]:
        w = np.asarray(layer["w"], np.float32)
        scale = np.abs(w).max(axis=0) / 127.0
        scale = np.maximum(scale, _EPS)
        wq = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
        out_layers.append({
            "wq": jnp.asarray(wq),
            "scale": jnp.asarray(scale, jnp.float32),
            "b": jnp.asarray(np.asarray(layer["b"], np.float32)),
        })
    return {
        "norm": {
            "mu": jnp.asarray(np.asarray(params["norm"]["mu"], np.float32)),
            "sigma": jnp.asarray(np.asarray(params["norm"]["sigma"], np.float32)),
        },
        "layers": out_layers,
    }


def _quantize_rows(h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8: (B, F) f32 -> ((B, F) int8, (B,) f32 scale)."""
    amax = jnp.max(jnp.abs(h), axis=1)
    s = jnp.maximum(amax / 127.0, _EPS)
    q = jnp.clip(jnp.rint(h / s[:, None]), -127, 127).astype(jnp.int8)
    return q, s


def _q_dense(h: jax.Array, layer: Mapping[str, Any]) -> jax.Array:
    """One quantized dense layer: f32 in, f32 out, int8 MXU matmul inside."""
    q, s_x = _quantize_rows(h)
    acc = jax.lax.dot_general(
        q, layer["wq"], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * s_x[:, None] * layer["scale"][None, :] + layer["b"]


def logits(params: Params, x: jax.Array) -> jax.Array:
    h = (x.astype(jnp.float32) - params["norm"]["mu"]) / params["norm"]["sigma"]
    layers = params["layers"]
    for layer in layers[:-1]:
        h = jax.nn.relu(_q_dense(h, layer))
    return _q_dense(h, layers[-1]).reshape(x.shape[0])


@jax.jit
def apply(params: Params, x: jax.Array) -> jax.Array:
    """proba_1 per row: (B, F) -> (B,), int8 matmuls on the MXU."""
    return jax.nn.sigmoid(logits(params, x))


def apply_numpy(params: Params, x: np.ndarray) -> np.ndarray:
    """Host-tier forward with the SAME quantized math (int32 accumulate)."""
    from ccfd_tpu.utils.metrics_math import stable_sigmoid

    h = (np.asarray(x, np.float32) - np.asarray(params["norm"]["mu"])) / np.asarray(
        params["norm"]["sigma"]
    )
    layers = params["layers"]
    for li, layer in enumerate(layers):
        amax = np.abs(h).max(axis=1)
        s_x = np.maximum(amax / 127.0, _EPS)
        q = np.clip(np.rint(h / s_x[:, None]), -127, 127).astype(np.int8)
        acc = q.astype(np.int32) @ np.asarray(layer["wq"], np.int32)
        # scales combine FIRST: with a degenerate (activation-exploding)
        # model, acc * s_x can overflow float32 to inf and a zero weight
        # channel (scale 0) then turns it into nan (inf * 0); the combined
        # per-(row, channel) scale keeps every factor finite, and the clamp
        # stops an inf from one layer poisoning the next layer's s_x.
        # For healthy models both are no-ops modulo float rounding.
        h = acc.astype(np.float32) * (
            s_x[:, None] * np.asarray(layer["scale"], np.float32)[None, :]
        ) + np.asarray(layer["b"], np.float32)
        h = np.clip(h, -_H_CLAMP, _H_CLAMP)
        if li < len(layers) - 1:
            h = np.maximum(h, 0.0)
    return stable_sigmoid(h.reshape(x.shape[0]))


def register(base_params: Params | None = None) -> None:
    """Register the quantized graph as model ``mlp_q8``.

    ``init`` quantizes a fresh (or provided) f32 MLP so ``Scorer(
    model_name="mlp_q8")`` works standalone; production flows call
    ``quantize_mlp`` on trained params and pass them explicitly.
    """
    from ccfd_tpu.models import mlp
    from ccfd_tpu.models.registry import ModelSpec, register_model

    def init(key=None, **kw):
        p = base_params if base_params is not None else mlp.init(
            key if key is not None else jax.random.PRNGKey(0), **kw
        )
        if "norm" not in p:
            p = mlp.set_normalizer(
                p, np.zeros(p["layers"][0]["w"].shape[0], np.float32),
                np.ones(p["layers"][0]["w"].shape[0], np.float32),
            )
        return quantize_mlp(p)

    register_model(
        ModelSpec("mlp_q8", init, apply, logits, trainable=False,
                  apply_numpy=apply_numpy)
    )
