"""3-layer MLP tabular fraud scorer — the flagship TPU model.

BASELINE.json configs[2]: "3-layer MLP tabular scorer (jax.jit, single v5e
chip)". Design is MXU-first: hidden widths are multiples of 128 so every
matmul tiles exactly onto the 128x128 systolic array; compute runs in
bfloat16 with float32 accumulation (``preferred_element_type``); feature
standardization is a fused scale/shift at the input (folded constants, one
multiply-add that XLA fuses into the first matmul's producer).

Params are a plain pytree of float32 master weights:
  {"norm": {"mu": (F,), "sigma": (F,)},
   "layers": [{"w": (F,H), "b": (H,)}, {"w": (H,H), "b": (H,)}, {"w": (H,1), "b": (1,)}]}

The same ``apply`` serves single-chip jit scoring and the pjit-sharded
multi-chip path (ccfd_tpu/parallel): hidden dims shard over the "model" mesh
axis, batch over "data".
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES

Params = Mapping[str, Any]

DEFAULT_HIDDEN = 256  # multiple of 128 -> exact MXU tiling


def init(
    key: jax.Array,
    num_features: int = NUM_FEATURES,
    hidden: int = DEFAULT_HIDDEN,
    depth: int = 3,
) -> Params:
    dims = [num_features] + [hidden] * (depth - 1) + [1]
    keys = jax.random.split(key, depth)
    layers = []
    for i in range(depth):
        fan_in = dims[i]
        w = jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32)
        w = w * jnp.sqrt(2.0 / fan_in)
        layers.append({"w": w, "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    return {
        "norm": {
            "mu": jnp.zeros((num_features,), jnp.float32),
            "sigma": jnp.ones((num_features,), jnp.float32),
        },
        "layers": layers,
    }


def set_normalizer(params: Params, mean: np.ndarray, std: np.ndarray) -> Params:
    sigma = np.where(np.asarray(std) == 0.0, 1.0, np.asarray(std))
    return {
        "norm": {
            "mu": jnp.asarray(mean, jnp.float32),
            "sigma": jnp.asarray(sigma, jnp.float32),
        },
        "layers": params["layers"],
    }


def logits(params: Params, x: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    # the normalizer is data statistics, not a trainable parameter
    mu = jax.lax.stop_gradient(params["norm"]["mu"])
    sigma = jax.lax.stop_gradient(params["norm"]["sigma"])
    h = (x - mu) / sigma
    h = h.astype(compute_dtype)
    layers = params["layers"]
    for layer in layers[:-1]:
        h = jnp.dot(h, layer["w"].astype(compute_dtype), preferred_element_type=jnp.float32)
        h = jax.nn.relu(h + layer["b"])
        h = h.astype(compute_dtype)
    last = layers[-1]
    z = jnp.dot(h, last["w"].astype(compute_dtype), preferred_element_type=jnp.float32)
    return (z + last["b"]).reshape(x.shape[0])


@partial(jax.jit, static_argnames=("compute_dtype",))
def apply(params: Params, x: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    """proba_1 per row: (B, F) -> (B,)."""
    return jax.nn.sigmoid(logits(params, x, compute_dtype))


def apply_numpy(params: Params, x: np.ndarray) -> np.ndarray:
    """Pure-numpy forward (f32), semantically `apply` without a device.

    The float32 reference the device paths are compared against (the
    chip smoke, the parity tests), and what the wedge fallback, the
    challenger slot and an explicit host tier score with. Tolerance vs the
    bf16 device path is ~1e-2 in probability (asserted by tests); params
    must be host numpy arrays.
    """
    from ccfd_tpu.utils.metrics_math import stable_sigmoid

    h = (np.asarray(x, np.float32) - params["norm"]["mu"]) / params["norm"]["sigma"]
    layers = params["layers"]
    for layer in layers[:-1]:
        h = np.maximum(h @ layer["w"] + layer["b"], 0.0)
    last = layers[-1]
    z = (h @ last["w"] + last["b"]).reshape(x.shape[0])
    return stable_sigmoid(z)


def loss_fn(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    pos_weight: float = 1.0,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Weighted binary cross-entropy on logits (numerically stable)."""
    from ccfd_tpu.models.losses import weighted_bce_from_logits

    return weighted_bce_from_logits(logits(params, x, compute_dtype), y, pos_weight)


def fit_numpy_reference(
    X: np.ndarray,
    y: np.ndarray,
    hidden: int = 32,
    steps: int = 300,
    lr: float = 0.05,
    seed: int = 0,
) -> tuple[np.ndarray, float]:
    """Tiny numpy SGD MLP used only as an accuracy sanity reference in tests."""
    rng = np.random.default_rng(seed)
    mean, std = X.mean(0), np.where(X.std(0) == 0, 1.0, X.std(0))
    Xs = (X - mean) / std
    w1 = rng.normal(0, np.sqrt(2.0 / X.shape[1]), (X.shape[1], hidden))
    b1 = np.zeros(hidden)
    w2 = rng.normal(0, np.sqrt(2.0 / hidden), (hidden,))
    b2 = 0.0
    n = Xs.shape[0]
    for step in range(steps):
        idx = rng.integers(0, n, size=min(512, n))
        xb, yb = Xs[idx], y[idx]
        h = np.maximum(xb @ w1 + b1, 0.0)
        z = h @ w2 + b2
        p = 1.0 / (1.0 + np.exp(-z))
        g = (p - yb) / len(yb)
        gw2 = h.T @ g
        gb2 = g.sum()
        gh = np.outer(g, w2) * (h > 0)
        gw1 = xb.T @ gh
        gb1 = gh.sum(0)
        w1 -= lr * gw1
        b1 -= lr * gb1
        w2 -= lr * gw2
        b2 -= lr * gb2
    h = np.maximum(Xs @ w1 + b1, 0.0)
    p = 1.0 / (1.0 + np.exp(-(h @ w2 + b2)))
    return p, float(((p > 0.5) == (y > 0.5)).mean())
