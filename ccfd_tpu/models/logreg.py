"""Logistic-regression fraud scorer (parity with the reference ``modelfull``).

The reference serves a scikit-learn classifier in a Seldon pod
(reference deploy/model/modelfull.json:18-52, image ``nakfour/modelfull``)
returning a fraud probability ``proba_1`` per 30-feature row. Here the same
capability is a single fused affine + sigmoid under ``jax.jit``: feature
standardization (the sklearn ``StandardScaler`` stage) is *folded into* the
weights at conversion time, so the TPU hot path is one (B,30)x(30,) dot —
no separate normalize pass, nothing for XLA to schedule but one kernel.

Params are a plain pytree ``{"w": (F,), "b": ()}`` in float32. Scoring casts
to the configured compute dtype for the dot and accumulates in float32.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES

Params = Mapping[str, Any]


def init(key: jax.Array, num_features: int = NUM_FEATURES) -> Params:
    wkey, _ = jax.random.split(key)
    return {
        "w": jax.random.normal(wkey, (num_features,), jnp.float32) * 0.01,
        "b": jnp.zeros((), jnp.float32),
    }


def logits(params: Params, x: jax.Array, compute_dtype=jnp.float32) -> jax.Array:
    w = params["w"].astype(compute_dtype)
    z = jnp.dot(x.astype(compute_dtype), w, preferred_element_type=jnp.float32)
    return z + params["b"].astype(jnp.float32)


@partial(jax.jit, static_argnames=("compute_dtype",))
def apply(params: Params, x: jax.Array, compute_dtype=jnp.float32) -> jax.Array:
    """proba_1 for each row of x: (B, F) -> (B,)."""
    return jax.nn.sigmoid(logits(params, x, compute_dtype))


def apply_numpy(params: Params, x: np.ndarray) -> np.ndarray:
    """Pure-numpy forward (f32) for the serving host tier: small request
    batches skip the device round trip entirely (see mlp.apply_numpy)."""
    from ccfd_tpu.utils.metrics_math import stable_sigmoid

    z = np.asarray(x, np.float32) @ np.asarray(params["w"], np.float32)
    z = (z + np.float32(params["b"])).reshape(x.shape[0])
    return stable_sigmoid(z)


def fold_standardizer(
    w: np.ndarray, b: float, mean: np.ndarray, scale: np.ndarray
) -> Params:
    """Fold ``(x - mean) / scale`` into (w, b): w' = w/scale, b' = b - w·(mean/scale)."""
    scale = np.where(scale == 0.0, 1.0, scale)
    w_f = (np.asarray(w, np.float64) / scale).astype(np.float32)
    b_f = np.float32(b - np.dot(np.asarray(w, np.float64), mean / scale))
    return {"w": jnp.asarray(w_f), "b": jnp.asarray(b_f)}


def from_sklearn(clf, scaler=None) -> Params:
    """Convert a fitted sklearn LogisticRegression (+optional StandardScaler)."""
    w = np.asarray(clf.coef_).reshape(-1)
    b = float(np.asarray(clf.intercept_).reshape(()))
    if scaler is not None:
        return fold_standardizer(w, b, np.asarray(scaler.mean_), np.asarray(scaler.scale_))
    return {"w": jnp.asarray(w, jnp.float32), "b": jnp.asarray(b, jnp.float32)}


def fit_numpy(
    X: np.ndarray, y: np.ndarray, l2: float = 1.0, iters: int = 50
) -> Params:
    """Self-contained IRLS trainer (no sklearn): standardizes then folds back.

    Used by tests when scikit-learn is unavailable.
    """
    mean = X.mean(axis=0)
    scale = X.std(axis=0)
    scale = np.where(scale == 0.0, 1.0, scale)
    Xs = (X - mean) / scale
    n, f = Xs.shape
    Xb = np.concatenate([Xs, np.ones((n, 1))], axis=1)
    beta = np.zeros(f + 1)
    reg = np.eye(f + 1) * l2
    reg[-1, -1] = 0.0
    for _ in range(iters):
        z = Xb @ beta
        p = 1.0 / (1.0 + np.exp(-z))
        wgt = np.maximum(p * (1.0 - p), 1e-6)
        g = Xb.T @ (p - y) + reg @ beta
        H = (Xb * wgt[:, None]).T @ Xb + reg
        step = np.linalg.solve(H, g)
        beta = beta - step
        if np.max(np.abs(step)) < 1e-8:
            break
    return fold_standardizer(beta[:f], float(beta[f]), mean, scale)
