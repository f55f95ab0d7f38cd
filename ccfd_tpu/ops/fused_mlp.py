"""Pallas TPU kernel: fully fused MLP fraud scoring.

The serving hot op is tiny-model/huge-batch: a 3-layer MLP whose weights
(~0.3 MB in bf16) fit in VMEM many times over, fed with tens of thousands
of 30-feature rows per dispatch. The fused kernel:

- keeps ALL weights resident in VMEM for the whole grid (BlockSpecs with a
  constant index map), so HBM traffic is exactly one read of x and one
  write of the probabilities — the theoretical minimum;
- normalization is pre-folded into W1/b1 (an affine composed with an
  affine), so the kernel body is 3 matmuls + 2 relus + a sigmoid on the
  VPU/MXU with zero intermediate HBM round-trips;
- features are zero-padded 30 -> 128 host-side once (weights likewise), so
  every matmul is exactly lane-aligned (128-wide) for the MXU;
- the grid tiles the batch; each program scores a (TILE, 128) slab in
  bfloat16 with float32 accumulation.

On non-TPU backends the same kernel runs under ``interpret=True`` so tests
exercise identical code paths on the CPU mesh (SURVEY.md §4).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES

LANE = 128  # TPU lane width: last-dim alignment target
DEFAULT_TILE = 512
INPUT_DTYPE = "bfloat16"  # wire format for rows: half the H2D bytes


def fit_tile(rows: int) -> int:
    """Largest power-of-two-ish tile <= DEFAULT_TILE dividing ``rows`` —
    the ONE tiling policy every caller (both kernels' dispatch paths)
    shares."""
    tile = min(rows, DEFAULT_TILE)
    while rows % tile:
        tile //= 2
    return tile


def _pad_to(a: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - a.shape[0]
    if pad <= 0:
        return a
    return np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)


def fold_for_kernel(params: Mapping[str, Any]) -> dict[str, jax.Array]:
    """MLP params (ccfd_tpu.models.mlp layout) -> kernel weights.

    Folds the standardizer into layer 0 and zero-pads the feature dim to the
    TPU lane width: with s = 1/sigma, (x - mu) * s @ W1 + b1 ==
    x @ (s[:, None] * W1) + (b1 - (mu * s) @ W1).
    """
    mu = np.asarray(params["norm"]["mu"], np.float32)
    sigma = np.asarray(params["norm"]["sigma"], np.float32)
    s = 1.0 / np.where(sigma == 0.0, 1.0, sigma)
    layers = params["layers"]
    if len(layers) != 3:
        raise ValueError("fused kernel expects a 3-layer MLP")
    w1 = np.asarray(layers[0]["w"], np.float32)
    b1 = np.asarray(layers[0]["b"], np.float32)
    w1_folded = s[:, None] * w1
    b1_folded = b1 - (mu * s) @ w1
    return {
        "w1": jnp.asarray(_pad_to(w1_folded, LANE)),  # (128, H)
        "b1": jnp.asarray(b1_folded),
        "w2": jnp.asarray(np.asarray(layers[1]["w"], np.float32)),
        "b2": jnp.asarray(np.asarray(layers[1]["b"], np.float32)),
        "w3": jnp.asarray(np.asarray(layers[2]["w"], np.float32)),  # (H, 1)
        "b3": jnp.asarray(np.asarray(layers[2]["b"], np.float32)),
    }


# ccfd-lint: hot-path
def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, out_ref):
    x = x_ref[:].astype(jnp.bfloat16)
    h = jnp.dot(x, w1_ref[:].astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    h = jnp.maximum(h + b1_ref[:], 0.0).astype(jnp.bfloat16)
    h = jnp.dot(h, w2_ref[:].astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    h = jnp.maximum(h + b2_ref[:], 0.0).astype(jnp.bfloat16)
    # final layer as an elementwise reduce: (T, H) * (H,) -> (T, 1)
    w3 = w3_ref[:].astype(jnp.bfloat16).reshape(1, -1)
    z = jnp.sum(
        h.astype(jnp.float32) * w3.astype(jnp.float32), axis=1, keepdims=True
    )
    out_ref[:] = jax.nn.sigmoid(z + b3_ref[:])


def pad_features(x: jax.Array) -> jax.Array:
    """(B, F) -> (B, 128) zero-padded."""
    b, f = x.shape
    if f == LANE:
        return x
    return jnp.pad(x, ((0, 0), (0, LANE - f)))


@partial(jax.jit, static_argnames=("tile", "interpret"))
# ccfd-lint: hot-path
def fused_mlp_score(
    kernel_params: Mapping[str, jax.Array],
    x: jax.Array,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jax.Array:
    """(B, F<=128) float or bfloat16 -> (B,) float32 proba. B must be a tile
    multiple. bfloat16 input is the fast path: the kernel computes in bf16
    regardless, and bf16 rows halve the host->HBM transfer for identical
    numerics."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)
    x = pad_features(x)
    batch = x.shape[0]
    if batch % tile != 0:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    hidden = kernel_params["w2"].shape[0]
    grid = (batch // tile,)

    def xmap(i):
        return (i, 0)

    def const(i):
        return (0, 0)

    mem = pltpu.VMEM  # weights resident in VMEM for the whole grid

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, LANE), xmap, memory_space=mem),
            pl.BlockSpec((LANE, hidden), const, memory_space=mem),
            pl.BlockSpec((hidden,), lambda i: (0,), memory_space=mem),
            pl.BlockSpec((hidden, hidden), const, memory_space=mem),
            pl.BlockSpec((hidden,), lambda i: (0,), memory_space=mem),
            pl.BlockSpec((hidden, 1), const, memory_space=mem),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=mem),
        ],
        out_specs=pl.BlockSpec((tile, 1), xmap, memory_space=mem),
        interpret=interpret,
    )(
        x,
        kernel_params["w1"],
        kernel_params["b1"],
        kernel_params["w2"],
        kernel_params["b2"],
        kernel_params["w3"],
        kernel_params["b3"],
    )
    return out.reshape(batch)


# uniform entry point for Scorer's fused-module dispatch (the q8 sibling
# ccfd_tpu/ops/fused_mlp_q8.py exposes the same name)
fused_score = fused_mlp_score


def make_score_fn(params: Mapping[str, Any], tile: int = DEFAULT_TILE):
    """Returns proba_fn(x_padded_batch) using the fused kernel; interpret mode
    is selected automatically off-TPU."""
    kp = fold_for_kernel(params)
    # Mosaic lowering needs real TPU hardware; everywhere else (the CPU test
    # mesh) the interpreter runs the identical kernel body.
    interpret = jax.default_backend() == "cpu"

    def score(x: jax.Array) -> jax.Array:
        return fused_mlp_score(kp, x, tile=tile, interpret=interpret)

    return score
