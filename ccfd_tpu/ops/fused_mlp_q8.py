"""Pallas TPU kernel: fully fused int8-quantized MLP fraud scoring.

The int8 sibling of :mod:`ccfd_tpu.ops.fused_mlp`: same tiny-model/
huge-batch serving shape (weights resident in VMEM for the whole grid, one
HBM read of x and one write of the probabilities), but the two hidden
matmuls run int8 x int8 -> int32 on the MXU — the mode the systolic array
executes at up to twice the bf16 rate — and the weights sit in VMEM at a
quarter of f32.

The math is EXACTLY :func:`ccfd_tpu.ops.quant.logits` (the served XLA
``mlp_q8`` graph): normalize f32 -> per-row symmetric int8 requantization
before every layer -> int32 accumulate -> f32 dequant + bias (+ relu).
Differences from the XLA graph are layout only:

- activations never round-trip to HBM between layers (the XLA path
  materializes each layer's output);
- the last layer's int math runs elementwise on the VPU in f32: products
  of two int8 values and their 256-term partial sums are integers below
  2^24, all exactly representable in f32, so the result equals the XLA
  path's int32 accumulate bit-for-bit before the final dequant;
- rows ship as f32, exactly like the XLA path receives them, so the
  kernel is numerically indistinguishable from the served graph
  (max prob delta ~1e-7, asserted in tests/test_fused_q8.py).  bf16 rows
  would halve H2D bytes but double the effective quantization noise
  (measured 0.058 max prob delta vs the XLA graph) — the int8 path's
  accuracy budget is already spent on weight+activation quantization, so
  the wire keeps f32.

On non-TPU backends the kernel runs under ``interpret=True`` so the CPU
test mesh exercises the identical body (SURVEY.md §4).

Reference parity context: the quantized graph serves the same Seldon
REST contract as the reference's ``modelfull``
(/root/reference/deploy/model/modelfull.json:37-44); quantization itself
has no reference analog — it exists for the TPU serving regime.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

# geometry and the feature/row pad helpers are the bf16 kernel's — one
# source of truth for the lane width and tiling defaults
from ccfd_tpu.ops.fused_mlp import (  # noqa: E402
    DEFAULT_TILE,
    LANE,
    _pad_to as _pad_rows,
    fit_tile,
    pad_features,
)

INPUT_DTYPE = "float32"  # wire format for rows: exact parity with XLA q8
_EPS = 1e-8


def fold_for_kernel(params: Mapping[str, Any]) -> dict[str, jax.Array]:
    """quantized MLP params (ops/quant.py layout) -> kernel weights.

    The normalizer CANNOT be folded into int8 weights the way the f32
    kernel folds it (per-input scaling would break the per-output-channel
    quantization grid), so mu / sigma ride along as f32 vectors and the
    kernel normalizes explicitly — as a DIVISION by raw sigma, exactly
    like quant.logits: multiplying by a precomputed reciprocal differs in
    the last ulp, which can flip a quantization step at a rounding
    boundary (measured: up to 4e-3 prob delta on large-magnitude
    normalizers).  Padded feature columns get mu = 0 / sigma = 1, so
    padded features normalize to exactly 0 and the zero-padded rows of
    w1q contribute exactly 0 to the accumulate.
    """
    layers = params["layers"]
    if len(layers) != 3 or "wq" not in layers[0]:
        raise KeyError("fused q8 kernel expects a 3-layer quantized MLP")
    mu = np.asarray(params["norm"]["mu"], np.float32)
    sigma = np.asarray(params["norm"]["sigma"], np.float32)
    n_feat = mu.shape[0]
    if n_feat > LANE:
        raise ValueError(f"{n_feat} features > lane width {LANE}")
    w1q = np.asarray(layers[0]["wq"], np.int8)
    if w1q.shape[0] != n_feat:
        raise ValueError("normalizer/layer-0 feature-count mismatch")
    # w3 as f32: int8 products and their partial sums stay integer-exact
    # in f32 (< 2^24), see module docstring.  That bound holds only while
    # hidden <= 2^24 / 127^2 = 1040; the C++ front refuses wider models at
    # install (httpfront.cpp ccfd_front_set_host_q8_model) and the kernel
    # must refuse them too — hiddens are multiples of 128, so 1152+ is a
    # legal config that would silently break the asserted bit-parity with
    # the XLA int32 accumulate (ADVICE r4).
    hidden_last = int(np.asarray(layers[2]["wq"]).shape[0])
    if hidden_last > 1040:
        raise ValueError(
            f"fused q8 kernel: last-layer input width {hidden_last} > 1040 "
            "breaks the integer-exact f32 accumulate (2^24 bound); "
            "serve this model via the XLA mlp_q8 graph instead")
    w3f = np.asarray(layers[2]["wq"], np.float32).reshape(1, -1)
    return {
        "mu": jnp.asarray(np.pad(mu, (0, LANE - n_feat))),
        "sigma": jnp.asarray(np.pad(sigma, (0, LANE - n_feat),
                                    constant_values=1.0)),
        "w1q": jnp.asarray(_pad_rows(w1q, LANE)),  # (128, H) int8
        "s1": jnp.asarray(np.asarray(layers[0]["scale"], np.float32)),
        "b1": jnp.asarray(np.asarray(layers[0]["b"], np.float32)),
        "w2q": jnp.asarray(np.asarray(layers[1]["wq"], np.int8)),  # (H, H)
        "s2": jnp.asarray(np.asarray(layers[1]["scale"], np.float32)),
        "b2": jnp.asarray(np.asarray(layers[1]["b"], np.float32)),
        "w3f": jnp.asarray(w3f),  # (1, H) f32 holding int8 values
        "s3": jnp.asarray(np.asarray(layers[2]["scale"], np.float32)),
        "b3": jnp.asarray(np.asarray(layers[2]["b"], np.float32)),
    }


def _rowquant(h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 (same math as quant._quantize_rows)."""
    amax = jnp.max(jnp.abs(h), axis=1, keepdims=True)
    s = jnp.maximum(amax / 127.0, _EPS)
    q = jnp.clip(jnp.rint(h / s), -127, 127).astype(jnp.int8)
    return q, s


def _kernel(x_ref, mu_ref, sigma_ref, w1_ref, s1_ref, b1_ref,
            w2_ref, s2_ref, b2_ref, w3_ref, s3_ref, b3_ref, out_ref):
    x = x_ref[:].astype(jnp.float32)
    h = (x - mu_ref[:]) / sigma_ref[:]
    # layer 1: int8 MXU matmul, int32 accumulate
    q, sx = _rowquant(h)
    acc = jnp.dot(q, w1_ref[:], preferred_element_type=jnp.int32)
    h = jnp.maximum(acc.astype(jnp.float32) * sx * s1_ref[:] + b1_ref[:], 0.0)
    # layer 2
    q, sx = _rowquant(h)
    acc = jnp.dot(q, w2_ref[:], preferred_element_type=jnp.int32)
    h = jnp.maximum(acc.astype(jnp.float32) * sx * s2_ref[:] + b2_ref[:], 0.0)
    # layer 3 as an integer-exact f32 elementwise reduce on the VPU
    q, sx = _rowquant(h)
    z = jnp.sum(q.astype(jnp.float32) * w3_ref[:], axis=1, keepdims=True)
    out_ref[:] = jax.nn.sigmoid(z * sx * s3_ref[:] + b3_ref[:])


def _xmap(i):
    return (i, 0)


def _const2(i):
    return (0, 0)


def _const1(i):
    return (0,)


def _call_kernel(kernel_fn, lead_kinds, lead_arrays, kernel_params,
                 tile, interpret):
    """Shared pallas_call scaffolding for both q8 entry points: the lead
    inputs differ, the 9 VMEM-resident weight specs do not.

    ``lead_kinds``: one entry per lead array — ``("tiled", width)`` for a
    batch-tiled (tile, width) block, ``("const", length)`` for a
    grid-constant 1-D vector.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch = lead_arrays[0].shape[0]
    if batch % tile != 0:
        raise ValueError(f"batch {batch} not a multiple of tile {tile}")
    hidden = kernel_params["w2q"].shape[0]
    mem = pltpu.VMEM  # weights resident in VMEM for the whole grid
    lead_specs = [
        pl.BlockSpec((tile, dim), _xmap, memory_space=mem)
        if kind == "tiled"
        else pl.BlockSpec((dim,), _const1, memory_space=mem)
        for kind, dim in lead_kinds
    ]
    weight_specs = [
        pl.BlockSpec((LANE, hidden), _const2, memory_space=mem),
        pl.BlockSpec((hidden,), _const1, memory_space=mem),
        pl.BlockSpec((hidden,), _const1, memory_space=mem),
        pl.BlockSpec((hidden, hidden), _const2, memory_space=mem),
        pl.BlockSpec((hidden,), _const1, memory_space=mem),
        pl.BlockSpec((hidden,), _const1, memory_space=mem),
        pl.BlockSpec((1, hidden), _const2, memory_space=mem),
        pl.BlockSpec((1,), _const1, memory_space=mem),
        pl.BlockSpec((1,), _const1, memory_space=mem),
    ]
    out = pl.pallas_call(
        kernel_fn,
        out_shape=jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        grid=(batch // tile,),
        in_specs=lead_specs + weight_specs,
        out_specs=pl.BlockSpec((tile, 1), _xmap, memory_space=mem),
        interpret=interpret,
    )(
        *lead_arrays,
        kernel_params["w1q"],
        kernel_params["s1"],
        kernel_params["b1"],
        kernel_params["w2q"],
        kernel_params["s2"],
        kernel_params["b2"],
        kernel_params["w3f"],
        kernel_params["s3"],
        kernel_params["b3"],
    )
    return out.reshape(batch)


@partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_mlp_q8_score(
    kernel_params: Mapping[str, jax.Array],
    x: jax.Array,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jax.Array:
    """(B, F<=128) rows -> (B,) float32 proba.  B must be a tile multiple.
    f32 rows are the contract (exact parity with the XLA q8 graph); other
    float dtypes are accepted and widened/rounded to f32 first — including
    bf16, whose widening is lossless and keeps the kernel on one wire
    dtype (a bf16 fast path here would silently ship the degraded
    0.058-max-prob-delta behavior the module docstring warns against,
    with stricter sublane tiling on small fit_tile values; ADVICE r4)."""
    x = x.astype(jnp.float32)
    x = pad_features(x)
    return _call_kernel(
        _kernel,
        [("tiled", LANE), ("const", LANE), ("const", LANE)],
        (x, kernel_params["mu"], kernel_params["sigma"]),
        kernel_params, tile, interpret,
    )


# uniform entry point for Scorer's fused-module dispatch
fused_score = fused_mlp_q8_score


# ---------------------------------------------------------------------------
# int8-at-the-edge wire path: the host normalizes and row-quantizes, rows
# ship as int8 + one f32 scale each (34 B/row vs 120 B f32, 3.5x fewer
# H2D bytes), and the kernel starts straight at the first MXU matmul.
# Bit-identical to the full kernel / XLA graph: the host performs the
# model's OWN first requantization, just on the other side of the wire.
# Where H2D dominates the serving hop (the reason the bf16 kernel ships
# bf16 rows), this is the q8 path's wire lever; the numpy quantize cost
# rides the host, so the tradeoff has to be measured, not assumed (no
# cell of the benchmark serves ``mlp_q8`` yet: ROADMAP C4). The two sides of the wire divide in different hardware:
# on the chip the host's (x - mu) / sigma and the device's can differ in
# the last ulp, which moves a quantisation step for an occasional row
# (max 1.8e-3 in probability against the device's XLA graph in one probe
# of 16,384 rows on a v5e, PERF.md Findings PR 21); in interpret mode on
# the CPU both sides are the same arithmetic and parity is bit-exact.
# ---------------------------------------------------------------------------


def prequantize_rows_numpy(
    kernel_params: Mapping[str, Any], x: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side normalize + per-row symmetric int8 quantization.

    (B, F<=128) f32 rows -> ((B, F) int8, (B, 1) f32 scales), the exact
    math of the kernel's own first _rowquant (and quant._quantize_rows).
    The int8 rows stay UNPADDED — the wire carries F bytes per row; the
    device pads to the lane width inside the jit (padded columns quantize
    to exactly 0 either way, so the scales are unaffected).
    """
    mu = np.asarray(kernel_params["mu"], np.float32)
    sigma = np.asarray(kernel_params["sigma"], np.float32)
    x = np.asarray(x, np.float32)
    n_feat = x.shape[1]
    # DIVISION by raw sigma, exactly like quant.logits (see fold_for_kernel)
    h = (x - mu[:n_feat]) / sigma[:n_feat]
    amax = np.max(np.abs(h), axis=1, keepdims=True)
    s = np.maximum(amax / 127.0, _EPS).astype(np.float32)
    q = np.clip(np.rint(h / s), -127, 127).astype(np.int8)
    return q, s


def _kernel_preq(q_ref, s_ref, w1_ref, s1_ref, b1_ref,
                 w2_ref, s2_ref, b2_ref, w3_ref, s3_ref, b3_ref, out_ref):
    sx = s_ref[:]
    acc = jnp.dot(q_ref[:], w1_ref[:], preferred_element_type=jnp.int32)
    h = jnp.maximum(acc.astype(jnp.float32) * sx * s1_ref[:] + b1_ref[:], 0.0)
    q, sx = _rowquant(h)
    acc = jnp.dot(q, w2_ref[:], preferred_element_type=jnp.int32)
    h = jnp.maximum(acc.astype(jnp.float32) * sx * s2_ref[:] + b2_ref[:], 0.0)
    q, sx = _rowquant(h)
    z = jnp.sum(q.astype(jnp.float32) * w3_ref[:], axis=1, keepdims=True)
    out_ref[:] = jax.nn.sigmoid(z * sx * s3_ref[:] + b3_ref[:])


@partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_mlp_q8_score_preq(
    kernel_params: Mapping[str, jax.Array],
    q: jax.Array,
    s: jax.Array,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jax.Array:
    """((B, F<=128) int8 rows, (B, 1) f32 scales) -> (B,) float32 proba.
    Rows are padded to the lane width on DEVICE, so the H2D wire carries
    only F int8 bytes per row (34 B/row vs f32's 120 at F=30)."""
    if q.dtype != jnp.int8:
        raise ValueError("q must be int8 rows (see prequantize_rows_numpy)")
    q = pad_features(q)
    return _call_kernel(
        _kernel_preq, [("tiled", LANE), ("tiled", 1)], (q, s),
        kernel_params, tile, interpret,
    )
