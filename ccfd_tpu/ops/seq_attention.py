"""Pallas TPU kernel: the seq family's full self-attention, scores on the chip.

The serving programs of ``seq`` / ``seq_q8`` run one block of full,
unmasked self-attention over every history row (the last block attends
from the readout query alone). As XLA compiles
:func:`~ccfd_tpu.ops.ring_attention.reference_attention` the float32 scores
of a 1,024-row dispatch (4.3 GB at L = 512) cross HBM three times: written
by q k^T, read by the softmax's sum, read by p v. Here they live and die in
VMEM:

- the grid runs over history rows (and query blocks where a row's scores
  would not fit the budget); a row's whole k and v for every head are
  2 x L x 128 values, so there is no rotation over key blocks and no
  online softmax: a plain max / exp / sum per query row;
- operands come in the (B, L, 128) layout the qkv projection produces and
  the output leaves in it: lane-dense tiles and DMAs, where a head's 32
  columns would fill a quarter of each;
- a head is a lane mask, not a slice: q with the other heads' lanes zeroed
  contracts over all 128 lanes (the zeros add nothing, and a 128-wide MXU
  pass costs what a 32-wide one does), and p v is taken 128 wide and kept
  where the head's lanes are;
- precision is ``reference_attention``'s: operands as given (bf16 on the
  serving path), q k^T accumulated in float32, scale, max, exp and sum in
  float32, p cast to v's dtype for p v, float32 accumulation, output in
  v's dtype. The one difference is the order of the sums: the division by
  the denominator comes after p v.

:func:`attention` is what the serving programs call by default: it picks
the kernel from what its arguments show while the program is traced and
gives every other shape ``reference_attention``, which stays the plain,
differentiable definition (the kernel has no derivative and must not reach
``jax.grad``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels
from ccfd_tpu.ops.ring_attention import reference_attention

LANE = 128  # the heads of a row side by side fill exactly one lane tile
QUERY_BLOCKS = (512, 256, 128)  # rows of queries a grid step may take
# float32 scores of one head in one grid step (query block x keys): with
# the probabilities beside them, two heads in flight and k, v, q and the
# output double-buffered this stays inside the 16 MiB a kernel may use
SCORE_BYTES = 2 << 20
SCOPE = "seq.attention"  # the device capture's name for either path
KERNEL = "seq_attention"  # the kernel's name: in the capture and in a jaxpr


def query_block(length: int) -> int | None:
    """Rows of queries a grid step takes at this history length: the
    largest block that tiles the length and whose scores fit the budget;
    None where the kernel does not run (a length that is no multiple of
    the lane width, or whose smallest block of scores is over budget)."""
    if length % LANE:
        return None
    for rows in QUERY_BLOCKS:
        if length % rows == 0 and rows * length * 4 <= SCORE_BYTES:
            return rows
    return None


def kernel_fits(q_shape: tuple, k_shape: tuple, dtype) -> bool:
    """Whether :func:`attention` runs the kernel on (B, H, L, Dh) operands
    of these shapes: full self-attention (queries as many as keys, so not
    the readout block's single query), heads that fill one lane tile, a
    length the kernel tiles within its VMEM budget, a dtype the kernels
    serve and a backend that runs them (``ops/kernels.py``). A mesh is not
    asked about: ``SeqScorer`` hands each device its rows itself."""
    return (
        len(q_shape) == 4
        and tuple(q_shape) == tuple(k_shape)
        and q_shape[1] * q_shape[3] == LANE
        and query_block(q_shape[2]) is not None
        and kernels.serves(dtype)
        and kernels.backend_runs_pallas()
    )


# ccfd-lint: hot-path
def _kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # (rows, 128), (L, 128), (L, 128)
    head_dim = q.shape[-1] // heads
    scale = 1.0 / math.sqrt(head_dim)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[-1]), 1) // head_dim
    out = jnp.zeros(q.shape, jnp.float32)
    for h in range(heads):
        own = lane_head == h
        s = jax.lax.dot_general(
            jnp.where(own, q, jnp.zeros_like(q)), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        out = jnp.where(own, pv / p.sum(axis=-1, keepdims=True), out)
    o_ref[0] = out.astype(o_ref.dtype)


@jax.jit
# ccfd-lint: hot-path
def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """(B, H, L, Dh) -> (B, H, L, Dh), the contract of
    ``reference_attention``; only shapes :func:`kernel_fits` admits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, length, head_dim = q.shape
    width = heads * head_dim
    rows = query_block(length)
    if width != LANE or rows is None or k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"fused_attention does not tile q{q.shape} k{k.shape}")

    def merged(t):  # undoes the caller's head split: XLA cancels the pair
        return t.transpose(0, 2, 1, 3).reshape(batch, length, width)

    keys = pl.BlockSpec((1, length, width), lambda b, i: (b, 0, 0),
                        memory_space=pltpu.VMEM)
    block = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        partial(_kernel, heads=heads),
        out_shape=jax.ShapeDtypeStruct((batch, length, width), v.dtype),
        grid=(batch, length // rows),
        in_specs=[block, keys, keys],
        out_specs=block,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * heads * length * length * width,
            transcendentals=batch * heads * length * length,
            bytes_accessed=4 * batch * length * width * q.dtype.itemsize),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(merged(q), merged(k), merged(v))
    return out.reshape(batch, length, heads, head_dim).transpose(0, 2, 1, 3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """What the seq family's serving programs attend with: the kernel where
    :func:`kernel_fits`, ``reference_attention`` everywhere else. Decided
    while the program is traced, from shapes, dtype and backend alone."""
    with jax.named_scope(SCOPE):
        if kernel_fits(q.shape, k.shape, q.dtype):
            return fused_attention(q, k, v)
        return reference_attention(q, k, v)

