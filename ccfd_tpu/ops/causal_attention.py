"""Pallas TPU kernel: causal softmax attention over real keys, scores on the chip.

The ``hybrid_moe`` family's latent attention (``mla``) and its compressed
grouped-query attention (``cca``) both end in the same step: every token of
a window reads the real tokens at or before it. As XLA compiles the plain
definition (``models/hybrid_moe.py::_plain_causal_attention``: a row at a
time, four query blocks a row) each block's float32 scores
(heads, 480, <= 1,920) are written by q k^T, read by the softmax and read
again by p v, through HBM. Here they live and die in VMEM:

- operands by head, ``q`` (B, H, T, D), ``k`` (B, G, T, D), ``v``
  (B, G, T, Dv) with H a multiple of G: query head h reads key head
  h // (H / G), through the block index and not through a copy of k and v
  per query head. By head and not the lane-dense (B, T, H x D) view,
  because the transposes then fold into the fusions that make q, k, v and
  read the output, where the other view costs three relayout copies a layer;
- the grid runs over (row, query head, query block); a (row, key head)'s
  whole k and v (T x D and T x Dv values) stay in VMEM while the grid
  walks that head's query blocks (and the other query heads of its group);
- q and k may be a whole number of 128-lane tiles wide or end in half a
  tile (MLA's 128 + 64 rotary = 192: the block's last dimension is the
  array's, Mosaic keeps the 64 lanes beside them out of the product); the
  values, and so the output, fill whole tiles. The half tile costs the MXU
  a whole pass (3.80 ms a call of (8, 32, 1,920) at 192 against 2.88 at
  128, and 3.80 at 256), so padding q and k to 256 or handing them over in
  lane-tile pieces buys nothing in the kernel and costs copies around it;
- inside a grid step a loop over square key blocks with a running max and
  sum; the blocks wholly above the diagonal are not visited (the causal
  half of the work), the one on the diagonal is masked by position, and a
  key that is padding is masked in every block (an additive ``MASKED``,
  which in float32 is the replacement the plain path makes);
- precision is the plain path's: operands as given (bfloat16 on the serving
  path), q k^T accumulated in float32, scale, mask, max, exp and sum in
  float32, p cast to v's dtype for p v, float32 accumulation, output in
  the dtype asked for. What differs is the order of the sums: by key
  block, and the division by the denominator after p v.

A query that is padding has every key masked: its output is the mean of the
values it visited, finite, and read by no real token.

:func:`kernel_fits` is the selection ``_causal_attention`` makes while the
program is traced, from shapes, dtype and backend alone; the kernel has no
derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels

LANE = 128  # values fill whole lane tiles, T whole blocks of them
HALF = LANE // 2  # q and k: whole tiles, or whole tiles and a half (128 + 64)
BLOCKS = (640, 512, 384, 256, 128)  # square: queries and keys of a step
# float32 scores of one (query block, key block): with the probabilities
# beside them in float32 and in v's dtype this stays well inside the 16 MiB
# a kernel may use
SCORE_BYTES = 2 << 20
# a (row, key head)'s whole k and v, double-buffered
KEYS_BYTES = 8 << 20
MASKED = -1e30  # the plain path's: a key that is padding or after the query
KERNEL = "causal_attention"  # the kernel's name: in the capture and in a jaxpr


def block_for(tokens: int, width: int, v_width: int, itemsize: int) -> int | None:
    """Tokens a side of the square (query block, key block) a grid step
    works on: the largest of ``BLOCKS`` that tiles the window and whose
    scores fit the budget; None where the kernel does not run (a window
    that is no multiple of the lane width, or whose k and v do not fit:
    a row of k counted as the whole lane tiles VMEM gives it)."""
    held = -(-width // LANE) * LANE
    if tokens % LANE or 2 * tokens * (held + v_width) * itemsize > KEYS_BYTES:
        return None
    for side in BLOCKS:
        if tokens % side == 0 and side * side * 4 <= SCORE_BYTES:
            return side
    return None


def kernel_fits(q_shape: tuple, k_shape: tuple, v_shape: tuple, dtype) -> bool:
    """Whether ``_causal_attention`` runs the kernel on operands of these
    by-head shapes (B, H, T, D), (B, G, T, D), (B, G, T, Dv): a query-key
    width of whole lane tiles or whole tiles and a half (128, 192, 256 ..),
    a value width of whole lane tiles, query heads that divide evenly over
    the key heads, a window the kernel tiles within its VMEM budget, a
    dtype the kernels serve and a backend that runs them
    (``ops/kernels.py``; a mesh is not asked about). Refused, and so on the
    plain path: heads of 16 or 64, values of 64, a window that is no
    multiple of 128 tokens."""
    if not len(q_shape) == len(k_shape) == len(v_shape) == 4:
        return False
    (b, h, t, d), (_, g, _, dv) = q_shape, v_shape
    return (
        tuple(k_shape) == (b, g, t, d)
        and tuple(v_shape[:3]) == (b, g, t)
        and h % g == 0
        and d >= LANE and d % HALF == 0 and dv % LANE == 0
        and kernels.serves(dtype)
        and block_for(t, d, dv, jnp.dtype(dtype).itemsize) is not None
        and kernels.backend_runs_pallas()
    )


# ccfd-lint: hot-path
def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale: float, side: int):
    from jax.experimental import pallas as pl

    mine = pl.program_id(2)  # this query block; its diagonal key block
    q = q_ref[0, 0]  # (side, D)

    def visit(j, carry, diagonal: bool):
        high, total, mixed = carry
        at = pl.multiple_of(j * side, side)
        k = k_ref[0, 0, pl.ds(at, side), :]
        v = v_ref[0, 0, pl.ds(at, side), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s + mask_ref[0, pl.ds(j, 1), :]  # MASKED where the key is padding
        if diagonal:
            s = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                <= jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), s, MASKED)
        higher = jnp.maximum(high, s.max(axis=-1, keepdims=True))
        fade = jnp.exp(high - higher)
        p = jnp.exp(s - higher)
        return (higher, fade * total + p.sum(axis=-1, keepdims=True),
                fade * mixed + jnp.dot(p.astype(v.dtype), v,
                                       preferred_element_type=jnp.float32))

    start = (jnp.full((side, 1), MASKED, jnp.float32),
             jnp.zeros((side, 1), jnp.float32),
             jnp.zeros((side, v_ref.shape[-1]), jnp.float32))
    below = jax.lax.fori_loop(0, mine, partial(visit, diagonal=False), start)
    _, total, mixed = visit(mine, below, diagonal=True)
    o_ref[0, 0] = (mixed / total).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("scale", "dtype", "side"))
# ccfd-lint: hot-path
def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           real: jax.Array, scale: float, dtype,
                           side: int | None = None) -> jax.Array:
    """``q`` (B, H, T, D), ``k`` (B, G, T, D), ``v`` (B, G, T, Dv), ``real``
    (B, T) bool -> (B, H, T, Dv) in ``dtype``: token t's softmax over the
    real tokens at or before it, of ``scale`` q k^T. Only shapes
    :func:`kernel_fits` admits (D a multiple of ``HALF`` from ``LANE`` up, Dv
    of ``LANE``); ``side`` overrides :func:`block_for` (a test's way to
    several blocks in a short window)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, tokens, width = q.shape
    groups, v_width = v.shape[1], v.shape[3]
    if side is None:
        side = block_for(tokens, width, v_width, q.dtype.itemsize)
    if (side is None or tokens % side or heads % groups
            or k.shape != (batch, groups, tokens, width)
            or v.shape[:3] != (batch, groups, tokens)):
        raise ValueError(
            f"fused_causal_attention does not tile q{q.shape} k{k.shape} "
            f"v{v.shape}")
    per = heads // groups
    blocks = tokens // side
    mask = jnp.where(real, 0.0, MASKED).astype(jnp.float32).reshape(
        batch, blocks, side)
    visited = blocks * (blocks + 1) // 2 * side * side  # (query, key) pairs

    def queries(last):
        return pl.BlockSpec((1, 1, side, last), lambda b, h, i: (b, h, i, 0),
                            memory_space=pltpu.VMEM)

    def keys(last):
        return pl.BlockSpec((1, 1, tokens, last),
                            lambda b, h, i: (b, h // per, 0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        partial(_kernel, scale=scale, side=side),
        out_shape=jax.ShapeDtypeStruct((batch, heads, tokens, v_width), dtype),
        grid=(batch, heads, blocks),
        in_specs=[queries(width), keys(width), keys(v_width),
                  pl.BlockSpec((1, blocks, side), lambda b, h, i: (b, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=queries(v_width),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * visited * (width + v_width),
            transcendentals=batch * heads * visited,
            bytes_accessed=batch * tokens * (
                heads * width * q.dtype.itemsize
                + groups * (width + v_width) * k.dtype.itemsize
                + heads * v_width * jnp.dtype(dtype).itemsize)),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(q, k, v, mask)
