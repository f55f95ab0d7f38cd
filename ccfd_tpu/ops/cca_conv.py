"""Pallas TPU kernel: CCA's convolved latent on token-major blocks.

``models/hybrid_moe.py::cca`` projects its input down to q~ (H heads), k~
and v (G heads each, D wide), and between those three products and
``_causal_attention`` runs a chain that XLA walks as some thirty passes of
float32 (B, T, heads, D) arrays: the value heads' later half shifted by a
token, a depthwise causal convolution over q~ | k~, a grouped one that is a
D x D product a head and tap, the q-k means over the H : G grouping, two L2
norms, ``tau``, the half-width rotary and three casts, with ``_back`` (mask,
pad, slice) and ``per_head``'s relayouts between them. A Pallas operand's
layout is the kernel's to choose (``ops/short_conv.py``), so here:

- the operands are the projections as the matmuls leave them, (B, T,
  heads x D) float32 token-major; a grid step is a row's tile of tokens
  (``tile_for``) for all H + G heads, tokens down the sublanes, heads x D
  along the lanes; q (B, H, T, D), k and v (B, G, T, D) leave in the
  serving dtype by head, what ``ops/causal_attention.py``'s kernel reads:
  ``cca`` hands ``_causal_attention`` their transposes, token-major as
  the plain chain's, and XLA cancels those against the seam's own (left
  token-major, (B, T, H D) -> (B, T, H, D) is no bitcast of a tiled
  layout and XLA made two relayouts a tensor of it);
- a grid step is straight-line code over its tile, no loop over strips:
  the mask, the value shift, the depthwise taps in float32 and their bias,
  ``c0`` rounded to the serving dtype (as it stands, and masked and a
  token back, once a tap of the grouped convolution); then a head at a
  time the grouped taps, a D x D product a tap with float32 accumulation,
  summed newest first as ``_causal_taps`` sums them, the bias, and that
  head's share of the means, its norm, ``tau``, the rotary and the cast.
  The described chip's scheduler lays one head's vector work beside the
  next head's products (6,258 bundles a step of 384 tokens, the vector
  slots three quarters full, where the same chain walked in strips of 32
  tokens under ``fori_loop`` took 11,710: a loop's body is scheduled
  alone, and the last walk's chain of a lane reduction, ``rsqrt`` and two
  lane rotations is latency that only other heads' work can hide);
- a lag of one token is a rotation down the sublanes of the tile stood on
  the masked last sublane tile of the tile before (``short_conv``'s
  means), which is kept in VMEM from a row's tile to its next (the grid
  walks a row's tiles in order) and is zeros before a row's first tile:
  what ``jnp.pad`` gives ``_back``. No other state crosses tiles;
- the rotary's cosines and sines come in, computed outside by the lines
  ``_rotary`` has, laid along a head's D lanes (cos | cos | 1.. and
  -sin | sin | 0..), so that a turn is x cos + (x rotated by half the
  rotary width, either way) sin: the same products and sums;
- the arithmetic is ``cca``'s at its precision and in its order; the
  current token's taps see it unmasked, the lagged ones masked, as there.

:func:`kernel_fits` is the selection ``cca`` makes while the program is
traced; the kernel has no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels

LANE = 128
SUBLANES = 8  # a lag is under a sublane tile: taps - 1 < SUBLANES
# tokens a grid step holds: the largest that tiles the window and fits VMEM
# (1,920 -> 384, five steps a row; the tests' 240 -> 16, fifteen edges for
# the lag to cross); whole bfloat16 tiles
TILES = (384, 256, 128, 64, 32, 16)
# a step's blocks twice over and its scratch, counted by ``_vmem_bytes``:
# under the compiler's default, so none is asked for
VMEM_BYTES = 14 << 20
KERNEL = "cca_conv"  # the kernel's name: in the capture and in a jaxpr
F32 = jnp.float32


def _vmem_bytes(tile: int, wide: int, narrow: int, head: int, taps: int,
                itemsize: int) -> int:
    """What a grid step holds: q~, k~ and v, the mask and the two rotary
    operands (a lane tile each at least) and the grouped taps' blocks in,
    q, k and v out, all twice over; ``c0`` a tap and ``c1`` once (no
    scratch of the kernel's: what the compiler spills of them)."""
    lanes = wide + narrow  # q~ | k~
    blocks = (4 * tile * (lanes + narrow + LANE + 2 * max(head, LANE))
              + itemsize * (taps * (lanes // head) * head * head
                            + tile * (lanes + narrow)))
    return 2 * blocks + tile * lanes * (taps * itemsize + 4)


def tile_for(tokens: int, wide: int, narrow: int, conv0: tuple, conv1: tuple,
             dtype) -> int | None:
    """Tokens a grid step holds of a window of ``tokens`` with q~ ``wide``
    and k~ ``narrow`` lanes, the depthwise taps' shape ``conv0`` (K0, (H +
    G) D) and the grouped ones' ``conv1`` (K1, H + G, D, D), served in
    ``dtype``: the largest of ``TILES`` that tiles the window and whose
    step fits ``VMEM_BYTES``; None where none does or the shapes are not
    the kernel's: heads of whole lane tiles, H a multiple of G, taps that
    reach back less than a sublane tile."""
    if len(conv0) != 2 or len(conv1) != 4:
        return None
    taps, n, head = conv1[0], conv1[1], conv1[3]
    if not (head % LANE == 0 and conv1[2] == head
            and narrow > 0 and wide % narrow == 0
            and wide + narrow == n * head == conv0[1]
            and 1 <= conv0[0] <= SUBLANES and 1 <= taps <= SUBLANES):
        return None
    return next((size for size in TILES if tokens % size == 0 and _vmem_bytes(
        size, wide, narrow, head, taps, jnp.dtype(dtype).itemsize)
        <= VMEM_BYTES), None)


def kernel_fits(z, wq, wk, conv0, conv1, dtype) -> bool:
    """Whether ``cca`` runs the kernel on the projections of ``z`` (B, T,
    hidden) by ``wq`` (hidden, H D) and ``wk`` (hidden, G D), with the
    depthwise taps ``conv0`` and the grouped ones ``conv1`` (arrays or
    their shapes), served in ``dtype``: shapes and a window that
    :func:`tile_for` tiles, and what ``ops/kernels.py`` asks of every
    family. Refused, and so on the plain chain: the tests' presets (heads
    of 16), a ragged window, a mesh."""
    return (
        (len(z.shape), len(wq.shape), len(wk.shape)) == (3, 2, 2)
        and kernels.serves(dtype)
        and tile_for(z.shape[1], wq.shape[1], wk.shape[1], tuple(conv0.shape),
                     tuple(conv1.shape), dtype) is not None
        and kernels.off_mesh(z, wq, wk, conv1)
        and kernels.backend_runs_pallas()
    )


def _dot(a, b):
    """One product as its operands stand: bfloat16 in one pass, float32
    whole; said outright, so that a caller's ``default_matmul_precision``
    does not reach into the kernel."""
    return jnp.dot(a, b, preferred_element_type=F32,
                   precision=jax.lax.Precision.HIGHEST if a.dtype == F32
                   else jax.lax.Precision.DEFAULT)


# ccfd-lint: hot-path
def _kernel(q_ref, k_ref, v_ref, keep_ref, cos_ref, sin_ref, w0_ref, b0_ref,
            w1_ref, b1_ref, tau_ref, qo_ref, ko_ref, vo_ref,
            u_tail, c_tail, v_tail, *, rot: int, eps: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, wide = q_ref.shape[1:]
    narrow = k_ref.shape[2]
    taps0, (taps1, n, d) = w0_ref.shape[0], w1_ref.shape[:3]
    groups = narrow // d
    per = wide // narrow
    now_v = (groups - groups // 2) * d  # the value lanes that read this token
    dtype = qo_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():  # before a row's first token: zeros
        for tail in (u_tail, c_tail, v_tail):
            tail[...] = jnp.zeros(tail.shape, F32)

    def stand(tail, masked):
        """``masked`` (tile, lanes) stood on what the tile before left in
        ``tail``, which then takes this tile's last sublane tile."""
        stood = jnp.concatenate([tail[...], masked], axis=0)
        tail[...] = masked[tile - SUBLANES:]
        return stood

    def back(stood, lag=1):  # the tile, ``lag`` tokens back
        return pltpu.roll(stood, lag, 0)[SUBLANES:]

    keep = keep_ref[0]
    v = v_ref[0]
    if now_v < narrow:
        v = jnp.concatenate(
            [v[:, :now_v], back(stand(v_tail, v[:, now_v:] * keep))], axis=1)
    for g in range(groups):
        vo_ref[0, g] = v[:, g * d:(g + 1) * d].astype(dtype)

    u = jnp.concatenate([q_ref[0], k_ref[0]], axis=1)
    w0 = w0_ref[...]
    c0 = u * w0[taps0 - 1:taps0]
    stood = stand(u_tail, u * keep)
    for lag in range(1, taps0):
        c0 = c0 + back(stood, lag) * w0[taps0 - 1 - lag:taps0 - lag]
    c0 = (c0 + b0_ref[...]).astype(dtype)
    # as it stands, then masked and 1 .. taps1 - 1 tokens back (0 / 1: the
    # mask is exact in either dtype)
    stood = stand(c_tail, c0.astype(F32) * keep)
    c0 = [c0, *(back(stood, lag).astype(dtype) for lag in range(1, taps1))]

    cos, sin = cos_ref[0], sin_ref[0]
    first_half = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 1) < rot // 2

    def grouped(h):  # a head's D x D block a tap, newest first, and the bias
        lanes = slice(h * d, (h + 1) * d)
        c1 = _dot(c0[0][:, lanes], w1_ref[taps1 - 1, h])
        for lag in range(1, taps1):
            c1 = c1 + _dot(c0[lag][:, lanes], w1_ref[taps1 - 1 - lag, h])
        return c1 + b1_ref[:, lanes]

    def leave(x, ref, head, scale=None):
        x = x * (math.sqrt(d) * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) + eps))
        if scale is not None:
            x = x * scale
        if rot:
            x = x * cos + jnp.where(
                first_half, pltpu.roll(x, d - rot // 2, 1),
                pltpu.roll(x, rot // 2, 1)) * sin
        ref[0, head] = x.astype(dtype)

    for g in range(groups):
        at_k = slice(g * d, (g + 1) * d)
        k_lat = k_ref[0, :, at_k]
        total = None
        for j in range(per):
            h = g * per + j
            q_lat = q_ref[0, :, h * d:(h + 1) * d]
            total = q_lat if total is None else total + q_lat
            leave(grouped(h) + (q_lat + k_lat) * 0.5, qo_ref, h)
        leave(grouped(per * groups + g) + (total / per + k_lat) * 0.5, ko_ref,
              g, tau_ref[:, at_k])


@partial(jax.jit, static_argnames=("rot", "dtype", "eps"))
# ccfd-lint: hot-path
def cca_conv(q_lat: jax.Array, k_lat: jax.Array, v: jax.Array,
             keep: jax.Array, cos: jax.Array, sin: jax.Array,
             conv0: jax.Array, conv0_b: jax.Array, conv1: jax.Array,
             conv1_b: jax.Array, tau: jax.Array, *, rot: int, dtype,
             eps: float):
    """``q_lat`` (B, T, H D), ``k_lat`` and ``v`` (B, T, G D) float32;
    ``keep`` (B, T, 1) (0 on a row's padding); ``cos`` and ``sin`` (B, T,
    D) float32, the turn of the leading ``rot`` lanes of a head laid along
    its D (cos | cos | 1.., -sin | sin | 0..); ``conv0`` (K0, (H + G) D)
    with the last tap on the current token and its bias ((H + G) D,);
    ``conv1`` (K1, H + G, D, D) and its bias; ``tau`` (G,) -> q (B, H, T,
    D), k and v (B, G, T, D) in ``dtype``, by head: the transposes of what
    ``cca``'s plain chain hands ``_causal_attention``. Only shapes
    :func:`kernel_fits` admits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, tokens, wide = q_lat.shape
    narrow = k_lat.shape[2]
    taps0, lanes = conv0.shape
    taps1, n, head, _ = conv1.shape
    dtype = jnp.dtype(dtype)
    tile = tile_for(tokens, wide, narrow, conv0.shape, conv1.shape, dtype)
    if tile is None or rot % 2 or rot > head:
        raise ValueError(f"cca_conv does not tile q{q_lat.shape} "
                         f"k{k_lat.shape} with conv1{conv1.shape}")
    keep = keep.astype(F32).reshape(batch, tokens, 1)
    later = narrow // head // 2 * head  # the value lanes a token back

    def tokens_by(width):  # a row's tile of tokens, ``width`` lanes
        return pl.BlockSpec((1, tile, width), lambda b, i: (b, i, 0),
                            memory_space=pltpu.VMEM)

    def by_head(heads):  # the same tokens, a head's (tile, D) after another
        return pl.BlockSpec((1, heads, tile, head), lambda b, i: (b, 0, i, 0),
                            memory_space=pltpu.VMEM)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        partial(_kernel, rot=rot, eps=eps),
        out_shape=[jax.ShapeDtypeStruct((batch, w // head, tokens, head),
                                        dtype)
                   for w in (wide, narrow, narrow)],
        grid=(batch, tokens // tile),
        in_specs=[tokens_by(wide), tokens_by(narrow), tokens_by(narrow),
                  tokens_by(1), tokens_by(head), tokens_by(head),
                  whole(taps0, lanes), whole(1, lanes),
                  whole(taps1, n, head, head), whole(1, lanes),
                  whole(1, narrow)],
        out_specs=[by_head(w // head) for w in (wide, narrow, narrow)],
        scratch_shapes=[pltpu.VMEM((SUBLANES, lanes), F32),
                        pltpu.VMEM((SUBLANES, lanes), F32),
                        pltpu.VMEM((SUBLANES, max(later, LANE)), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=batch * tokens * lanes * (2 * taps1 * head + 2 * taps0
                                            + 16),
            transcendentals=batch * tokens * n,
            bytes_accessed=batch * tokens * (
                (4 + dtype.itemsize) * (lanes + narrow)
                + 4 * (LANE + 2 * head))),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(q_lat, k_lat, v, keep, cos, sin, conv0, conv0_b.reshape(1, lanes),
      conv1.astype(dtype), conv1_b.reshape(1, lanes),
      jnp.repeat(tau.astype(F32), head).reshape(1, narrow))
    return tuple(out)
