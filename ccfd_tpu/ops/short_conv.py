"""Pallas TPU kernel: the Mamba-2 short convolution on token-major blocks.

``models/hybrid_moe.py::mamba2`` convolves the x | B | C columns of its
in-projection along the tokens (``_short_conv``: K taps a channel, causal),
adds a bias and takes SiLU. However ``_short_conv`` is written, XLA lays the
convolution out with the tokens minor, so a transposing copy of (B, T, wide)
goes in, and a slice and a copy back to token-major come out before
``ops/ssd_scan.py``'s kernel, whose operands are token-major. A Pallas
operand's layout is the kernel's to choose, so here:

- the operand is ``proj`` (B, T, gate | x B C | dt) as the projection's
  matmul leaves it; a block is a row's whole window for some lane tiles
  ((1, T, lanes): tokens down the sublanes, channels along the lanes),
  addressed by ``BlockSpec`` at the columns' offset: no slice of ``proj``
  is made for the kernel, and columns past the last block (dt, which may
  end in a ragged lane tile) are never addressed;
- each of the ``widths`` (x, B, C) leaves as an array of its own, (B, T,
  width) float32 token-major: what ``ssd_scan`` takes. It is the same body
  called once an output, each at the widest block that tiles its columns;
- inside a block the window is walked in strips of tokens (``STRIPS``:
  long enough that a strip's lane tiles fill the vector slots side by
  side, short enough to stay in registers): a strip is masked (``keep``:
  zeros where a row has padding), stood on the masked last sublane tile
  of the strip before it (zeros before the first: what ``jnp.pad`` gives
  ``_short_conv``), and a lag of 1 .. K - 1 tokens is a rotation down the
  sublanes of that; no halo crosses blocks;
- the arithmetic is ``_short_conv``'s at its precision: float32 whatever
  ``proj`` holds, the mask before the taps, the taps summed oldest first,
  the bias, SiLU.

:func:`kernel_fits` is the selection ``mamba2`` makes while the program is
traced; the kernel has no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial
from itertools import accumulate

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels

LANE = 128
SUBLANES = 8  # a lag is under a sublane tile: K - 1 < SUBLANES
# tokens a step of the walk down a block takes: whole bfloat16 tiles; at 32
# the described chip's schedule has 10.8 bundles a register, at 16 16. Every
# served window (1,920 tokens; chip_smoke.py's 768) takes 32; 16 is for the
# tests' windows of 8 records (240 tokens), which only the interpreter runs
STRIPS = (32, 16)
BLOCKS = (256, 128)  # lanes a block holds: the widest that tiles the columns
# a block and its output, both twice over, and the mask's (T, 1) lane-padded:
# under the compiler's default, so none is asked for
VMEM_BYTES = 14 << 20
KERNEL = "short_conv"  # the kernel's name: in the capture and in a jaxpr
F32 = jnp.float32


def lanes_for(*columns: int) -> int | None:
    """Lanes a block holds: the largest of ``BLOCKS`` that tiles every one
    of ``columns`` (the offsets a block is addressed at, and the width);
    None where none does."""
    return next((n for n in BLOCKS if not any(c % n for c in columns)), None)


def strip_for(tokens: int) -> int | None:
    """Tokens a strip of a window of ``tokens`` holds: the largest of
    ``STRIPS`` that tiles it; None where none does."""
    return next((n for n in STRIPS if tokens % n == 0), None)


def starts_of(widths: tuple) -> list:
    """Where each of ``widths``, one after another, starts."""
    return [0, *accumulate(widths)][:-1]


def _vmem_bytes(tokens: int, lanes: int) -> int:
    """What a grid step holds, counted as float32: the block and its
    output twice over, and the mask, a token a sublane, padded to a lane
    tile, twice."""
    return 4 * tokens * (4 * lanes + 2 * LANE)


def kernel_fits(proj, taps, at: int, widths: tuple) -> bool:
    """Whether ``mamba2`` runs the kernel on the columns ``at`` onward of
    ``proj`` (B, T, W), ``widths`` wide one after another, with ``taps``
    (K, their sum) (arrays or their shapes): every offset and width whole
    lane tiles, all of them inside ``proj``, a window of whole strips
    whose block fits VMEM, taps that reach back less than a sublane tile,
    and what ``ops/kernels.py`` asks of every family. Refused, and so on
    ``_short_conv``: the tests' presets (40 and 80 columns), a ragged
    width, a mesh, a window too long for a block."""
    if len(proj.shape) != 3 or len(taps.shape) != 2:
        return False
    (_, t, w), (k, wide) = proj.shape, taps.shape
    blocks = [lanes_for(at, *edge) for edge in zip(starts_of(widths), widths)]
    return (
        bool(widths) and wide == sum(widths) and at + wide <= w
        and None not in blocks
        and 1 <= k <= SUBLANES and strip_for(t) is not None
        and _vmem_bytes(t, max(blocks)) <= VMEM_BYTES
        and kernels.serves(proj.dtype)
        and kernels.off_mesh(proj, taps)
        and kernels.backend_runs_pallas()
    )


# ccfd-lint: hot-path
def _kernel(u_ref, keep_ref, taps_ref, bias_ref, o_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, size = taps_ref.shape[0], strip_for(u_ref.shape[1])
    taps = taps_ref[...].astype(F32)
    bias = bias_ref[...].astype(F32)

    def strip(i, before):
        rows = pl.ds(pl.multiple_of(i * size, size), size)
        now = u_ref[0, rows, :].astype(F32) * keep_ref[0, rows, :]
        stood = jnp.concatenate([before, now], axis=0)
        # taps[j] is on the token k - 1 - j back, the oldest summed first
        out = None
        for j in range(k):
            lag = k - 1 - j
            term = (pltpu.roll(stood, lag, 0)[SUBLANES:] if lag else now
                    ) * taps[j:j + 1]
            out = term if out is None else out + term
        o_ref[0, rows, :] = jax.nn.silu(out + bias)
        return now[size - SUBLANES:]

    jax.lax.fori_loop(0, u_ref.shape[1] // size, strip,
                      jnp.zeros((SUBLANES, u_ref.shape[2]), F32))


@partial(jax.jit, static_argnames=("at", "widths"))
# ccfd-lint: hot-path
def short_conv(proj: jax.Array, taps: jax.Array, bias: jax.Array,
               keep: jax.Array, at: int, widths: tuple):
    """``proj`` (B, T, W), ``taps`` (K, wide) with the last on the current
    token, ``bias`` (wide,), ``keep`` (B, T, 1) float32 (0 on a row's
    padding) -> for each of ``widths``, one after another from column
    ``at``: SiLU(conv(proj's columns * keep) + bias), (B, T, width)
    float32. Only shapes :func:`kernel_fits` admits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, tokens, _ = proj.shape
    k, wide = taps.shape
    if not kernel_fits(proj, taps, at, widths):
        raise ValueError(f"short_conv does not tile {widths} from column "
                         f"{at} of proj{proj.shape} with taps{taps.shape}")
    keep = keep.astype(F32).reshape(batch, tokens, 1)
    bias = bias.reshape(1, wide)

    def one(start: int, width: int):  # taps' columns start .. start + width
        lanes = lanes_for(at, start, width)
        here, there = start // lanes, (at + start) // lanes
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((batch, tokens, width), F32),
            grid=(batch, width // lanes),
            in_specs=[
                pl.BlockSpec((1, tokens, lanes),
                             lambda b, j: (b, 0, there + j),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tokens, 1), lambda b, j: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, lanes), lambda b, j: (0, here + j),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, lanes), lambda b, j: (0, here + j),
                             memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, tokens, lanes),
                                   lambda b, j: (b, 0, j),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            cost_estimate=pl.CostEstimate(
                flops=batch * tokens * width * (2 * k + 5),
                transcendentals=batch * tokens * width,
                bytes_accessed=batch * tokens * width * (
                    proj.dtype.itemsize + 4)),
            name=KERNEL,
            interpret=kernels.interpreted(),
        )(proj, keep, taps, bias)

    return tuple(map(one, starts_of(widths), widths))
