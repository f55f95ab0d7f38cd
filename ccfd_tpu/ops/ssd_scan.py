"""Pallas TPU kernel: the Mamba-2 chunked scan, decays and state on the chip.

``models/hybrid_moe.py::_ssd`` is the selective state-space recurrence
S_t = e^(a_t) S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t a chunk of tokens at
a time. As XLA compiles it, the pairwise decays e^(R_t - R_s) of a chunk
(at the served shape (4, 3, 128, 640, 640) float32: 2.5 GB a layer) are
written and read through HBM, and x, dt x and y are copied into and out of
a by-head layout, because a head's 64 values are half a lane tile. Here a
(row, chunk, block of heads) is held in VMEM:

- operands in the layout ``mamba2`` has: ``x`` (B, T, H x P) lane-dense,
  ``bm`` and ``cm`` (B, T, G x N), ``dt`` and the running log-decay R as
  (B, T, H), R a second time as (B, H, T): a decay e^(R_t - R_s) wants t
  down the sublanes and s along the lanes, and the two small arrays are
  XLA's to orient. y leaves as (B, T, H x P). No (.., C, C) array and no
  by-head copy of x, dt x or y crosses HBM; dt x and the skip D x are
  taken inside;
- the grid runs over (row, chunk, block of heads), the head blocks
  innermost: a (row, chunk)'s B, C, dt and R blocks stay put while the
  grid walks the heads, and what the heads of a group share is computed
  at the group's first step into scratch: the scores C_t . B_s of the
  triangle's blocks, and C and B^T split into the bfloat16 pieces the
  products with the state take. Every head's state (N x P float32, two
  heads of 64 side by side in a lane tile) lives in VMEM scratch across
  the chunks of a row (a row's chunks are consecutive steps of the
  sequential chunk axis), zeroed at a row's first chunk;
- a head is a lane mask, not a slice (``ops/seq_attention.py``): dt x with
  the other head's lanes zeroed goes through a 128-wide MXU pass, which
  costs what a 64-wide one does, and the heads of a lane tile add up
  into one (tokens, 128) block of y;
- inside a chunk the triangle is tiled in square blocks of ``side``
  tokens and none above the diagonal is visited (15 of 25 at 640 / 128);
  the block on the diagonal is masked by position before the exponential;
- the arithmetic is ``_ssd``'s: decays, scores, state and sums float32; a
  decay is the exponential of a difference of running sums that is <= 0,
  never a quotient; the products inside a chunk are one bfloat16 pass
  (what ``KDA_INSIDE`` = ``DEFAULT`` is on the chip), those that touch the
  state three (``KDA_PRECISION`` = ``HIGH``: a value split into a
  bfloat16 and the bfloat16 of what that left). Under the interpreter
  (``exact``) every product is float32, as ``DEFAULT`` and ``HIGH`` both
  are on the CPU;
- the state moves by ``chunk`` tokens, the caller's (``Mamba2.chunk_for``):
  a window that is no whole number of chunks is padded on the left with
  tokens of dt = a = 0, which pass the state unchanged.

:func:`kernel_fits` is the selection ``mamba2`` makes while the program is
traced, from shapes, dtype, backend and where the operands lie; the kernel
has no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels

LANE = 128  # heads side by side fill lane tiles; the state is N = 128 x n wide
SIDES = (256, 128)  # tokens a side of a square block of a chunk's triangle
TILES = 2  # lane tiles of x and y a grid step takes
# what a step holds: the blocks double-buffered, the scores of a chunk, the
# pieces of B and C, every head's state; asked of the compiler (its default
# is 16 MiB of a v5e's 128)
VMEM_BYTES = 64 << 20
KERNEL = "ssd_scan"  # the kernel's name: in the capture and in a jaxpr
F32, BF16 = jnp.float32, jnp.bfloat16


def side_for(chunk: int) -> int | None:
    """Tokens a side of the square blocks a chunk's triangle is tiled in:
    the largest of ``SIDES`` that tiles the chunk; None where none does."""
    return next((s for s in SIDES if chunk % s == 0), None)


def _vmem_bytes(chunk: int, heads: int, head_dim: int, state: int,
                tiles: int) -> int:
    """What a grid step holds, counted as float32: the blocks of x, y, B,
    C, dt and R twice over, and the scratch (a chunk's scores, the pieces
    of C and B^T, every head's state)."""
    blocks = 2 * 4 * chunk * (2 * tiles * LANE + 2 * state + 3 * heads)
    scratch = 4 * (chunk * chunk + 4 * chunk * state
                   + heads * head_dim * state)
    return blocks + scratch


def kernel_fits(x, bm, chunk: int) -> bool:
    """Whether ``mamba2`` runs the kernel on ``x`` (B, T, H, P) with B and
    C like ``bm`` (B, T, G, N) at a chunk of ``chunk`` tokens (arrays or
    their shapes: what is read is shape, dtype and where they lie): heads
    of 64 or 128 values (two or one a lane tile) that fill the step's lane
    tiles inside one group, a state of whole lane tiles, a chunk of whole
    blocks whose scratch fits, and what ``ops/kernels.py`` asks of every
    family: a dtype the kernels serve, operands on no mesh and a backend
    that runs them. Refused, and so on ``_ssd``: heads of 16, a state of
    16, a chunk of 32 (the tests' presets), a mesh."""
    if len(x.shape) != 4 or len(bm.shape) != 4:
        return False
    (b, t, h, p), (_, _, g, n) = x.shape, bm.shape
    if h % g or p not in (LANE // 2, LANE):
        return False
    tiles = tiles_for(h // g, p)
    return (
        tuple(bm.shape[:2]) == (b, t)
        and n % LANE == 0 and ((h // g) * p) % (tiles * LANE) == 0
        and side_for(chunk) is not None
        and _vmem_bytes(chunk, h, p, n, tiles) <= VMEM_BYTES
        and kernels.serves(x.dtype)
        and kernels.off_mesh(x, bm)
        and kernels.backend_runs_pallas()
    )


def tiles_for(heads_a_group: int, head_dim: int) -> int:
    """Lane tiles a grid step takes: ``TILES`` where a group's heads fill
    them, else one."""
    return TILES if (heads_a_group * head_dim) % (TILES * LANE) == 0 else 1


def _split(v, exact: bool):
    """A float32 value as the pieces a product at ``HIGH`` multiplies: the
    bfloat16 nearest it and the bfloat16 of what that left (``exact``: the
    value itself and nothing)."""
    if exact:
        return v, None
    hi = v.astype(BF16)
    return hi, (v - hi.astype(F32)).astype(BF16)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """One product as its operands stand: bfloat16 pieces in one pass,
    float32 (``exact``) whole; said outright, so that a caller's
    ``default_matmul_precision`` does not reach into the kernel."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == F32
        else jax.lax.Precision.DEFAULT)


def _dot3(a, b, dims=(((1,), (0,)), ((), ()))):
    """Split operands' product in three bfloat16 passes (hi hi + hi lo +
    lo hi), or one float32 product where they were not split."""
    (a_hi, a_lo), (b_hi, b_lo) = a, b
    if a_lo is None:
        return _dot(a_hi, b_hi, dims)
    return _dot(a_hi, b_hi, dims) + (_dot(a_hi, b_lo, dims)
                                     + _dot(a_lo, b_hi, dims))


# ccfd-lint: hot-path
def _kernel(x_ref, b_ref, c_ref, dt_ref, run_ref, row_ref, d_ref, o_ref,
            state, scores, c_hi, c_lo, bt_hi, bt_lo, *, side: int,
            head_dim: int, tiles: int, steps_a_group: int, exact: bool):
    from jax.experimental import pallas as pl

    chunk = x_ref.shape[1]
    heads = dt_ref.shape[2]
    per_tile = LANE // head_dim  # heads side by side in a lane tile
    blocks = chunk // side
    step = pl.program_id(2)
    low = F32 if exact else BF16

    @pl.when(pl.program_id(1) == 0)
    def _():  # a row's first chunk: its heads start from nothing
        state[step] = jnp.zeros(state.shape[1:], F32)

    @pl.when(step % steps_a_group == 0)
    def _():  # a group's first step: what its heads share
        cm, bm = c_ref[0].astype(F32), b_ref[0].astype(F32)
        for v, hi, lo in ((cm, c_hi, c_lo), (bm.T, bt_hi, bt_lo)):
            hi[...], rest = _split(v, exact)
            if not exact:
                lo[...] = rest
        b_low = bm.astype(low)
        for i in range(blocks):
            for j in range(i + 1):
                scores[i * side:(i + 1) * side, j * side:(j + 1) * side] = (
                    _dot(c_hi[i * side:(i + 1) * side],
                         b_low[j * side:(j + 1) * side],
                         (((1,), (1,)), ((), ()))))

    dt, run = dt_ref[0], run_ref[0]  # (C, H): a token a sublane
    head_at = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1) // head_dim
    below = (jax.lax.broadcasted_iota(jnp.int32, (side, side), 1)
             <= jax.lax.broadcasted_iota(jnp.int32, (side, side), 0))

    def column(v, head):  # (C, H) -> (C, 1): one head's
        return jnp.sum(jnp.where(head_at == head, v, 0.0), axis=1,
                       keepdims=True)

    def along(head):  # (H, C) -> (1, C): one head's R, a token a lane
        rows = 8 if heads % 8 == 0 else heads  # a load of whole sublanes
        base = pl.multiple_of(head // rows * rows, rows)
        some = row_ref[0, pl.ds(base, rows), :]
        return jnp.sum(jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, some.shape, 0) == head - base, some, 0.0), axis=0,
            keepdims=True)

    def by_lane(columns):  # the tile's heads' columns -> (C, 128)
        out = columns[0]
        for k in range(1, per_tile):
            out = jnp.where(lane_head == k, columns[k], out)
        return jnp.broadcast_to(out, (chunk, LANE))

    c_pieces = (c_hi[...], None if exact else c_lo[...])
    bt_pieces = (bt_hi[...], None if exact else bt_lo[...])
    for q in range(tiles):
        first = (step * tiles + q) * per_tile  # the tile's first head
        lanes = slice(q * LANE, (q + 1) * LANE)
        x = x_ref[0, :, lanes].astype(F32)
        runs = [column(run, first + k) for k in range(per_tile)]
        flat_runs = [along(first + k) for k in range(per_tile)]
        u = by_lane([column(dt, first + k) for k in range(per_tile)]) * x
        since = by_lane(runs)  # R_t: the decay since the chunk began
        last = since[chunk - 1:chunk]
        s0 = state[step, q]  # (N, 128): the tile's heads' states
        from_state = _dot3(c_pieces, _split(s0, exact)) * jnp.exp(since)
        state[step, q] = jnp.exp(last) * s0 + _dot3(
            bt_pieces, _split(u * jnp.exp(last - since), exact))
        # dt x of each head alone, the other heads' lanes zeroed
        alone = [jnp.where(lane_head == k, u, 0.0).astype(low)
                 for k in range(per_tile)]
        skip = d_ref[:, lanes] * x
        for i in range(blocks):
            rows = slice(i * side, (i + 1) * side)
            acc = from_state[rows] + skip[rows]
            for k in range(per_tile):
                mine = jnp.broadcast_to(runs[k][rows], (side, side))
                for j in range(i + 1):
                    cols = slice(j * side, (j + 1) * side)
                    gap = mine - flat_runs[k][:, cols]
                    if j == i:
                        gap = jnp.where(below, gap, -jnp.inf)
                    acc = acc + _dot(
                        (scores[rows, cols] * jnp.exp(gap)).astype(low),
                        alone[k][cols])
            o_ref[0, rows, lanes] = acc


@partial(jax.jit, static_argnames=("chunk", "side", "exact"))
# ccfd-lint: hot-path
def ssd_scan(x: jax.Array, bm: jax.Array, cm: jax.Array, dt: jax.Array,
             a: jax.Array, d: jax.Array, chunk: int, side: int | None = None,
             exact: bool | None = None):
    """``x`` (B, T, H, P), ``bm`` and ``cm`` (B, T, G, N), ``dt`` and the
    log-decays ``a`` <= 0 (B, T, H), the skip's ``d`` (H,) -> ``(y + d x
    (B, T, H, P) float32, the most negative running sum of a inside a
    chunk)``: ``_ssd``'s contract with the skip inside. Only shapes
    :func:`kernel_fits` admits; ``side`` overrides :func:`side_for` (a
    test's way to several blocks in a short chunk); ``exact`` (the
    interpreter's default) multiplies in float32 where the chip takes
    bfloat16 passes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, tokens, heads, head_dim = x.shape
    groups, width = bm.shape[2:]
    per = heads // groups
    if side is None:
        side = side_for(chunk)
    tiles = tiles_for(per, head_dim)
    if exact is None:
        exact = kernels.interpreted()
    lanes = tiles * LANE
    if (side is None or chunk % side or LANE % head_dim or width % LANE
            or heads % groups or (per * head_dim) % lanes
            or bm.shape != (batch, tokens, groups, width)
            or cm.shape != bm.shape):
        raise ValueError(f"ssd_scan does not tile x{x.shape} b{bm.shape} "
                         f"at a chunk of {chunk}")
    lead = -tokens % chunk
    chunks = (tokens + lead) // chunk

    def flat(v):  # (B, T, ...) -> (B, lead + T, the rest as one lane axis)
        v = v.reshape(batch, tokens, -1)
        return jnp.pad(v, ((0, 0), (lead, 0), (0, 0))) if lead else v

    dt, a = flat(dt.astype(F32)), flat(a.astype(F32))
    run = jnp.cumsum(a.reshape(batch, chunks, chunk, heads), axis=2).reshape(
        batch, chunks * chunk, heads)
    steps = heads * head_dim // lanes  # grid steps over the heads
    steps_a_group = per * head_dim // lanes
    low = F32 if exact else BF16
    visited = chunks * (chunk // side) * (chunk // side + 1) // 2 * side ** 2

    def tokens_of(last, at):
        return pl.BlockSpec((1, chunk, last), at, memory_space=pltpu.VMEM)

    def of_group(b, c, j):
        return b, c, j // steps_a_group

    y = pl.pallas_call(
        partial(_kernel, side=side, head_dim=head_dim, tiles=tiles,
                steps_a_group=steps_a_group, exact=exact),
        out_shape=jax.ShapeDtypeStruct(
            (batch, chunks * chunk, heads * head_dim), F32),
        grid=(batch, chunks, steps),
        in_specs=[
            tokens_of(lanes, lambda b, c, j: (b, c, j)),
            tokens_of(width, of_group), tokens_of(width, of_group),
            tokens_of(heads, lambda b, c, j: (b, c, 0)),
            tokens_of(heads, lambda b, c, j: (b, c, 0)),
            pl.BlockSpec((1, heads, chunk), lambda b, c, j: (b, 0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda b, c, j: (0, j),
                         memory_space=pltpu.VMEM)],
        out_specs=tokens_of(lanes, lambda b, c, j: (b, c, j)),
        scratch_shapes=[
            pltpu.VMEM((steps, tiles, width, LANE), F32),  # the states
            pltpu.VMEM((chunk, chunk), F32),  # C_t . B_s, a group's
            pltpu.VMEM((chunk, width), low), pltpu.VMEM((chunk, width), low),
            pltpu.VMEM((width, chunk), low), pltpu.VMEM((width, chunk), low)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * (
                visited * (heads * LANE + groups * width)
                + 3 * 2 * chunks * chunk * width * heads * head_dim),
            transcendentals=batch * heads * (visited
                                             + 2 * chunks * chunk * head_dim),
            bytes_accessed=batch * chunks * chunk * (
                heads * head_dim * (x.dtype.itemsize + 4)
                + 2 * groups * width * bm.dtype.itemsize + 3 * heads * 4)),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(flat(x), flat(bm), flat(cm), dt, run, run.transpose(0, 2, 1),
      jnp.repeat(d.astype(F32), head_dim)[None])
    return (y[:, lead:].reshape(batch, tokens, heads, head_dim), run.min())
