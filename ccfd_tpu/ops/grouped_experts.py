"""Pallas TPU kernels: the held experts' body as two grouped matmuls.

An expert's body is the model's: SwiGLU of three matrices (``gate``, ``up``,
``down``), or relu squared between two (``up``, ``down``) with no gate.
``models/hybrid_moe.py::held_experts`` sorts a layer's (token, held expert)
pairs by expert and lays each expert's group out in row tiles, the last one
part empty, so a tile belongs to one expert. The plain path multiplies one
tile a trip of a ``fori_loop``: it cuts the expert's matrices out of
the stacked ``(held, hidden, width)`` arrays and reads them again for the
expert's next tile, and the read waits for the products. Here the stacked
arrays are addressed in place:

- a product's grid runs over (block of output columns, row tile) with the
  tiles innermost; ``expert_of`` (tiles,) is a prefetched scalar array and
  a tile's weight blocks are ``w[expert_of[tile], :, column block]`` of the
  stacked arrays in HBM: no slice is materialised. The kernel fetches them
  itself, a *run* (the consecutive tiles of one expert) at a time into one
  of two VMEM slots: the run's first step waits for its blocks and starts
  the next run's, so an expert's blocks stay on the chip across its tiles
  and the next expert's 8-16 MB arrive under the whole run's products, not
  under one step's (which at 256 rows is as long as the fetch: with the
  block index left to the pipeline the kernel ran at 72% of the MXU on the
  rows it multiplied, fetching itself at 83%). A pass over the tiles reads
  each visited expert's column block once; the row tiles, through the
  pipeline, once a pass;
- the contraction is whole inside a step (a row tile is ``tile`` x hidden
  or ``tile`` x width values), so there is no accumulator across steps;
- the grid's tile extent is the count of tiles to visit, a value of the
  program and not of its shape: tiles past it cost nothing; and a group's
  last tile, part empty (half of it on average, a fifth to a third of the
  rows multiplied where an expert has two or three tiles), multiplies only
  its blocks of ``SUB_ROWS`` rows that hold a pair (``live_of``, one more
  prefetched scalar a tile): the rest of the tile is left unwritten, and
  nobody reads it;
- two kernels: ``expert_up`` = silu(x gate) * (x up), both products and the
  gate in float32, or relu(x up)^2 of the one product, written in the
  compute dtype; ``expert_down`` = h down, float32 accumulation, written in
  the compute dtype: what ``_swiglu`` / ``_relu2`` and the ``astype`` after
  it do on the plain path, in the same precision. What differs is the order
  of the MXU's partial sums.

:func:`kernel_fits` is the selection ``held_experts`` makes while the
program is traced, from shapes, dtype, backend and where the weights lie
(a mesh keeps the plain loop until someone measures one); :func:`row_tile`
and :func:`block_for` read the tile and the column blocks from the widths.
The kernels have no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels

LANE = 128  # hidden and expert widths fill whole lane tiles
ROW_TILES = (512, 256, 128)  # rows of one expert's group a grid step takes
SUB_ROWS = 128  # rows a part-empty tile is multiplied by at a time
# an expert's group ends in a tile that is half empty on average: a tile
# may be this share of the pairs an expert gets when the router spreads
# them evenly (tokens x experts a token / routed experts), no more
TILE_SHARE = 0.6
# the weight blocks of one grid step, double-buffered; with the row tile,
# the output block and the float32 products beside them a step stays inside
# VMEM_BYTES, which is asked of the compiler (its default is 16 MiB of a
# v5e's 128)
WEIGHT_BYTES = 16 << 20
VMEM_BYTES = 48 << 20
UP, DOWN, ROWS = "expert_up", "expert_down", "expert_rows"
KERNELS = (UP, DOWN, ROWS)  # the kernels' names: in a capture and in a jaxpr


def row_tile(pairs_an_expert: float) -> int:
    """Rows of a tile where an expert expects ``pairs_an_expert`` pairs:
    the largest of ``ROW_TILES`` within ``TILE_SHARE`` of them (a larger
    tile feeds the MXU longer from one weight block, a smaller one leaves
    less of a group's last tile empty), the smallest where none is."""
    for tile in ROW_TILES:
        if tile <= TILE_SHARE * pairs_an_expert:
            return tile
    return ROW_TILES[-1]


def block_for(contract: int, width: int, operands: int,
              itemsize: int) -> int | None:
    """Output columns of the weight block a grid step multiplies by, where
    ``operands`` matrices of (contract, width) are read side by side: the
    widest multiple of the lane width that divides ``width`` with the
    blocks inside ``WEIGHT_BYTES``; None where the widths are no multiples
    of the lane width or not even one lane tile of columns fits."""
    if contract % LANE or width % LANE:
        return None
    for blocks in range(1, width // LANE + 1):
        block = width // blocks
        if (width % blocks == 0 and block % LANE == 0
                and 2 * operands * contract * block * itemsize
                <= WEIGHT_BYTES):
            return block
    return None


def kernel_fits(up, dtype, operands: int = 2) -> bool:
    """Whether ``held_experts`` runs the kernels on experts whose stacked
    ``up`` is this array (held, hidden, width), ``operands`` such matrices
    side by side in the first product (SwiGLU's gate and up: 2; relu
    squared's up: 1): widths that fill whole lane tiles and whose blocks
    fit, and what ``ops/kernels.py`` asks of every family: a dtype the
    kernels serve, one device (the operand lies on no mesh) and a backend
    that runs them."""
    if len(up.shape) != 3:
        return False
    _, hidden, width = up.shape
    itemsize = _itemsize(up, dtype)
    return (
        kernels.serves(dtype)
        and block_for(hidden, width, operands, itemsize) is not None
        and block_for(width, hidden, 1, itemsize) is not None
        and kernels.off_mesh(up)
        and kernels.backend_runs_pallas()
    )


def _itemsize(w, dtype) -> int:
    """Bytes a value of a weight block takes on the chip: the blocks are
    held as they are stored and multiplied in the compute ``dtype`` (a
    float32 program over a bfloat16 checkpoint widens a block it holds)."""
    return max(jnp.dtype(w.dtype).itemsize, jnp.dtype(dtype).itemsize)


# ccfd-lint: hot-path
def _kernel(*refs, operands: int, block: int, passes: int, placed: bool,
            relu2: bool = False):
    """A grid step (column block j, row tile i): the tile times its
    expert's ``operands`` column blocks (two: silu of the first times the
    second; one: the bare product, or with ``relu2`` its relu squared).
    ``refs``: the prefetched scalars
    (each tile's expert, each tile's run, the first tile of the run after
    it or -1, the count of runs, each tile's live rows[, the tile the
    output starts at]), the row tile, the stacked matrices whole in HBM[, the buffer the output
    aliases, not read], the output block, two slots of weight blocks and
    their DMA semaphores. A run is the consecutive tiles of one expert:
    its first step waits for the run's blocks and starts the fetch of the
    next run's (at a pass's end, of the next pass's first) into the other
    slot, so a fetch has the whole run to finish in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    expert_ref, run_ref, next_ref, runs_ref, live_ref = refs[:5]
    x_ref, *w_hbm = refs[5 + placed:6 + placed + operands]
    o_ref, held, sem = refs[-3:]
    j, i = pl.program_id(0), pl.program_id(1)

    def fetch(tile, at, slot):
        return [pltpu.make_async_copy(
            w.at[expert_ref[tile], :,
                 pl.ds(pl.multiple_of(at * block, block), block)],
            held.at[slot, n], sem.at[slot, n]) for n, w in enumerate(w_hbm)]

    slot = (j * runs_ref[0] + run_ref[i]) % 2

    @pl.when(jnp.logical_or(
        i == 0, expert_ref[i] != expert_ref[jnp.maximum(i - 1, 0)]))
    def _a_run_begins():
        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _nobody_fetched_the_first():
            for copy in fetch(0, 0, 0):
                copy.start()

        for copy in fetch(i, j, slot):
            copy.wait()
        ahead = next_ref[i]

        @pl.when(ahead >= 0)
        def _the_next_run():
            for copy in fetch(ahead, j, 1 - slot):
                copy.start()

        @pl.when(jnp.logical_and(ahead < 0, j + 1 < passes))
        def _the_next_pass():
            for copy in fetch(0, j + 1, 1 - slot):
                copy.start()

    def multiply(lo: int, rows: int):
        x = x_ref[lo:lo + rows, :]
        parts = [jnp.dot(x, held[slot, n].astype(x.dtype),
                         preferred_element_type=jnp.float32)
                 for n in range(operands)]
        if operands == 2:  # gate and up
            parts = [jax.nn.silu(parts[0]) * parts[1]]
        elif relu2:  # up alone, no gate
            parts = [jnp.square(jax.nn.relu(parts[0]))]
        o_ref[lo:lo + rows, :] = parts[0].astype(o_ref.dtype)

    tile = x_ref.shape[0]
    if tile <= SUB_ROWS:
        return multiply(0, tile)
    # a tile whose last block holds a pair: one product; a group's last,
    # part-empty tile: its blocks that hold one
    whole = live_ref[i] > tile - SUB_ROWS
    pl.when(whole)(partial(multiply, 0, tile))
    for lo in range(0, tile - SUB_ROWS, SUB_ROWS):
        pl.when(jnp.logical_and(jnp.logical_not(whole), lo < live_ref[i]))(
            partial(multiply, lo, SUB_ROWS))


def _runs(expert_of, visit):
    """By tile, for the first ``visit`` tiles: the run (consecutive tiles
    of one expert) it belongs to, and the first tile of the run after it
    (-1: none follows); and the count of runs, (1,)."""
    tiles = len(expert_of)
    at = jnp.arange(tiles, dtype=jnp.int32)
    live = at < visit
    begins = live & ((at == 0) | (expert_of != jnp.roll(expert_of, 1)))
    run_of = jnp.cumsum(begins, dtype=jnp.int32) - 1
    later = jax.lax.cummin(jnp.where(begins, at, tiles), reverse=True)
    after = jnp.concatenate([later[1:], jnp.full((1,), tiles, jnp.int32)])
    return (run_of, jnp.where(after < tiles, after, -1),
            jnp.sum(begins, dtype=jnp.int32).reshape(1))


# ccfd-lint: hot-path
def _product(x, weights, expert_of, runs, live_of, visit, dtype, *,
             tile: int, name: str, into=None, first=None,
             relu2: bool = False):
    """``x`` (tiles x tile, contract) against the stacked ``weights`` (each
    (held, contract, width)), tile i by expert ``expert_of[i]``, the first
    ``visit`` tiles (``runs``: :func:`_runs` of them) and of each its
    first ``live_of[i]`` rows, rounded up to ``SUB_ROWS``: (tiles x tile,
    width) in ``dtype``, the other rows unwritten; one matrix gives the
    product (with ``relu2`` its relu squared), two give silu(x w0) * (x
    w1). With ``into`` (more rows, width) and ``first``
    the tiles are written there from tile ``first`` on, in place, and
    ``into`` comes back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, contract = x.shape
    width = weights[0].shape[-1]
    block = block_for(contract, width, len(weights),
                      _itemsize(weights[0], x.dtype))
    if block is None or rows % tile or any(
            w.shape[1:] != (contract, width) for w in weights):
        raise ValueError(f"{name} does not tile x{x.shape} by "
                         f"{[w.shape for w in weights]}")
    placed = into is not None
    scalars = (expert_of, *runs, live_of) + (
        (first.reshape(1),) if placed else ())
    operands = (x, *weights) + ((into,) if placed else ())
    return pl.pallas_call(
        partial(_kernel, operands=len(weights), block=block,
                passes=width // block, placed=placed, relu2=relu2),
        out_shape=jax.ShapeDtypeStruct(into.shape if placed
                                       else (rows, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(width // block, visit),
            in_specs=[pl.BlockSpec((tile, contract), lambda j, i, *_: (i, 0),
                                   memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
            out_specs=pl.BlockSpec(
                (tile, block),
                (lambda j, i, *s: (s[-1][0] + i, j)) if placed
                else (lambda j, i, *s: (i, j)), memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, len(weights), contract, block),
                           weights[0].dtype),
                pltpu.SemaphoreType.DMA((2, len(weights)))]),
        input_output_aliases={len(scalars) + len(operands) - 1: 0}
        if placed else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(weights) * rows * contract * width,
            transcendentals=rows * width * (len(weights) - 1),
            bytes_accessed=x.dtype.itemsize * rows * contract * (
                width // block)
            + weights[0].dtype.itemsize * len(weights) * (rows // tile)
            * contract * width + rows * width * jnp.dtype(dtype).itemsize),
        name=name,
        interpret=kernels.interpreted(),
    )(*scalars, *operands)


@partial(jax.jit, static_argnames=("tile",))
# ccfd-lint: hot-path
def grouped_swiglu(x: jax.Array, gate: jax.Array, up: jax.Array,
                   down: jax.Array, expert_of: jax.Array, live_of: jax.Array,
                   visit: jax.Array, into: jax.Array, first: jax.Array,
                   tile: int) -> jax.Array:
    """``x`` (tiles x tile, hidden) in the compute dtype, row tile i of
    expert ``expert_of[i]`` (tiles,) int32 with its first ``live_of[i]``
    (tiles,) int32 rows real: that expert's SwiGLU of the rows, by the
    stacked matrices ``gate``, ``up`` (held, hidden, width) and ``down``
    (held, width, hidden), written into ``into`` (more tiles x tile,
    hidden) from its tile ``first`` (int32 scalar) on, in place and in its
    dtype; ``into`` comes back. Only the first ``visit`` (int32 scalar)
    tiles are computed and written, and of a tile its real rows (rounded
    up to ``SUB_ROWS``). Only shapes :func:`kernel_fits` admits."""
    runs = _runs(expert_of, visit)
    h = _product(x, (gate, up), expert_of, runs, live_of, visit, x.dtype,
                 tile=tile, name=UP)
    return _product(h, (down,), expert_of, runs, live_of, visit, into.dtype,
                    tile=tile, name=DOWN, into=into, first=first)


@partial(jax.jit, static_argnames=("tile",))
# ccfd-lint: hot-path
def grouped_relu2(x: jax.Array, up: jax.Array, down: jax.Array,
                  expert_of: jax.Array, live_of: jax.Array,
                  visit: jax.Array, into: jax.Array, first: jax.Array,
                  tile: int) -> jax.Array:
    """:func:`grouped_swiglu` for experts of two matrices and no gate:
    relu(x ``up``)^2 ``down`` of each tile's rows by its expert, written
    into ``into`` from its tile ``first`` on; the same tiles, scalars and
    kernels (``expert_up`` with one operand squares its rectified
    product)."""
    runs = _runs(expert_of, visit)
    h = _product(x, (up,), expert_of, runs, live_of, visit, x.dtype,
                 tile=tile, name=UP, relu2=True)
    return _product(h, (down,), expert_of, runs, live_of, visit, into.dtype,
                    tile=tile, name=DOWN, into=into, first=first)


# ``models/hybrid_moe.py::EXPERT_BODIES``' names -> the grouped form; each
# takes the body's stacked matrices in that table's order
GROUPED = {"swiglu": grouped_swiglu, "relu2": grouped_relu2}


def uninitialised(shape: tuple, dtype) -> jax.Array:
    """A buffer nobody has written: what the grouped bodies write the
    tiles into. ``held_experts`` reads only rows a kernel wrote, so the
    574 MB of zeros a layer of Mistral-Small-4 would start from are 0.7 ms
    of writes for nothing. (XLA has no such allocation; a kernel that
    leaves its output alone is one.)"""
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda o_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name=ROWS,
        interpret=kernels.interpreted())()
