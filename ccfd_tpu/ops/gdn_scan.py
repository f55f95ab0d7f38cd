"""Pallas TPU kernel: the scalar-decay delta rule of Gated DeltaNet, state on
the chip.

``models/hybrid_moe.py::_gdn_chunk`` is S_t = e^(g_t) S_(t-1) + beta_t k_t
(v_t - (e^(g_t) S_(t-1))^T k_t)^T, o_t = S_t^T q_t, a chunk of tokens at a
time, with one log-decay g a value head and token and ``per`` value heads
on each key head. As XLA compiles the loop over chunks, the (B, Hk, per,
128, 128) float32 state, the (C, C) factors and the stacked 8 x 8 blocks of
the triangular inverse cross HBM on every trip (120 a window at chunks of
16), and the L2 norms, the casts and the gated norm around the loop are
float32 passes over (B, T, Hv x dv) of their own. Here a (pair of rows,
block of value heads, run of spans) is held in VMEM:

- operands token-major, as ``gdn``'s projection and ``ops/short_conv.py``
  leave them: ``q`` and ``k`` as (B, T, Hk x dk), ``v`` and o as (B, T, Hv
  x dv) (a head is whole lane tiles, so a head of a block is a slice of
  whole tiles with the tokens down the sublanes, and a chunk a slice of
  whole sublane tiles), ``g`` and ``beta`` as (B, T, Hv), the gate's ``z``
  read where it lies in the projection (a block index at its column, no
  slice). Nothing is relaid out around the call and nothing is transposed
  inside but the (128, Hv) running sums and beta. No state, no (C, C)
  array and no block of the inverse crosses HBM;
- the grid runs over (rows, block of value heads, run of spans), the runs
  innermost and in order: every value head's state (dk x dv float32) lives
  in VMEM scratch across a row's chunks, zeroed at a row's first. A step
  takes ``windows_for`` rows, ``heads_for`` value heads (whole key heads)
  and ``kda_scan.spans_for`` spans;
- a *span* is ``kda_scan.SPAN`` = 128 tokens and is worked in two stages.
  **Across the span**, a row of the step at a time (a ``fori_loop``: the
  rows share one traced body), its heads side by side through every
  product (``ops/kda_scan.py`` says why), with its 128 / chunk chunks'
  pairwise matrices side by side as the diagonal blocks of one (128, 128)
  matrix: where the caller left them to the kernel (``unit``), the L2
  norms of q and k by head, q's scale and the rounding of q, k and v to
  the serving dtype; K K^T and Q K^T once a key head; the running sums G
  of g inside each chunk for all value heads in one product with a
  triangle of ones (g as three bfloat16 pieces: exact ones, float32
  sums); a value head's pairwise factor exp(where(t >= i, G_t - G_i,
  -inf)), **masked before the exponential** and so <= 1 however fast a
  head forgets (g has no bound: nothing is a quotient of two
  exponentials); the masked blocked unit-lower-triangular inverse
  (``kda_scan._unit_lower_inverse``: float32 at ``HIGHEST``); the solve's
  and the output's (C, C) blocks then moved to the first lanes by a
  product with zeros and ones (exact); all into scratch. **Down the
  span's chunks**, a ``fori_loop`` over sublane slices of that scratch
  and of the blocks, the step's rows and heads all side by side (a
  chunk's products with the state wait for one another, four answers of
  the MXU a chunk; the other chains fill the waits): the products with
  the carried state (three bfloat16 passes: ``ssd_scan._split``; a
  bfloat16 operand is its own first piece and has no second, so its
  third pass is left out: it would add zeros; the passes go through the
  MXU as ONE product, side by side along the contracted axis, so that
  the (dk, dv) answer is popped once), u = solve (v - e^G K S), o = e^G
  Q S + P u, S = e^(G_C) S + K^T (e^(G_C - G) u), the last with the
  chunk's rows contracted as they stand; where the caller handed the gate
  over (``z``, ``norm``), o times rsqrt(mean o^2 + eps) times the weight
  times SiLU(z), in the serving dtype. e^(G_t), e^(G_C - G_i) and e^(G_C)
  are columns and rows broadcast, never (C, dk) arrays;
- the arithmetic is ``_gdn_chunk``'s, and ``gdn``'s around it: the
  products inside a chunk one bfloat16 pass (``KDA_INSIDE``), those that
  touch the carried state three (``KDA_PRECISION``), the inverse float32
  at ``HIGHEST``, everything else float32. Under the interpreter
  (``exact``) every product is float32;
- a window that is no whole number of spans is padded on the left with
  tokens of g = 0 and beta = 0, which pass the state unchanged: whole
  chunks more than the loop's padding, so the chunks' edges are the same.

:func:`kernel_fits` is the selection ``gdn`` makes while the program is
traced, from shapes, dtype, backend and where the operands lie; the kernel
has no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels
from ccfd_tpu.ops.kda_scan import (BASE, CHUNKS, HEADS, NT, SPAN,
                                   _running_sums, _unit_lower_inverse,
                                   spans_for)
from ccfd_tpu.ops.ssd_scan import BF16, F32, LANE, _dot, _split

# what a step holds: the blocks double-buffered, every head's state and a
# span's factors; asked of the compiler (its default is 16 MiB of a v5e's 128)
VMEM_BYTES = 64 << 20
KERNEL = "gdn_scan"  # the kernel's name: in the capture and in a jaxpr
# rows of the batch a grid step takes side by side: a chunk's products with
# the state wait for one another (four answers of the MXU a chunk), so a
# step's chains are what fills the waits; another row's heads are chains
# whose factors the same traced code makes (a loop over the step's rows)
WINDOWS = (2, 1)
TN = (((0,), (0,)), ((), ()))  # a^T b


def heads_for(value_heads: int, per: int) -> int:
    """Value heads a grid step takes: the largest of ``kda_scan.HEADS``
    that tiles them in whole key heads (``per`` value heads each); one key
    head's where none does."""
    return next((n for n in HEADS if value_heads % n == 0 and n % per == 0),
                per)


def windows_for(batch: int) -> int:
    """Rows of the batch a grid step takes side by side: the largest of
    ``WINDOWS`` that tiles them."""
    return next(n for n in WINDOWS if batch % n == 0)


def _vmem_bytes(windows: int, tokens: int, heads: int, per: int, dk: int,
                dv: int) -> int:
    """What a grid step holds, counted as float32: the blocks of q, k, v,
    z, o, g and beta twice over, the states, and a span's normed k and q,
    solve, pairwise factor and running sums a value head."""
    blocks = 2 * tokens * (2 * heads // per * dk + 3 * heads * dv + 2 * LANE)
    return 4 * windows * (blocks + heads * (
        dk * dv + 2 * SPAN * dk // per + 2 * SPAN * SPAN + SPAN * dv))


def kernel_fits(q, v, chunk: int, dtype=None) -> bool:
    """Whether ``gdn`` runs the kernel on ``q`` (and ``k``) (B, T, Hk, dk)
    and ``v`` (B, T, Hv, dv) at chunks of ``chunk`` tokens, rounded to
    ``dtype`` inside where that is not theirs already (arrays or
    their shapes: what is read is shape, dtype and where they lie): keys
    and values of whole lane tiles, whole key heads' worth of value heads,
    a chunk of ``kda_scan.CHUNKS``, a step's blocks, states and factors
    inside ``VMEM_BYTES``, and what ``ops/kernels.py`` asks of every
    family: a dtype the kernels serve, operands on no mesh and a backend
    that runs them. Refused, and so on the loop over ``_gdn_chunk``: heads
    of 16 (the tests' presets), a chunk of 48, a mesh."""
    if len(q.shape) != 4 or len(v.shape) != 4:
        return False
    (b, t, hk, dk), (_, _, hv, dv) = q.shape, v.shape
    if tuple(v.shape[:2]) != (b, t) or not hk or hv % hk:
        return False
    per = hv // hk
    return (
        dk % LANE == 0 and dv % LANE == 0 and chunk in CHUNKS
        and _vmem_bytes(windows_for(b), SPAN * spans_for(-(-t // SPAN)),
                        heads_for(hv, per), per, dk, dv) <= VMEM_BYTES
        and jnp.dtype(q.dtype) == jnp.dtype(v.dtype)
        and kernels.serves(q.dtype)
        and kernels.serves(q.dtype if dtype is None else dtype)
        and kernels.off_mesh(q, v)
        and kernels.backend_runs_pallas()
    )


def _halves(x, exact: bool):
    """``ssd_scan._split`` of an operand as it stands: a bfloat16 is its
    own first piece and has no second."""
    if x.dtype == BF16 and not exact:
        return x, None
    return _split(x.astype(F32), exact)


def _on_state(a, b, dims=(((1,), (0,)), ((), ()))):
    """``ssd_scan._dot3`` of two :func:`_halves`, hi hi + hi lo + lo hi,
    as ONE product: the passes' operands side by side along the contracted
    axis, so that the MXU adds the passes up in its float32 accumulator
    and the result is popped once, not once a pass; a pass that a piece
    which is not there would fill with zeros is left out."""
    (a_hi, a_lo), (b_hi, b_lo) = a, b
    passes = [(a_hi, b_hi)] + [(x, y) for x, y in ((a_hi, b_lo), (a_lo, b_hi))
                               if x is not None and y is not None]
    (on_a,), (on_b,) = dims[0]
    return _dot(jnp.concatenate([x for x, _ in passes], on_a),
                jnp.concatenate([y for _, y in passes], on_b), dims)


# ccfd-lint: hot-path
def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, chunk: int,
            per: int, unit: float | None, gate: float | None, exact: bool):
    from jax.experimental import pallas as pl

    z_ref, norm_ref = rest[:2] if gate is not None else (None, None)
    o_ref, state, keys, queries, solve, pair, since = rest[-7:]

    windows = q_ref.shape[0]  # rows of the batch a step takes side by side
    heads = state.shape[0] // windows  # the step's value heads a row
    key_heads = heads // per
    dk, dv = state.shape[1:]
    low = F32 if exact else BF16
    served = keys.dtype  # what q, k and v are rounded to before the scan

    @pl.when(pl.program_id(2) == 0)
    def _():  # a row's first chunk: its heads start from nothing
        state[...] = jnp.zeros(state.shape, F32)

    # a span's pairwise matrices: its chunks' blocks on the diagonal
    at_row = jax.lax.broadcasted_iota(jnp.int32, (SPAN, SPAN), 0)
    at_col = jax.lax.broadcasted_iota(jnp.int32, (SPAN, SPAN), 1)
    by = chunk.bit_length() - 1  # a chunk is a power of two
    same = at_row >> by == at_col >> by
    ones = (same & (at_col <= at_row)).astype(low)
    # times this, a block's columns come to stand in the first lanes
    to_front = (at_row & (chunk - 1) == at_col).astype(low)
    every = beta_ref.shape[2]  # the value heads of the model
    along = jax.lax.broadcasted_iota(jnp.int32, (1, every), 1)
    down = jax.lax.broadcasted_iota(jnp.int32, (every, 1), 0)
    first = pl.program_id(1) * heads  # the step's first value head

    def column(x, h):  # (SPAN, every) -> (SPAN, 1): one head's
        return jnp.sum(jnp.where(along == first + h, x, 0.0), axis=1,
                       keepdims=True)

    def flat(x, h):  # (every, SPAN) -> (1, SPAN): one head's
        return jnp.sum(jnp.where(down == first + h, x, 0.0), axis=0,
                       keepdims=True)

    def normed(x, scale: float):
        """A key head's (SPAN, dk) as the scan takes it: L2-normed where
        the caller left that to the kernel, then in the served dtype."""
        if unit is not None:
            x = x.astype(F32)
            x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + unit)
            if scale != 1.0:
                x = x * scale
        return x.astype(served)

    def one_span(i, carry):
        rows = pl.ds(pl.multiple_of(i * SPAN, SPAN), SPAN)

        def across(w, carry):
            """Row ``w`` of the step: what every chunk's trip down the
            span reads, into scratch; its heads side by side."""
            for j in range(key_heads):
                at = slice(j * dk, (j + 1) * dk)
                keys[w * key_heads + j] = normed(k_ref[w, rows, at], 1.0)
                queries[w * key_heads + j] = normed(q_ref[w, rows, at],
                                                    dk ** -0.5)
            k = [keys[w * key_heads + j].astype(low)
                 for j in range(key_heads)]
            kk = [_dot(x, x, NT) for x in k]
            qk = [_dot(queries[w * key_heads + j].astype(low), x, NT)
                  for j, x in enumerate(k)]
            run = _running_sums(ones, g_ref[w, rows, :], exact)  # G_t
            betas = beta_ref[w, rows, :]
            run_t, betas_t = run.T, betas.T  # a token a lane
            mine = [column(run, h) for h in range(heads)]
            beta = [column(betas, h) for h in range(heads)]
            decay = [jnp.exp(jnp.where(same & (at_row >= at_col),
                                       m - flat(run_t, h), -jnp.inf))
                     for h, m in enumerate(mine)]
            inverse = _unit_lower_inverse(
                [jnp.where(at_row > at_col, kk[h // per] * d, 0.0) * b
                 for h, (d, b) in enumerate(zip(decay, beta))],
                at_row, at_col, chunk, exact)
            for h, (inv, d, m) in enumerate(zip(inverse, decay, mine)):
                factors = ((inv * flat(betas_t, h)).astype(low),
                           (qk[h // per] * d).astype(low))
                for ref, x in zip((solve, pair), factors):
                    ref[w * heads + h] = x if chunk == SPAN else _dot(
                        x, to_front).astype(low)
                since[w * heads + h] = jnp.broadcast_to(m, (SPAN, dv))
            return carry

        jax.lax.fori_loop(0, windows, across, 0)
        each = [(w, h) for w in range(windows) for h in range(heads)]

        def one_chunk(c, carry):
            """A chunk of the step's rows and heads against their states,
            all side by side through every product: a chunk's chain of
            products with the state is as long as the MXU's answers take,
            and the other chains fill its waits."""
            inside = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            here = pl.ds(pl.multiple_of(i * SPAN + c * chunk, chunk), chunk)
            k = [keys[n, inside, :] for n in range(windows * key_heads)]
            kq = [_halves(jnp.concatenate([x, queries[n, inside, :]]), exact)
                  for n, x in enumerate(k)]
            k = [_halves(x, exact) for x in k]
            of = [w * key_heads + h // per for w, h in each]  # its key head
            s0 = [state[n] for n in range(len(each))]
            # K S and Q S in one product
            f = [_on_state(kq[j], _split(s, exact)) for j, s in zip(of, s0)]
            run = [since[n, inside, :] for n in range(len(each))]  # G_t
            whole = [jnp.exp(x) for x in run]
            v = [v_ref[w, here, h * dv:(h + 1) * dv].astype(served).astype(
                F32) for w, h in each]
            u = [_dot(solve[n, inside, :chunk],
                      (y - x[:chunk] * e).astype(low))
                 for n, (y, x, e) in enumerate(zip(v, f, whole))]
            last = [since[n, pl.ds(c * chunk + chunk - 1, 1), :]
                    for n in range(len(each))]  # G_C
            for n, (w, h) in enumerate(each):
                at = slice(h * dv, (h + 1) * dv)
                out = f[n][chunk:] * whole[n] + _dot(
                    pair[n, inside, :chunk], u[n].astype(low))
                if gate is not None:  # ``_rms`` times w, times SiLU(z)
                    out = out * jax.lax.rsqrt(jnp.mean(
                        out * out, -1, keepdims=True) + gate) * norm_ref[
                        ...] * jax.nn.silu(z_ref[w, here, at].astype(F32))
                o_ref[w, here, at] = out.astype(o_ref.dtype)
                state[n] = s0[n] * jnp.exp(last[n]) + _on_state(
                    k[of[n]],
                    _split(u[n] * jnp.exp(last[n] - run[n]), exact), TN)
            return carry

        jax.lax.fori_loop(0, SPAN // chunk, one_chunk, 0)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // SPAN, one_span, 0)


@partial(jax.jit, static_argnames=("chunk", "unit", "dtype", "at", "eps",
                                   "exact"))
# ccfd-lint: hot-path
def gdn_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int, unit: float | None = None,
             dtype=None, z: jax.Array | None = None,
             norm: jax.Array | None = None, at: int = 0,
             eps: float | None = None, exact: bool | None = None):
    """``q``, ``k`` (B, T, Hk, dk), ``v`` (B, T, Hv, dv), the log-decays
    ``g`` <= 0 and ``beta`` (B, T, Hv) -> o (B, T, Hv, dv) float32: the
    loop over ``_gdn_chunk`` from a zero state, value head j on key head j
    // (Hv / Hk). With ``unit`` (an epsilon) q and k arrive as the
    convolution leaves them and are L2-normed by head inside (x rsqrt(sum
    x^2 + unit), q times dk^-0.5), and q, k and v rounded to ``dtype``,
    before the scan: ``gdn``'s own arithmetic without its float32 passes
    through HBM. With ``z`` (B, T, W) and ``norm`` (dv,), o leaves gated:
    every head's values RMS-normed (``eps``) times ``norm``, times SiLU of
    ``z``'s Hv x dv columns from ``at`` (read where they lie in the
    projection), in ``dtype``. Only shapes :func:`kernel_fits` admits;
    ``exact`` (the
    interpreter's default) multiplies in float32 where the chip takes
    bfloat16 passes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, tokens, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    if exact is None:
        exact = kernels.interpreted()
    if (dk % LANE or dv % LANE or chunk not in CHUNKS or not key_heads
            or heads % key_heads or k.shape != q.shape
            or v.shape[:2] != q.shape[:2]
            or not g.shape == beta.shape == v.shape[:3]):
        raise ValueError(f"gdn_scan does not tile q{q.shape} v{v.shape} "
                         f"at a chunk of {chunk}")
    if dtype is None:
        dtype = q.dtype
    served = jnp.dtype(q.dtype if unit is None else dtype)
    per = heads // key_heads
    lead = -tokens % SPAN  # whole chunks of padding: the edges stay
    spans = (tokens + lead) // SPAN
    chunks = spans * (SPAN // chunk)
    step_heads, step_spans = heads_for(heads, per), spans_for(spans)
    windows = windows_for(batch)
    step_tokens = step_spans * SPAN
    low = F32 if exact else BF16
    each = windows * step_heads  # states a step holds

    def flat(x):  # (B, T, ...) -> (B, lead + T, the rest as one lane axis)
        x = x.reshape(batch, tokens, -1)
        return jnp.pad(x, ((0, 0), (lead, 0), (0, 0))) if lead else x

    def tokens_of(lanes, at):
        return pl.BlockSpec((windows, step_tokens, lanes), at,
                            memory_space=pltpu.VMEM)

    def by_head(b, j, c):
        return b, c, j

    def all_heads(b, j, c):
        return b, c, 0

    gate = []
    if z is not None:
        lanes = step_heads * dv
        if lead or at % lanes:  # not addressable where it lies: a copy
            z, at = flat(z[..., at:at + heads * dv]), 0
        gate = [(z, tokens_of(lanes, lambda b, j, c: (b, c, at // lanes + j))),
                (norm.astype(F32).reshape(1, dv), pl.BlockSpec(
                    (1, dv), lambda b, j, c: (0, 0), memory_space=pltpu.VMEM))]

    inverse = 2 * (BASE.bit_length() - 2) + 2 * (chunk // BASE
                                                 ).bit_length() - 2
    o = pl.pallas_call(
        partial(_kernel, chunk=chunk, per=per, unit=unit,
                gate=eps if gate else None, exact=exact),
        out_shape=jax.ShapeDtypeStruct((batch, chunks * chunk, heads * dv),
                                       dtype if gate else F32),
        grid=(batch // windows, heads // step_heads, spans // step_spans),
        in_specs=[tokens_of(step_heads // per * dk, by_head)] * 2
        + [tokens_of(step_heads * dv, by_head)]
        + [tokens_of(heads, all_heads)] * 2 + [spec for _, spec in gate],
        out_specs=tokens_of(step_heads * dv, by_head),
        scratch_shapes=[
            pltpu.VMEM((each, dk, dv), F32),  # the states
            pltpu.VMEM((each // per, SPAN, dk), served),  # a span's k ..
            pltpu.VMEM((each // per, SPAN, dk), served),  # .. and q, normed
            pltpu.VMEM((each, SPAN, SPAN), low),  # solve, by chunk
            pltpu.VMEM((each, SPAN, SPAN), low),  # Q K^T e^(G - G)
            pltpu.VMEM((each, SPAN, dv), F32)],  # G_t along the lanes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            # bfloat16 passes a value head and chunk: K K^T and Q K^T (a
            # key head's, shared), the inverse 6 a product, the blocks to
            # the front 2, with the state (2 + 2) of 3 each, u and P u 1
            flops=2 * batch * heads * chunks * chunk * (
                2 * SPAN * dk // per + 6 * inverse * SPAN + 2 * SPAN
                + 3 * 3 * dk * dv + 2 * chunk * dv),
            transcendentals=batch * heads * chunks * chunk * (SPAN + 3 * dv),
            bytes_accessed=batch * chunks * chunk * (
                2 * key_heads * dk * q.dtype.itemsize
                + heads * (dv * (v.dtype.itemsize + (
                    4 + jnp.dtype(dtype).itemsize if gate else 4)) + 8))),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), flat(beta.astype(F32)),
      *(x for x, _ in gate))
    return o[:, lead:].reshape(batch, tokens, heads, dv)
