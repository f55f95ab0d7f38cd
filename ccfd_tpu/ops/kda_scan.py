"""Pallas TPU kernel: the KDA chunked delta-rule scan, state on the chip.

``models/hybrid_moe.py::_kda_chunk`` is the gated delta rule S_t =
Diag(alpha_t) S_(t-1) + beta_t k_t (v_t - S_(t-1)^T Diag(alpha_t) k_t)^T,
o_t = S_t^T q_t, a chunk of tokens at a time. As XLA compiles the loop over
chunks, the (B, H, 128, 128) float32 state crosses HBM on every trip, the
operands of every ``bthc,bihc->bhti`` are copied into a by-head layout, the
strips of 16 rows are padded and joined, and the 8 x 8 blocks of the
triangular inverse are stacked and re-joined. Here a (row, block of heads,
run of spans) is held in VMEM:

- operands by head with the tokens along the lanes: ``q``, ``k``, ``v``
  and the log-decays ``g`` as (B, H, d, T), o likewise, float32; ``beta``
  as (B, T, H). A head is ``d`` = 128 x n keys and as many values, whole
  lane tiles, so a (d, 128 tokens) block turns into (128 tokens, d) on the
  chip's transpose unit, which stands idle otherwise. That is the layout
  XLA gives ``kda``'s projections and their convolutions on the chip
  (f32[B, T, H, d]{1,3,2,0}: the tokens minor-most), so the transposes
  around the call are bitcasts; the token-major (B, T, H x d) view is not
  free there (its tiles run over (T, H x d), the array's over (H, d) or
  (d, T)): it cost five relayout copies a layer. No by-head copy, no
  (.., C, C) array, no strip, no block of the inverse and no state
  crosses HBM;
- the grid runs over (row, block of heads, run of spans), the runs
  innermost and in order: every head's state (d x d float32) lives in VMEM
  scratch across a row's chunks, zeroed at a row's first. A *span* is
  ``SPAN`` = 128 tokens, 128 / chunk chunks side by side: their pairwise
  matrices fill the lanes as the diagonal blocks of one (128, 128)
  matrix, so two chunks of 64 cost the masks, the running sums and the
  inverse's products once. A step takes ``heads_for`` heads through
  ``spans_for`` spans, **the heads side by side through every product**:
  a product waits 131 cycles for the MXU and the MXU's passes stay in
  program order, so one head after another would run at a chain's
  latency, and heads interleaved fill each other's waits;
- the arithmetic is ``_kda_chunk``'s: the running sums G of g inside a
  chunk float32 (a product with a triangle of ones, g as three bfloat16
  pieces: exact ones, float32 sums); e^(G_t - G_i) formed per ``sub`` rows
  against the running sum at the strip's first row, so that neither factor
  leaves float32; the products inside a chunk one bfloat16 pass
  (``KDA_INSIDE``), those that touch the carried state three
  (``KDA_PRECISION``: ``ssd_scan._split`` / ``_dot3``), the
  unit-lower-triangular inverse float32 at ``HIGHEST``: the six products
  of three bfloat16 pieces that precision is made of (:func:`_dot6`).
  Under the interpreter (``exact``) every product is float32;
- the inverse is ``_unit_lower_inverse``'s, on the whole matrix behind
  masks: the 8 x 8 blocks on the diagonal by the nilpotent series (with
  n = -a, (I + n)(I + n^2)(I + n^4): no power grows past a few tens),
  then neighbouring blocks joined, size by size, up to the chunk: with T
  the inverse of the blocks of ``size`` and c the corners below them, the
  inverse at twice the size is T - T c T. The whole matrix is never
  squared (its powers reach 1e5 and cancel);
- the state is held by values (d rows of values, keys along the lanes):
  its decay e^(G_last) is then a row that broadcasts down the sublanes;
- a window that is no whole number of spans is padded on the left with
  tokens of g = 0 and beta = 0, which pass the state unchanged: whole
  chunks more than the loop's padding, so the chunks' edges are the same.

:func:`kernel_fits` is the selection ``kda`` makes while the program is
traced, from shapes, dtype, backend and where the operands lie; the kernel
has no derivative and must not reach ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ccfd_tpu.ops import kernels
from ccfd_tpu.ops.ssd_scan import BF16, F32, LANE, _dot, _dot3, _split

BASE = 8  # rows of a diagonal block the nilpotent series inverts
SPAN = LANE  # tokens whose pairwise matrices fill the lanes: whole chunks
CHUNKS = (128, 64, 32, 16)  # tokens a chunk: BASE x a power of two
# heads a grid step takes, the most that tile them: eight side by side run
# a seventh faster than four, and cost a start twice the seconds of tracing
# and lowering the unrolled body (12 s of ``setup_s`` on the chip's host)
HEADS = (4, 2, 1)
SPANS_A_STEP = 5  # spans a grid step takes, at most
# what a step holds: the blocks double-buffered and every head's state;
# asked of the compiler (its default is 16 MiB of a v5e's 128)
VMEM_BYTES = 64 << 20
KERNEL = "kda_scan"  # the kernel's name: in the capture and in a jaxpr
NT = (((1,), (1,)), ((), ()))  # a b^T


def heads_for(heads: int) -> int:
    """Heads a grid step takes: the largest of ``HEADS`` that tiles them."""
    return next(n for n in HEADS if heads % n == 0)


def spans_for(spans: int) -> int:
    """Spans a grid step takes: the most, up to ``SPANS_A_STEP``, that
    tile a row's."""
    return next(m for m in range(min(spans, SPANS_A_STEP), 0, -1)
                if spans % m == 0)


def _vmem_bytes(tokens: int, heads: int, width: int) -> int:
    """What a grid step holds, counted as float32: the blocks of q, k, v,
    g, o and beta twice over, and the states."""
    return 4 * (2 * tokens * (5 * heads * width + LANE)
                + heads * width * width)


def kernel_fits(q, v, chunk: int, sub: int) -> bool:
    """Whether ``kda`` runs the kernel on ``q`` (and ``k``, ``g``) (B, T,
    H, dk) and ``v`` (B, T, H, dv) at chunks of ``chunk`` tokens and
    strips of ``sub`` (arrays or their shapes: what is read is shape,
    dtype and where they lie): keys and values of as many whole lane
    tiles, a chunk of ``CHUNKS`` that ``sub`` tiles, a step's blocks and
    states inside ``VMEM_BYTES``, and what ``ops/kernels.py`` asks of every
    family: a dtype the kernels serve, operands on no mesh and a backend
    that runs them. Refused, and so on the loop over ``_kda_chunk``: heads
    of 16 (the tests' presets), a chunk of 48, a mesh."""
    if len(q.shape) != 4 or tuple(v.shape) != tuple(q.shape):
        return False
    _, t, h, d = q.shape
    return (
        d % LANE == 0 and chunk in CHUNKS and sub % (2 * BASE) == 0
        and chunk % sub == 0
        and _vmem_bytes(SPAN * spans_for(-(-t // SPAN)), heads_for(h),
                        d) <= VMEM_BYTES
        and jnp.dtype(q.dtype) == jnp.dtype(v.dtype)
        and kernels.serves(q.dtype)
        and kernels.off_mesh(q, v)
        and kernels.backend_runs_pallas()
    )


def _pieces(x, exact: bool):
    """A float32 value as the three pieces a product at ``HIGHEST``
    multiplies, each a float32 that a bfloat16 holds: its first 8 bits of
    mantissa, the next 8 and the last 8 (``exact``: the value alone)."""
    if exact:
        return (x,)

    def head(y):  # the bits a bfloat16 keeps
        bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), F32)

    hi = head(x)
    mid = head(x - hi)
    return hi, mid, (x - hi) - mid


def _running_sums(ones, g, exact: bool):
    """The running sums of ``g`` (S, d) down each chunk: the product with
    the chunks' lower triangles of ones; on the chip g as its three
    bfloat16 pieces (a one is exact, the sums are float32: the sum's own
    accuracy), the smallest first."""
    return sum(_dot(ones, x.astype(ones.dtype))
               for x in reversed(_pieces(g, exact)))


def _dot6(a, b):
    """``a`` (M, K) float32 times ``b``, :func:`_pieces` of a (K, N): the
    six products of pieces a float32 product at ``HIGHEST`` is made of
    (a1 b1 + a1 b2 + a2 b1 + a1 b3 + a2 b2 + a3 b1), the pieces of ``a``
    that meet one piece of ``b`` stacked, so that a piece of ``b`` is
    loaded into the MXU once: three passes where six would load."""
    if len(b) == 1:
        return _dot(a, b[0])
    rows = a.shape[0]
    a1, a2, a3 = _pieces(a, False)

    def one_pass(x, y):  # the pieces are bfloat16s: one pass is exact
        return jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())), preferred_element_type=F32,
            precision=jax.lax.Precision.DEFAULT)

    by_b1 = one_pass(jnp.concatenate([a1, a2, a3]), b[0])
    by_b2 = one_pass(jnp.concatenate([a1, a2]), b[1])
    by_b3 = one_pass(a1, b[2])
    return (by_b1[:rows] + (by_b1[rows:2 * rows] + by_b2[:rows])
            + (by_b1[2 * rows:] + by_b2[rows:] + by_b3))


def _unit_lower_inverse(each, at_row, at_col, chunk: int, exact: bool):
    """(I + a)^-1 for every ``a`` (S, S) float32 of ``each``, of strictly
    lower-triangular diagonal blocks of ``chunk`` rows (the chunks of a
    span, each inverted for itself), as
    ``hybrid_moe._unit_lower_inverse`` blocks one, every product float32
    at ``HIGHEST`` (:func:`_dot6`; ``exact``: a float32 product);
    ``at_row`` / ``at_col`` are the (S, S) row and column numbers. A
    matrix of diagonal blocks is multiplied *packed*: its blocks side by
    side as (size, S), the sum of its row blocks, times the other factor
    whole gives the product's blocks side by side, in ``size`` rows of the
    MXU where the whole matrix would take S. The matrices of ``each`` (a
    step's heads) go through every product side by side."""
    c = each[0].shape[0]

    def blocks(size):  # entries inside the diagonal blocks of ``size``
        by = size.bit_length() - 1  # a size is a power of two
        return at_row >> by == at_col >> by

    def packed(x, size, half=False):  # the sum of x's row blocks
        if half:  # of the lower halves of its blocks of 2 x size
            return x.reshape(-1, 2, size, c)[:, 1].sum(0)
        return x.reshape(-1, size, c).sum(0)

    def whole(x, where):  # packed blocks back in their places
        return jnp.where(where, jnp.tile(x, (c // x.shape[0], 1)), 0.0)

    ns = [jnp.where(blocks(BASE), -a, 0.0) for a in each]
    steps = [packed(n, BASE) for n in ns]
    ns = [_pieces(n, exact) for n in ns]
    eye = packed((at_row == at_col).astype(F32), BASE)
    invs = [eye + step for step in steps]
    power = 1
    while power * 2 < BASE:
        steps = [_dot6(step, n) for step, n in zip(steps, ns)]
        ns = [_pieces(whole(step, blocks(BASE)), exact) for step in steps]
        invs = [inv + _dot6(inv, n) for inv, n in zip(invs, ns)]
        power *= 2
    invs = [whole(inv, blocks(BASE)) for inv in invs]
    size = BASE
    while size < chunk:
        # [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]: the
        # lower halves' rows of the pairs, packed, times C, times the whole
        below = blocks(2 * size) & ~blocks(size) & (at_row > at_col)
        corners = [_dot6(packed(inv, size, half=True),
                         _pieces(jnp.where(below, a, 0.0), exact))
                   for inv, a in zip(invs, each)]
        corners = [_dot6(corner, _pieces(inv, exact))
                   for corner, inv in zip(corners, invs)]
        invs = [inv - whole(corner, below)
                for inv, corner in zip(invs, corners)]
        size *= 2
    return invs


# ccfd-lint: hot-path
def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, *,
            chunk: int, sub: int, heads: int, exact: bool):
    from jax.experimental import pallas as pl

    width = q_ref.shape[2]
    low = F32 if exact else BF16

    @pl.when(pl.program_id(2) == 0)
    def _():  # a row's first chunk: its heads start from nothing
        state[...] = jnp.zeros(state.shape, F32)

    # a span's pairwise matrices: its chunks' blocks on the diagonal
    at_row = jax.lax.broadcasted_iota(jnp.int32, (SPAN, SPAN), 0)
    at_col = jax.lax.broadcasted_iota(jnp.int32, (SPAN, SPAN), 1)
    by = chunk.bit_length() - 1  # a chunk is a power of two
    same = at_row >> by == at_col >> by
    ones = (same & (at_col <= at_row)).astype(low)
    head_at = jax.lax.broadcasted_iota(jnp.int32, (1, beta_ref.shape[2]), 1)
    first = pl.program_id(1) * heads  # the step's first head

    def among(x, lo):  # rows lo.. of a span's (SPAN, width), zeros around
        parts = [jnp.zeros((lo, width), x.dtype), x,
                 jnp.zeros((SPAN - lo - x.shape[0], width), x.dtype)]
        return jnp.concatenate([part for part in parts if part.shape[0]])

    def pairwise(q, k, run):
        """The pairwise matrices of a span, ``sub`` rows at a time: k and
        q of the strip against the k of its chunk up to the strip's end,
        each side's decay taken against the running sum at the strip's
        first row: (a, p) (SPAN, SPAN), masked to what lies before a row
        in its chunk (p: and the row itself)."""
        strips = []
        for lo in range(0, SPAN, sub):
            start = lo // chunk * chunk  # the strip's chunk's first token
            ref = run[lo - 1:lo] if lo > start else jnp.zeros_like(run[:1])
            mine = jnp.exp(run[lo:lo + sub] - ref)
            cols = k[start:lo + sub] * jnp.exp(ref - run[start:lo + sub])
            strips.append(_dot(
                jnp.concatenate([k[lo:lo + sub] * mine,
                                 q[lo:lo + sub] * mine]).astype(low),
                among(cols.astype(low), start), NT))  # a's rows, then p's
        return (jnp.where(same & (at_row > at_col),
                          jnp.concatenate([s[:sub] for s in strips]), 0.0),
                jnp.where(same & (at_row >= at_col),
                          jnp.concatenate([s[sub:] for s in strips]),
                          0.0).astype(low))

    def one_span(i, carry):
        """A span of the step's heads, the heads side by side through
        every product (they wait for one another's nowhere)."""
        rows = pl.ds(pl.multiple_of(i * SPAN, SPAN), SPAN)
        q, k, v = ([ref[0, h, :, rows].astype(F32).T for h in range(heads)]
                   for ref in (q_ref, k_ref, v_ref))
        betas = beta_ref[0, rows, :]  # (SPAN, H): a token a sublane
        beta = [jnp.sum(jnp.where(head_at == first + h, betas, 0.0), axis=1,
                        keepdims=True) for h in range(heads)]  # (SPAN, 1)
        # G_t: the running sums inside each chunk
        run = [_running_sums(ones, g_ref[0, h, :, rows].T, exact)
               for h in range(heads)]
        a, p = zip(*[pairwise(*x) for x in zip(q, k, run)])
        solve = [x.astype(low) for x in _unit_lower_inverse(
            [x * b for x, b in zip(a, beta)], at_row, at_col, chunk,
            exact)]
        decay = [jnp.exp(x) for x in run]
        reads = [(x * d, y * d) for x, y, d in zip(k, q, decay)]
        s0 = [state[h] for h in range(heads)]  # (values, keys): S^T
        outs = []
        for lo in range(0, SPAN, chunk):  # the state, a chunk at a time
            here = slice(lo, lo + chunk)
            # (e^G_t k_t)^T S and (e^G_t q_t)^T S in one product
            from_state = [_dot3(
                _split(jnp.concatenate([x[here] for x in r]), exact),
                _split(s, exact), NT) for r, s in zip(reads, s0)]
            u = [_dot(t[here], among(
                (b[here] * (x[here] - f[:chunk])).astype(low), lo))
                for t, b, x, f in zip(solve, beta, v, from_state)]
            out = [f[chunk:] + _dot(x[here], among(y.astype(low), lo))
                   for f, x, y in zip(from_state, p, u)]
            outs.append(out)
            last = [x[lo + chunk - 1:lo + chunk] for x in run]
            s0 = [s * jnp.exp(e) + _dot3(
                _split(y.T, exact),
                _split(x[here] * jnp.exp(e - r[here]), exact))
                for s, e, y, x, r in zip(s0, last, u, k, run)]
        for h, s in enumerate(s0):
            o_ref[0, h, :, rows] = jnp.concatenate([o[h] for o in outs]).T
            state[h] = s
        return carry

    jax.lax.fori_loop(0, o_ref.shape[3] // SPAN, one_span, 0)


@partial(jax.jit, static_argnames=("chunk", "sub", "exact"))
# ccfd-lint: hot-path
def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int, sub: int,
             exact: bool | None = None):
    """``q``, ``k``, ``v`` (B, T, H, d), the log-decays ``g`` <= 0 (B, T,
    H, d) and ``beta`` (B, T, H) -> o (B, T, H, d) float32: the loop over
    ``_kda_chunk`` from a zero state. Only shapes :func:`kernel_fits`
    admits; ``exact`` (the interpreter's default) multiplies in float32
    where the chip takes bfloat16 passes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, tokens, heads, width = q.shape
    if exact is None:
        exact = kernels.interpreted()
    if (width % LANE or chunk not in CHUNKS or sub % (2 * BASE)
            or chunk % sub
            or not k.shape == v.shape == g.shape == q.shape
            or beta.shape != q.shape[:3]):
        raise ValueError(f"kda_scan does not tile q{q.shape} v{v.shape} "
                         f"at a chunk of {chunk}")
    lead = -tokens % SPAN  # whole chunks of padding: the edges stay
    spans = (tokens + lead) // SPAN
    chunks = spans * (SPAN // chunk)
    step_heads, step_spans = heads_for(heads), spans_for(spans)
    step_tokens = step_spans * SPAN

    def padded(x):  # (B, T, ...) -> (B, lead + T, ...)
        return jnp.pad(x, ((0, 0), (lead, 0)) + ((0, 0),) * (x.ndim - 2)
                       ) if lead else x

    by_head = pl.BlockSpec((1, step_heads, width, step_tokens),
                           lambda b, j, c: (b, j, 0, c),
                           memory_space=pltpu.VMEM)
    inverse = 2 * (BASE.bit_length() - 2) + 2 * (chunk // BASE
                                                 ).bit_length() - 2
    o = pl.pallas_call(
        partial(_kernel, chunk=chunk, sub=sub, heads=step_heads,
                exact=exact),
        out_shape=jax.ShapeDtypeStruct(
            (batch, heads, width, chunks * chunk), F32),
        grid=(batch, heads // step_heads, spans // step_spans),
        in_specs=[by_head] * 4 + [pl.BlockSpec(
            (1, step_tokens, heads), lambda b, j, c: (b, c, 0),
            memory_space=pltpu.VMEM)],
        out_specs=by_head,
        scratch_shapes=[pltpu.VMEM((step_heads, width, width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            # bfloat16 passes: the sums 3, the strips 1, the inverse 6 a
            # product, the state's three products 3 each, u and p u 1
            flops=2 * batch * heads * chunks * chunk * (
                3 * chunk * width + 2 * chunk * width
                + 6 * inverse * chunk * chunk
                + 3 * 3 * width * width + 2 * chunk * width),
            transcendentals=batch * heads * chunks * chunk * width * (
                chunk // sub + 3),
            bytes_accessed=batch * chunks * chunk * heads * (
                width * (3 * q.dtype.itemsize + 8) + 4)),
        name=KERNEL,
        interpret=kernels.interpreted(),
    )(*(jnp.transpose(padded(x), (0, 2, 3, 1))
        for x in (q, k, v, g.astype(F32))), padded(beta.astype(F32)))
    return jnp.transpose(o, (0, 3, 1, 2))[:, lead:]
