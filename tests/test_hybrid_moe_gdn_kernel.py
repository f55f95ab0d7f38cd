"""``hybrid_moe``'s Gated DeltaNet scan behind ``_scalar_delta_scan``: the
Pallas kernel (ops/gdn_scan.py, interpreted on the CPU) against the loop
over ``_gdn_chunk`` through XLA and against the delta rule a token at a
time, and which shapes select which. The small preset of
``tests/benchmark/qwen3next_small_config.json`` has heads of 16 and never
holds the kernel, so here it gets heads a lane tile wide: every chunk the
kernel admits, one and two value heads a key head, windows that are and are
not whole spans of 128 tokens (1,920 among them), one and two rows a grid
step, decays down to -40 a token, padding in front of a row and inside a
window, both input dtypes, both arithmetics (the interpreter's float32
products and the chip's bfloat16 passes), the L2 norms taken inside, what
the programs' own jaxprs say they hold, the ``pallas_call`` at the served
shape, and Mosaic's own word on it."""

import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import (cca_moe_f32, gdn_moe_f32, hybrid_moe_f32,
                                 mhc_moe_f32, mla_moe_f32, ssm_moe_f32,
                                 ssm_relu2_moe_f32)
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import gdn_scan as gs
from ccfd_tpu.ops import kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
# the served shape: 8 windows of 1,920 tokens, 16 key heads on 32 value heads
# of 128, chunks of 16
SERVED_Q, SERVED_V, SERVED_CHUNK = (8, 1920, 16, 128), (8, 1920, 32, 128), 16
# a Gated DeltaNet layer and the attention layer at heads a lane tile wide
LANE_WIDE = {"linear_num_key_heads": 1, "linear_num_value_heads": 2,
             "linear_key_head_dim": 128, "linear_value_head_dim": 128,
             "layers_kept": [0, 3]}


def _small(name):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


def _unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + hm.L2_EPS)


def _operands(t=128, hk=1, per=2, pads=(0, 9), dtype=F32, seed=0, d=128,
              low=40.0, raw=False):
    """q, k, v, g, beta as ``gdn`` makes them: unit keys, queries scaled
    (``raw``: as the convolution leaves them, no norm yet), log-decays
    drawn down to -``low`` a token, 0 < beta < 1, and g = beta = 0 on the
    ``pads[i]`` padding tokens on the left of row i."""
    rng = np.random.default_rng(seed)
    b, hv = len(pads), hk * per
    real = (np.arange(t)[None, :] >= np.asarray(pads)[:, None])
    q = rng.normal(size=(b, t, hk, d))
    k = rng.normal(size=(b, t, hk, d))
    if not raw:
        q, k = _unit(q) * d ** -0.5, _unit(k)
    v = rng.normal(size=(b, t, hv, d))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(low), size=(b, t, hv)))
    beta = rng.uniform(0.0, 1.0, size=(b, t, hv))
    g, beta = g * real[..., None], beta * real[..., None]
    return tuple(jnp.asarray(x, kind) for x, kind in (
        (q, dtype), (k, dtype), (v, dtype), (g, F32), (beta, F32)))


def _a_token_at_a_time(q, k, v, g, beta):
    """S_t = e^(g_t) S_(t-1), then S_t += beta_t k_t (v_t - S_t^T k_t)^T,
    o_t = S_t^T q_t, value head j on key head j // per:
    ``benchmark/reference/gdn_moe_f32.py``'s step, in float64 on the
    host."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, t, hv, dv = v.shape
    per = hv // q.shape[2]
    q, k = np.repeat(q, per, 2), np.repeat(k, per, 2)
    state = np.zeros((b, hv, q.shape[-1], dv))
    o = np.empty_like(v)
    for i in range(t):
        state = state * np.exp(g[:, i])[..., None, None]
        seen = np.einsum("bhk,bhkv->bhv", k[:, i], state)
        state = state + (beta[:, i][..., None, None] * k[:, i][..., None]
                         * (v[:, i] - seen)[..., None, :])
        o[:, i] = np.einsum("bhk,bhkv->bhv", q[:, i], state)
    return o


def _through_xla(q, k, v, g, beta, chunk, l2=None, gate=None):
    """``_scalar_delta_scan`` with the kernel refused: the loop over
    ``_gdn_chunk``, float32 products."""
    with mock.patch.object(gs, "kernel_fits", return_value=False), \
            jax.default_matmul_precision("highest"):
        return jax.jit(
            lambda *operands: hm._scalar_delta_scan(*operands, chunk, l2,
                                                    gate))(q, k, v, g, beta)


# -- the kernel against the loop over _gdn_chunk and against the recurrence ------

@pytest.mark.parametrize("dtype,bound", [(F32, 1e-5), (BF16, 1e-5)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("per", [1, 2], ids=["one_a_key_head",
                                             "two_a_key_head"])
@pytest.mark.parametrize("t,chunk,pads", [
    (128, 16, (0, 9)), (128, 32, (0, 9)), (128, 64, (0, 9)),
    (128, 128, (0, 9)), (200, 16, (3, 150)), (130, 128, (0, 9)),
    (100, 64, (0,)), (256, 32, (0, 9, 256))],
    ids=["chunks_of_16", "chunks_of_32", "chunks_of_64", "chunks_of_128",
         "padded_to_two_spans", "padded_past_a_span", "padded_one_row",
         "three_rows_one_of_padding"])
def test_the_kernel_equals_the_loop_through_xla_and_the_recurrence(
        t, chunk, pads, per, dtype, bound):
    """Every chunk the kernel admits; rows with no padding and with padding
    of their own; a window that is no whole number of spans (the kernel
    pads it on the left to whole spans of 128 tokens: whole chunks more
    than the loop's padding, so the chunks' edges are the loop's); one,
    two and three rows (two a grid step where they pair up); decays down
    to -40 a token, where a quotient of two exponentials would be inf or
    nan: every output is finite; q, k and v in float32 and in bfloat16
    (widened inside: sums, decays, inverse and state are float32 either
    way)."""
    operands = _operands(t, per=per, pads=pads, dtype=dtype, seed=t + chunk)
    assert float(operands[3].min()) < -30
    got = gs.gdn_scan(*operands, chunk=chunk)
    want = _through_xla(*operands, chunk)
    assert got.shape == want.shape == operands[2].shape
    assert got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < bound
    truth = _a_token_at_a_time(*operands)
    assert np.abs(np.asarray(got) - truth).max() < bound
    if 0 in pads:  # a row with no padding says something
        assert np.abs(truth[pads.index(0)]).max() > 0.01
    for row, pad in enumerate(pads):  # padding reads the zero state
        assert not np.asarray(got)[row, :pad].any()


@pytest.mark.parametrize("per", [1, 2])
def test_a_window_of_1920_tokens_is_fifteen_spans_in_three_steps(per):
    """The served window at the served chunk: 120 chunks, the state handed
    on across chunk, span and grid-step edges; against the recurrence."""
    operands = _operands(1920, per=per, pads=(0,), seed=7)
    got = np.asarray(gs.gdn_scan(*operands, chunk=16))
    assert np.isfinite(got).all()
    assert np.abs(got - _a_token_at_a_time(*operands)).max() < 1e-5
    assert np.abs(got - np.asarray(_through_xla(*operands, 16))).max() < 1e-5


@pytest.mark.parametrize("chunk", [16, 128])
def test_padding_inside_a_window_passes_the_state_unchanged(chunk):
    """Tokens of g = beta = 0 in the middle of a window (a whole chunk of
    16 of them and a few more): what comes after reads the state the
    tokens before them left, as if they were not there."""
    q, k, v, g, beta = _operands(256, pads=(0, 0), seed=3, low=0.5)
    hole = slice(100, 123)
    g, beta = g.at[:, hole].set(0.0), beta.at[:, hole].set(0.0)
    got = np.asarray(gs.gdn_scan(q, k, v, g, beta, chunk=chunk))
    kept = np.r_[0:100, 123:256]
    without = np.asarray(gs.gdn_scan(
        *(x[:, kept] for x in (q, k, v, g, beta)), chunk=chunk))
    assert np.abs(got[:, kept] - without).max() < 1e-5
    assert np.abs(without[:, 100:]).max() > 0.01
    truth = _a_token_at_a_time(q, k, v, g, beta)
    assert np.abs(got - truth).max() < 1e-5


@pytest.mark.parametrize("per", [1, 2])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chips_bfloat16_passes_stay_near_the_float32_products(per, chunk):
    """``exact=False`` is what Mosaic compiles: one bfloat16 pass inside a
    chunk, the passes with the state side by side along the contracted
    axis (a bfloat16 operand has one piece, a float32 one two), the
    inverse's products as the six products of three pieces. Under the
    interpreter it is held to the float32 products: the mean gap is a
    bfloat16's rounding of the products inside a chunk."""
    for dtype in (F32, BF16):
        operands = _operands(256, hk=2, per=per, pads=(0, 130), dtype=dtype,
                             low=4.0)
        want = _a_token_at_a_time(*operands)
        got = np.asarray(gs.gdn_scan(*operands, chunk=chunk, exact=False))
        gap = np.abs(got - want)
        assert np.isfinite(got).all()
        assert gap.mean() < 6e-3 * np.abs(want).mean()
        assert gap.max() < 0.05 * np.abs(want).max()


def test_fast_decays_stay_finite_in_the_chips_passes():
    """-40 a token on every head and token, and a draw down to it."""
    q, k, v, g, beta = _operands(128, pads=(0, 9), dtype=BF16)
    for hard in (g, jnp.full_like(g, -40.0)):
        got = np.asarray(gs.gdn_scan(q, k, v, hard, beta, chunk=16,
                                     exact=False))
        assert np.isfinite(got).all()
        want = _a_token_at_a_time(q, k, v, hard, beta)
        assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


@pytest.mark.parametrize("dtype,bound", [(F32, 1e-5), (BF16, 1e-5)])
def test_the_norms_inside_are_the_norms_outside(dtype, bound):
    """``unit``: q and k arrive as the convolution leaves them, float32
    and of any length; the kernel L2-norms them by head, scales q and
    rounds q, k and v to the compute dtype, as ``_scalar_delta_scan`` does
    in front of the loop."""
    raw = _operands(200, hk=2, per=2, pads=(0, 37), raw=True, low=4.0)
    got = np.asarray(gs.gdn_scan(*raw, chunk=16, unit=hm.L2_EPS,
                                 dtype=dtype))
    want = np.asarray(_through_xla(*raw, 16, (hm.L2_EPS, dtype)))
    assert np.abs(got - want).max() < bound
    q, k, v, g, beta = raw
    normed = ((_unit(np.asarray(q)) * 128 ** -0.5), _unit(np.asarray(k)), v)
    rounded = tuple(jnp.asarray(x, dtype) for x in normed)
    assert np.abs(got - _a_token_at_a_time(*rounded, g, beta)).max() < 2e-5
    selected = np.asarray(jax.jit(hm._scalar_delta_scan, static_argnums=(
        5, 6))(*raw, 16, (hm.L2_EPS, dtype)))
    assert np.array_equal(selected, got)


@pytest.mark.parametrize("dtype,bound", [(F32, 2e-5), (BF16, 0.04)])
@pytest.mark.parametrize("t,at", [(256, 1024), (200, 1024), (256, 640)],
                         ids=["in_place", "padded", "off_the_block"])
def test_the_gate_inside_is_the_gate_outside(t, at, dtype, bound):
    """``z``, ``norm``: o leaves RMS-normed by head times the weight, times
    SiLU of the projection's columns from ``at``, in the compute dtype, as
    ``_scalar_delta_scan`` gates the loop's answer: the columns read where
    they lie (whole blocks of a step's heads from ``at``), or copied out
    where the window is padded or ``at`` is no whole block."""
    raw = _operands(t, hk=2, per=2, pads=(0, 37), raw=True, low=4.0)
    rng = np.random.default_rng(t + at)
    proj = jnp.asarray(rng.normal(size=(2, t, at + 512 + 128)), F32)
    weight = jnp.asarray(rng.uniform(0.5, 1.5, size=(128,)), F32)
    got = gs.gdn_scan(*raw, chunk=16, unit=hm.L2_EPS, dtype=dtype, z=proj,
                      norm=weight, at=at, eps=1e-6)
    gate = (proj, at, weight, 1e-6)
    want = _through_xla(*raw, 16, (hm.L2_EPS, dtype), gate)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    assert got.shape == want.shape == raw[2].shape
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert gap.max() < bound  # bfloat16: a rounding of values up to 8
    ungated = np.asarray(gs.gdn_scan(*raw, chunk=16, unit=hm.L2_EPS,
                                     dtype=dtype), np.float64)
    silu = np.asarray(proj, np.float64)[..., at:at + 512].reshape(
        2, t, 4, 128)
    silu = silu / (1.0 + np.exp(-silu))
    by_hand = ungated / np.sqrt((ungated ** 2).mean(-1, keepdims=True)
                                + 1e-6) * np.asarray(weight) * silu
    assert np.abs(np.asarray(got, np.float64) - by_hand).max() < max(
        bound, 1e-4)
    selected = jax.jit(lambda *operands: hm._scalar_delta_scan(
        *operands, 16, (hm.L2_EPS, dtype), gate))(*raw)
    assert np.array_equal(np.asarray(selected, np.float32),
                          np.asarray(got, np.float32))


def test_the_state_is_handed_on_and_no_later_token_moves_an_earlier_one():
    """A change to an early token moves every later o, across chunk, span
    and grid-step edges (768 tokens are six spans: two steps of three); a
    later token moves no earlier one."""
    q, k, v, g, beta = _operands(768, pads=(0, 0), low=0.05)
    base = np.asarray(gs.gdn_scan(q, k, v, g, beta, chunk=16))
    early = np.asarray(gs.gdn_scan(q, k, v.at[:, 5].add(1.0), g, beta,
                                   chunk=16))
    late = np.asarray(gs.gdn_scan(
        q.at[:, 700].add(1.0), k.at[:, 700].add(1.0), v.at[:, 700].add(1.0),
        g, beta, chunk=16))
    assert np.array_equal(base[:, :5], early[:, :5])
    moved = np.abs(early - base).max(axis=(0, 2, 3))
    assert (moved[5:] > 0).all()
    assert moved[767] > 1e-8
    assert np.array_equal(base[:, :700], late[:, :700])
    assert (np.abs(late - base).max(axis=(0, 2, 3))[700:] > 0).all()


def test_heads_twice_as_wide_take_the_kernel_too():
    """Keys and values of two lane tiles a head."""
    operands = _operands(128, d=256, low=4.0)
    got = np.asarray(gs.gdn_scan(*operands, chunk=32))
    assert np.abs(got - _a_token_at_a_time(*operands)).max() < 1e-5


# -- which shapes select which -------------------------------------------------------

@pytest.mark.parametrize("q,v,chunk,dtype,fits", [
    (SERVED_Q, SERVED_V, SERVED_CHUNK, BF16, True),
    (SERVED_Q, SERVED_V, SERVED_CHUNK, F32, True),
    ((4, 1920, 16, 128), (4, 1920, 32, 128), 16, F32, True),
    ((2, 240, 1, 128), (2, 240, 2, 128), 32, F32, True),  # the lane-wide one
    ((3, 100, 2, 128), (3, 100, 2, 128), 64, F32, True),  # a ragged window
    ((2, 256, 2, 256), (2, 256, 4, 128), 128, F32, True),  # keys of 2 tiles
    ((2, 256, 1, 128), (2, 256, 8, 128), 16, F32, True),  # 8 on a key head
    ((3, 240, 2, 16), (3, 240, 4, 16), 32, F32, False),  # the small preset
    ((2, 256, 2, 64), (2, 256, 4, 64), 64, F32, False),  # half a lane tile
    ((2, 256, 2, 192), (2, 256, 2, 192), 64, F32, False),  # a tile and a half
    ((2, 256, 2, 128), (2, 256, 4, 128), 48, F32, False),  # no power of two
    ((2, 256, 2, 128), (2, 256, 4, 128), 8, F32, False),  # under a tile
    ((2, 256, 2, 128), (2, 256, 4, 128), 256, F32, False),  # over a span
    ((2, 256, 2, 128), (2, 256, 3, 128), 64, F32, False),  # 1.5 a key head
    ((2, 256, 2, 128), (2, 256, 4, 128), 64, jnp.float16, False),
    ((2, 256, 256), (2, 256, 4, 128), 64, F32, False),  # no axis of heads
    ((2, 1920, 2, 2048), (2, 1920, 4, 2048), 64, F32, False),  # 16 MiB each
], ids=["served", "served_f32", "served_batch_4", "lane_wide",
        "ragged_window", "keys_256", "eight_a_key_head", "small_preset",
        "heads_64", "heads_192", "chunk_48", "chunk_8", "chunk_256",
        "ragged_heads", "float16", "no_heads", "over_vmem"])
def test_which_shapes_the_kernel_takes(q, v, chunk, dtype, fits):
    assert gs.kernel_fits(jax.ShapeDtypeStruct(q, dtype),
                          jax.ShapeDtypeStruct(v, dtype), chunk) is fits


def test_another_dtype_an_unserved_one_and_a_mesh_keep_the_loop_through_xla():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    q = jax.ShapeDtypeStruct((2, 256, 2, 128), F32)
    v = jax.ShapeDtypeStruct((2, 256, 4, 128), F32)
    assert gs.kernel_fits(q, v, 64) and gs.kernel_fits(q, v, 64, BF16)
    assert not gs.kernel_fits(q, v, 64, jnp.float16)  # rounded to it inside
    assert not gs.kernel_fits(
        q, jax.ShapeDtypeStruct(v.shape, BF16), 64)  # v another than q
    assert not gs.kernel_fits(
        q, jax.ShapeDtypeStruct((2, 128, 4, 128), F32), 64)  # other tokens
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    assert not gs.kernel_fits(jax.ShapeDtypeStruct(
        q.shape, F32, sharding=NamedSharding(mesh, PartitionSpec())), v, 64)


@pytest.mark.parametrize("heads,per,step", [
    (32, 2, 4), (32, 1, 4), (4, 2, 4), (6, 2, 2), (2, 2, 2), (3, 1, 1),
    (8, 8, 8), (6, 3, 3)])
def test_a_step_takes_the_most_value_heads_that_tile_them_in_key_heads(
        heads, per, step):
    assert gs.heads_for(heads, per) == step


@pytest.mark.parametrize("batch,step", [(8, 2), (4, 2), (2, 2), (3, 1),
                                        (1, 1)])
def test_a_step_takes_two_rows_where_they_pair_up(batch, step):
    assert gs.windows_for(batch) == step


def _holds_kernel(fn, *args) -> bool:
    return kernels.held_by(fn, *args, names=(gs.KERNEL,))


def _shape(*dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("q,v,chunk,dtype,kernel", [
    (SERVED_Q, SERVED_V, SERVED_CHUNK, BF16, True),
    ((2, 240, 1, 128), (2, 240, 2, 128), 32, F32, True),
    ((3, 240, 2, 16), (3, 240, 4, 16), 32, F32, False),
    ((2, 256, 2, 64), (2, 256, 4, 64), 64, F32, False),
], ids=["served", "lane_wide", "small_preset", "heads_64"])
def test_the_programs_jaxpr_says_which_path_was_taken(q, v, chunk, dtype,
                                                      kernel):
    def scan(q, k, v, g, beta):
        return hm._scalar_delta_scan(q, k, v, g, beta, chunk)

    assert _holds_kernel(
        scan, _shape(*q, dtype=dtype), _shape(*q, dtype=dtype),
        _shape(*v, dtype=dtype), _shape(*v[:3]), _shape(*v[:3])) is kernel


def test_the_selection_runs_the_kernel_where_it_fits():
    """``_scalar_delta_scan`` itself, jitted, at a window of two spans
    behind padding."""
    operands = _operands(150, hk=2, per=2, low=4.0)
    got = jax.jit(hm._scalar_delta_scan, static_argnums=5)(*operands, 64)
    assert np.abs(np.asarray(got)
                  - np.asarray(_through_xla(*operands, 64))).max() < 1e-5


# -- the pallas_call at the served shape ---------------------------------------------

@functools.cache
def _pallas_call():
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, g, beta, z, w: gs.gdn_scan(
            q, k, v, g, beta, chunk=SERVED_CHUNK, unit=hm.L2_EPS, dtype=BF16,
            z=z, norm=w, at=8192, eps=1e-6, exact=False))(
        _shape(*SERVED_Q), _shape(*SERVED_Q), _shape(*SERVED_V),
        _shape(*SERVED_V[:3]), _shape(*SERVED_V[:3]),
        _shape(8, 1920, 12288), _shape(128))
    calls = [e for e in kernels.equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


def test_the_pallas_call_is_pinned_at_the_served_shape():
    """The name, the grid, the operands and their blocks: q, k and v
    token-major as the convolution leaves them (float32, a head a lane
    tile), two rows, two key heads on four value heads and five spans (640
    tokens) a step, the runs of spans innermost; g and beta a (rows,
    run)'s for all heads; z the projection itself, its blocks from column
    8,192 (block 16 of 512 lanes); the norm's weight whole; o gated, in
    bfloat16; the eight states, the four normed k and q and the eight
    chains' factors in scratch; no operand is (.., 16, 16), nothing is
    aliased."""
    call = _pallas_call()
    grid = call.params["grid_mapping"]
    assert call.params["name"] == gs.KERNEL == "gdn_scan"
    assert grid.grid == (4, 8, 3)
    assert (grid.num_inputs, grid.num_outputs) == (7, 1)
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in grid.block_mappings] == [
        (2, 640, 256)] * 2 + [(2, 640, 512)] + [(2, 640, 32)] * 2 + [
        (2, 640, 512), (1, 128), (2, 640, 512)]
    assert [(v.aval.shape, v.aval.dtype) for v in call.invars] == [
        ((8, 1920, 2048), jnp.dtype(F32))] * 2 + [
        ((8, 1920, 4096), jnp.dtype(F32))] + [
        ((8, 1920, 32), jnp.dtype(F32))] * 2 + [
        ((8, 1920, 12288), jnp.dtype(F32)), ((1, 128), jnp.dtype(F32))]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
        ((8, 1920, 4096), jnp.dtype(BF16))]
    assert not call.params["input_output_aliases"]
    scratch = [(a.shape, a.dtype) for a in list(
        call.params["jaxpr"].invars)[-6:] for a in [a.aval.inner_aval]]
    assert scratch == [
        ((8, 128, 128), jnp.dtype(F32)), ((4, 128, 128), jnp.dtype(BF16)),
        ((4, 128, 128), jnp.dtype(BF16)), ((8, 128, 128), jnp.dtype(BF16)),
        ((8, 128, 128), jnp.dtype(BF16)), ((8, 128, 128), jnp.dtype(F32))]
    assert call.params["compiler_params"]["mosaic_tpu"].dimension_semantics \
        == ("parallel", "parallel", "arbitrary")
    body = [e.primitive.name
            for e in kernels.equations(call.params["jaxpr"])]
    # the spans of a step, a row's factors, a span's chunks: loops, so the
    # body does not grow with the window, the rows or the chunks a span
    assert body.count("scan") == 3
    # a row's four heads: K K^T and Q K^T of two key heads, the sums' 3
    # passes, the inverse's 6 products of 3 passes a head, a head's two
    # factors to the front; the eight chains of a chunk: with the state
    # twice, u and P u
    assert body.count("dot_general") == (
        2 * 2 + 3 + 4 * (6 * 3 + 2) + 8 * 4)
    # a key head's k and q, and the gate's norm a chain
    assert body.count("rsqrt") == 2 * 2 + 8
    assert len(body) < 2500


# -- the real shape, compiled for the chip that is described and not attached -------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch,precision", [(8, None), (4, "highest")])
def test_mosaic_compiles_the_kernel_at_a_dispatch_of_the_real_model(
        one_chip, as_on_the_chip, batch, precision):
    """What the interpreter cannot refuse (tiling, VMEM, a slice off the
    sublane grid, a product whose precision a caller's
    ``default_matmul_precision("highest")`` would change if it did not
    name its own) the chip's compiler can, and nothing runs; both batch
    sizes the cell serves."""
    def shape(*dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, v = (batch, *SERVED_Q[1:]), (batch, *SERVED_V[1:])
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(
            lambda q, k, v, g, beta, z, w: gs.gdn_scan.__wrapped__(
                q, k, v, g, beta, chunk=SERVED_CHUNK, unit=hm.L2_EPS,
                dtype=BF16, z=z, norm=w, at=8192, eps=1e-6)).lower(
            shape(*q), shape(*q), shape(*v), shape(*v[:3]), shape(*v[:3]),
            shape(batch, 1920, 12288), shape(128)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the whole models -------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide():
    """(configuration, parameters, settings) of the lane-wide preset."""
    config = {**_small("qwen3next"), **LANE_WIDE}
    return (config, gdn_moe_f32.make_params(config),
            hm.HybridConfig.from_dict(config))


def _program(cfg, dtype=F32):
    return lambda p, h, f: hm.apply_serving(p, h, f, cfg, dtype)


def _window(records=8, rows=2):
    return (jax.ShapeDtypeStruct((rows, records, 30), np.float32),
            jax.ShapeDtypeStruct((rows,), np.int32))


def test_the_lane_wide_program_holds_the_kernel_and_the_small_one_does_not(
        wide):
    config, params, cfg = wide
    assert gs.KERNEL in kernels.kernels_of(_program(cfg), params, *_window())
    small = _small("qwen3next")
    shapes = jax.eval_shape(lambda: gdn_moe_f32.make_params(small))
    assert not kernels.kernels_of(
        _program(hm.HybridConfig.from_dict(small)), shapes, *_window())


@pytest.mark.parametrize("name,ref", [
    ("ling3", hybrid_moe_f32), ("mistral4", mla_moe_f32),
    ("zaya1", cca_moe_f32), ("xing4", mhc_moe_f32),
    ("granite4h", ssm_moe_f32), ("nemotron3n", ssm_relu2_moe_f32)])
def test_a_model_without_the_mixer_holds_no_scalar_delta_kernel(name, ref):
    """At the small presets and at 64 records (1,920 tokens: where their
    attention could tile)."""
    small = _small(name)
    cfg = hm.HybridConfig.from_dict(small)
    shapes = jax.eval_shape(lambda: ref.make_params(small))
    for records in (8, 64):
        assert gs.KERNEL not in kernels.kernels_of(
            _program(cfg), shapes, *_window(records))


def test_the_mixer_through_the_kernel_equals_the_reference(wide):
    """``gdn`` alone at 300 tokens (padded on the left to three spans of
    128), one row with 37 padding tokens, in float32: the kernel, norms
    inside, against the reference's recurrence a token at a time."""
    config, params, cfg = wide
    p = params["layers"][0]["mixer"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 300, config["hidden_size"])), F32)
    real = jnp.asarray(np.arange(300)[None, :] >= np.array([[0], [37]]))

    def mixer(p, x, real):
        return hm.gdn(p, x, real, cfg, F32)[0]

    assert _holds_kernel(mixer, p, x, real)
    with jax.default_matmul_precision("highest"):
        want = gdn_moe_f32.gdn(p, x, real, config)
        got = mixer(p, x, real)
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype,worst,mean", [(F32, 5e-4, 5e-5),
                                              (BF16, None, 0.05)])
def test_the_model_equals_the_reference_through_the_kernel(wide, dtype, worst,
                                                           mean):
    """8 records = 240 tokens, two spans of 128 with 16 tokens of padding
    in front: a full window, a short history and a single record."""
    from benchmark.reference import table

    config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(0)
    filled = np.asarray([8, 3, 1], np.int32)
    hist = np.zeros((3, 8, 30), np.float32)
    for i, k in enumerate(filled):
        hist[i, 8 - k:] = rows[rng.integers(0, len(rows), k)]
    want, _ = gdn_moe_f32.forward(params, config, hist, filled)
    assert _holds_kernel(_program(cfg, dtype), params, hist, filled)
    with jax.default_matmul_precision("highest"):
        _, aux = hm.apply_serving(params, hist, filled, cfg, dtype)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    assert gap.mean() < mean
    if worst is not None:
        assert gap.max() < worst
