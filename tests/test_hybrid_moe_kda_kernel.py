"""``hybrid_moe``'s KDA scan behind ``_delta_scan``: the Pallas kernel
(ops/kda_scan.py, interpreted on the CPU) against the loop over
``_kda_chunk`` through XLA and against the gated delta rule a token at a
time, and which shapes select which. The small preset of
``tests/benchmark/ling3_small_config.json`` has heads of 16 and never holds
the kernel, so here it gets heads a lane tile wide (2 heads of 128): 1, 2
and 30 chunks, padding in front, a window that is no whole number of
chunks, a row of padding alone, both input dtypes, both arithmetics (the
interpreter's float32 products and the chip's bfloat16 passes), identical
tokens under a slow gate, the gate at its bound, what the programs' own
jaxprs say they hold, the ``pallas_call`` at the served shape, and Mosaic's
own word on it."""

import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import (cca_moe_f32, hybrid_moe_f32, mhc_moe_f32,
                                 mla_moe_f32, ssm_moe_f32, table)
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import kda_scan as ks
from ccfd_tpu.ops import kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
SUB = hm.KDA_SUB
# the served shape: 8 windows of 1,920 tokens, 32 heads of 128, chunks of 64
SERVED = (8, 1920, 32, 128)
# two KDA layers (the dense one and one with experts) at heads a lane tile wide
LANE_WIDE = {"num_attention_heads": 2, "head_dim": 128, "v_head_dim": 128,
             "layers_kept": [0, 2]}


def _small(name):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


def _operands(t=128, heads=2, pads=(0, 37), dtype=F32, seed=0, width=128,
              gate=(2.0, -2.0)):
    """q, k, v, g, beta as ``kda`` makes them: unit keys, queries scaled,
    -5 < g <= 0, 0 < beta < 1, and everything 0 on the ``pads[i]`` padding
    tokens on the left of row i."""
    rng = np.random.default_rng(seed)
    b = len(pads)
    real = (np.arange(t)[None, :] >= np.asarray(pads)[:, None])
    keep = real[..., None, None]

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + hm.L2_EPS)

    shape = (b, t, heads, width)
    q = unit(rng.normal(size=shape)) * width ** -0.5 * keep
    k = unit(rng.normal(size=shape)) * keep
    v = rng.normal(size=shape) * keep
    g = -5.0 / (1.0 + np.exp(-(rng.normal(size=shape) * gate[0] + gate[1])))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=shape[:3]))) * real[..., None]
    return tuple(jnp.asarray(x, d) for x, d in (
        (q, dtype), (k, dtype), (v, dtype), (g * keep, F32), (beta, F32))), keep


def _a_token_at_a_time(q, k, v, g, beta):
    """S_t = Diag(e^g_t) S_(t-1), then S_t += beta_t k_t (v_t - S_t^T
    k_t)^T, o_t = S_t^T q_t: ``benchmark/reference/hybrid_moe_f32.py``'s
    step, in float64 on the host."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, t, h, d = q.shape
    state = np.zeros((b, h, d, d))
    o = np.empty_like(v)
    for i in range(t):
        state = state * np.exp(g[:, i])[..., None]
        seen = np.einsum("bhk,bhkv->bhv", k[:, i], state)
        state = state + (beta[:, i, :, None, None] * k[:, i][..., None]
                         * (v[:, i] - seen)[..., None, :])
        o[:, i] = np.einsum("bhk,bhkv->bhv", q[:, i], state)
    return o


def _through_xla(q, k, v, g, beta, chunk):
    """``_delta_scan`` with the kernel refused: the loop over
    ``_kda_chunk``, float32 products."""
    with mock.patch.object(ks, "kernel_fits", return_value=False), \
            jax.default_matmul_precision("highest"):
        return jax.jit(hm._delta_scan, static_argnums=5)(q, k, v, g, beta,
                                                         chunk)


def _kernel(q, k, v, g, beta, chunk=64, **how):
    return ks.kda_scan(q, k, v, g, beta, chunk=chunk, sub=SUB, **how)


# -- the kernel against the loop over _kda_chunk and against the recurrence ------

@pytest.mark.parametrize("dtype,bound", [(F32, 1e-5), (BF16, 2e-4)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t,chunk,pads", [
    (64, 64, (0, 37)), (128, 64, (0, 37)), (1920, 64, (0,)),
    (100, 64, (0, 37)), (200, 64, (3, 150)), (128, 64, (0, 128)),
    (128, 32, (0, 37)), (256, 128, (0, 37)), (96, 16, (0, 37))],
    ids=["one_chunk", "two_chunks", "thirty_chunks", "padded_to_two_chunks",
         "padded_past_a_chunk", "a_row_of_padding", "chunks_of_32",
         "chunks_of_128", "chunks_of_16"])
def test_the_kernel_equals_the_loop_through_xla_and_the_recurrence(
        t, chunk, pads, dtype, bound):
    """Rows with no padding and with padding that ends inside a chunk, a
    window that is no whole number of chunks (the kernel pads it on the
    left to whole spans of 128 tokens: whole chunks more than the loop's
    padding, so the chunks' edges are the loop's), a row that is padding
    alone; q, k and v in float32 and in bfloat16 (widened inside: sums,
    decays, inverse and state are float32 either way)."""
    operands, keep = _operands(t, pads=pads, dtype=dtype)
    got = _kernel(*operands, chunk=chunk)
    want = _through_xla(*operands, chunk)
    assert got.shape == want.shape == operands[0].shape
    assert got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < bound
    truth = _a_token_at_a_time(*operands)
    assert np.abs((np.asarray(got) - truth) * keep).max() < bound
    if 0 in pads:  # a row with no padding says something
        assert np.abs(truth[pads.index(0)]).max() > 0.01
    for row, pad in enumerate(pads):  # padding reads the zero state
        assert not np.asarray(got)[row, :pad].any()


@pytest.mark.parametrize("heads", [2, 8], ids=["two_heads", "eight_heads"])
def test_the_chips_bfloat16_passes_stay_near_the_float32_products(heads):
    """``exact=False`` is what Mosaic compiles: one bfloat16 pass inside a
    chunk, three on the state (a value split into a bfloat16 and the
    bfloat16 of what that left), the inverse's products as the six
    products of three pieces. Under the interpreter it is held to the
    float32 products: the mean gap is a bfloat16's rounding of the
    products inside a chunk."""
    operands, keep = _operands(256, heads=heads, pads=(0, 130))
    want = _a_token_at_a_time(*operands) * keep
    got = np.asarray(_kernel(*operands, exact=False)) * keep
    gap = np.abs(got - want)
    assert gap.mean() < 6e-3 * np.abs(want).mean()
    assert gap.max() < 0.05 * np.abs(want).max()
    assert np.abs(np.asarray(_kernel(*operands)) * keep - want).max() < 1e-5


def test_the_inverse_is_the_blocked_one_product_for_product():
    """``_unit_lower_inverse`` of a span of two chunks: each chunk's block
    inverted for itself, to float32's rounding, with the pieces' six
    products as with float32 ones; an all-ones triangle (identical tokens,
    beta 1, no decay) included: its inverse is two diagonals."""
    rng = np.random.default_rng(1)
    at = np.arange(128)
    inside = (at[:, None] // 64 == at[None, :] // 64) & (
        at[:, None] > at[None, :])
    row = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    each = [np.where(inside, rng.normal(size=(128, 128)) * 0.3, 0.0),
            np.where(inside, 1.0, 0.0)]
    want = [np.linalg.inv(np.eye(128) + a) for a in each]
    for exact, bound in ((True, 2e-5), (False, 2e-5)):
        got = ks._unit_lower_inverse(
            [jnp.asarray(a, F32) for a in each], row, col, 64, exact)
        for g, w in zip(got, want):
            assert np.abs(np.asarray(g) - w).max() < bound * max(
                1.0, np.abs(w).max())
    assert np.abs(want[1]).max() == 1.0  # 1 on the diagonal, -1 below it


def test_identical_tokens_under_a_slow_gate_stay_with_the_recurrence():
    """The case of ``tests/test_hybrid_moe.py::
    test_the_chunked_scan_holds_identical_tokens_under_a_slow_gate`` at
    the kernel's widths: a column's tokens repeat from record to record,
    so the keys of a chunk are all but equal and with a slow gate every
    entry of the chunk's triangular matrix is near beta. The blocked
    inverse stays with the recurrence (squaring the whole matrix does
    not), in float32 and in the chip's passes."""
    (q, k, v, g, beta), keep = _operands(200, pads=(0, 0), seed=5)
    rng = np.random.default_rng(5)

    def all_but_equal(x):
        return x[:, :1] + 0.01 * jnp.asarray(rng.normal(size=x.shape), F32)

    slow = (all_but_equal(q), all_but_equal(k), all_but_equal(v),
            g * 0.0 - 0.01, beta * 0.0 + 0.95)
    want = _a_token_at_a_time(*slow)
    got = np.asarray(_kernel(*slow))
    assert np.isfinite(got).all()
    assert np.allclose(got, want, atol=5e-4, rtol=5e-4)
    assert np.allclose(np.asarray(_through_xla(*slow, 64)), want, atol=5e-4,
                       rtol=5e-4)
    passes = np.asarray(_kernel(*slow, exact=False))
    assert np.isfinite(passes).all()
    assert np.abs(passes - want).mean() < 0.02 * np.abs(want).mean()


def test_the_gate_at_its_bound_stays_finite():
    """g = -5 on every key of every token (``kda_lower_bound`` over whole
    strips of 16: e^80 on one side of a strip's products, e^-80 on the
    other): finite, and the loop's own to the last digits. There a strip's
    last rows meet float32's smallest normal numbers (e^-80 k flushes to
    zero under 6.5e-4), so both stand 3e-4 off the recurrence, where at
    -4.5 a token they stand 1e-8 off."""
    (q, k, v, g, beta), keep = _operands(128, pads=(0, 37))
    for gate, bound in ((-5.0, 5e-4), (-4.5, 1e-5)):
        hard = (q, k, v, jnp.full_like(g, gate) * keep, beta)
        want = _a_token_at_a_time(*hard)
        got = np.asarray(_kernel(*hard))
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() < bound
        assert np.abs(got - np.asarray(_through_xla(*hard, 64))).max() < 1e-6
        passes = np.asarray(_kernel(*hard, exact=False))
        assert np.isfinite(passes).all()
        assert np.abs(passes - want).max() < 0.02


def test_the_state_is_handed_on_and_no_later_token_moves_an_earlier_one():
    """A change to an early token moves every later o, across chunk, span
    and grid-step edges (768 tokens are six spans: two steps of three); a
    later token moves no earlier one."""
    (q, k, v, g, beta), _ = _operands(768, pads=(0, 0))
    g = g * 0.01  # slow decays, so that the first chunk still shows
    base = np.asarray(_kernel(q, k, v, g, beta))
    early = np.asarray(_kernel(q, k, v.at[:, 5].add(1.0), g, beta))
    late = np.asarray(_kernel(q.at[:, 700].add(1.0), k.at[:, 700].add(1.0),
                              v.at[:, 700].add(1.0), g, beta))
    assert np.array_equal(base[:, :5], early[:, :5])
    moved = np.abs(early - base).max(axis=(0, 2, 3))
    assert (moved[5:] > 0).all()
    assert moved[767] > 1e-8
    assert np.array_equal(base[:, :700], late[:, :700])
    assert (np.abs(late - base).max(axis=(0, 2, 3))[700:] > 0).all()


def test_heads_twice_as_wide_take_the_kernel_too():
    """Keys and values of two lane tiles a head."""
    operands, keep = _operands(128, width=256)
    got = np.asarray(_kernel(*operands))
    assert np.abs((got - _a_token_at_a_time(*operands)) * keep).max() < 1e-5


# -- which shapes select which -------------------------------------------------------

@pytest.mark.parametrize("shape,chunk,sub,dtype,fits", [
    (SERVED, 64, SUB, BF16, True),
    (SERVED, 64, SUB, F32, True),
    ((2, 240, 2, 128), 64, SUB, F32, True),  # the lane-wide preset
    ((2, 100, 2, 128), 64, SUB, F32, True),  # no whole number of chunks
    ((2, 256, 3, 256), 128, SUB, F32, True),  # heads of two lane tiles
    ((2, 256, 2, 128), 16, SUB, F32, True),
    ((2, 256, 2, 128), 32, 32, F32, True),
    ((3, 240, 4, 16), 64, SUB, F32, False),  # the small preset: heads of 16
    ((2, 256, 2, 64), 64, SUB, F32, False),  # half a lane tile
    ((2, 256, 2, 192), 64, SUB, F32, False),  # a tile and a half
    ((2, 256, 2, 128), 48, SUB, F32, False),  # no power of two of blocks
    ((2, 256, 2, 128), 256, SUB, F32, False),  # wider than a span
    ((2, 256, 2, 128), 64, 8, F32, False),  # strips of half a bfloat16 tile
    ((2, 256, 2, 128), 64, 48, F32, False),  # strips that do not tile it
    ((2, 256, 2, 128), 64, SUB, jnp.float16, False),
    ((2, 256, 128), 64, SUB, F32, False),  # no axis of heads
    ((2, 1920, 8, 2048), 64, SUB, F32, False),  # four states of 16 MiB
], ids=["served", "served_f32", "lane_wide", "ragged_window", "heads_256",
        "chunk_16", "sub_32", "small_preset", "heads_64", "heads_192",
        "chunk_48", "chunk_256", "sub_8", "sub_48", "float16", "no_heads",
        "over_vmem"])
def test_which_shapes_the_kernel_takes(shape, chunk, sub, dtype, fits):
    x = jax.ShapeDtypeStruct(shape, dtype)
    assert ks.kernel_fits(x, x, chunk, sub) is fits


def test_other_values_than_keys_and_a_mesh_keep_the_loop_through_xla():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    q = jax.ShapeDtypeStruct((2, 256, 2, 128), F32)
    assert ks.kernel_fits(q, q, 64, SUB)
    assert not ks.kernel_fits(
        q, jax.ShapeDtypeStruct((2, 256, 2, 256), F32), 64, SUB)
    assert not ks.kernel_fits(
        q, jax.ShapeDtypeStruct((2, 256, 2, 128), BF16), 64, SUB)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    assert not ks.kernel_fits(jax.ShapeDtypeStruct(
        q.shape, F32, sharding=NamedSharding(mesh, PartitionSpec())), q, 64,
        SUB)


@pytest.mark.parametrize("heads,step", [(32, 4), (4, 4), (6, 2), (2, 2),
                                        (3, 1)])
def test_a_step_takes_the_most_heads_that_tile_them(heads, step):
    assert ks.heads_for(heads) == step


@pytest.mark.parametrize("spans,step", [(15, 5), (1, 1), (2, 2), (6, 3),
                                        (7, 1), (10, 5)])
def test_a_step_takes_the_most_spans_that_tile_a_row(spans, step):
    assert ks.spans_for(spans) == step


def _holds_kernel(fn, *args) -> bool:
    return kernels.held_by(fn, *args, names=(ks.KERNEL,))


def _shape(*dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("shape,chunk,dtype,kernel", [
    (SERVED, 64, BF16, True),
    ((2, 240, 2, 128), 64, F32, True),
    ((3, 240, 4, 16), 64, F32, False),
    ((2, 256, 2, 64), 64, F32, False),
], ids=["served", "lane_wide", "small_preset", "heads_64"])
def test_the_programs_jaxpr_says_which_path_was_taken(shape, chunk, dtype,
                                                      kernel):
    def scan(q, k, v, g, beta):
        return hm._delta_scan(q, k, v, g, beta, chunk)

    x = _shape(*shape, dtype=dtype)
    assert _holds_kernel(scan, x, x, x, _shape(*shape),
                         _shape(*shape[:3])) is kernel


def test_the_selection_runs_the_kernel_where_it_fits():
    """``_delta_scan`` itself, jitted, at a window of three chunks behind
    padding."""
    operands, _ = _operands(150)
    got = jax.jit(hm._delta_scan, static_argnums=5)(*operands, 64)
    assert np.abs(np.asarray(got)
                  - np.asarray(_through_xla(*operands, 64))).max() < 1e-5


# -- the pallas_call at the served shape ---------------------------------------------

@functools.cache
def _pallas_call():
    x = _shape(*SERVED, dtype=BF16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, g, beta: ks.kda_scan(q, k, v, g, beta, chunk=64,
                                             sub=SUB, exact=False))(
        x, x, x, _shape(*SERVED), _shape(*SERVED[:3]))
    calls = [e for e in kernels.equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


def test_the_pallas_call_is_pinned_at_the_served_shape():
    """The name, the grid, the operands and their blocks: q, k, v, g and o
    by head with the tokens along the lanes (the layout the chip's
    projections leave them in), four heads and five spans (640 tokens) a
    step, the runs of spans innermost; beta a (row, run)'s for all heads;
    the four states in scratch; no operand is (.., 64, 64), nothing is
    aliased."""
    call = _pallas_call()
    grid = call.params["grid_mapping"]
    assert call.params["name"] == ks.KERNEL == "kda_scan"
    assert grid.grid == (8, 8, 3)
    assert (grid.num_inputs, grid.num_outputs) == (5, 1)
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in grid.block_mappings] == [
        (1, 4, 128, 640)] * 4 + [(1, 640, 32), (1, 4, 128, 640)]
    assert [(v.aval.shape, v.aval.dtype) for v in call.invars] == [
        ((8, 32, 128, 1920), jnp.dtype(BF16))] * 3 + [
        ((8, 32, 128, 1920), jnp.dtype(F32)), ((8, 1920, 32), jnp.dtype(F32))]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
        ((8, 32, 128, 1920), jnp.dtype(F32))]
    assert not call.params["input_output_aliases"]
    scratch = [(a.shape, a.dtype) for a in list(
        call.params["jaxpr"].invars)[-1:] for a in [a.aval.inner_aval]]
    assert scratch == [((4, 128, 128), jnp.dtype(F32))]
    assert call.params["compiler_params"]["mosaic_tpu"].dimension_semantics \
        == ("parallel", "parallel", "arbitrary")
    cost = call.params["cost_estimate"]
    each = 8 * 32 * 1920  # (row, head, token)s
    assert cost.transcendentals == each * 128 * (64 // SUB + 3)
    assert cost.bytes_accessed == 8 * 1920 * 32 * (128 * (3 * 2 + 8) + 4)
    assert cost.flops == 2 * each * (
        (3 + 2) * 64 * 128 + 6 * 10 * 64 * 64 + 9 * 128 * 128 + 2 * 64 * 128)
    body = [e.primitive.name
            for e in kernels.equations(call.params["jaxpr"])]
    # a span of four heads: two chunks of four strips each and a decay
    # and a hand-over a chunk; 3 products for the sums, 8 strips, 10
    # products of the inverse in 3 passes each, and a chunk's four with the
    # state (3 + 1 + 1 + 3)
    assert body.count("exp") == 4 * (2 * 8 + 1 + 2 * 2)
    assert body.count("dot_general") == 4 * (3 + 8 + 10 * 3 + 2 * 8)
    assert body.count("scan") == 1  # the spans of a step


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


@pytest.mark.parametrize("precision", [None, "highest"])
def test_mosaic_compiles_the_kernel_at_a_dispatch_of_the_real_model(
        one_chip, as_on_the_chip, precision):
    """What the interpreter cannot refuse (tiling, VMEM, a slice off the
    sublane grid, a product whose precision a caller's
    ``default_matmul_precision("highest")`` would change if it did not
    name its own) the chip's compiler can, and nothing runs."""
    def shape(*dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x = shape(*SERVED, dtype=BF16)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(
            lambda q, k, v, g, beta: ks.kda_scan.__wrapped__(
                q, k, v, g, beta, chunk=64, sub=SUB)).lower(
            x, x, x, shape(*SERVED), shape(*SERVED[:3])).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the whole models -------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide():
    """(configuration, parameters, settings) of the lane-wide preset."""
    config = {**_small("ling3"), **LANE_WIDE}
    return (config, hybrid_moe_f32.make_params(config),
            hm.HybridConfig.from_dict(config))


def _program(cfg, dtype=F32):
    return lambda p, h, f: hm.apply_serving(p, h, f, cfg, dtype)


def _window(records=8, rows=2):
    return (jax.ShapeDtypeStruct((rows, records, 30), np.float32),
            jax.ShapeDtypeStruct((rows,), np.int32))


def test_the_lane_wide_program_holds_the_kernel_and_the_small_one_does_not(
        wide):
    config, params, cfg = wide
    assert kernels.kernels_of(_program(cfg), params, *_window()) == {
        ks.KERNEL}
    small = _small("ling3")
    shapes = jax.eval_shape(lambda: hybrid_moe_f32.make_params(small))
    assert not kernels.kernels_of(
        _program(hm.HybridConfig.from_dict(small)), shapes, *_window())


@pytest.mark.parametrize("name,ref", [
    ("mistral4", mla_moe_f32), ("zaya1", cca_moe_f32),
    ("xing4", mhc_moe_f32), ("granite4h", ssm_moe_f32)])
def test_a_model_without_the_mixer_holds_no_delta_kernel(name, ref):
    """At the small presets and at 64 records (1,920 tokens: where their
    attention could tile)."""
    small = _small(name)
    cfg = hm.HybridConfig.from_dict(small)
    shapes = jax.eval_shape(lambda: ref.make_params(small))
    for records in (8, 64):
        assert ks.KERNEL not in kernels.kernels_of(
            _program(cfg), shapes, *_window(records))


def test_the_mixer_through_the_kernel_equals_the_reference(wide):
    """``kda`` alone at 300 tokens (padded on the left to three spans of
    128), one row with 37 padding tokens, in float32: the kernel against
    the reference's recurrence a token at a time."""
    config, params, cfg = wide
    p = params["layers"][1]["mixer"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 300, config["hidden_size"])), F32)
    real = jnp.asarray(np.arange(300)[None, :] >= np.array([[0], [37]]))

    def mixer(p, x, real):
        return hm.kda(p, x, real, cfg, F32)

    assert _holds_kernel(mixer, p, x, real)
    with jax.default_matmul_precision("highest"):
        want = hybrid_moe_f32.kda(p, x, real, config)
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
    config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(0)
    filled = np.asarray([8, 3, 1], np.int32)
    hist = np.zeros((3, 8, 30), np.float32)
    for i, k in enumerate(filled):
        hist[i, 8 - k:] = rows[rng.integers(0, len(rows), k)]
    want, want_pairs = hybrid_moe_f32.forward(params, config, hist, filled)
    assert _holds_kernel(_program(cfg, dtype), params, hist, filled)
    with jax.default_matmul_precision("highest"):
        _, aux = hm.apply_serving(params, hist, filled, cfg, dtype)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    assert gap.mean() < mean
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["pairs"].sum(1)), want_pairs)
