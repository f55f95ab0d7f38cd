"""``hybrid_moe``'s Mamba-2 scan behind ``_state_scan``: the Pallas kernel
(ops/ssd_scan.py, interpreted on the CPU) against the chunked scan through
XLA (``_ssd``) and against the recurrence a token at a time, and which
shapes select which. The small preset of
``tests/benchmark/granite4h_small_config.json`` has heads of 16 with a
state of 16 and never holds the kernel, so here it gets one lane-wide
preset (8 heads of 64, a state of 128, chunks of 128): every chunking, one
group and several, padding in front, both input dtypes, both
arithmetics (the interpreter's float32 products and the chip's bfloat16
passes), the state handed from chunk to chunk, what the programs' own
jaxprs say they hold, the ``pallas_call`` at the served shape, and Mosaic's
own word on it."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import (cca_moe_f32, hybrid_moe_f32, mhc_moe_f32,
                                 mla_moe_f32, ssm_moe_f32, table)
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import kernels, short_conv
from ccfd_tpu.ops import ssd_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
# the served shape: 4 windows of 1,920 tokens, 128 heads of 64, one group,
# a state of 128, chunks of 640
SERVED = ((4, 1920, 128, 64), (4, 1920, 1, 128), 640)
LANE_WIDE = {"hidden_size": 256, "mamba_n_heads": 8, "mamba_d_head": 64,
             "mamba_d_state": 128, "mamba_n_groups": 1, "scan_chunk": 128}


def _small(name):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


def _operands(t=256, heads=4, groups=1, pads=(0, 37), dtype=F32, seed=0,
              head_dim=64, state=128):
    """x, B, C, dt, a, D as ``mamba2`` makes them: dt = a = 0 on the
    ``pads[i]`` padding tokens on the left of row i, a <= 0."""
    rng = np.random.default_rng(seed)
    b = len(pads)
    real = (np.arange(t)[None, :] >= np.asarray(pads)[:, None])[..., None]
    x = jnp.asarray(rng.normal(size=(b, t, heads, head_dim)), dtype)
    bm = jnp.asarray(rng.normal(size=(b, t, groups, state)) / 8, dtype)
    cm = jnp.asarray(rng.normal(size=(b, t, groups, state)) / 8, dtype)
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(b, t, heads))))
                     * real, F32)
    a = -jnp.asarray(np.exp(rng.normal(size=heads) * 0.5), F32) * dt
    d = jnp.asarray(rng.normal(size=heads), F32)
    return (x, bm, cm, dt, a, d), real[..., None]


def _a_token_at_a_time(x, bm, cm, dt, a, d):
    """S_t = e^(a_t) S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, in
    float64 on the host."""
    x, bm, cm, dt, a, d = (np.asarray(v, np.float64) for v in (
        x, bm, cm, dt, a, d))
    b, t, h, p = x.shape
    per = h // bm.shape[2]
    bm, cm = np.repeat(bm, per, axis=2), np.repeat(cm, per, axis=2)
    state = np.zeros((b, h, p, bm.shape[-1]))
    y = np.empty_like(x)
    for i in range(t):
        state = (state * np.exp(a[:, i])[..., None, None]
                 + (dt[:, i, :, None] * x[:, i])[..., None]
                 * bm[:, i, :, None, :])
        y[:, i] = np.einsum("bhpn,bhn->bhp", state, cm[:, i])
    return y + d[:, None] * x


def _through_xla(x, bm, cm, dt, a, d, chunk):
    f32 = [v.astype(F32) for v in (x, bm, cm)]
    y, low = hm._ssd(*f32, dt, a, chunk)
    return y + d[:, None] * f32[0], low


# -- the kernel against _ssd and against the recurrence ----------------------------

@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 2], ids=["one_group", "two_groups"])
@pytest.mark.parametrize("t,chunk,side", [
    (256, 256, None), (256, 256, 128), (256, 128, None), (384, 128, None),
    (384, 256, 128), (200, 128, None)],
    ids=["one_chunk", "one_chunk_three_blocks", "two_chunks", "three_chunks",
         "padded_on_the_left", "padded_to_two_chunks"])
def test_the_kernel_equals_the_scan_through_xla_and_the_recurrence(
        t, chunk, side, groups, dtype):
    """Rows with no padding and with padding that ends inside the first
    block; one group for all four heads and two groups of two; x, B and C
    in float32 and in bfloat16 (widened inside: decays, scores, state and
    sums are float32 either way)."""
    operands, real = _operands(t, groups=groups, dtype=dtype)
    got, low = ss.ssd_scan(*operands, chunk=chunk, side=side)
    want, want_low = _through_xla(*operands, chunk)
    assert got.shape == want.shape == operands[0].shape
    assert got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    assert np.allclose(np.asarray(got) * real, np.asarray(want) * real,
                       atol=2e-4, rtol=2e-4)
    assert float(low) == pytest.approx(float(want_low), rel=1e-6)
    assert np.allclose(np.asarray(got) * real,
                       _a_token_at_a_time(*operands) * real, atol=2e-4,
                       rtol=2e-4)


@pytest.mark.parametrize("groups", [1, 2], ids=["two_tiles_a_step",
                                                "one_tile_a_step"])
def test_the_chips_bfloat16_passes_stay_near_the_float32_products(groups):
    """``exact=False`` is what Mosaic compiles: one bfloat16 pass inside a
    chunk, three on the state (a value split into a bfloat16 and the
    bfloat16 of what that left). Under the interpreter it is held to the
    float32 products: the mean gap is a bfloat16's rounding of the product
    inside a chunk, far under it on what only the state carries."""
    operands, real = _operands(256, groups=groups, pads=(0, 130))
    want = _a_token_at_a_time(*operands) * real
    got, _ = ss.ssd_scan(*operands, chunk=128, exact=False)
    gap = np.abs(np.asarray(got) * real - want)
    assert gap.mean() < 4e-3 * np.abs(want).mean()
    assert gap.max() < 0.05 * np.abs(want).max()
    exact, _ = ss.ssd_scan(*operands, chunk=128)
    assert np.abs(np.asarray(exact) * real - want).max() < 2e-4
    # the state alone: C of the first chunk zeroed, so what the second
    # chunk's tokens read of the first comes through the state's products
    x, bm, cm, dt, a, d = operands
    alone = (x, bm, cm.at[:, :128].set(0.0), dt, a, jnp.zeros_like(d))
    want = _a_token_at_a_time(*alone)[:, 128:]
    got, _ = ss.ssd_scan(*alone, chunk=128, exact=False)
    got0, _ = ss.ssd_scan(x, bm, cm.at[:, :].set(0.0), dt, a,
                          jnp.zeros_like(d), chunk=128, exact=False)
    assert not np.asarray(got0).any()
    assert np.abs(np.asarray(got)[:, 128:] - want).mean() < 4e-3 * np.abs(
        want).mean()


def test_the_state_is_handed_from_chunk_to_chunk():
    """A change to an early token moves every later y, across two chunk
    edges; a later token moves no earlier one."""
    (x, bm, cm, dt, a, d), _ = _operands(384, pads=(0, 0))
    # slow decays, so that the first chunk still shows in the third
    a = a * 0.01
    base, _ = ss.ssd_scan(x, bm, cm, dt, a, d, chunk=128)
    early, _ = ss.ssd_scan(x.at[:, 5].add(1.0), bm, cm, dt, a, d, chunk=128)
    late, _ = ss.ssd_scan(x.at[:, 300].add(1.0), bm.at[:, 300].add(1.0), cm,
                          dt, a, d, chunk=128)
    base, early, late = (np.asarray(v) for v in (base, early, late))
    assert np.array_equal(base[:, :5], early[:, :5])
    moved = np.abs(early - base).max(axis=(0, 2, 3))
    assert (moved[5:] > 0).all()
    assert moved[383] > 1e-4
    assert np.array_equal(base[:, :300], late[:, :300])
    assert (np.abs(late - base).max(axis=(0, 2, 3))[300:] > 0).all()


def test_padding_passes_the_state_unchanged():
    """A row whose first 130 tokens are padding (dt = a = 0) gives on its
    real tokens what the same row without them gives, whatever x, B and C
    hold where the padding is."""
    operands, _ = _operands(256, pads=(130,))
    alone = tuple(v[:, 130:] if v.ndim > 1 else v for v in operands)
    got, _ = ss.ssd_scan(*operands, chunk=128)
    want, _ = ss.ssd_scan(*alone, chunk=128)
    assert np.allclose(np.asarray(got)[:, 130:], np.asarray(want), atol=2e-5)
    # and a window of padding alone is the skip's D x, with no decay at all
    x, bm, cm, dt, a, d = operands
    got, low = ss.ssd_scan(x, bm, cm, dt * 0, a * 0, d, chunk=128)
    assert np.allclose(np.asarray(got), np.asarray(d[:, None] * x))
    assert float(low) == 0.0


# -- which shapes select which -------------------------------------------------------

@pytest.mark.parametrize("x,b,chunk,dtype,fits", [
    (*SERVED, F32, True),
    (*SERVED, BF16, True),
    ((2, 240, 8, 64), (2, 240, 1, 128), 128, F32, True),  # the lane-wide preset
    ((2, 256, 4, 64), (2, 256, 2, 128), 256, F32, True),  # two groups of two
    ((2, 256, 2, 128), (2, 256, 1, 256), 128, F32, True),  # heads of 128
    ((2, 256, 6, 64), (2, 256, 3, 128), 128, F32, True),  # a tile a group
    # the small preset: 8 heads of 16, a state of 16, chunks of 32
    ((3, 240, 8, 16), (3, 240, 2, 16), 32, F32, False),
    ((2, 256, 8, 16), (2, 256, 1, 128), 128, F32, False),  # heads of 16
    ((2, 256, 4, 64), (2, 256, 1, 16), 128, F32, False),  # a state of 16
    ((2, 256, 4, 64), (2, 256, 1, 128), 32, F32, False),  # a chunk of 32
    ((2, 256, 4, 64), (2, 256, 1, 128), 200, F32, False),  # of no whole block
    ((2, 256, 3, 64), (2, 256, 3, 128), 128, F32, False),  # half a tile a group
    ((2, 256, 4, 64), (2, 256, 1, 128), 128, jnp.float16, False),
    ((2, 256, 4, 64), (2, 200, 1, 128), 128, F32, False),  # another window
    # the scores of a chunk and every head's state no longer fit
    ((2, 8192, 128, 64), (2, 8192, 1, 128), 4096, F32, False),
], ids=["served", "served_bf16", "lane_wide", "two_groups", "heads_128",
        "tile_a_group", "small_preset", "heads_16", "state_16", "chunk_32",
        "chunk_200", "half_tile_a_group", "float16", "other_window",
        "over_vmem"])
def test_which_shapes_the_kernel_takes(x, b, chunk, dtype, fits):
    assert ss.kernel_fits(jax.ShapeDtypeStruct(x, dtype),
                          jax.ShapeDtypeStruct(b, dtype), chunk) is fits


def test_a_mesh_keeps_the_scan_through_xla():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x, bm = (2, 256, 4, 64), jax.ShapeDtypeStruct((2, 256, 1, 128), F32)
    assert ss.kernel_fits(jax.ShapeDtypeStruct(x, F32), bm, 128)
    assert not ss.kernel_fits(jax.ShapeDtypeStruct(
        x, F32, sharding=NamedSharding(mesh, PartitionSpec())), bm, 128)


@pytest.mark.parametrize("chunk,side", [(640, 128), (256, 256), (128, 128),
                                        (384, 128), (512, 256), (200, None),
                                        (32, None)])
def test_the_block_comes_from_the_chunk(chunk, side):
    assert ss.side_for(chunk) == side


def _holds_kernel(fn, *args) -> bool:
    return kernels.held_by(fn, *args, names=(ss.KERNEL,))


def _shape(*dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("x,b,chunk,kernel", [
    (*SERVED, True),
    ((2, 240, 8, 64), (2, 240, 1, 128), 128, True),
    ((3, 240, 8, 16), (3, 240, 2, 16), 32, False),
    ((2, 256, 4, 64), (2, 256, 1, 128), 32, False),
], ids=["served", "lane_wide", "small_preset", "chunk_32"])
def test_the_programs_jaxpr_says_which_path_was_taken(x, b, chunk, kernel):
    def scan(x, bm, cm, dt, a, d):
        return hm._state_scan(x, bm, cm, dt, a, d, chunk)

    heads = _shape(*x[:3])
    assert _holds_kernel(scan, _shape(*x), _shape(*b), _shape(*b), heads,
                         heads, _shape(x[2])) is kernel


def test_the_selection_runs_the_kernel_where_it_fits():
    """``_state_scan`` itself, jitted, at a window of two chunks."""
    operands, real = _operands(256)
    got, low = jax.jit(hm._state_scan, static_argnums=6)(*operands, 128)
    want, want_low = _through_xla(*operands, 128)
    assert np.allclose(np.asarray(got) * real, np.asarray(want) * real,
                       atol=2e-4, rtol=2e-4)
    assert float(low) == pytest.approx(float(want_low), rel=1e-6)


# -- the pallas_call at the served shape ---------------------------------------------

@functools.cache
def _pallas_call():
    (b, t, h, p), (_, _, g, n), chunk = SERVED
    jaxpr = jax.make_jaxpr(
        lambda x, bm, cm, dt, a, d: ss.ssd_scan(x, bm, cm, dt, a, d,
                                                chunk=chunk, exact=False))(
        _shape(b, t, h, p), _shape(b, t, g, n), _shape(b, t, g, n),
        _shape(b, t, h), _shape(b, t, h), _shape(h))
    calls = [e for e in kernels.equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


def test_the_pallas_call_is_pinned_at_the_served_shape():
    """As the chip runs it (``exact=False``: its bfloat16 passes, which the
    interpreter's default here would make float32). The name, the grid, the operands and their blocks: x and y
    lane-dense, two lane tiles (four heads) a step; B, C, dt and R a
    (row, chunk)'s, whatever the step; R a second time by head; the scores
    of a chunk, the pieces of C and B^T and every head's state in scratch;
    no operand is (.., 640, 640) and none is by head."""
    call = _pallas_call()
    grid = call.params["grid_mapping"]
    assert call.params["name"] == ss.KERNEL == "ssd_scan"
    assert grid.grid == (4, 3, 32)
    assert (grid.num_inputs, grid.num_outputs) == (7, 1)
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in grid.block_mappings] == [
        (1, 640, 256), (1, 640, 128), (1, 640, 128), (1, 640, 128),
        (1, 640, 128), (1, 128, 640), (1, 256), (1, 640, 256)]
    assert [v.aval.shape for v in call.invars] == [
        (4, 1920, 8192), (4, 1920, 128), (4, 1920, 128), (4, 1920, 128),
        (4, 1920, 128), (4, 128, 1920), (1, 8192)]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
        ((4, 1920, 8192), jnp.dtype(F32))]
    scratch = [(a.shape, a.dtype) for a in list(
        call.params["jaxpr"].invars)[-6:] for a in [a.aval.inner_aval]]
    assert scratch == [((32, 2, 128, 128), jnp.dtype(F32)),
                       ((640, 640), jnp.dtype(F32)),
                       ((640, 128), jnp.dtype(BF16)),
                       ((640, 128), jnp.dtype(BF16)),
                       ((128, 640), jnp.dtype(BF16)),
                       ((128, 640), jnp.dtype(BF16))]
    visited = 3 * 15 * 128 * 128  # 15 of 25 blocks a chunk
    assert call.params["cost_estimate"].transcendentals == 4 * 128 * (
        visited + 2 * 1920 * 64)
    body = [e.primitive.name
            for e in kernels.equations(call.params["jaxpr"])]
    # a step: 2 lane tiles x 2 heads x 15 blocks of decays, each an
    # exponential and a product; and per lane tile three exponentials and
    # two products of three passes with the state
    assert body.count("exp") == 2 * (2 * 15 + 3)
    assert body.count("dot_general") == 15 + 2 * (2 * 15 + 6)


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
    """What the interpreter cannot refuse (tiling, VMEM, a load off the
    sublane grid, a bfloat16 product asked for at float32 precision: what
    a caller's ``default_matmul_precision("highest")`` would make of a
    product that does not name its own) the chip's compiler can, and
    nothing runs."""
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, F32, sharding=one_chip)

    (b, t, h, p), (_, _, g, n), chunk = SERVED
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(
            lambda x, bm, cm, dt, a, d: ss.ssd_scan.__wrapped__(
                x, bm, cm, dt, a, d, chunk=chunk)).lower(
            shape(b, t, h, p), shape(b, t, g, n), shape(b, t, g, n),
            shape(b, t, h), shape(b, t, h), shape(h)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mosaic_compiles_the_kernel_at_eight_groups_of_b_and_c(
        one_chip, as_on_the_chip):
    """The second model's dispatch (PR 49): 8 windows of 1,920 tokens, 64
    heads of 64 in 8 groups of B and C (a group's 8 heads are two steps of
    two lane tiles), chunks of 640: the scores and the split pieces are a
    group's, computed at its first step."""
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, F32, sharding=one_chip)

    b, t, h, p, g, n = 8, 1920, 64, 64, 8, 128
    assert ss.kernel_fits(shape(b, t, h, p), shape(b, t, g, n), 640)
    compiled = jax.jit(
        lambda x, bm, cm, dt, a, d: ss.ssd_scan.__wrapped__(
            x, bm, cm, dt, a, d, chunk=640)).lower(
        shape(b, t, h, p), shape(b, t, g, n), shape(b, t, g, n),
        shape(b, t, h), shape(b, t, h), shape(h)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the whole models -------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide():
    """(configuration, parameters, settings) of the lane-wide preset."""
    config = {**_small("granite4h"), **LANE_WIDE}
    return (config, ssm_moe_f32.make_params(config),
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
        ss.KERNEL, short_conv.KERNEL}  # the convolution before it too
    small = _small("granite4h")
    shapes = jax.eval_shape(lambda: ssm_moe_f32.make_params(small))
    assert not kernels.kernels_of(
        _program(hm.HybridConfig.from_dict(small)), shapes, *_window())


@pytest.mark.parametrize("name,ref", [
    ("mistral4", mla_moe_f32), ("zaya1", cca_moe_f32),
    ("xing4", mhc_moe_f32), ("ling3", hybrid_moe_f32)])
def test_a_model_without_the_mixer_holds_no_scan_kernel(name, ref):
    """At the small presets and at 64 records (1,920 tokens: where their
    attention could tile)."""
    small = _small(name)
    cfg = hm.HybridConfig.from_dict(small)
    shapes = jax.eval_shape(lambda: ref.make_params(small))
    for records in (8, 64):
        assert ss.KERNEL not in kernels.kernels_of(
            _program(cfg), shapes, *_window(records))


def test_the_mixer_through_the_kernel_equals_the_reference(wide):
    """``mamba2`` alone at 300 tokens (padded on the left to three chunks
    of 128), one row with 37 padding tokens, in float32: the kernel
    against the reference's recurrence a token at a time."""
    config, params, cfg = wide
    p = ssm_moe_f32.layer_of(params, 1)["mixer"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 300, config["hidden_size"])), F32)
    real = jnp.asarray(np.arange(300)[None, :] >= np.array([[0], [37]]))

    def mixer(p, x, real):
        return hm.mamba2(p, x, real, cfg, F32)

    assert _holds_kernel(mixer, p, x, real)
    with jax.default_matmul_precision("highest"):
        want = ssm_moe_f32.mamba(p, x, real, config)
        got, low = mixer(p, x, real)
    assert -500 < float(low) < -1
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype,worst,mean", [(F32, 2e-4, 2e-5),
                                              (BF16, None, 0.05)])
def test_the_model_equals_the_reference_through_the_kernel(wide, dtype, worst,
                                                           mean):
    """8 records = 240 tokens, two chunks of 128 with 16 tokens of padding
    in front: a full window, a short history and a single record."""
    config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(0)
    filled = np.asarray([8, 3, 1], np.int32)
    hist = np.zeros((3, 8, 30), np.float32)
    for i, k in enumerate(filled):
        hist[i, 8 - k:] = rows[rng.integers(0, len(rows), k)]
    want, want_choice = ssm_moe_f32.forward(params, config, hist, filled)
    assert _holds_kernel(_program(cfg, dtype), params, hist, filled)
    with jax.default_matmul_precision("highest"):
        _, aux = hm.apply_serving(params, hist, filled, cfg, dtype)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    assert gap.mean() < mean
    assert float(aux["ssm_log_decay_min"]) < 0
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
