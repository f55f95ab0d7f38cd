"""``hybrid_moe``'s Mamba-2 short convolution behind ``_conv_silu``: the
Pallas kernel (ops/short_conv.py, interpreted on the CPU) against
``_short_conv`` + bias + SiLU through XLA, and which shapes select which.
The small presets (``tests/benchmark/granite4h_small_config.json``: 40
columns of x, B and C; ``nemotron3n_small_config.json``: 192 from column 128,
in widths of 128, 32, 32) never hold the kernel, so here each gets a
lane-wide preset: one group of B and C and two, both input dtypes, padding on
the left so the mask matters, the first K - 1 tokens, windows of one strip
and of several, what the programs' own jaxprs say they hold, the
``pallas_call`` at both served shapes, Mosaic's own word on them, and the
mixers and the whole models through both kernels against the plain
references."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import (cca_moe_f32, hybrid_moe_f32, mhc_moe_f32,
                                 mla_moe_f32, ssm_moe_f32, ssm_relu2_moe_f32,
                                 table)
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import kernels
from ccfd_tpu.ops import short_conv as sc
from ccfd_tpu.ops import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
# the served shapes: (proj, the first column, the widths of x, B and C)
# granite4h: 4 windows, inner 8,192, one group of B and C, 128 values of dt
GRANITE = ((4, 1920, 16768), 8192, (8192, 128, 128))
# nemotron3n: 8 windows, inner 4,096, eight groups, 64 of dt (a ragged tile)
NEMOTRON = ((8, 1920, 10304), 4096, (4096, 1024, 1024))
LANE_WIDE = {
    "granite4h": (ssm_moe_f32, {
        "hidden_size": 256, "mamba_n_heads": 8, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_n_groups": 1, "scan_chunk": 128}),
    "nemotron3n": (ssm_relu2_moe_f32, {
        "hidden_size": 256, "mamba_num_heads": 16, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 2, "scan_chunk": 128}),
}


def _small(name):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


def _operands(t=64, inner=256, groups=1, state=128, heads=4, pads=(0, 37),
              dtype=F32, taps=4, seed=0):
    """proj = [gate | x B C | dt], taps, bias and keep as ``mamba2`` has
    them: ``pads[i]`` padding tokens on the left of row i, where ``proj``
    holds what a padded token's projection would (anything but zeros)."""
    rng = np.random.default_rng(seed)
    widths = (inner, groups * state, groups * state)
    proj = jnp.asarray(
        rng.normal(size=(len(pads), t, 2 * inner + sum(widths[1:]) + heads)),
        dtype)
    w = jnp.asarray(rng.normal(size=(taps, sum(widths))) / 2, F32)
    bias = jnp.asarray(rng.normal(size=sum(widths)) / 10, F32)
    keep = jnp.asarray(np.arange(t)[None, :] >= np.asarray(pads)[:, None],
                       F32)[..., None]
    return (proj, w, bias, keep), inner, widths


def _through_xla(proj, taps, bias, keep, at, widths):
    """``_short_conv`` on the masked columns, the bias, SiLU: one array,
    the widths side by side."""
    b, t, _ = proj.shape
    k, wide = taps.shape
    u = proj[..., at:at + wide].astype(F32) * keep
    return jax.nn.silu(hm._short_conv(
        u.reshape(b, t, -1, 1), taps.reshape(k, -1, 1))
        + bias.reshape(-1, 1)).reshape(b, t, wide)


# -- the kernel against _short_conv -------------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 2], ids=["one_group", "two_groups"])
@pytest.mark.parametrize("t", [32, 48, 64, 240],
                         ids=["one_strip", "three_strips_of_16",
                              "two_strips_of_32", "fifteen_strips"])
def test_the_kernel_equals_the_convolution_through_xla(t, groups, dtype):
    """A row with no padding and one whose first 37 (a strip and part of
    the next; at 32 tokens: all of it) are padding; x at blocks of 256
    lanes, B and C at 128 or 256; float32 out whatever comes in."""
    operands, at, widths = _operands(t, groups=groups, dtype=dtype)
    got = sc.short_conv(*operands, at=at, widths=widths)
    assert [(v.shape, v.dtype) for v in got] == [
        ((2, t, n), jnp.dtype(F32)) for n in widths]
    want = _through_xla(*operands, at, widths)
    assert np.allclose(np.concatenate(got, axis=-1), np.asarray(want),
                       atol=2e-6, rtol=2e-6)


def test_the_first_tokens_read_zeros_and_padding_is_masked_before_the_taps():
    """Token 0 is its own tap alone, token 1 two taps', and so on: what
    ``jnp.pad`` gives ``_short_conv``; a row's first real token reads
    nothing of the padding before it, whatever ``proj`` holds there, and a
    padding token leaves as SiLU(bias)."""
    (proj, taps, bias, keep), at, widths = _operands(64, pads=(0, 37))
    x = np.asarray(sc.short_conv(proj, taps, bias, keep, at=at,
                                 widths=widths)[0], np.float64)
    u = np.asarray(proj[..., at:at + widths[0]], np.float64)
    w, b = (np.asarray(v, np.float64)[..., :widths[0]] for v in (taps, bias))

    def silu(v):
        return v / (1 + np.exp(-v))

    k = w.shape[0]
    for token in range(k):
        alone = sum(w[k - 1 - lag] * u[0, token - lag]
                    for lag in range(token + 1))
        assert np.allclose(x[0, token], silu(alone + b), atol=2e-6)
    assert np.allclose(x[1, 37], silu(w[k - 1] * u[1, 37] + b), atol=2e-6)
    assert np.allclose(x[1, :37], silu(b), atol=2e-6)
    other = proj.at[1, :37].set(7.0)
    again = sc.short_conv(other, taps, bias, keep, at=at, widths=widths)[0]
    assert np.array_equal(np.asarray(again), x.astype(np.float32))


def test_a_strips_edge_hands_on_what_the_taps_reach_back_to():
    """A change to the last token of a strip moves the next K - 1 tokens
    (the first of the strip after it) and no other."""
    (proj, taps, bias, keep), at, widths = _operands(96, pads=(0, 0))
    assert sc.strip_for(96) == 32
    base = sc.short_conv(proj, taps, bias, keep, at=at, widths=widths)[0]
    moved = sc.short_conv(proj.at[:, 31, at:].add(1.0), taps, bias, keep,
                          at=at, widths=widths)[0]
    changed = np.abs(np.asarray(moved) - np.asarray(base)).max(axis=(0, 2))
    assert (changed[31:35] > 0).all()
    assert not changed[:31].any() and not changed[35:].any()


@pytest.mark.parametrize("taps", [1, 2, 4, 8])
def test_any_number_of_taps_under_a_sublane_tile(taps):
    operands, at, widths = _operands(48, taps=taps)
    got = sc.short_conv(*operands, at=at, widths=widths)
    assert np.allclose(np.concatenate(got, axis=-1),
                       np.asarray(_through_xla(*operands, at, widths)),
                       atol=2e-6, rtol=2e-6)


# -- which shapes select which -------------------------------------------------------

@pytest.mark.parametrize("proj,at,widths,taps,dtype,fits", [
    (*GRANITE, 4, F32, True),
    (*GRANITE, 4, BF16, True),
    (*NEMOTRON, 4, F32, True),
    ((2, 240, 1288), 512, (512, 128, 128), 4, F32, True),  # lane-wide, 1 group
    ((2, 240, 2576), 1024, (1024, 256, 256), 4, F32, True),  # and 2
    ((2, 48, 1288), 512, (512, 128, 128), 8, F32, True),  # eight taps
    ((2, 240, 768), 0, (512, 128, 128), 4, F32, True),  # from column 0, no dt
    # the small presets: granite4h 128 + 2 x 32 from 128, 8 heads of dt;
    # nemotron3n the same widths in 2 groups of 16
    ((3, 240, 328), 128, (128, 32, 32), 4, F32, False),
    ((2, 240, 1160), 512, (512, 64, 64), 4, F32, False),  # a ragged B and C
    ((2, 240, 1224), 448, (448, 128, 128), 4, F32, False),  # a ragged inner
    ((2, 240, 700), 512, (512, 128, 128), 4, F32, False),  # past proj's end
    ((2, 300, 1288), 512, (512, 128, 128), 4, F32, False),  # no whole strips
    ((2, 240, 1288), 512, (512, 128, 128), 9, F32, False),  # past a sublane tile
    ((2, 240, 1288), 512, (512, 128, 128), 4, jnp.float16, False),
    # a window whose block no longer fits beside its output
    ((2, 8192, 1288), 512, (512, 128, 128), 4, F32, False),
    ((2, 240, 1288), 512, (), 4, F32, False),
], ids=["granite4h", "granite4h_bf16", "nemotron3n", "lane_wide",
        "lane_wide_two_groups", "eight_taps", "from_column_0", "small_preset",
        "ragged_groups", "ragged_inner", "past_the_end",
        "window_300", "nine_taps", "float16", "over_vmem", "no_widths"])
def test_which_shapes_the_kernel_takes(proj, at, widths, taps, dtype, fits):
    assert sc.kernel_fits(
        jax.ShapeDtypeStruct(proj, dtype),
        jax.ShapeDtypeStruct((taps, sum(widths)), F32), at, widths) is fits


def test_a_mesh_keeps_the_convolution_through_xla():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    proj, taps = (2, 240, 1288), jax.ShapeDtypeStruct((4, 768), F32)
    assert sc.kernel_fits(jax.ShapeDtypeStruct(proj, F32), taps, 512,
                          (512, 128, 128))
    assert not sc.kernel_fits(jax.ShapeDtypeStruct(
        proj, F32, sharding=NamedSharding(mesh, PartitionSpec())), taps, 512,
        (512, 128, 128))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert not sc.kernel_fits(jax.ShapeDtypeStruct(proj, F32), taps, 512,
                                  (512, 128, 128))


@pytest.mark.parametrize("columns,lanes", [
    ((8192, 0, 8192), 256), ((8192, 8192, 128), 128), ((8192, 8320, 128), 128),
    ((4096, 4096, 1024), 256), ((4096, 5120, 1024), 256),
    ((128, 128, 256), 128), ((128, 0, 40), None), ((64, 0, 128), None)])
def test_the_block_comes_from_the_columns(columns, lanes):
    assert sc.lanes_for(*columns) == lanes


@pytest.mark.parametrize("tokens,strip", [(1920, 32), (240, 16), (48, 16),
                                          (64, 32), (300, None), (8, None)])
def test_the_strip_comes_from_the_window(tokens, strip):
    assert sc.strip_for(tokens) == strip


def _holds_kernel(fn, *args, names=(sc.KERNEL,)) -> bool:
    return kernels.held_by(fn, *args, names=names)


def _shape(*dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("proj,at,widths,kernel", [
    (*GRANITE, True), (*NEMOTRON, True),
    ((2, 240, 1288), 512, (512, 128, 128), True),
    ((3, 240, 328), 128, (128, 32, 32), False),
    ((2, 300, 1288), 512, (512, 128, 128), False),
], ids=["granite4h", "nemotron3n", "lane_wide", "small_preset", "window_300"])
def test_the_programs_jaxpr_says_which_path_was_taken(proj, at, widths,
                                                      kernel):
    def conv(proj, taps, bias, keep):
        return hm._conv_silu(proj, taps, bias, keep, at, widths)

    assert _holds_kernel(
        conv, _shape(*proj), _shape(4, sum(widths)), _shape(sum(widths)),
        _shape(*proj[:2], 1)) is kernel


@pytest.mark.parametrize("t", [240, 300], ids=["the_kernel", "through_xla"])
def test_the_selection_gives_one_convolution_on_either_path(t, monkeypatch):
    """``_conv_silu`` itself, jitted: a window the kernel takes and one it
    refuses, each against the path that was not taken."""
    operands, at, widths = _operands(t, pads=(0, 41))
    got = jax.jit(hm._conv_silu, static_argnums=(4, 5))(*operands, at, widths)
    assert [v.shape for v in got] == [(2, t, n) for n in widths]
    want = _through_xla(*operands, at, widths)
    assert np.allclose(np.concatenate(got, axis=-1), np.asarray(want),
                       atol=2e-6, rtol=2e-6)
    if t == 240:  # and the path through XLA cut into the same three
        monkeypatch.setattr(sc, "kernel_fits", lambda *a: False)
        plain = hm._conv_silu(*operands, at, widths)
        assert all(np.allclose(np.asarray(a), np.asarray(b), atol=2e-6)
                   for a, b in zip(got, plain, strict=True))


# -- the pallas_call at the served shapes --------------------------------------------

@functools.cache
def _pallas_calls(proj, at, widths):
    jaxpr = jax.make_jaxpr(
        lambda p, w, b, keep: sc.short_conv(p, w, b, keep, at=at,
                                            widths=widths))(
        _shape(*proj), _shape(4, sum(widths)), _shape(sum(widths)),
        _shape(*proj[:2], 1))
    return [e for e in kernels.equations(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("served,grids,blocks", [
    (GRANITE, [(4, 32), (4, 1), (4, 1)], [256, 128, 128]),
    (NEMOTRON, [(8, 16), (8, 4), (8, 4)], [256, 256, 256]),
], ids=["granite4h", "nemotron3n"])
def test_the_pallas_call_is_pinned_at_the_served_shapes(served, grids,
                                                        blocks):
    """One call an output, each under the kernel's name: the operand is
    ``proj`` whole (no slice of it is an operand: its columns are the
    ``BlockSpec``'s to address, nemotron3n's ragged last tile of dt past
    every block), a block a row's whole window for some lane tiles, the
    mask a (row, window)'s, the taps and bias the block's columns'; x, B
    and C leave float32 token-major. The body is a loop over strips, a
    few tens of equations whatever the window: no unrolled strips for the
    chip's host to trace."""
    proj, at, widths = served
    calls = _pallas_calls(*served)
    assert len(calls) == 3
    wide = sum(widths)
    for call, grid, lanes, width in zip(calls, grids, blocks, widths,
                                        strict=True):
        mapping = call.params["grid_mapping"]
        assert call.params["name"] == sc.KERNEL == "short_conv"
        assert mapping.grid == grid == (proj[0], width // lanes)
        assert (mapping.num_inputs, mapping.num_outputs) == (4, 1)
        assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
                for m in mapping.block_mappings] == [
            (1, 1920, lanes), (1, 1920, 1), (4, lanes), (1, lanes),
            (1, 1920, lanes)]
        assert [v.aval.shape for v in call.invars] == [
            proj, (proj[0], 1920, 1), (4, wide), (1, wide)]
        assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
            ((proj[0], 1920, width), jnp.dtype(F32))]
        assert sc._vmem_bytes(1920, lanes) <= sc.VMEM_BYTES < 16 << 20
        body = [e.primitive.name
                for e in kernels.equations(call.params["jaxpr"])]
        assert body.count("while") + body.count("scan") == 1
        assert body.count("roll") == 3  # the lags of four taps
        assert len(body) < 80


def test_the_blocks_address_the_columns_where_they_lie():
    """The index maps, asked: x's block j is ``proj``'s lane block
    ``at / lanes + j``, B's and C's follow at their own offsets, and the
    taps' and the bias's blocks are the same columns counted from x."""
    for served in (GRANITE, NEMOTRON):
        _, at, widths = served
        start = 0
        for call, width in zip(_pallas_calls(*served), widths, strict=True):
            lanes = sc.lanes_for(at, start, width)
            maps = [m.index_map_jaxpr
                    for m in call.params["grid_mapping"].block_mappings]
            for j in (0, width // lanes - 1):
                found = [tuple(int(i) for i in jax.core.eval_jaxpr(
                    m.jaxpr, m.consts, 2, j)) for m in maps]
                assert found == [
                    (2, 0, (at + start) // lanes + j), (2, 0, 0),
                    (0, start // lanes + j), (0, start // lanes + j),
                    (2, 0, j)]
            start += width


# -- the real shapes, compiled for the chip that is described and not attached -----

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


@pytest.mark.parametrize("served", [GRANITE, NEMOTRON],
                         ids=["granite4h", "nemotron3n"])
def test_mosaic_compiles_the_kernel_at_a_dispatch_of_the_real_models(
        one_chip, as_on_the_chip, served):
    """What the interpreter cannot refuse (a block off the lane grid, a
    rotation down the sublanes Mosaic has no rule for, VMEM past the
    compiler's default, which this kernel does not raise) the chip's
    compiler can, and nothing runs. Three custom calls, and no slice or
    transpose of ``proj`` before them: its columns are not cut out. (A
    copy of the parameter itself is the entry layout's, where the last
    axis is no whole lane tiles: inside a program the projection writes
    the layout the calls ask for.)"""
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, F32, sharding=one_chip)

    proj, at, widths = served
    text = jax.jit(
        lambda p, w, b, keep: sc.short_conv.__wrapped__(
            p, w, b, keep, at=at, widths=widths)).lower(
        shape(*proj), shape(4, sum(widths)), shape(sum(widths)),
        shape(*proj[:2], 1)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    big = f"f32[{proj[0]},1920,"
    assert not [line for line in text.splitlines()
                if big in line and f"{big}1]" not in line
                and (" slice(" in line or " transpose(" in line
                     or (" copy(" in line and " copy(%p." not in line))]


# -- the mixers and the whole models ----------------------------------------------------

@pytest.fixture(scope="module", params=sorted(LANE_WIDE))
def wide(request):
    """(reference, configuration, parameters, settings) of a lane-wide
    preset: granite4h's with one group of B and C, nemotron3n's with two
    and the gated norm inside each."""
    ref, widths = LANE_WIDE[request.param]
    config = {**_small(request.param), **widths}
    return (ref, config, ref.make_params(config),
            hm.HybridConfig.from_dict(config))


@pytest.fixture(scope="module", params=sorted(LANE_WIDE))
def small(request):
    """(reference, configuration) of a small preset as the tests have it."""
    return LANE_WIDE[request.param][0], _small(request.param)


def _program(cfg, dtype=F32):
    return lambda p, h, f: hm.apply_serving(p, h, f, cfg, dtype)


def _window(records=8, rows=2):
    return (jax.ShapeDtypeStruct((rows, records, 30), np.float32),
            jax.ShapeDtypeStruct((rows,), np.int32))


def test_the_lane_wide_program_holds_both_kernels(wide):
    ref, config, params, cfg = wide
    held = kernels.held(_program(cfg), params, *_window())
    assert held["conv_kernel"] == held["ssd_kernel"] == 1
    assert held["kda_kernel"] == held["gdn_kernel"] == 0


def test_the_small_program_holds_neither_kernel(small):
    ref, config = small
    shapes = jax.eval_shape(lambda: ref.make_params(config))
    held = kernels.held(_program(hm.HybridConfig.from_dict(config)), shapes,
                        *_window())
    assert held["conv_kernel"] == held["ssd_kernel"] == 0


@pytest.mark.parametrize("name,ref", [
    ("mistral4", mla_moe_f32), ("zaya1", cca_moe_f32),
    ("xing4", mhc_moe_f32), ("ling3", hybrid_moe_f32)])
def test_a_model_without_the_mixer_holds_no_conv_kernel(name, ref):
    """At the small presets and at 64 records (1,920 tokens: the cells'
    window); ``ling3``'s KDA mixers and ``zaya1``'s CCA convolve too, and
    keep their own paths through XLA."""
    small = _small(name)
    cfg = hm.HybridConfig.from_dict(small)
    shapes = jax.eval_shape(lambda: ref.make_params(small))
    for records in (8, 64):
        assert kernels.held(_program(cfg), shapes, *_window(records))[
            "conv_kernel"] == 0


def _mixer_of(ref, params):
    for i in range(len(params["layers"])):
        layer = ref.layer_of(params, i)
        if "conv" in layer.get("mixer", {}):
            return layer["mixer"]
    raise AssertionError("no Mamba-2 layer in the preset")


def test_the_mixer_through_both_kernels_equals_the_reference(wide):
    """``mamba2`` alone at 304 tokens (19 strips of 16; three chunks of
    128 with padding in front), one row with 37 padding tokens, in
    float32: the convolution's kernel into the scan's against the
    reference's convolution and recurrence a token at a time, within what
    the scan's own test holds."""
    ref, config, params, cfg = wide
    p = _mixer_of(ref, params)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 304, config["hidden_size"])), F32)
    real = jnp.asarray(np.arange(304)[None, :] >= np.array([[0], [37]]))

    def mixer(p, x, real):
        return hm.mamba2(p, x, real, cfg, F32)

    assert _holds_kernel(mixer, p, x, real)
    assert _holds_kernel(mixer, p, x, real, names=(ssd_scan.KERNEL,))
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(p, x, real, config)
        got, low = mixer(p, x, real)
    assert -500 < float(low) < -1
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_the_scan_is_handed_float32_token_major_arrays(wide):
    """Between the two kernels: x (B, T, inner), B and C (B, T, groups x
    state), float32, each the output of a ``short_conv`` call; no slice of
    the convolution's output is cut for the scan."""
    ref, config, params, cfg = wide
    p = _mixer_of(ref, params)
    s = cfg.mixer("mamba2")
    inner, gn = s.heads * s.head_dim, s.groups * s.state
    jaxpr = jax.make_jaxpr(lambda p, x, real: hm.mamba2(p, x, real, cfg, F32))(
        p, _shape(2, 240, config["hidden_size"]), _shape(2, 240, dtype=bool))
    eqns = list(kernels.equations(jaxpr.jaxpr))
    conv = [e for e in eqns if e.primitive.name == "pallas_call"
            and e.params["name"] == sc.KERNEL]
    assert [(e.outvars[0].aval.shape, e.outvars[0].aval.dtype)
            for e in conv] == [((2, 240, n), jnp.dtype(F32))
                               for n in (inner, gn, gn)]
    (scan,) = [e for e in eqns if e.primitive.name == "pallas_call"
               and e.params["name"] == ssd_scan.KERNEL]
    assert [v.aval.shape for v in scan.invars[:3]] == [
        (2, 256, inner), (2, 256, gn), (2, 256, gn)]  # padded to two chunks
    assert all(v.aval.dtype == F32 for v in scan.invars[:3])
    sliced = [e for e in eqns if e.primitive.name == "slice"
              and e.invars[0].aval.shape[:2] == (2, 240)
              and e.invars[0].aval.shape[-1] in (inner + 2 * gn, inner, gn)]
    assert not sliced


@pytest.mark.parametrize("dtype,worst,mean", [(F32, 2e-4, 2e-5),
                                              (BF16, None, 0.05)])
def test_the_model_equals_the_reference_through_both_kernels(wide, dtype,
                                                             worst, mean):
    """8 records = 240 tokens, 15 strips of 16: a full window, a short
    history and a single record."""
    ref, config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(0)
    filled = np.asarray([8, 3, 1], np.int32)
    hist = np.zeros((3, 8, 30), np.float32)
    for i, k in enumerate(filled):
        hist[i, 8 - k:] = rows[rng.integers(0, len(rows), k)]
    want, want_choice = ref.forward(params, config, hist, filled)
    assert _holds_kernel(_program(cfg, dtype), params, hist, filled)
    with jax.default_matmul_precision("highest"):
        _, aux = hm.apply_serving(params, hist, filled, cfg, dtype)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    assert gap.mean() < mean
    assert float(aux["ssm_log_decay_min"]) < 0
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
