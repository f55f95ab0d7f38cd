"""``hybrid_moe``'s CCA latent behind ``cca``: the Pallas kernel
(ops/cca_conv.py, interpreted on the CPU) against the plain chain through
XLA (``_cca_latent``), and which shapes select which. The small preset
(``tests/benchmark/zaya1_small_config.json``: heads of 16) never holds the
kernel, so here it gets lane-wide presets (heads of 128): both serving
dtypes, H : G of 4 : 1, 2 : 1 and 1 : 1, padding on the left so that the
masks matter, a first real token inside a tile and on a tile's first row,
the lag across a tile's edge, what the programs' own jaxprs say they hold,
the ``pallas_call`` at both served batch sizes, Mosaic's own word on them,
and the whole model through the kernel against the plain reference."""

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
from ccfd_tpu.ops import causal_attention
from ccfd_tpu.ops import cca_conv as cc
from ccfd_tpu.ops import kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
# zaya1 as served: 1,920 tokens, hidden 2,048, 8 : 2 heads of 128, two taps
# each; the cell's batches of 4 and 8
HIDDEN, HEADS, GROUPS, HEAD = 2048, 8, 2, 128
LANE_WIDE = {"head_dim": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "hidden_size": 128}


def _small(name="zaya1"):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


def _mixer(h=4, g=2, d=128, hidden=96, taps=(2, 2), seed=0):
    """A CCA mixer's parameters as ``cca_moe_f32.make_params`` lays them
    out, at widths of the test's choosing."""
    rng = np.random.default_rng(seed)
    n = h + g
    p = {"wq": rng.normal(size=(hidden, h * d)) / np.sqrt(hidden),
         "wk": rng.normal(size=(hidden, g * d)) / np.sqrt(hidden),
         "wv": rng.normal(size=(hidden, g * d)) / np.sqrt(hidden),
         "conv0": rng.normal(size=(taps[0], n * d)) * 0.3,
         "conv0_b": rng.normal(size=(n * d,)) * 0.02,
         "conv1": rng.normal(size=(taps[1], n, d, d)) / np.sqrt(d),
         "conv1_b": rng.normal(size=(n * d,)) * 0.02,
         "tau": 1 + 0.1 * rng.normal(size=(g,))}
    return ({k: jnp.asarray(v, F32) for k, v in p.items()},
            hm.Cca(h, g, d, d // 2, 5e6))


def _window(t, pads, hidden=96, seed=1):
    """z, real and position of rows with ``pads[i]`` padding tokens on the
    left of row i; z holds anything but zeros on the padding."""
    rng = np.random.default_rng(seed)
    pads = np.asarray(pads)[:, None]
    return (jnp.asarray(rng.normal(size=(len(pads), t, hidden)), F32),
            jnp.asarray(np.arange(t)[None] >= pads),
            jnp.asarray(np.maximum(np.arange(t)[None] - pads, 0)))


def _both(p, s, z, real, position, dtype):
    with jax.default_matmul_precision("highest"):
        want = hm._cca_latent(p, z, real, position, s, dtype)
        got = hm._cca_latent_kernel(p, z, real, position, s, dtype)
    assert [(a.shape, a.dtype) for a in got] == [
        (b.shape, b.dtype) for b in want]
    return ([np.asarray(a, np.float32) for a in got],
            [np.asarray(b, np.float32) for b in want])


# -- the kernel against the plain chain ---------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("h,g", [(4, 1), (4, 2), (2, 2)],
                         ids=["four_to_one", "two_to_one", "one_to_one"])
@pytest.mark.parametrize("t,pads", [
    (32, (0, 5)), (64, (0, 37)), (240, (0, 16)), (240, (41, 239)),
    (768, (0, 384))], ids=[
        "one_strip", "inside_a_tile", "on_a_tiles_first_row",
        "fifteen_tiles", "two_tiles_of_384"])
@pytest.mark.parametrize("tensor", ["q", "k", "v"])
def test_the_kernel_equals_the_plain_chain(t, pads, h, g, dtype, tensor):
    """Real tokens and padding alike (a padding token's q, k and v are its
    own unmasked taps', as in the plain chain). float32: to the rounding
    of sums in another order; bfloat16: the chains agree before the
    casts, so all but a few values are equal; those few sit on a rounding's
    edge (the output's: one step; ``c0``'s: a step of one of the 128
    values a head's product sums)."""
    got, want = _latent(t, pads, h, g, jnp.dtype(dtype).name)
    a, b = ({"q": 0, "k": 1, "v": 2}[tensor] for _ in "ab")
    a, b = got[a], want[b]
    if dtype == F32:
        assert np.allclose(a, b, atol=3e-6, rtol=3e-6)
        return
    gap = np.abs(a - b)
    assert (gap > 0).mean() < 0.01
    assert (gap <= np.abs(b) * 2.0 ** -7 + 0.004).all()


@functools.cache
def _latent(t, pads, h, g, dtype):
    p, s = _mixer(h, g)
    return _both(p, s, *_window(t, pads), jnp.dtype(dtype))


def test_padding_is_masked_before_the_lagged_taps_and_after_no_other():
    """What the padding holds moves no real token's q, k or v, bit for
    bit: the first real token's lagged taps and shifted value read zeros.
    The padding's own outputs follow what it holds (the current token's
    taps see it unmasked, as in the plain chain)."""
    p, s = _mixer()
    z, real, position = _window(64, (0, 37))
    other = z.at[1, :37].set(7.0)
    keep = np.asarray(real)
    for dtype in (F32, BF16):
        base = hm._cca_latent_kernel(p, z, real, position, s, dtype)
        moved = hm._cca_latent_kernel(p, other, real, position, s, dtype)
        for a, b in zip(base, moved, strict=True):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.array_equal(a[keep], b[keep])
            assert not np.array_equal(a[~keep], b[~keep])


def test_a_row_of_padding_alone_and_a_first_token_read_zeros():
    """Token 0 of a full row is its own taps alone: equal to the same
    token at the head of a row that had padding before it."""
    p, s = _mixer()
    z, real, position = _window(64, (0, 0))
    shifted = jnp.concatenate([jnp.full((2, 16, z.shape[2]), 3.0), z], 1)
    late = jnp.asarray(np.arange(80)[None] >= np.array([[16], [16]]))
    where = jnp.asarray(np.maximum(np.arange(80)[None] - 16, 0)).repeat(2, 0)
    for dtype in (F32, BF16):
        a = hm._cca_latent_kernel(p, z, real, position, s, dtype)
        b = hm._cca_latent_kernel(p, shifted, late, where, s, dtype)
        for x, y in zip(a, b, strict=True):
            assert np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(y, np.float32)[:, 16:])


def _entry(q_lat, k_lat, v, keep, p, s, dtype=F32):
    b, t, _ = q_lat.shape
    d = s.head_dim
    lanes = jnp.ones((b, t, d), F32), jnp.zeros((b, t, d), F32)  # no turn
    return cc.cca_conv(q_lat, k_lat, v, keep, *lanes, p["conv0"],
                       p["conv0_b"], p["conv1"], p["conv1_b"], p["tau"],
                       rot=s.rotary_dim, dtype=jnp.dtype(dtype), eps=hm.L2_EPS)


@pytest.mark.parametrize("t,edge", [(64, 32), (240, 16), (768, 384)],
                         ids=["inside_a_tile", "a_tiles_of_16",
                              "a_tiles_of_384"])
def test_an_edge_hands_on_what_the_taps_reach_back_to(t, edge):
    """A change to the last token before a tile's edge (and to one inside
    a tile) moves q and k there and at the two tokens after it (the
    depthwise tap, then the grouped one on its output) and v there and at
    the next (the shifted heads), and no other token."""
    p, s = _mixer(4, 2)
    rng = np.random.default_rng(2)
    q_lat, k_lat, v = (jnp.asarray(rng.normal(size=(1, t, w)), F32)
                       for w in (512, 256, 256))
    keep = jnp.ones((1, t, 1), F32)
    base = _entry(q_lat, k_lat, v, keep, p, s)
    at = edge - 1
    moved = _entry(q_lat.at[:, at].add(1.0), k_lat.at[:, at].add(1.0),
                   v.at[:, at].add(1.0), keep, p, s)
    for a, b, reach in zip(base, moved, (3, 3, 2), strict=True):
        changed = np.abs(np.asarray(a) - np.asarray(b)).max(axis=(0, 1, 3))
        assert (changed[at:at + reach] > 0).all()
        assert not changed[:at].any() and not changed[at + reach:].any()


@pytest.mark.parametrize("taps", [(1, 1), (2, 2), (3, 2), (2, 4), (4, 8)],
                         ids=lambda t: f"{t[0]}_and_{t[1]}")
def test_any_number_of_taps_under_a_sublane_tile(taps):
    p, s = _mixer(2, 2, taps=taps)
    got, want = _both(p, s, *_window(48, (0, 21)), F32)
    for a, b in zip(got, want, strict=True):
        assert np.allclose(a, b, atol=3e-6, rtol=3e-6)


def test_the_kernel_leaves_by_head_and_the_mixer_hands_on_token_major():
    """The entry's outputs are (B, heads, T, D), what the attention kernel
    reads; ``cca`` turns them back to the plain chain's (B, T, G, per, D)
    and (B, T, G, D): a transpose that cancels against the attention
    seam's own where both kernels run."""
    p, s = _mixer(4, 2)
    z, real, position = _window(64, (0, 9))
    q, k, v = _entry(hm._mm(z, p["wq"], F32), hm._mm(z, p["wk"], F32),
                     hm._mm(z, p["wv"], F32), real[..., None].astype(F32),
                     p, s, BF16)
    assert [(x.shape, x.dtype) for x in (q, k, v)] == [
        ((2, 4, 64, 128), BF16), ((2, 2, 64, 128), BF16),
        ((2, 2, 64, 128), BF16)]
    q, k, v = hm._cca_latent_kernel(p, z, real, position, s, BF16)
    assert (q.shape, k.shape, v.shape) == (
        (2, 64, 2, 2, 128), (2, 64, 2, 128), (2, 64, 2, 128))


# -- which shapes select which -------------------------------------------------------

def _fits(batch=8, t=1920, hidden=HIDDEN, h=HEADS, g=GROUPS, d=HEAD,
          taps=(2, 2), dtype=BF16, sharding=None):
    def shape(*dims, dt=F32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    return cc.kernel_fits(
        shape(batch, t, hidden), shape(hidden, h * d), shape(hidden, g * d),
        shape(taps[0], (h + g) * d), shape(taps[1], h + g, d, d), dtype)


@pytest.mark.parametrize("case,fits", [
    ({}, True), ({"batch": 4}, True), ({"dtype": F32}, True),
    ({"t": 240, "h": 4, "hidden": 128}, True),  # the lane-wide preset
    ({"h": 2}, True), ({"g": 1}, True), ({"d": 256}, True),
    ({"taps": (4, 8)}, True),
    ({"d": 16, "hidden": 64, "t": 240}, False),  # the small preset
    ({"d": 192}, False),  # a head and a half
    ({"h": 3}, False),  # query heads that no key-value head divides
    ({"t": 300}, False), ({"t": 1912}, False),  # no whole tiles of 16
    ({"taps": (2, 9)}, False), ({"taps": (9, 2)}, False),
    ({"dtype": jnp.float16}, False),
    ({"h": 64, "g": 64, "dtype": F32}, False),  # no tile fits beside its taps
], ids=["served", "served_batch_4", "served_float32", "lane_wide",
        "one_to_one", "eight_to_one", "heads_of_256", "eight_taps",
        "small_preset", "head_of_192", "three_to_two", "window_300",
        "window_1912", "nine_grouped_taps", "nine_depthwise_taps", "float16",
        "over_vmem"])
def test_which_shapes_the_kernel_takes(case, fits):
    assert _fits(**case) is fits


def test_a_mesh_keeps_the_plain_chain():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    assert _fits()
    assert not _fits(sharding=NamedSharding(mesh, PartitionSpec()))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert not _fits()


@pytest.mark.parametrize("tokens,itemsize,tile", [
    (1920, 2, 384), (1920, 4, 128), (240, 4, 16), (768, 2, 384),
    (256, 2, 256), (64, 4, 64), (48, 2, 16), (300, 2, None), (8, 2, None)])
def test_the_tile_comes_from_the_window_and_what_fits(tokens, itemsize, tile):
    """At zaya1's widths: bfloat16 holds five tiles of 384 a window,
    float32 (``c0`` twice as wide in VMEM) fifteen of 128."""
    dtype = {2: BF16, 4: F32}[itemsize]
    assert cc.tile_for(tokens, 1024, 256, (2, 1280), (2, 10, 128, 128),
                       dtype) == tile
    if tile is not None:
        assert cc._vmem_bytes(tile, 1024, 256, 128, 2, itemsize) <= (
            cc.VMEM_BYTES) < 16 << 20


def _holds_kernel(fn, *args, names=(cc.KERNEL,)) -> bool:
    return kernels.held_by(fn, *args, names=names)


def _shape(*dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("t,d,kernel", [(240, 128, True), (1920, 128, True),
                                        (300, 128, False), (240, 16, False)],
                         ids=["lane_wide", "a_served_window", "window_300",
                              "heads_of_16"])
def test_the_mixers_jaxpr_says_which_path_was_taken(t, d, kernel):
    p, s = _mixer(4, 2, d)
    p["wo"] = jnp.zeros((4 * d, 96), F32)
    cfg = hm.HybridConfig.from_dict(dict(
        _small(), head_dim=d, num_attention_heads=4, hidden_size=96))

    def mixer(p, z, real, position):
        return hm.cca(p, z, real, position, cfg, F32)

    shapes = jax.tree.map(lambda a: _shape(*a.shape), p)
    assert _holds_kernel(mixer, shapes, _shape(2, t, 96),
                         _shape(2, t, dtype=bool),
                         _shape(2, t, dtype=jnp.int32)) is kernel


def test_the_selection_gives_one_mixer_on_either_path(monkeypatch):
    """``cca`` itself at a window the kernel takes, against the same call
    with the kernel refused: the attention and the out-projection after
    either latent."""
    p, s = _mixer(4, 2)
    p["wo"] = jnp.asarray(np.random.default_rng(4).normal(
        size=(512, 96)) / 22, F32)
    cfg = hm.HybridConfig.from_dict(dict(
        _small(), head_dim=128, num_attention_heads=4, hidden_size=96))
    z, real, position = _window(240, (0, 41))
    with jax.default_matmul_precision("highest"):
        got = hm.cca(p, z, real, position, cfg, F32)
        monkeypatch.setattr(cc, "kernel_fits", lambda *a: False)
        want = hm.cca(p, z, real, position, cfg, F32)
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-5, rtol=2e-5)


# -- the pallas_call at the served shapes --------------------------------------------

def _served(batch, dtype=BF16):
    n = HEADS + GROUPS
    return (_shape(batch, 1920, HEADS * HEAD),
            _shape(batch, 1920, GROUPS * HEAD),
            _shape(batch, 1920, GROUPS * HEAD), _shape(batch, 1920, 1),
            _shape(batch, 1920, HEAD), _shape(batch, 1920, HEAD),
            _shape(2, n * HEAD), _shape(n * HEAD),
            _shape(2, n, HEAD, HEAD, dtype=dtype), _shape(n * HEAD),
            _shape(GROUPS))


def _call(*a, dtype=BF16):
    return cc.cca_conv.__wrapped__(*a, rot=64, dtype=jnp.dtype(dtype),
                                   eps=hm.L2_EPS)


@pytest.mark.parametrize("batch", [4, 8])
def test_the_pallas_call_is_pinned_at_the_served_shapes(batch):
    """One call under the kernel's name: the three projections whole (no
    slice of them is an operand), a block a row's 384 tokens for every
    head's lanes, the mask a (row, tile)'s, the turn's lanes a head wide;
    q, k and v leave by head in the serving dtype. The body is
    straight-line code over the tile, ten heads one after another: a few
    hundred equations whatever the window, for the chip's host to trace
    once an executable."""
    (call,) = [e for e in kernels.equations(
        jax.make_jaxpr(_call)(*_served(batch)).jaxpr)
        if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert call.params["name"] == cc.KERNEL == "cca_conv"
    assert mapping.grid == (batch, 5)
    assert (mapping.num_inputs, mapping.num_outputs) == (11, 3)
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in mapping.block_mappings] == [
        (1, 384, 1024), (1, 384, 256), (1, 384, 256), (1, 384, 1),
        (1, 384, 128), (1, 384, 128), (2, 1280), (1, 1280),
        (2, 10, 128, 128), (1, 1280), (1, 256),
        (1, 8, 384, 128), (1, 2, 384, 128), (1, 2, 384, 128)]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
        ((batch, 8, 1920, 128), BF16), ((batch, 2, 1920, 128), BF16),
        ((batch, 2, 1920, 128), BF16)]
    found = [tuple(int(i) for i in jax.core.eval_jaxpr(
        m.index_map_jaxpr.jaxpr, m.index_map_jaxpr.consts, 3, 2))
        for m in mapping.block_mappings]
    assert found == [(3, 2, 0)] * 6 + [(0, 0), (0, 0), (0, 0, 0, 0), (0, 0),
                                       (0, 0)] + [(3, 0, 2, 0)] * 3
    body = [e.primitive.name for e in kernels.equations(call.params["jaxpr"])]
    assert body.count("while") + body.count("scan") == 0
    assert body.count("dot_general") == 20  # a head and tap
    assert len(body) < 400


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


@pytest.mark.parametrize("batch", [4, 8])
def test_mosaic_compiles_the_kernel_at_a_dispatch_of_the_real_model(
        one_chip, as_on_the_chip, batch):
    """What the interpreter cannot refuse (a block off the lane grid, a
    rotation Mosaic has no rule for, VMEM past the compiler's default,
    which this kernel does not raise) the chip's compiler can, and nothing
    runs."""
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _served(batch)]
    # a function of this test's own: a trace of ``_call`` made under the
    # interpreter by another test is not found again
    text = jax.jit(lambda *a: _call(*a)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_the_compiled_mixer_hands_the_kernels_outputs_to_the_attention(
        one_chip, as_on_the_chip, monkeypatch):
    """``cca`` at a dispatch of the real model, compiled whole: the three
    projections' products feed ``cca_conv``, whose three outputs are the
    attention kernel's operands as they stand (the transposes on either
    side of the seam cancel); under ``cca.conv`` no copy, transpose, pad or
    concatenate of a (8, 1,920, .., 128) array is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = jax.eval_shape(lambda: _mixer(HEADS, GROUPS, HEAD, HIDDEN)[0])
    p = {k: jax.ShapeDtypeStruct(
        v.shape, BF16 if k in ("wq", "wk", "wv", "conv1") else F32,
        sharding=one_chip) for k, v in p.items()}
    p["wo"] = jax.ShapeDtypeStruct((HEADS * HEAD, HIDDEN), BF16,
                                   sharding=one_chip)
    cfg = hm.HybridConfig.from_dict(dict(
        _small(), head_dim=HEAD, hidden_size=HIDDEN))

    def shape(*dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = jax.jit(lambda p, z, real, position: hm.cca(
        p, z, real, position, cfg, BF16)).lower(
        p, shape(8, 1920, HIDDEN), shape(8, 1920, dtype=bool),
        shape(8, 1920, dtype=jnp.int32)).compile().as_text()
    lines = text.splitlines()
    (conv,) = [l for l in lines if " custom-call(" in l and "cca_conv" in
               l.split(" = ")[0]]
    (attend,) = [l for l in lines if " custom-call(" in l
                 and causal_attention.KERNEL in l.split(" = ")[0]]
    outputs = [l.split(" = ")[0].strip().lstrip("%") for l in lines
               if " get-tuple-element(" in l
               and conv.split(" = ")[0].strip() in l]
    assert len(outputs) == 3
    operands = attend.split("custom-call(")[1].split(")")[0]
    assert all("%" + name in operands for name in outputs)
    def result(line):  # "bf16[8,1920,1024]" of "%x = bf16[8,1920,1024]{..} copy("
        return line.split(" = ")[-1].split("{")[0].split("[")[-1]

    # but for the mask and the turn's lanes, which are a token's and no head's
    small = ("8,1920]", "8,1920,1]", "8,1920,32]", "8,1920,128]")
    moved = [l for l in lines if "cca.conv" in l and "1920" in result(l)
             and not result(l).endswith(small) and any(
                 f" {kind}(" in l for kind in (
                     "copy", "transpose", "pad", "concatenate"))]
    assert not moved, moved


# -- the whole model ------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide():
    config = {**_small(), **LANE_WIDE}
    return (config, cca_moe_f32.make_params(config),
            hm.HybridConfig.from_dict(config))


def _program(cfg, dtype=F32):
    return lambda p, h, f: hm.apply_serving(p, h, f, cfg, dtype)


def _records(records=8, rows=2):
    return (jax.ShapeDtypeStruct((rows, records, 30), np.float32),
            jax.ShapeDtypeStruct((rows,), np.int32))


def test_the_lane_wide_program_holds_the_kernel(wide):
    config, params, cfg = wide
    held = kernels.held(_program(cfg), params, *_records())
    assert held["cca_kernel"] == 1
    assert held["conv_kernel"] == held["ssd_kernel"] == held["kda_kernel"] == 0
    assert held["gdn_kernel"] == 0


@pytest.mark.parametrize("name,ref", [
    ("zaya1", cca_moe_f32), ("mistral4", mla_moe_f32), ("xing4", mhc_moe_f32),
    ("ling3", hybrid_moe_f32), ("granite4h", ssm_moe_f32),
    ("nemotron3n", ssm_relu2_moe_f32)])
def test_a_small_preset_holds_no_cca_kernel(name, ref):
    """At 8 records and at 64 (1,920 tokens: the cells' window): ``zaya1``'s
    heads of 16 keep the plain chain, the five others have no such mixer."""
    small = _small(name)
    cfg = hm.HybridConfig.from_dict(small)
    shapes = jax.eval_shape(lambda: ref.make_params(small))
    for records in (8, 64):
        assert kernels.held(_program(cfg), shapes, *_records(records))[
            "cca_kernel"] == 0


@pytest.mark.parametrize("dtype,worst,mean", [(F32, 2e-4, 2e-5),
                                              (BF16, None, 0.05)])
def test_the_model_equals_the_reference_through_the_kernel(wide, dtype, worst,
                                                           mean):
    """``test_hybrid_moe_cca.py``'s limits at every position: 8 records =
    240 tokens, fifteen tiles of 16: a full window, a short history and a
    single record."""
    config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(0)
    filled = np.asarray([8, 3, 1], np.int32)
    hist = np.zeros((3, 8, 30), np.float32)
    for i, k in enumerate(filled):
        hist[i, 8 - k:] = rows[rng.integers(0, len(rows), k)]
    want, want_choice = cca_moe_f32.forward(params, config, hist, filled,
                                            every_position=True)
    assert _holds_kernel(
        lambda p, h, f: hm.logits_everywhere(p, h, f, cfg, dtype), params,
        hist, filled)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = np.asarray(cca_moe_f32.shared.real_tokens(
        jnp.asarray(filled), 8, 30))
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)


def test_a_verdict_through_the_kernel_is_the_same_at_every_window(wide):
    """One history of 5 records at windows of 8, 16 and 64 records (tiles
    of 16, 32 and 384 tokens, the first real token inside one): one
    verdict and one routing, with other records where the padding is."""
    config, params, cfg = wide
    rows = table.surrogate_rows(4096, 7)[0]
    rng = np.random.default_rng(9)
    hist = rows[rng.integers(0, len(rows), 5)]
    verdicts, choices = [], []
    for length in (8, 16, 64):
        window = rows[rng.integers(0, len(rows), length)][None].copy()
        window[0, length - 5:] = hist
        with jax.default_matmul_precision("highest"):
            proba, aux = hm.apply_serving(params, window, np.array([5]),
                                          cfg, F32)
        verdicts.append(float(proba[0]))
        choices.append(np.asarray(aux["row_choice"]))
    assert np.allclose(verdicts, verdicts[0], rtol=1e-4, atol=1e-7)
    assert all(np.array_equal(c, choices[0]) for c in choices)
