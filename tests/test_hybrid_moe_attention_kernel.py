"""``hybrid_moe``'s causal attention behind ``_causal_attention``: the Pallas
kernel (ops/causal_attention.py, interpreted on the CPU) against the plain
path, and which shapes select which. The small presets of
``tests/benchmark/*_small_config.json`` have heads of 16 and never hold the
kernel, so here each model that attends through ``_causal_attention`` gets
one tile-wide preset (``mistral4``-like: 2 heads of 64 + 64 against values
of 128; ``zaya1``-like: 2 groups x 2 queries x 128; ``xing4``- and
``ling3``-like: 2 heads of 128 + 64 = 192, a tile and a half, against
values of 128), the small preset with
its head widths replaced: the kernel at every real position for plain heads
and grouped queries, at a query-key width of one lane tile and of a tile
and a half, with padding on the left, a window of padding alone,
one block and several; causality; what the program's own jaxpr says it
holds, and that the ``pallas_call`` is one and the same at both widths;
the whole models against their float32 references; and the served
path through ``SeqScorer`` with its engagement counters."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import (cca_moe_f32, hybrid_moe_f32, mhc_moe_f32,
                                 mla_moe_f32, table)
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import causal_attention as ca
from ccfd_tpu.ops import kernels, seq_attention
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
COLS = 30
# a window of the store's 30-column records is a multiple of 128 tokens
# from 64 records on: the shortest the served path can hold the kernel at
SERVED_LENGTH = 64

# a tile and a half in q and k (128 + 64 rotary) against values of 128: the
# MLA of ``xing4`` (every layer: one dense, one with experts) and of
# ``ling3`` (one layer in six: a KDA layer beside it)
WIDE_192 = {"num_attention_heads": 2, "num_key_value_heads": 2,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "num_hidden_layers": 2}
TILE_WIDE = {
    "mistral4": (mla_moe_f32, "mistral4_small_config.json", {
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "qk_head_dim": 128,
        "v_head_dim": 128, "head_dim": 128,
        "num_hidden_layers": 2, "layers_kept": [0, 1]}),
    "zaya1": (cca_moe_f32, "zaya1_small_config.json", {
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
        "num_hidden_layers": 2, "layers_kept": [0, 1],
        "layer_types": ["hybrid", "hybrid"]}),
    "xing4": (mhc_moe_f32, "xing4_small_config.json",
              {**WIDE_192, "layers_kept": [1, 2]}),
    "ling3": (hybrid_moe_f32, "ling3_small_config.json",
              {**WIDE_192, "head_dim": 128, "layers_kept": [2, 5]}),
}
WIDTHS = pytest.mark.parametrize("width", [128, 192],
                                 ids=["tile", "tile_and_a_half"])


@pytest.fixture(scope="module", params=sorted(TILE_WIDE))
def model(request):
    """(reference module, configuration, parameters, settings) of one
    tile-wide preset."""
    ref, file, wide = TILE_WIDE[request.param]
    with open(os.path.join(ROOT, "tests", "benchmark", file)) as f:
        config = {**json.load(f), **wide}
    return ref, config, ref.make_params(config), hm.HybridConfig.from_dict(
        config)


def _operands(grouped: bool, t: int, pads, dtype, seed=0, width=128,
              v_width=128):
    """q, k, v in ``_causal_attention``'s layout and ``real`` (B, T) with
    ``pads[i]`` tokens of padding on the left of row i."""
    rng = np.random.default_rng(seed)
    b = len(pads)
    q_shape = (b, t, 2, 2, width) if grouped else (b, t, 2, width)
    q = jnp.asarray(rng.normal(size=q_shape), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, 2, width)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, 2, v_width)), dtype)
    real = jnp.asarray(np.arange(t)[None, :] >= np.asarray(pads)[:, None])
    return q, k, v, real


def _by_head(x):
    b, t = x.shape[:2]
    return x.reshape(b, t, -1, x.shape[-1]).transpose(0, 2, 1, 3)


def _kernel(q, k, v, real, scale, dtype, side=None):
    """The kernel on ``_causal_attention``'s layout, at a block of its own
    choice or the test's."""
    out = ca.fused_causal_attention(_by_head(q), _by_head(k), _by_head(v),
                                    real, scale, jnp.dtype(dtype), side=side)
    return out.transpose(0, 2, 1, 3).reshape(q.shape[:-1] + v.shape[-1:])


def _holds_kernel(fn, *shapes) -> bool:
    return kernels.held_by(fn, *shapes, names=(ca.KERNEL,))


# -- the kernel against the plain path ---------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(F32, 3e-6), (BF16, 0.04)])
@pytest.mark.parametrize("t,side", [(256, None), (256, 128), (384, 128)],
                         ids=["one_block", "two_blocks", "three_blocks"])
@pytest.mark.parametrize("grouped", [False, True],
                         ids=["plain_heads", "grouped_queries"])
@WIDTHS
def test_the_kernel_equals_the_plain_path_at_every_real_position(
        width, grouped, t, side, dtype, tol):
    """Rows with no padding, with padding that ends inside the first block
    and inside a later one, and a row of padding alone (finite, read by
    nobody); q and k one lane tile wide, and a tile and a half against
    values of one."""
    pads = (0, 37, 130, t)
    q, k, v, real = _operands(grouped, t, pads, dtype, width=width)
    scale = 1.0 / math.sqrt(width)
    with jax.default_matmul_precision("highest"):
        want = hm._plain_causal_attention(q, k, v, real, scale, dtype)
        got = _kernel(q, k, v, real, scale, dtype, side)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    at = np.asarray(real)
    assert np.abs(got - want)[at].max() < tol


def test_values_wider_than_the_keys_come_out_at_their_width():
    q, k, v, real = _operands(False, 256, (0, 5), F32, v_width=256)
    want = hm._plain_causal_attention(q, k, v, real, 0.1, F32)
    got = hm._causal_attention(q, k, v, real, 0.1, F32)
    assert got.shape == (2, 256, 2, 256)
    assert np.abs(np.asarray(got) - np.asarray(want))[
        np.asarray(real)].max() < 3e-6


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["plain_heads", "grouped_queries"])
@WIDTHS
def test_a_later_token_moves_no_earlier_output(width, grouped):
    """Across a block's edge too: token 200 of 256 in blocks of 128."""
    q, k, v, real = _operands(grouped, 256, (0, 20), BF16, width=width)
    before = _kernel(q, k, v, real, 0.1, BF16, 128)
    after = _kernel(q.at[:, 200].add(1.0), k.at[:, 200].add(-2.0),
                    v.at[:, 200].add(3.0), real, 0.1, BF16, 128)
    before, after = np.asarray(before, np.float32), np.asarray(after,
                                                               np.float32)
    assert np.array_equal(before[:, :200], after[:, :200])
    assert not np.array_equal(before[:, 200:], after[:, 200:])


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["plain_heads", "grouped_queries"])
@WIDTHS
def test_the_selection_runs_the_kernel_where_it_fits(width, grouped):
    """``_causal_attention`` itself, at a window of two of its own blocks
    (768 = 2 x 384)."""
    q, k, v, real = _operands(grouped, 768, (0, 400), BF16, seed=2,
                              width=width)
    assert ca.block_for(768, width, 128, 2) == 384
    want = hm._plain_causal_attention(q, k, v, real, 0.09, BF16)
    got = jax.jit(hm._causal_attention, static_argnums=(4, 5))(
        q, k, v, real, 0.09, BF16)
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))[
        np.asarray(real)].max() < 0.04


# -- which shapes select which -------------------------------------------------------

def _shape(*dims, dtype=BF16):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("q,k,v,kernel", [
    # the two real configurations at a dispatch of 8 windows of 1,920
    ((8, 1920, 32, 128), (8, 1920, 32, 128), (8, 1920, 32, 128), True),
    ((8, 1920, 2, 4, 128), (8, 1920, 2, 128), (8, 1920, 2, 128), True),
    # the tile-wide presets
    ((2, 256, 2, 128), (2, 256, 2, 128), (2, 256, 2, 128), True),
    ((2, 256, 2, 2, 128), (2, 256, 2, 128), (2, 256, 2, 128), True),
    # ling3's MLA (one layer in six) and xing4's (every layer): 128 + 64
    # wide in q and k, a tile and a half, against values of 128
    ((8, 1920, 32, 192), (8, 1920, 32, 192), (8, 1920, 32, 128), True),
    ((8, 1920, 32, 192), (8, 1920, 32, 192), (8, 1920, 32, 128), True),
    ((2, 256, 2, 192), (2, 256, 2, 192), (2, 256, 2, 128), True),
    ((2, 256, 2, 2, 320), (2, 256, 2, 320), (2, 256, 2, 128), True),
    # q and k under one tile, or ending in no half tile
    ((2, 256, 2, 64), (2, 256, 2, 64), (2, 256, 2, 128), False),
    ((2, 256, 2, 160), (2, 256, 2, 160), (2, 256, 2, 128), False),
    # the small presets: heads of 16
    ((3, 240, 4, 16), (3, 240, 4, 16), (3, 240, 4, 16), False),
    ((3, 256, 2, 4, 16), (3, 256, 2, 16), (3, 256, 2, 16), False),
    # a window that is no multiple of 128 tokens
    ((2, 200, 2, 128), (2, 200, 2, 128), (2, 200, 2, 128), False),
    # values that fill no lane tile
    ((2, 256, 2, 128), (2, 256, 2, 128), (2, 256, 2, 64), False),
    ((2, 256, 2, 192), (2, 256, 2, 192), (2, 256, 2, 64), False),
    ((2, 256, 2, 192), (2, 256, 2, 192), (2, 256, 2, 192), False),
], ids=["mistral4", "zaya1", "wide_mla", "wide_cca", "ling3_192", "xing4_192",
        "wide_192", "grouped_320", "qk_64", "qk_160", "heads_16",
        "grouped_16", "t_200", "v_64", "qk_192_v_64", "qk_192_v_192"])
def test_the_programs_jaxpr_says_which_path_was_taken(q, k, v, kernel):
    def attend(q, k, v, real):
        return hm._causal_attention(q, k, v, real, 0.1, BF16)

    real = _shape(q[0], q[1], dtype=jnp.bool_)
    assert _holds_kernel(attend, _shape(*q), _shape(*k), _shape(*v),
                         real) is kernel
    # and the scorer's reading (``ops/kernels.py::held``) counts it as
    # attention's, and none of it as ``seq``'s kernel
    assert kernels.held(attend, _shape(*q), _shape(*k), _shape(*v),
                        real)["attn_kernel"] == kernel
    assert not kernels.held_by(
        attend, _shape(*q), _shape(*k), _shape(*v), real,
        names=(seq_attention.KERNEL,))


@pytest.mark.parametrize("tokens,width,itemsize,side", [
    (1920, 128, 2, 640), (256, 128, 2, 256), (384, 128, 4, 384),
    (768, 128, 2, 384), (1024, 128, 2, 512), (128, 256, 2, 128),
    (200, 128, 2, None), (1920, 192, 2, 640),
    (16384, 128, 2, None),  # k and v of a row no longer fit beside the rest
])
def test_the_block_comes_from_the_window(tokens, width, itemsize, side):
    assert ca.block_for(tokens, width, width, itemsize) == side


@pytest.mark.parametrize("tokens,width,itemsize,side", [
    (1920, 192, 2, 640), (1920, 192, 4, 640), (768, 192, 2, 384),
    (256, 320, 4, 256),
    # a row of k 192 wide is held as 256 lanes: 2 x 3,072 x (256 + 128) x 4
    # bytes pass the budget that 2 x 3,072 x (192 + 128) x 4 would meet
    (3072, 192, 4, None), (3072, 128, 4, 512),
])
def test_a_half_tile_of_keys_is_budgeted_as_a_whole_one(tokens, width,
                                                       itemsize, side):
    assert ca.block_for(tokens, width, 128, itemsize) == side


@pytest.mark.parametrize("width,v_width,fits", [
    (128, 128, True), (192, 128, True), (256, 128, True), (320, 128, True),
    (192, 256, True), (64, 128, False), (96, 128, False), (160, 128, False),
    (200, 128, False), (192, 64, False), (192, 192, False), (16, 16, False),
])
def test_which_widths_the_kernel_takes(width, v_width, fits):
    """q and k: whole lane tiles, or whole tiles and a half, from one tile
    up; the values (the output's lanes): whole tiles, as before."""
    q, k, v = (2, 4, 256, width), (2, 2, 256, width), (2, 2, 256, v_width)
    for dtype in (BF16, F32):
        assert ca.kernel_fits(q, k, v, dtype) is fits
    assert not ca.kernel_fits(q, k, v, jnp.float16)


# -- one pallas_call at both widths ------------------------------------------------

@functools.cache
def _pallas_call(width, dtype=BF16):
    """The kernel's equation in the jaxpr of a dispatch of 8 windows of
    1,920 tokens, 32 heads."""
    jaxpr = jax.make_jaxpr(lambda q, k, v, real: ca.fused_causal_attention(
        q, k, v, real, 0.09, jnp.dtype(dtype)))(
        _shape(8, 32, 1920, width, dtype=dtype),
        _shape(8, 32, 1920, width, dtype=dtype),
        _shape(8, 32, 1920, 128, dtype=dtype),
        _shape(8, 1920, dtype=jnp.bool_))
    calls = [e for e in kernels.equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


def _body(call) -> list:
    """The kernel body's primitives in order, the loop's body inside."""
    return [e.primitive.name
            for e in kernels.equations(call.params["jaxpr"])]


@WIDTHS
def test_the_pallas_call_is_pinned_at_both_widths(width):
    """What the accepted 128-wide programs hold, spelled out: the name, the
    grid, the operands and their blocks, the cost the scheduler is told.
    The tile-and-a-half case stands beside it with the query-key width as
    its one difference, and the two bodies are one list of primitives, so
    an edit that moves one and not the other shows here."""
    call = _pallas_call(width)
    grid = call.params["grid_mapping"]
    assert call.params["name"] == ca.KERNEL == "causal_attention"
    assert grid.grid == (8, 32, 3)
    assert (grid.num_inputs, grid.num_outputs, len(call.invars)) == (4, 1, 4)
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in grid.block_mappings] == [
        (1, 1, 640, width), (1, 1, 1920, width), (1, 1, 1920, 128),
        (1, 3, 640), (1, 1, 640, 128)]
    assert [v.aval.shape for v in call.invars] == [
        (8, 32, 1920, width), (8, 32, 1920, width), (8, 32, 1920, 128),
        (8, 3, 640)]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [
        ((8, 32, 1920, 128), jnp.dtype(BF16))]
    assert not call.params["input_output_aliases"]
    visited = 6 * 640 * 640  # six of nine blocks
    cost = call.params["cost_estimate"]
    assert cost.flops == 2 * 8 * 32 * visited * (width + 128)
    assert cost.transcendentals == 8 * 32 * visited
    assert cost.bytes_accessed == 8 * 1920 * 32 * 2 * (2 * width + 2 * 128)
    body = _body(call)
    assert body == _body(_pallas_call(128))
    assert body.count("dot_general") == 4 and body.count("exp") == 4
    assert body.count("while") == 1


# -- the real shapes, compiled for the chip that is described and not attached -------

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


@pytest.mark.parametrize("heads,groups,width", [
    (32, 32, 128), (8, 2, 128), (32, 32, 192)],
    ids=["mistral4", "zaya1", "xing4"])
def test_mosaic_compiles_the_kernel_at_a_dispatch_of_the_real_models(
        one_chip, as_on_the_chip, heads, groups, width):
    """8 windows of 1,920 tokens, heads of 128 (``xing4``, and ``ling3``'s
    one such layer: 192 in q and k), bfloat16, blocks of 640: what the
    interpreter cannot refuse (tiling, VMEM, a block that ends in half a
    lane tile) the chip's compiler can, and nothing runs."""
    def shape(*dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert ca.block_for(1920, width, 128, 2) == 640
    compiled = jax.jit(
        lambda q, k, v, real: ca.fused_causal_attention.__wrapped__(
            q, k, v, real, 0.09, jnp.dtype(BF16))).lower(
        shape(8, heads, 1920, width), shape(8, groups, 1920, width),
        shape(8, groups, 1920, 128), shape(8, 1920, dtype=jnp.bool_)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the whole models at the tile-wide presets ---------------------------------------

def _windows(rows, filled, length, seed=0):
    rng = np.random.default_rng(seed)
    hist = np.zeros((len(filled), length, rows.shape[1]), np.float32)
    for i, k in enumerate(filled):
        hist[i, length - k:] = rows[rng.integers(0, len(rows), k)]
    return hist, np.asarray(filled, np.int32)


@pytest.fixture(scope="module")
def rows():
    return table.surrogate_rows(4096, 7)[0]


def test_the_mixer_holds_the_kernel_and_equals_the_reference(model):
    """``mla`` / ``cca`` alone at 256 tokens, one row padded on the left,
    in float32: the kernel against the reference's full masked softmax
    (``ling3``: the preset's second layer, its first is KDA)."""
    ref, config, params, cfg = model
    at, kind = next((i, mixer) for i, (mixer, _) in enumerate(cfg.layers)
                    if mixer in ("mla", "cca"))
    p = (ref.layer_of(params, at) if hasattr(ref, "layer_of")
         else params["layers"][at])["mixer"]
    rng = np.random.default_rng(3)
    t, pads = 256, np.asarray([0, 37])
    x = jnp.asarray(rng.normal(size=(2, t, config["hidden_size"])), F32)
    real = jnp.asarray(np.arange(t)[None, :] >= pads[:, None])
    position = jnp.asarray(np.maximum(np.arange(t)[None, :] - pads[:, None],
                                      0))

    def mixer(p, x, real, position):
        return hm.MIXERS[kind](p, x, real, position, cfg, F32)[0]

    assert _holds_kernel(mixer, p, x, real, position)
    with jax.default_matmul_precision("highest"):
        if hasattr(ref, kind):
            want = getattr(ref, kind)(p, x, real, position, config)
        else:  # ``mhc_moe_f32`` calls ``mla_moe_f32``'s with its own reading
            want = mla_moe_f32._mla(p, x, real, position,
                                    **ref.mla_dims(config))
        got = mixer(p, x, real, position)
    assert np.abs(np.asarray(got) - np.asarray(want))[
        np.asarray(real)].max() < 2e-4


@pytest.mark.parametrize("dtype,worst,mean", [(F32, 5e-4, 5e-5),
                                              (BF16, None, 0.05)])
def test_the_model_equals_the_reference_through_the_kernel(
        model, rows, dtype, worst, mean):
    """64 records = 1,920 tokens: three blocks of 640, a full window, a
    short history and a single record."""
    ref, config, params, cfg = model
    hist, filled = _windows(rows, [64, 9, 1], SERVED_LENGTH)
    want, want_counts = ref.forward(params, config, hist, filled)
    assert _holds_kernel(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg=cfg,
                                         compute_dtype=dtype),
        params, hist, filled)
    with jax.default_matmul_precision("highest"):
        _, aux = hm.apply_serving(params, hist, filled, cfg=cfg,
                                  compute_dtype=dtype)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    assert gap.mean() < mean
    if worst is not None:
        assert gap.max() < worst
        # each row's chosen pairs by layer and expert; ``hybrid_moe_f32``
        # (``ling3``) counts the served pairs a layer
        got_counts = (aux["row_choice"] if want_counts.ndim == 3
                      else aux["pairs"].sum(1))
        assert np.array_equal(np.asarray(got_counts), want_counts)


def test_a_keyed_stream_through_the_scorer_attends_with_the_kernel(
        model, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    at a window the kernel tiles: record for record the float32
    reference's verdict, every dispatch counted as one of the kernel's,
    and the grid says so (the ``seq.enqueue`` phase's ``attn_kernel``
    is the flag the counter is counted by)."""
    from ccfd_tpu.metrics.prom import Registry

    ref, config, params, cfg = model
    reg = Registry()
    scorer = SeqScorer(params, length=SERVED_LENGTH, batch_sizes=(4,),
                       compute_dtype="float32", registry=reg,
                       family="hybrid_moe", family_config=cfg)
    rng = np.random.default_rng(11)
    customers = rng.choice([3, 5, 8], size=10, p=[0.6, 0.3, 0.1])
    sent = rows[rng.integers(0, len(rows), len(customers))]
    served = np.concatenate([
        scorer.score(sent[lo:lo + 4], [int(c) for c in customers[lo:lo + 4]])
        for lo in range(0, len(customers), 4)])
    hist, filled = ref.histories(
        customers, np.arange(len(customers)), sent,
        np.arange(len(customers)), SERVED_LENGTH,
        np.full((9, 1), -1, np.int64))
    logits, _ = ref.forward(params, config, hist, filled)
    want = 1.0 / (1.0 + np.exp(-np.asarray(ref.verdict_logit(
        np.asarray(logits), config), np.float64)))
    assert np.allclose(served, want, rtol=2e-3, atol=1e-6)
    grid = scorer.executable_grid()["grid"]
    assert [(g["b_bucket"], g["attn_kernel"], g["flat_wire"]) for g in grid
            ] == [(4, True, False)]
    dispatches = reg.counter("seq_bucket_dispatch_total").total()
    assert dispatches >= 3
    assert reg.counter(
        "seq_attention_kernel_dispatch_total").total() == dispatches
