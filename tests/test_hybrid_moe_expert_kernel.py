"""``hybrid_moe``'s held experts behind ``held_experts``: the grouped-matmul
kernels (ops/grouped_experts.py, interpreted on the CPU) against the plain
tile loop, and which shapes select which. The small presets of
``tests/benchmark/*_small_config.json`` have experts of 64 x 32 and never
hold the kernels (the three models' own test files serve them lane-wide
beside their small presets); here the layer alone: every way a group can
lie on the tiles, what the program's own jaxpr says it holds, where the
tile and the blocks come from, and the chip's compiler at a dispatch of
each real model's experts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import grouped_experts as ge
from ccfd_tpu.ops.kernels import held_by, kernels_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
HIDDEN, WIDTH = 128, 256
TILE = 32  # rows of a tile here: many tiles of few rows
# the three cells' experts: held, hidden, width, routed, a token's experts
CELLS = {"ling3": (128, 2560, 768, 512, 8), "zaya1": (16, 2048, 2048, 17, 1),
         "mistral4": (32, 4096, 2048, 128, 4)}
TOKENS = 8 * 1920  # a dispatch of the benchmark's cells


@pytest.fixture(scope="module")
def base():
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "mistral4_small_config.json")) as f:
        return hm.HybridConfig.from_dict(json.load(f))


def _cfg(base, held_first, held_count, routed, per_token):
    return dataclasses.replace(base, held_first=held_first,
                               held_count=held_count, routed=routed,
                               per_token=per_token)


def _experts(held, dtype, hidden=HIDDEN, width=WIDTH, seed=0):
    rng = np.random.default_rng(seed)
    return {"gate": jnp.asarray(rng.normal(size=(held, hidden, width))
                                / hidden ** 0.5, dtype),
            "up": jnp.asarray(rng.normal(size=(held, hidden, width))
                              / hidden ** 0.5, dtype),
            "down": jnp.asarray(rng.normal(size=(held, width, hidden))
                                / width ** 0.5, dtype)}


def _both(ex, z, chosen, w, cfg, dtype, monkeypatch, tile=TILE):
    """``held_experts`` through the kernels, then through the plain loop
    (the selection turned off): ``((y, pairs, served), (y, pairs,
    served))``."""
    args = (ex, z, jnp.asarray(chosen, jnp.int32), w)
    assert _holds_kernels(
        lambda *a: hm.held_experts(*a, cfg, dtype, tile=tile), *args)
    kernels = hm.held_experts(*args, cfg, dtype, tile=tile)
    with monkeypatch.context() as m:
        m.setattr(ge, "kernel_fits", lambda *_: False)
        assert not _holds_kernels(
            lambda *a: hm.held_experts(*a, cfg, dtype, tile=tile), *args)
        return kernels, hm.held_experts(*args, cfg, dtype, tile=tile)


def _holds_kernels(fn, *args) -> bool:
    return held_by(fn, *args, names=ge.KERNELS)


def _random_choice(rng, n, routed, k, real=None):
    chosen = np.stack([rng.permutation(routed)[:k] for _ in range(n)])
    if real is not None:
        chosen[~real] = -1
    return chosen


# -- the kernels against the loop ----------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (BF16, 0.02)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("k,routed,held_first,held", [
    (1, 5, 0, 4),  # a top-1 router whose last output is the skip
    (4, 16, 0, 16),  # every expert held
    (4, 16, 4, 4),  # the second of four shares: three pairs in four absent
    (8, 32, 8, 8),
], ids=["k1_skip", "k4_all_held", "k4_second_share", "k8_second_share"])
def test_the_kernels_equal_the_loop(base, monkeypatch, dtype, tol, k, routed,
                                    held_first, held):
    """Random routing with a fifth of the tokens padding, several chunks
    of several tiles: ``y`` to the rows' rounding, ``pairs`` and ``served``
    exactly, and no pair dropped."""
    monkeypatch.setattr(hm, "MOE_CHUNK", 4 * TILE)
    cfg = _cfg(base, held_first, held, routed, k)
    rng = np.random.default_rng(k)
    n = 300
    z = jnp.asarray(rng.normal(size=(n, HIDDEN)), F32)
    real = rng.uniform(size=n) > 0.2
    chosen = _random_choice(rng, n, routed, k, real)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), F32)
    (y, pairs, served), (want, want_pairs, want_served) = _both(
        _experts(held, dtype), z, chosen, w, cfg, dtype, monkeypatch)
    assert np.array_equal(np.asarray(pairs), np.asarray(want_pairs))
    mine = (chosen >= held_first) & (chosen < held_first + held)
    assert int(served) == int(want_served) == int(pairs.sum()) == mine.sum()
    assert np.abs(np.asarray(y) - np.asarray(want)).max() <= tol * max(
        1.0, float(np.abs(np.asarray(want)).max()))
    assert not np.asarray(y)[~real].any()


@pytest.mark.parametrize("case", [
    "an_expert_with_no_pair", "an_expert_with_every_pair",
    "a_group_that_ends_on_a_tiles_edge", "no_pair_at_all",
    "one_chunk_and_one_tile_more"])
def test_every_way_a_group_lies_on_the_tiles(base, monkeypatch, case):
    """The layouts the random cases may miss, in float32, against the
    experts applied one by one."""
    monkeypatch.setattr(hm, "MOE_CHUNK", 4 * TILE)
    held, k, n = 4, 2, 4 * TILE
    cfg = _cfg(base, 2, held, 8, k)
    rng = np.random.default_rng(9)
    chosen = np.full((n, k), -1)
    if case == "an_expert_with_no_pair":  # expert 3 (local 1) stays empty
        chosen[:, 0] = rng.choice([2, 4, 5], size=n)
        chosen[:, 1] = 7  # another chip's
    elif case == "an_expert_with_every_pair":
        chosen[:, 0], chosen[:, 1] = 4, 0
    elif case == "a_group_that_ends_on_a_tiles_edge":
        chosen[:2 * TILE, 0] = 2  # exactly two tiles
        chosen[2 * TILE:3 * TILE, 0] = 3  # exactly one
        chosen[3 * TILE:, 0] = 5
        chosen[:TILE + 1, 1] = 4  # one tile and one row
    elif case == "one_chunk_and_one_tile_more":  # 4 tiles a chunk, 5 live
        chosen[:, 0] = 2
        chosen[:TILE, 1] = 5
    ex = _experts(held, F32, seed=1)
    z = jnp.asarray(rng.normal(size=(n, HIDDEN)), F32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), F32)
    (y, pairs, served), (want, want_pairs, want_served) = _both(
        ex, z, chosen, w, cfg, F32, monkeypatch)
    local = chosen - 2
    counts = [int((local == e).sum()) for e in range(held)]
    assert list(np.asarray(pairs)) == list(np.asarray(want_pairs)) == counts
    assert int(served) == int(want_served) == sum(counts)
    one_by_one = np.zeros((n, HIDDEN), np.float32)
    with jax.default_matmul_precision("highest"):
        for e in range(held):
            part = np.asarray(hm._swiglu(
                {name: m[e] for name, m in ex.items()}, z, F32))
            for slot in range(k):
                at = local[:, slot] == e
                one_by_one[at] += np.asarray(w)[at, slot:slot + 1] * part[at]
    assert np.allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert np.allclose(np.asarray(y), one_by_one, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("tile", [256, 512])
def test_a_part_empty_tile_multiplies_only_its_live_blocks(base, monkeypatch,
                                                           tile):
    """Tiles of more than ``SUB_ROWS`` rows: a group's last tile is
    multiplied a block of ``SUB_ROWS`` at a time, as far as it holds
    pairs, and the rest stays unwritten; the answer is the loop's. Groups
    of 1 row, of one block and one row, of a tile less one row, of a whole
    tile, and of a tile and one row."""
    assert tile > ge.SUB_ROWS
    held, k = 5, 1
    sizes = [1, ge.SUB_ROWS + 1, tile - 1, tile, tile + 1]
    n = sum(sizes) + 7  # seven tokens whose expert another chip holds
    cfg = _cfg(base, 1, held, 8, k)
    chosen = np.concatenate([np.full(size, 1 + e) for e, size in
                             enumerate(sizes)] + [np.zeros(7, int)])
    rng = np.random.default_rng(tile)
    chosen = chosen[rng.permutation(n)].reshape(n, k)
    z = jnp.asarray(rng.normal(size=(n, HIDDEN)), F32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), F32)
    (y, pairs, served), (want, want_pairs, want_served) = _both(
        _experts(held, F32, seed=3), z, chosen, w, cfg, F32, monkeypatch,
        tile=tile)
    assert list(np.asarray(pairs)) == list(np.asarray(want_pairs)) == sizes
    assert int(served) == int(want_served) == sum(sizes)
    assert np.allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert not np.asarray(y)[chosen[:, 0] == 0].any()


# -- which body runs -------------------------------------------------------------------

@pytest.mark.parametrize("hidden,width,dtype,mesh,kernels", [
    (128, 256, BF16, False, True),
    (256, 128, F32, False, True),
    (128, 192, BF16, False, False),  # a width that fills no lane tile
    (64, 32, F32, False, False),  # the small presets' experts
    (128, 256, jnp.float16, False, False),
    (128, 256, BF16, True, False),  # a mesh keeps the loop
], ids=["lane_wide", "float32", "width_192", "small_preset", "float16",
        "mesh"])
def test_the_programs_jaxpr_says_which_body_was_taken(base, hidden, width,
                                                      dtype, mesh, kernels):
    """The selection is made while the program is traced, from the
    experts' widths, the dtype and where the weights lie; the scorer's
    reading (``_Program.kernels_held``) looks for these names."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = _cfg(base, 0, 8, 16, 4)
    ex = _experts(8, dtype, hidden, width)
    if mesh:
        over = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
        ex = jax.device_put(ex, NamedSharding(over, P("ep", None, None)))
    rng = np.random.default_rng(2)
    z = jnp.asarray(rng.normal(size=(96, hidden)), F32)
    chosen = jnp.asarray(_random_choice(rng, 96, 16, 4), jnp.int32)
    w = jnp.ones((96, 4), F32)

    def layer(ex, z, chosen, w):
        return hm.held_experts(ex, z, chosen, w, cfg, dtype)

    assert ge.kernel_fits(ex["gate"], dtype) == kernels
    assert _holds_kernels(layer, ex, z, chosen, w) == kernels
    held = kernels_of(layer, ex, z, chosen, w)
    assert held == (frozenset(ge.KERNELS) if kernels else frozenset())
    y, pairs, served = jax.jit(layer)(ex, z, chosen, w)
    assert int(served) == int(pairs.sum()) == int(
        (np.asarray(chosen) < 8).sum())
    assert bool(jnp.isfinite(y).all())


@pytest.mark.parametrize("cell,tile,up_block,down_block", [
    ("ling3", 128, 768, 2560),  # 240 pairs an expert expected
    ("zaya1", 512, 1024, 2048),  # 903
    ("mistral4", 256, 512, 2048),  # 480
])
def test_the_tile_and_the_blocks_come_from_the_widths(cell, tile, up_block,
                                                      down_block):
    _, hidden, width, routed, k = CELLS[cell]
    assert ge.row_tile(TOKENS * k / routed) == tile
    assert ge.block_for(hidden, width, 2, 2) == up_block
    assert ge.block_for(width, hidden, 1, 2) == down_block
    for contract, out, operands, block in ((hidden, width, 2, up_block),
                                           (width, hidden, 1, down_block)):
        assert out % block == 0 and block % ge.LANE == 0
        assert 2 * operands * contract * block * 2 <= ge.WEIGHT_BYTES
    assert ge.block_for(2560, 192, 2, 2) is None
    assert ge.row_tile(10.0) == ge.ROW_TILES[-1]


# -- the chip's compiler ---------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A v5e described, not attached: Mosaic compiles for it here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_mosaic_compiles_the_kernels_at_a_dispatch_of_the_real_models(
        one_chip, as_on_the_chip, cell):
    """A chunk of rows against each real model's stacked experts in
    bfloat16 at the tile its dispatch picks, written in place into a
    buffer of four chunks: what the interpreter cannot
    refuse (tiling, VMEM) the chip's compiler can, and nothing runs."""
    held, hidden, width, routed, k = CELLS[cell]
    tile = ge.row_tile(TOKENS * k / routed)

    def shape(*dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, gate, up, down, expert_of, live_of, visit, into, first:
        ge.grouped_swiglu.__wrapped__(
            x, gate, up, down, expert_of, live_of, visit, into, first,
            tile=tile)).lower(
        shape(hm.MOE_CHUNK, hidden), shape(held, hidden, width),
        shape(held, hidden, width), shape(held, width, hidden),
        shape(hm.MOE_CHUNK // tile, dtype=jnp.int32),
        shape(hm.MOE_CHUNK // tile, dtype=jnp.int32), shape(dtype=jnp.int32),
        shape(4 * hm.MOE_CHUNK, hidden), shape(dtype=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_mosaic_compiles_the_relu2_kernels_at_a_dispatch_of_the_sixth_model(
        one_chip, as_on_the_chip):
    """Experts of two matrices, 2,688 x 1,856 stored at 1,920 columns /
    rows (15 lane tiles, three blocks of 640 up and of 896 down), 64 held,
    tiles of 256 rows: the one-operand kernel with its relu squared."""
    held, hidden, width, routed, k = 64, 2688, 1920, 128, 6
    tile = ge.row_tile(TOKENS * k / routed)
    assert tile == 256 and ge.kernel_fits(
        jax.ShapeDtypeStruct((held, hidden, width), BF16), BF16, 1)

    def shape(*dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, up, down, expert_of, live_of, visit, into, first:
        ge.grouped_relu2.__wrapped__(
            x, up, down, expert_of, live_of, visit, into, first,
            tile=tile)).lower(
        shape(hm.MOE_CHUNK, hidden), shape(held, hidden, width),
        shape(held, width, hidden),
        shape(hm.MOE_CHUNK // tile, dtype=jnp.int32),
        shape(hm.MOE_CHUNK // tile, dtype=jnp.int32), shape(dtype=jnp.int32),
        shape(4 * hm.MOE_CHUNK, hidden), shape(dtype=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
