"""The ``hybrid_moe`` history family (models/hybrid_moe.py) against its
plain reference (benchmark/reference/hybrid_moe_f32.py) at the small
preset, seeded weights, on the CPU: each layer kind alone, the whole
model, the expert-parallel share, padding, droplessness, and the served
path through ``HistoryStore`` + ``SeqScorer`` by the registry's name."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.models import registry
from ccfd_tpu.ops import grouped_experts, kernels
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "ling3_small_config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def params(small):
    return ref.make_params(small)


@pytest.fixture(scope="module")
def rows():
    return table.surrogate_rows(4096, 7)[0]


def _three_layers(small, params):
    """KDA + dense, KDA + experts, MLA + experts of the preset: every
    layer kind at less than half the compile."""
    model = dict(small, layers_kept=[0, 2, 5], num_hidden_layers=3)
    return model, dict(params, layers=[params["layers"][i]
                                       for i in (0, 1, 4)])


def _windows(rows, filled, length, seed=0):
    rng = np.random.default_rng(seed)
    hist = np.zeros((len(filled), length, rows.shape[1]), np.float32)
    for i, k in enumerate(filled):
        hist[i, length - k:] = rows[rng.integers(0, len(rows), k)]
    return hist, np.asarray(filled, np.int32)


def _layer(params, small, mixer=None, ffn=None):
    kinds = ref.layer_kinds(small)
    for kind, p in zip(kinds, params["layers"]):
        if (mixer is None or kind[0] == mixer) and (
                ffn is None or kind[1] == ffn):
            return p
    raise AssertionError((mixer, ffn))


def _inputs(small, n=2, t=100, pad=(0, 37), seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, small["hidden_size"])).astype(np.float32)
    real = np.arange(t)[None, :] >= np.asarray(pad)[:, None]
    position = np.maximum(np.arange(t)[None, :] - np.asarray(pad)[:, None], 0)
    return jnp.asarray(x), jnp.asarray(real), jnp.asarray(position)


@pytest.mark.parametrize("kind,chunk", [("kda", 64), ("kda", 16),
                                        ("mla", None), ("route", None),
                                        ("moe", None)])
def test_each_layer_kind_agrees_with_the_reference(small, params, kind,
                                                   chunk):
    """The chunked KDA scan against the token-by-token recurrence (at
    both chunk sizes, with padding on the left and a length that is no
    multiple of the chunk), MLA against the full masked softmax, routing
    (same experts, same weights, the bias included) and the expert layer
    with its tile loop against the loop over experts."""
    model = dict(small, kda_chunk=chunk) if chunk else small
    cfg = hm.HybridConfig.from_dict(model)
    x, real, position = _inputs(small)
    with jax.default_matmul_precision("highest"):
        if kind == "kda":
            p = _layer(params, small, mixer="kda")["mixer"]
            want = ref.kda(p, x, real, small)
            got = hm.kda(p, x, real, cfg, F32)
        elif kind == "mla":
            p = _layer(params, small, mixer="mla")["mixer"]
            want = ref.mla(p, x, real, position, small)
            got = hm.mla(p, x, real, position, cfg, F32)
        else:
            p = _layer(params, small, ffn="moe")["ffn"]
            flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
            want_e, want_w = ref.route(p, flat, flat_real, small)
            got_e, got_w = hm.route(p, flat, flat_real, cfg)
            order = np.argsort(np.asarray(got_e), axis=1)
            want_order = np.argsort(np.asarray(want_e), axis=1)
            assert np.array_equal(
                np.take_along_axis(np.asarray(got_e), order, 1),
                np.take_along_axis(np.asarray(want_e), want_order, 1))
            assert not np.array_equal(  # the bias changes a choice
                np.sort(np.asarray(got_e), 1), np.sort(np.asarray(hm.route(
                    dict(p, bias=jnp.zeros_like(p["bias"])), flat,
                    flat_real, cfg)[0]), 1))
            got = np.take_along_axis(np.asarray(got_w), order, 1)
            want = np.take_along_axis(np.asarray(want_w), want_order, 1)
            if kind == "moe":
                want, want_pairs = ref.moe(p, x, real, small)
                got, _, counts = hm.moe(p, x, None, real, cfg, F32)
                assert int(counts["served"]) == int(
                    counts["pairs"].sum()) == want_pairs
                assert int(counts["row_pairs"].sum()) == want_pairs
                assert int(counts["skipped"]) == 0
    keep = np.asarray(real)[..., None] if np.ndim(got) == 3 else True
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("experts", ["small", "lane_wide"])
def test_slice_logits_agree_at_every_position(small, params, rows, experts):
    """The whole model against the reference; ``lane_wide``: hidden and
    expert widths of 128, so the held experts multiply through the
    grouped kernels (``ops/grouped_experts.py``, interpreted here) where
    the small preset's 64 x 32 keep the tile loop, one layer of each kind."""
    if experts == "lane_wide":
        small, params = _three_layers(small, params)
        small = dict(small, hidden_size=128, moe_intermediate_size=128)
        params = ref.make_params(small)
    cfg = hm.HybridConfig.from_dict(small)
    hist, filled = _windows(rows, [8, 3, 1], 8)
    want, want_pairs = ref.forward(params, small, hist, filled,
                                   every_position=True)
    assert kernels.held_by(
        lambda p, h, f: hm.logits_everywhere(p, h, f, cfg, F32), params,
        hist, filled, names=grouped_experts.KERNELS) == (
            experts == "lane_wide")
    got, aux = hm.logits_everywhere(params, hist, filled, cfg, F32)
    real = np.asarray(ref.real_tokens(jnp.asarray(filled), 8, 30))
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-3
    assert np.array_equal(np.asarray(aux["pairs"]).sum(1), want_pairs)
    assert int(aux["routed_tokens"]) == int(real.sum())
    # bfloat16 serving stays near it (a token near a tie may choose another
    # expert, so the widest gap is wide; the mean is not): one layer of
    # each kind
    model, fewer = (small, params) if experts == "lane_wide" \
        else _three_layers(small, params)
    want, _ = ref.forward(fewer, model, hist, filled, every_position=True)
    served, _ = hm.logits_everywhere(
        fewer, hist, filled, hm.HybridConfig.from_dict(model), jnp.bfloat16)
    assert np.abs(np.asarray(served) - np.asarray(want))[real].mean() < 0.1


def test_the_four_shares_add_up_to_the_uncut_layer(small, params):
    """What each of the 4 chips computes of one expert layer (its 4 of the
    16 experts), with the shared expert counted once, adds up to the
    reference's layer over all 16 experts."""
    x, real, _ = _inputs(small)
    whole_model = dict(small, num_experts=16,
                       experts_held={"first": 0, "count": 16})
    p = ref.make_params(whole_model)["layers"][1]["ffn"]
    with jax.default_matmul_precision("highest"):
        want, all_pairs = ref.moe(p, x, real, whole_model)
        shared = ref.swiglu(p["shared"], x)
        total, pairs = shared, 0
        for share in range(4):
            held = {"first": 4 * share, "count": 4}
            mine = dict(p, experts={k: v[4 * share:4 * share + 4]
                                    for k, v in p["experts"].items()})
            cfg = hm.HybridConfig.from_dict(dict(small, experts_held=held))
            got, _, counts = hm.moe(mine, x, None, real, cfg, F32)
            total = total + (got - shared)
            pairs += int(counts["served"])
    assert pairs == all_pairs == int(real.sum()) * small[
        "num_experts_per_tok"]
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(total) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, rows):
    """One history of 5 records at windows of 8, 16 and 64 records gives
    one verdict, and the padding tokens serve no pair (one layer of each
    kind: a window is a program of its own to compile)."""
    model, params = _three_layers(small, params)
    cfg = hm.HybridConfig.from_dict(model)
    hist, _ = _windows(rows, [5], 5)
    verdicts, pairs = [], []
    for length in (8, 16, 64):
        window = np.zeros((1, length, 30), np.float32)
        window[0, length - 5:] = hist[0]
        proba, aux = hm.apply_serving(params, window, np.array([5]), cfg,
                                      F32)
        verdicts.append(float(proba[0]))
        pairs.append(np.asarray(aux["pairs"]))
        assert int(aux["routed_tokens"]) == 5 * 30
    assert np.allclose(verdicts, verdicts[0], rtol=1e-4, atol=1e-7)
    assert all(np.array_equal(p, pairs[0]) for p in pairs)
    _, all_pad = hm.apply_serving(params, np.zeros((2, 8, 30), np.float32),
                                  np.zeros(2, np.int32), cfg, F32)
    assert int(all_pad["pairs_served"]) == 0
    assert int(all_pad["routed_tokens"]) == 0


def test_dropless_under_total_skew(small):
    """Every token sends all its pairs to held experts and most of them to
    one: every pair is served, whatever the tile size."""
    cfg = hm.HybridConfig.from_dict(small)
    rng = np.random.default_rng(1)
    n, d, f = 700, small["hidden_size"], small["moe_intermediate_size"]
    z = jnp.asarray(rng.normal(size=(n, d)), F32)
    ex = {"gate": jnp.asarray(rng.normal(size=(4, d, f)) / 8, F32),
          "up": jnp.asarray(rng.normal(size=(4, d, f)) / 8, F32),
          "down": jnp.asarray(rng.normal(size=(4, f, d)) / 8, F32)}
    chosen = np.zeros((n, 4), np.int32)
    chosen[:, 1:] = -1  # one pair a token, all to expert 0
    chosen[::50, 1] = 3
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, 4)), F32)
    for tile in (256, 64):
        y, pairs, served = hm.held_experts(ex, z, jnp.asarray(chosen), w,
                                           cfg, F32, tile=tile)
        assert int(served) == n + len(range(0, n, 50))
        assert list(np.asarray(pairs)) == [n, 0, 0, len(range(0, n, 50))]
        want = np.asarray(w[:, :1]) * np.asarray(ref.swiglu(
            {k: v[0] for k, v in ex.items()}, z))
        rest = np.asarray(w[::50, 1:2]) * np.asarray(ref.swiglu(
            {k: v[3] for k, v in ex.items()}, z[::50]))
        want[::50] += rest
        assert np.allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)


def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had."""
    from ccfd_tpu.metrics.prom import Registry

    length = 8
    reg = Registry()
    scorer = SeqScorer(params, length=length, batch_sizes=(4, 16),
                       compute_dtype="float32", registry=reg,
                       family="hybrid_moe",
                       family_config=hm.HybridConfig.from_dict(small))
    kept = []
    scorer.aux_tap = lambda idx, m, aux: kept.append((idx, m, aux))
    rng = np.random.default_rng(11)
    customers = rng.choice([3, 5, 8, 13], size=37, p=[0.55, 0.25, 0.15, 0.05])
    sent = rows[rng.integers(0, len(rows), len(customers))]
    served = np.concatenate([
        scorer.score(sent[lo:lo + 9], [int(c) for c in customers[lo:lo + 9]])
        for lo in range(0, len(customers), 9)])
    hist, filled = ref.histories(
        customers, np.arange(len(customers)), sent,
        np.arange(len(customers)), length,
        np.full((14, 1), -1, np.int64))
    logits, _ = ref.forward(params, small, hist, filled)
    want = 1.0 / (1.0 + np.exp(-np.asarray(ref.verdict_logit(
        np.asarray(logits), small), np.float64)))
    assert np.allclose(served, want, rtol=2e-3, atol=1e-6)
    grid = scorer.executable_grid()
    assert grid["model"] == "hybrid_moe" and grid["experts_held"] == [0, 4]
    assert reg.counter("moe_pairs_served_total").total() == reg.counter(
        "moe_pairs_routed_total").total() > 0
    assert reg.counter("lm_tokens_total").total() == int(filled.sum()) * 30
    assert sum(m for _, m, _ in kept) == len(customers)
    with pytest.raises(ValueError, match="hybrid_moe"):
        scorer.swap_params(params)
    assert reg.counter("seq_swap_refused_total").total() == 1


@pytest.mark.parametrize("name", ["seq", "seq_q8"])
def test_seq_families_score_through_the_registry_as_before(name):
    """``seq`` and ``seq_q8`` found by name and by their tree: the scorer
    serves what the family's own ``apply_serving`` gives."""
    from ccfd_tpu.models import seq as seq_mod
    from ccfd_tpu.ops import seq_quant

    p = seq_mod.init(jax.random.PRNGKey(0))
    direct = seq_mod.apply_serving
    if name == "seq_q8":
        p, direct = seq_quant.quantize_seq(p), seq_quant.apply_serving
    assert registry.history_family_of(p).name == name
    assert not registry.get_history(name).reads_filled
    rng = np.random.default_rng(2)
    x = rng.normal(size=(5, 30)).astype(np.float32)
    by_tree = SeqScorer(p, length=16, batch_sizes=(8,))
    by_name = SeqScorer(p, length=16, batch_sizes=(8,), family=name)
    assert by_tree.executable_grid()["model"] == name
    hist = np.zeros((8, 16, 30), np.float32)
    hist[:5, -1] = x
    want = np.asarray(direct(p, hist, jnp.bfloat16, pos_length=16))[:5]
    assert np.array_equal(by_tree.score(x, [1, 2, 3, 4, 5]), want)
    assert np.array_equal(by_name.score(x, [1, 2, 3, 4, 5]), want)
    with pytest.raises(KeyError, match="unknown history family"):
        SeqScorer(p, length=16, family="no_such_family")


def test_the_share_and_the_layout_over_the_chips_that_share_a_layer(
        small, params):
    """``expert_share`` names what each of 4 chips holds of 16 experts (the
    shares the add-up test sums), and ``hybrid_moe_rules`` covers the
    tree: experts and vocabulary over the expert axis, the rest whole."""
    from jax.sharding import PartitionSpec as P

    from ccfd_tpu.parallel import partition
    from ccfd_tpu.parallel.mesh import EXPERT_AXIS

    shares = [partition.expert_share(16, 4, i) for i in range(4)]
    assert shares[0] == small["experts_held"]
    assert [s["first"] for s in shares] == [0, 4, 8, 12]
    with pytest.raises(ValueError):
        partition.expert_share(16, 3, 0)
    specs = partition.match_partition_rules(
        partition.hybrid_moe_rules(EXPERT_AXIS), params)
    moe_layer = specs["layers"][1]
    assert moe_layer["ffn"]["experts"]["gate"] == P(EXPERT_AXIS, None, None)
    assert moe_layer["ffn"]["shared"]["gate"] == P()
    assert moe_layer["mixer"]["wq"] == P()
    assert specs["embed"] == P(EXPERT_AXIS, None)
    assert specs["head"] == P(None, EXPERT_AXIS)


def test_the_chunked_scan_holds_identical_tokens_under_a_slow_gate(
        small, params):
    """A column's tokens repeat from record to record, so keys inside a
    chunk can be all but equal; with a slow gate every entry of the
    chunk's triangular matrix is then near beta. The blocked inverse
    stays with the recurrence there (squaring the whole 64 x 64 matrix
    does not: its powers reach 1e5 and cancel)."""
    cfg = hm.HybridConfig.from_dict(small)
    p = dict(_layer(params, small, mixer="kda")["mixer"])
    p["dt_bias"] = jnp.full_like(p["dt_bias"], -6.0)  # alpha near 0.99
    p["wb"] = p["wb"] * 0.0 + 0.5  # beta near 1 for a positive token
    rng = np.random.default_rng(5)
    token = np.abs(rng.normal(size=(1, 1, small["hidden_size"])))
    x = jnp.asarray(np.repeat(token, 200, axis=1)
                    + 0.01 * rng.normal(size=(1, 200, small["hidden_size"])),
                    F32)
    real = jnp.ones((1, 200), bool)
    with jax.default_matmul_precision("highest"):
        want = ref.kda(p, x, real, small)
        got = hm.kda(p, x, real, cfg, F32)
    assert bool(jnp.isfinite(got).all())
    assert np.allclose(np.asarray(got), np.asarray(want), atol=5e-4,
                       rtol=5e-4)
