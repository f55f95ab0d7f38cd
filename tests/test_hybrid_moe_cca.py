"""The ``hybrid_moe`` family's ``zaya`` model (CCA attention, the router
that carries its state and may skip, top 1 of 16 experts, scaled residuals,
tied head; models/hybrid_moe.py) against its plain reference
(benchmark/reference/cca_moe_f32.py) at the small preset, seeded weights,
on the CPU: the whole model in both precisions, each part alone, padding,
causality, the carry, the skip, the grouping, the tied head, the halves of
the experts, and the served path through ``SeqScorer`` by the registry's
name."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cca_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import grouped_experts, kernels
from ccfd_tpu.models import registry
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
LENGTH, COLS = 8, 30


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "zaya1_small_config.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def params(small):
    return ref.make_params(small)


@pytest.fixture(scope="module")
def cfg(small):
    return hm.HybridConfig.from_dict(small)


@pytest.fixture(scope="module")
def rows():
    return table.surrogate_rows(4096, 7)[0]


def _windows(rows, filled, length=LENGTH, seed=0):
    rng = np.random.default_rng(seed)
    hist = np.zeros((len(filled), length, rows.shape[1]), np.float32)
    for i, k in enumerate(filled):
        hist[i, length - k:] = rows[rng.integers(0, len(rows), k)]
    return hist, np.asarray(filled, np.int32)


def _inputs(small, n=2, t=100, pad=(0, 37), seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, small["hidden_size"])).astype(np.float32)
    real = np.arange(t)[None, :] >= np.asarray(pad)[:, None]
    position = np.maximum(np.arange(t)[None, :] - np.asarray(pad)[:, None], 0)
    return jnp.asarray(x), jnp.asarray(real), jnp.asarray(position)


def _hidden(params, hist, filled, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.jit(hm.hidden_states, static_argnames=("cfg", "dtype"))(
            params, hist, filled, cfg=cfg, dtype=F32)


# -- the whole model, and each part ------------------------------------------------

@pytest.mark.parametrize("dtype,worst,mean", [
    (F32, 2e-4, 2e-5),  # the reference's own precision: tight
    (jnp.bfloat16, None, 0.05),  # as served: a token near a tie may choose
    # another expert, so the widest gap is wide; the mean is not
])
@pytest.mark.parametrize("experts", ["small", "lane_wide"])
def test_logits_agree_with_the_reference_at_every_position(
        small, params, cfg, rows, dtype, worst, mean, experts):
    """``lane_wide``: hidden and expert widths of 128, so the held experts
    multiply through the grouped kernels (``ops/grouped_experts.py``,
    interpreted here); the small preset's 64 x 32 keep the tile loop."""
    if experts == "lane_wide":
        small = dict(small, hidden_size=128, moe_intermediate_size=128)
        params, cfg = ref.make_params(small), hm.HybridConfig.from_dict(small)
    hist, filled = _windows(rows, [8, 3, 1])
    assert kernels.held_by(
        lambda p, h, f: hm.logits_everywhere(p, h, f, cfg, dtype), params,
        hist, filled, names=grouped_experts.KERNELS) == (
            experts == "lane_wide")
    want, want_choice = ref.forward(params, small, hist, filled,
                                    every_position=True)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = np.asarray(ref.shared.real_tokens(jnp.asarray(filled), LENGTH,
                                             COLS))
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    assert int(aux["routed_tokens"]) == int(real.sum())
    layers = len(small["layers_kept"])
    assert int(aux["pairs_served"]) + int(aux["skipped_tokens"]) == int(
        real.sum()) * layers
    assert int(aux["pairs_served"]) == int(np.asarray(aux["pairs"]).sum())
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
        assert int(aux["skipped_tokens"]) == int(want_choice[..., -1].sum())
        assert np.array_equal(np.asarray(aux["row_pairs"]),
                              want_choice[..., :-1].sum((1, 2)))


@pytest.mark.parametrize("part", ["cca", "route", "route_first", "experts"])
def test_each_part_agrees_with_the_reference(small, params, cfg, part):
    """CCA with padding on the left of one row (query blocks against the
    full masked softmax, grouped products against repeated heads, the
    shifted product against the product of the shifted input); the router
    with a state handed over and, at the first layer, with none (zeros in
    the program); the tile loop against the loop over experts."""
    p = ref.layer_of(params, 1)
    x, real, position = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.normal(size=(len(flat), small[
        "router_hidden_size"])), F32)
    with jax.default_matmul_precision("highest"):
        if part == "cca":
            want = ref.cca(p["mixer"], x, real, position, small)
            got = hm.cca(p["mixer"], x, real, position, cfg, F32)
        elif part == "experts":
            want, want_r, choice = ref.experts(p["ffn"], x, real, r, small)
            got, got_r, counts = hm.moe(p["ffn"], x, r, real, cfg, F32)
            assert np.array_equal(np.asarray(counts["row_choice"]), choice)
            assert int(counts["served"]) == int(choice[:, :-1].sum())
            assert int(counts["skipped"]) == int(choice[:, -1].sum())
            assert np.allclose(np.asarray(got_r), np.asarray(want_r),
                               atol=2e-4, rtol=2e-4)
        else:
            first = part == "route_first"
            want_e, want_w, want_r, _ = ref.route(
                p["ffn"], flat, flat_real, None if first else r, small)
            got_e, got_w, got_r = hm.route_carried(
                p["ffn"], flat, jnp.zeros_like(r) if first else r,
                flat_real, cfg)
            assert np.array_equal(np.asarray(got_e)[:, 0],
                                  np.asarray(want_e))
            assert not np.array_equal(  # the bias changes a choice
                np.asarray(got_e), np.asarray(hm.route_carried(
                    dict(p["ffn"], bias=p["ffn"]["bias"] * 20.0), flat,
                    jnp.zeros_like(r) if first else r, flat_real, cfg)[0]))
            assert np.allclose(np.asarray(got_r), np.asarray(want_r),
                               atol=2e-4, rtol=2e-4)
            got, want = got_w[:, 0], want_w
    keep = np.asarray(real)[..., None] if np.ndim(got) == 3 else True
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


# -- padding and causality ------------------------------------------------------------

@pytest.mark.parametrize("padding", ["zeros", "noise"])
def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows, padding):
    """One history of 5 records at windows of 8, 16 and 64 records gives
    one verdict and one routing, through two convolutions, the value shift
    and the router's carry; with other records where the padding is, too:
    the first real token's convolutions and shifted value read zeros, not
    what the padding holds."""
    hist, _ = _windows(rows, [5], 5)
    rng = np.random.default_rng(9)
    verdicts, choices = [], []
    for length in (8, 16, 64):
        window = np.zeros((1, length, COLS), np.float32)
        if padding == "noise":
            window[0] = rows[rng.integers(0, len(rows), length)]
        window[0, length - 5:] = hist[0]
        with jax.default_matmul_precision("highest"):
            proba, aux = hm.apply_serving(params, window, np.array([5]),
                                          cfg, F32)
        verdicts.append(float(proba[0]))
        choices.append(np.asarray(aux["row_choice"]))
        assert int(aux["routed_tokens"]) == 5 * COLS
    assert np.allclose(verdicts, verdicts[0], rtol=1e-4, atol=1e-7)
    assert all(np.array_equal(c, choices[0]) for c in choices)
    want, _ = ref.forward(params, small, hist, np.array([5], np.int32))
    p_want = 1.0 / (1.0 + np.exp(-float(ref.verdict_logit(
        np.asarray(want), small)[0])))
    assert verdicts[0] == pytest.approx(p_want, rel=1e-3)


def test_a_window_of_padding_alone_serves_and_skips_nothing(params, cfg):
    _, aux = hm.apply_serving(params, np.zeros((2, LENGTH, COLS), np.float32),
                              np.zeros(2, np.int32), cfg, F32)
    assert int(aux["pairs_served"]) == int(aux["skipped_tokens"]) == 0
    assert int(aux["routed_tokens"]) == 0
    assert not np.asarray(aux["row_choice"]).any()


def test_a_later_token_moves_no_earlier_hidden_state(params, cfg, rows):
    hist, filled = _windows(rows, [8, 6])
    other = hist.copy()
    other[:, -1] = rows[:2]  # the newest record of both rows
    x, _ = _hidden(params, hist, filled, cfg)
    y, _ = _hidden(params, other, filled, cfg)
    before = (LENGTH - 1) * COLS
    real = np.asarray(ref.shared.real_tokens(jnp.asarray(filled), LENGTH,
                                             COLS))[:, :before]
    # to rounding (a token's row in the expert tiles moves with the routing
    # of the tokens after it); a padding token's state is nobody's
    assert np.allclose(np.asarray(x)[:, :before][real],
                       np.asarray(y)[:, :before][real], atol=1e-5, rtol=0)
    assert np.abs(np.asarray(x)[:, before:] - np.asarray(y)[:, before:]
                  ).max() > 1e-3


# -- the carry, the skip, the grouping, the head ------------------------------------

def test_the_routers_state_crosses_the_layers_and_the_first_has_none(
        small, params, cfg, rows):
    """Moving the state layer 0 hands on moves layer 1's probabilities
    (``w``, the probability of the choice); without ``gamma`` every layer
    after the first routes otherwise and the first routes as before: it
    receives none."""
    p = ref.layer_of(params, 1)
    x, real, _ = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    r = jnp.ones((len(flat), small["router_hidden_size"]), F32)
    _, w0, r0 = hm.route_carried(p["ffn"], flat, r * 0.0, flat_real, cfg)
    _, w1, r1 = hm.route_carried(p["ffn"], flat, r, flat_real, cfg)
    keep = np.asarray(flat_real)
    assert np.abs(np.asarray(w1) - np.asarray(w0))[keep].max() > 1e-3
    gamma = np.asarray(p["ffn"]["router"]["gamma"])
    assert np.allclose((np.asarray(r1) - np.asarray(r0))[keep], gamma,
                       atol=1e-5)
    # a padding token hands the state on as it came
    assert np.array_equal(np.asarray(r1)[~keep], np.ones_like(gamma)[
        None].repeat((~keep).sum(), 0))
    hist, filled = _windows(rows, [8, 5])
    _, with_carry = _hidden(params, hist, filled, cfg)
    ffn = params["layers"]["ffn"]
    cut = dict(params, layers=dict(params["layers"], ffn=dict(
        ffn, router=dict(ffn["router"],
                         gamma=ffn["router"]["gamma"] * 0.0))))
    _, without = _hidden(cut, hist, filled, cfg)
    a, b = (np.asarray(t["row_choice"]) for t in (with_carry, without))
    assert np.array_equal(a[:, 0], b[:, 0])
    assert not np.array_equal(a[:, 1], b[:, 1])


def test_a_token_forced_to_the_skip_gets_no_expert_and_is_counted_once(
        small, params, cfg):
    p = ref.layer_of(params, 0)["ffn"]
    skip = small["num_experts_routed_over"] - 1
    forced = dict(p, bias=p["bias"].at[skip].set(10.0))
    x, real, _ = _inputs(small)
    r = jnp.zeros((x.shape[0] * x.shape[1], small["router_hidden_size"]))
    y, _, counts = hm.moe(forced, x, r, real, cfg, F32)
    n = int(np.asarray(real).sum())
    assert int(counts["skipped"]) == n and int(counts["served"]) == 0
    assert not np.asarray(counts["pairs"]).any()
    assert not np.asarray(y).any()  # the sublayer adds b_o alone
    assert np.asarray(counts["row_choice"])[:, skip].tolist() == np.asarray(
        real).sum(1).tolist()
    # unforced, some skip and some do not; each real token is one or the other
    y, _, counts = hm.moe(p, x, r, real, cfg, F32)
    assert int(counts["served"]) + int(counts["skipped"]) == n
    assert 0 < int(counts["served"])


@pytest.mark.parametrize("key_head,moved", [(0, True), (1, False)])
def test_query_head_h_belongs_to_key_head_h_over_4(small, params, cfg,
                                                   key_head, moved):
    """With an output projection that passes query heads 0-3 through and
    drops 4-7, changing the key projection of key head 1 changes nothing
    (neither the q-k mean nor the attention of heads 0-3 reads it) and
    changing key head 0's changes the result."""
    p = dict(ref.layer_of(params, 0)["mixer"])
    hd, d = small["head_dim"], small["hidden_size"]
    p["wo"] = jnp.eye(8 * hd, d, dtype=jnp.bfloat16)  # heads 0-3: 64 dims
    x, real, position = _inputs(small)
    base = hm.cca(p, x, real, position, cfg, F32)
    wk = np.asarray(p["wk"], np.float32)
    wk[:, key_head * hd:(key_head + 1) * hd] *= -1.0
    changed = hm.cca(dict(p, wk=jnp.asarray(wk, jnp.bfloat16)), x, real,
                     position, cfg, F32)
    assert (not np.array_equal(np.asarray(base), np.asarray(changed))
            ) is moved


def test_the_head_is_the_embedding(params, cfg):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, params["embed"].shape[1])), F32)
    with jax.default_matmul_precision("highest"):
        got = hm.slice_logits(params, x, cfg, F32)
        want = ref.tied_head(params, x, cfg.eps)
    assert got.shape == (3, params["embed"].shape[0])
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert "head" not in params


# -- the whole and its halves ------------------------------------------------------------

def test_the_two_halves_of_the_experts_add_up_to_the_layer(small, params,
                                                           cfg):
    """Experts 0-7 and 8-15 held in turn (``experts_held``): the two
    partial results add up to the all-held layer's, which is the
    reference's; every token is served in exactly one half or skipped in
    both, and the router's state is the same in each."""
    p = ref.layer_of(params, 1)["ffn"]
    x, real, _ = _inputs(small)
    r = jnp.asarray(np.random.default_rng(4).normal(size=(
        x.shape[0] * x.shape[1], small["router_hidden_size"])), F32)
    with jax.default_matmul_precision("highest"):
        want, want_r, choice = ref.experts(p, x, real, r, small)
        whole, _, all_counts = hm.moe(p, x, r, real, cfg, F32)
        total, served = 0.0, 0
        for first in (0, 8):
            held = {"first": first, "count": 8}
            half = hm.HybridConfig.from_dict(dict(
                small, num_experts=8, experts_held=held))
            mine = dict(p, experts={k: v[first:first + 8]
                                    for k, v in p["experts"].items()})
            y, r_half, counts = hm.moe(mine, x, r, real, half, F32)
            ref_y, _, _ = ref.experts(mine, x, real, r, dict(
                small, num_experts=8, experts_held=held))
            assert np.allclose(np.asarray(y), np.asarray(ref_y), atol=2e-4)
            assert np.allclose(np.asarray(r_half), np.asarray(want_r), atol=1e-5)
            assert int(counts["skipped"]) == int(all_counts["skipped"])
            total, served = total + y, served + int(counts["served"])
    n = int(np.asarray(real).sum())
    assert served == int(all_counts["served"]) == int(choice[:, :-1].sum())
    assert served + int(all_counts["skipped"]) == n
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(total) * keep, np.asarray(whole) * keep,
                       atol=1e-5)
    assert np.allclose(np.asarray(total) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


# -- stacked or listed, and the served path ---------------------------------------------

def test_a_listed_stack_gives_what_the_scanned_one_gives(params, cfg, rows):
    """The same layers as a list (unrolled, as a mixed stack is) and as
    one tree with the layers on the leading axis (scanned)."""
    hist, filled = _windows(rows, [8, 2])
    listed = dict(params, layers=[ref.layer_of(params, i) for i in range(3)])
    x, aux = _hidden(params, hist, filled, cfg)
    y, other = _hidden(listed, hist, filled, cfg)
    assert np.allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    for key in aux:
        assert np.array_equal(np.asarray(aux[key]), np.asarray(other[key]))


def test_the_settings_come_from_the_published_keys(small, cfg):
    assert cfg.mixers == (("cca", hm.Cca(
        heads=8, kv_heads=2, head_dim=16, rotary_dim=8, theta=5e6)),)
    assert cfg.layers == (("cca", "moe"),) * 3 and cfg.moe_layers == 3
    assert (cfg.routed, cfg.held_count, cfg.per_token) == (17, 16, 1)
    assert cfg.router == "carried_mlp" and cfg.routing is None
    assert cfg.residual == "scaled" and cfg.tied_head and cfg.eps == 1e-5
    assert registry.get_history("hybrid_moe").config_from(small) == cfg
    with pytest.raises(ValueError, match="zaya"):
        hm.HybridConfig.from_dict(dict(small, num_experts_per_tok=2))
    with pytest.raises(ValueError, match="num_experts"):
        hm.HybridConfig.from_dict(dict(small, num_experts=8))


def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had, and the counters add up."""
    from ccfd_tpu.metrics.prom import Registry

    reg = Registry()
    scorer = SeqScorer(params, length=LENGTH, batch_sizes=(4, 16),
                       compute_dtype="float32", registry=reg,
                       family="hybrid_moe", family_config=cfg)
    rng = np.random.default_rng(11)
    customers = rng.choice([3, 5, 8, 13], size=37, p=[0.55, 0.25, 0.15, 0.05])
    sent = rows[rng.integers(0, len(rows), len(customers))]
    served = np.concatenate([
        scorer.score(sent[lo:lo + 9], [int(c) for c in customers[lo:lo + 9]])
        for lo in range(0, len(customers), 9)])
    hist, filled = ref.histories(
        customers, np.arange(len(customers)), sent,
        np.arange(len(customers)), LENGTH, np.full((14, 1), -1, np.int64))
    logits, choice = ref.forward(params, small, hist, filled)
    want = 1.0 / (1.0 + np.exp(-np.asarray(ref.verdict_logit(
        np.asarray(logits), small), np.float64)))
    assert np.allclose(served, want, rtol=2e-3, atol=1e-6)
    grid = scorer.executable_grid()
    assert grid["model"] == "hybrid_moe" and grid["experts_held"] == [0, 16]
    assert grid["router"] == "carried_mlp"
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_skipped_tokens_total", "moe_routed_tokens_total",
        "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice[..., :-1].sum()
    assert total["moe_skipped_tokens_total"] == choice[..., -1].sum() > 0
    assert total["moe_pairs_served_total"] + total[
        "moe_skipped_tokens_total"] == total["moe_routed_tokens_total"] * 3
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
