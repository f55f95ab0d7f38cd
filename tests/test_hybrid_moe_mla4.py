"""The ``hybrid_moe`` family's ``mistral4`` model (MLA with low-rank queries,
interleaved YaRN rotary and its softmax scale in every layer, softmax top 4
over 32 experts of which 8 are held, one shared expert, untied head;
models/hybrid_moe.py) against its plain reference
(benchmark/reference/mla_moe_f32.py) at the small preset, seeded weights, on
the CPU: the whole model in both precisions, each part alone, the rotary's
frequencies against a table worked out by hand, padding, causality, stacked
against listed, the four shares of the experts, the settings of all four
models of the family, and the served path through ``SeqScorer``."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.ops import grouped_experts, kernels
from ccfd_tpu.models import registry
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
LENGTH, COLS = 8, 30


def _config(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    return _config("tests", "benchmark", "mistral4_small_config.json")


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


def _with_mla(cfg, **changes):
    """``cfg`` with some of its MLA settings replaced."""
    return dataclasses.replace(cfg, mixers=(("mla", dataclasses.replace(
        cfg.mixer("mla"), **changes)),))


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
    layers, per_token = len(small["layers_kept"]), small["num_experts_per_tok"]
    # every chosen pair is served here or is another chip's
    assert int(aux["pairs_served"]) + int(aux["pairs_absent"]) == int(
        real.sum()) * layers * per_token
    assert int(aux["pairs_served"]) == int(np.asarray(aux["pairs"]).sum())
    assert int(aux["skipped_tokens"]) == 0
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
        assert np.array_equal(np.asarray(aux["row_pairs"]),
                              want_choice[..., :8].sum((1, 2)))


@pytest.mark.parametrize("part", ["mla", "route", "experts"])
def test_each_part_agrees_with_the_reference(small, params, cfg, part):
    """MLA with padding on the left of one row and a length past the 64
    original positions (query blocks against the full masked softmax, the
    rotary laid out by halves against pairs where they stand, the query
    scale past L0); the softmax router (same experts, same weights); the
    tile loop and the shared expert against the loop over experts."""
    p = ref.layer_of(params, 1)
    x, real, position = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    with jax.default_matmul_precision("highest"):
        if part == "mla":
            want = ref.mla(p["mixer"], x, real, position, small)
            got = hm.mla(p["mixer"], x, real, position, cfg, F32)
        elif part == "route":
            want_e, want, _ = ref.route(p["ffn"], flat, flat_real, small)
            got_e, got = hm.route(p["ffn"], flat, flat_real, cfg)
            assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
            assert np.allclose(np.asarray(got).sum(1)[np.asarray(flat_real)],
                               small["routed_scaling_factor"], atol=1e-6)
        else:
            want, choice = ref.experts(p["ffn"], x, real, small)
            got, r, counts = hm.moe(p["ffn"], x, None, real, cfg, F32)
            assert r is None
            assert np.array_equal(np.asarray(counts["row_choice"]), choice)
            assert int(counts["served"]) == int(choice[:, :8].sum())
            assert int(counts["absent"]) == int(choice[:, 8:].sum())
    keep = np.asarray(real)[..., None] if np.ndim(got) == 3 else True
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_the_softmax_over_all_is_the_softmax_over_the_chosen_logits(
        small, params, cfg):
    """What ``assumed.scoring_func`` says: with the weights renormalised
    over the chosen four, softmax over all 32 then top 4 is top 4 of the
    logits then softmax over those."""
    p = ref.layer_of(params, 0)["ffn"]
    x, real, _ = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    with jax.default_matmul_precision("highest"):
        chosen, w = hm.route(p, flat, flat_real, cfg)
        logits = flat @ p["router"].astype(F32)
    top, at = jax.lax.top_k(logits, 4)
    keep = np.asarray(flat_real)
    assert np.array_equal(np.asarray(chosen)[keep], np.asarray(at)[keep])
    assert np.allclose(np.asarray(w)[keep], np.asarray(
        jax.nn.softmax(top, axis=-1))[keep], atol=1e-6)
    assert (np.asarray(chosen)[~keep] == -1).all()
    assert not np.asarray(w)[~keep].any()


# -- the rotary ----------------------------------------------------------------------

def _real_config():
    return _config("benchmark", "configs", "kafka_history_mistral4.json")


@pytest.mark.parametrize("which", ["small", "real"])
def test_yarn_frequencies_against_a_table_worked_out_by_hand(small, which):
    """Small preset: d = 8, theta 1e4, L0 = 64, factor 128. f = 1, 0.1,
    0.01, 0.001. low = floor(8 ln(64 / (32 x 2 pi)) / (2 ln 1e4)) = floor(
    -0.497) -> 0; high = ceil(8 ln(64 / (2 pi)) / (2 ln 1e4)) = ceil(1.008)
    = 2; ramp 0, 0.5, 1, 1: f' = 1, 0.05 + 0.05 / 128, 0.01 / 128, 0.001 /
    128. Published: d = 64, L0 = 8,192: low = floor(12.88) = 12, high =
    ceil(24.92) = 25: dims 0-12 keep f_i = 1e4^(-i / 32), dims 25-31 are
    f_i / 128, dim 18 is (6 / 13) f / 128 + (7 / 13) f."""
    model = small if which == "small" else _real_config()
    s = hm.Mla.read(model)
    got = np.asarray(hm._frequencies(s.theta, s.rope, s.yarn), np.float64)
    theirs = np.asarray(ref.yarn_frequencies(model["rope_parameters"],
                                             s.rope))
    if which == "small":
        want = [1.0, 0.05 + 0.05 / 128, 0.01 / 128, 0.001 / 128]
    else:
        f = 1e4 ** (-np.arange(32) / 32.0)
        want = f.copy()
        want[25:] = f[25:] / 128
        for i in range(13, 25):
            ramp = (i - 12) / 13.0
            want[i] = ramp * f[i] / 128 + (1 - ramp) * f[i]
        assert want[18] == pytest.approx(
            (6 / 13) * f[18] / 128 + (7 / 13) * f[18])
    assert np.allclose(got, want, rtol=2e-6)
    assert np.allclose(theirs, want, rtol=1e-12)
    # plain frequencies where the model has no YaRN
    assert np.allclose(np.asarray(hm._frequencies(1e4, 8)),
                       [1.0, 0.1, 0.01, 0.001], rtol=1e-6)


def test_interleaved_pairs_against_the_halves(small, params, cfg):
    """Pair i is dims (2i, 2i + 1): turning ``x`` by neighbours gives what
    turning its even dims followed by its odd dims gives by halves; the
    reference keeps each pair where it stands, and q k^T is the same. A
    model read as rotating by halves answers otherwise."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 50, 4, 8)), F32)
    position = jnp.asarray(np.arange(50)[None, :].repeat(2, 0))
    freq = hm._frequencies(1e4, 8)
    by_pairs = hm._rotary(x, position, freq, interleaved=True)
    by_halves = hm._rotary(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                           position, freq)
    assert np.array_equal(np.asarray(by_pairs), np.asarray(by_halves))
    theirs = ref.rotary_interleaved(x, position, tuple(np.asarray(freq)), 1.0)
    assert np.allclose(
        np.asarray(theirs)[..., 0::2], np.asarray(by_pairs)[..., :4],
        atol=1e-6)
    assert np.allclose(
        np.asarray(theirs)[..., 1::2], np.asarray(by_pairs)[..., 4:],
        atol=1e-6)
    p = ref.layer_of(params, 0)["mixer"]
    z, real, pos = _inputs(small)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(p, z, real, pos, small)
        halves = hm.mla(p, z, real, pos, _with_mla(cfg, interleaved=False),
                        F32)
    keep = np.asarray(real)[..., None]
    assert np.abs((np.asarray(halves) - np.asarray(want)) * keep).max() > 1e-2


def test_the_query_goes_through_its_normed_latent(small, params, cfg):
    """c_q = RMSNorm(z W_dq) with the norm's own weight, then W_uq: a
    changed norm weight changes the answer as the reference's does; a
    product of the two matrices at full rank (no norm) is another model."""
    p = dict(ref.layer_of(params, 2)["mixer"])
    z, real, pos = _inputs(small)
    keep = np.asarray(real)[..., None]
    other = dict(p, q_norm=p["q_norm"].at[::2].mul(3.0))
    with jax.default_matmul_precision("highest"):
        base = hm.mla(p, z, real, pos, cfg, F32)
        got = hm.mla(other, z, real, pos, cfg, F32)
        want = ref.mla(other, z, real, pos, small)
        full = dict(p, wq=(p["wdq"].astype(F32) @ p["wuq"].astype(F32)))
        unnormed = hm.mla(full, z, real, pos, _with_mla(cfg, q_rank=None),
                          F32)
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)
    assert np.abs((np.asarray(got) - np.asarray(base)) * keep).max() > 1e-2
    assert np.abs((np.asarray(unnormed) - np.asarray(base)) * keep
                  ).max() > 1e-2


def test_the_softmax_scale_is_yarns(small, cfg):
    """sigma = (nope + rope)^-0.5 x (0.1 ln(factor) + 1)^2 under YaRN with
    ``mscale_all_dim`` 1, the plain 1 / sqrt(width) without it; cos and sin
    are scaled by m(mscale) / m(mscale_all_dim) = 1."""
    m = 0.1 * math.log(128.0) + 1.0
    assert m == pytest.approx(1.4852, abs=1e-4)
    real = hm.Mla.read(_real_config())
    assert real.scale == pytest.approx(128 ** -0.5 * m * m)
    assert cfg.mixer("mla").scale == pytest.approx(16 ** -0.5 * m * m)
    assert real.turn_scale == cfg.mixer("mla").turn_scale == 1.0
    assert ref.dims(_real_config())["sigma"] == pytest.approx(real.scale)
    ling = hm.Mla.read(_config("benchmark", "configs",
                               "kafka_history_ling3.json"))
    assert ling.yarn is None and ling.scale == pytest.approx(192 ** -0.5)
    assert _with_mla(cfg, yarn=None).mixer("mla").scale == 0.25


@pytest.mark.parametrize("tokens", [240, 40])
def test_the_query_scale_past_the_original_positions(small, params, cfg,
                                                     tokens):
    """q_t <- q_t (1 + 0.1 ln(1 + floor(t / 64))) at the small preset. At
    240 tokens the program agrees with the reference, which computes it at
    every length; without it the rows before position 64 stay as they are
    (causal: their queries are unscaled and keys carry no scale) and the
    rows from 64 on move. At 40 tokens the program traces no scale at all
    and still agrees."""
    p = ref.layer_of(params, 0)["mixer"]
    z, real, pos = _inputs(small, n=1, t=tokens, pad=(0,))
    yarn = cfg.mixer("mla").yarn
    without = _with_mla(cfg, yarn=dataclasses.replace(yarn, query_beta=0.0))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mla(p, z, real, pos, small))
        got = np.asarray(hm.mla(p, z, real, pos, cfg, F32))
        plain = np.asarray(hm.mla(p, z, real, pos, without, F32))
    assert np.allclose(got, want, atol=2e-4, rtol=2e-4)
    assert np.allclose(plain[:, :64], got[:, :64], atol=1e-6)
    if tokens > 64:
        assert np.abs(plain[:, 64:] - got[:, 64:]).max() > 1e-3
    else:
        text = str(jax.make_jaxpr(lambda a: hm.mla(
            p, a, real, pos, cfg, F32))(z))
        assert "log1p" not in text and "floor" not in text


# -- padding and causality ------------------------------------------------------------

@pytest.mark.parametrize("padding", ["zeros", "noise"])
def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows, padding):
    """One history of 5 records at windows of 8, 16 and 64 records gives
    one verdict and one routing, with other records where the padding is,
    too: positions count from the first real token and padding keys are
    masked."""
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


def test_a_window_of_padding_alone_routes_nowhere(params, cfg):
    _, aux = hm.apply_serving(params, np.zeros((2, LENGTH, COLS), np.float32),
                              np.zeros(2, np.int32), cfg, F32)
    assert int(aux["pairs_served"]) == int(aux["pairs_absent"]) == 0
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
    assert np.allclose(np.asarray(x)[:, :before][real],
                       np.asarray(y)[:, :before][real], atol=1e-5, rtol=0)
    assert np.abs(np.asarray(x)[:, before:] - np.asarray(y)[:, before:]
                  ).max() > 1e-3


# -- the whole and its shares ------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(small):
    """What each of the 4 chips computes of one expert layer (its 8 of the
    32 experts), with the shared expert counted once, adds up to the
    reference's layer over all 32 experts; every chosen pair is served on
    exactly one chip, and each chip counts the other three's as absent."""
    x, real, _ = _inputs(small)
    whole = dict(small, n_routed_experts=32,
                 experts_held={"first": 0, "count": 32})
    p = ref.layer_of(ref.make_params(whole), 1)["ffn"]
    n = int(np.asarray(real).sum())
    with jax.default_matmul_precision("highest"):
        want, choice = ref.experts(p, x, real, whole)
        shared = ref.shared.swiglu(p["shared"], x)
        total, served = shared, 0
        for share in range(4):
            held = {"first": 8 * share, "count": 8}
            mine = dict(p, experts={k: v[8 * share:8 * share + 8]
                                    for k, v in p["experts"].items()})
            cfg = hm.HybridConfig.from_dict(dict(small, experts_held=held))
            got, _, counts = hm.moe(mine, x, None, real, cfg, F32)
            total = total + (got - shared)
            served += int(counts["served"])
            assert int(counts["served"]) + int(counts["absent"]) == 4 * n
            assert int(counts["served"]) == int(
                choice[:, 8 * share:8 * share + 8].sum())
    assert served == int(choice.sum()) == 4 * n
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(total) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_a_listed_stack_gives_what_the_scanned_one_gives(small, params, cfg,
                                                         rows):
    """``layer_stack`` ``listed`` draws the same values as ``scanned``, and
    the program unrolls the one where it scans the other."""
    hist, filled = _windows(rows, [8, 2])
    listed = ref.make_params(dict(small, layer_stack="listed"))
    assert isinstance(listed["layers"], list) and len(listed["layers"]) == 3
    for i in range(3):
        for a, b in zip(jax.tree.leaves(ref.layer_of(params, i)),
                        jax.tree.leaves(listed["layers"][i])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    x, aux = _hidden(params, hist, filled, cfg)
    y, other = _hidden(listed, hist, filled, cfg)
    assert np.allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    for key in aux:
        assert np.array_equal(np.asarray(aux[key]), np.asarray(other[key]))


# -- the settings, of all four models -----------------------------------------------------

def _ling_wants(cfg):
    assert [name for name, _ in cfg.mixers] == ["kda", "mla"]
    assert cfg.mixer("kda") == hm.Kda(heads=4, head_dim=16,
                                      lower_bound=-5.0, chunk=64)
    mla = cfg.mixer("mla")
    assert (mla.q_rank, mla.part_norms, mla.interleaved, mla.yarn) == (
        None, True, False, None)
    assert cfg.router == "top_k" and cfg.routing == hm.TopK(
        "sigmoid", True, 4, 2, 2.5)
    assert cfg.residual == "plain" and not cfg.tied_head
    assert {kind for kind, _ in cfg.layers} == {"kda", "mla"}
    assert ("kda", "dense") in cfg.layers


def _zaya_wants(cfg):
    assert cfg.mixers == (("cca", hm.Cca(
        heads=8, kv_heads=2, head_dim=16, rotary_dim=8, theta=5e6)),)
    assert cfg.router == "carried_mlp" and cfg.routing is None
    assert cfg.residual == "scaled" and cfg.tied_head
    assert cfg.layers == (("cca", "moe"),) * 3


def _mistral4_wants(cfg):
    assert cfg.mixers == (("mla", hm.Mla(
        heads=4, nope=8, rope=8, v_dim=16, kv_rank=16, q_rank=32,
        part_norms=False, interleaved=True, theta=1e4, yarn=hm.Yarn(
            factor=128.0, original=64, beta_fast=32.0, beta_slow=1.0,
            mscale=1.0, mscale_all_dim=1.0, query_beta=0.1))),)
    assert cfg.router == "top_k" and cfg.routing == hm.TopK(
        "softmax", False, 1, 1, 1.0)
    assert cfg.residual == "plain" and not cfg.tied_head
    assert cfg.layers == (("mla", "moe"),) * 3 and cfg.moe_layers == 3
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        32, 0, 8, 4)


def _xing4_wants(cfg):
    assert cfg.mixers == (("mla", hm.Mla(
        heads=4, nope=8, rope=8, v_dim=16, kv_rank=16, q_rank=32,
        part_norms=False, interleaved=True, theta=1e4, yarn=hm.Yarn(
            factor=64.0, original=64, beta_fast=32.0, beta_slow=1.0,
            mscale=1.0, mscale_all_dim=1.0, query_beta=0.0))),)
    assert cfg.router == "top_k" and cfg.routing == hm.TopK(
        "sigmoid", True, 1, 1, 2.0)
    assert cfg.residual == "mhc" and cfg.residual_settings == hm.Mhc(
        streams=4, sinkhorn_iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert not cfg.tied_head
    assert cfg.layers == (("mla", "dense"),) + (("mla", "moe"),) * 3
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        16, 0, 16, 4)


@pytest.mark.parametrize("preset,wants", [
    ("ling3_small_config.json", _ling_wants),
    ("zaya1_small_config.json", _zaya_wants),
    ("mistral4_small_config.json", _mistral4_wants),
    ("xing4_small_config.json", _xing4_wants)])
def test_a_model_is_its_kinds_settings_and_what_all_share(preset, wants):
    """``from_dict`` reads each model through its own small reader: the
    stack, one settings object a mixer kind, a router and a residual rule,
    and nothing of another model's (no field of ``HybridConfig`` is one
    model's)."""
    model = _config("tests", "benchmark", preset)
    cfg = hm.HybridConfig.from_dict(model)
    wants(cfg)
    assert registry.get_history("hybrid_moe").config_from(model) == cfg
    assert hash(cfg) == hash(hm.HybridConfig.from_dict(model))
    assert {name for name, _ in cfg.mixers} == {m for m, _ in cfg.layers}
    assert all(name in hm.MIXERS for name, _ in cfg.mixers)
    assert cfg.router in hm.ROUTERS and cfg.residual in hm.RESIDUALS
    assert (cfg.eps, cfg.bins) == (model["rms_norm_eps"], model["bins"])
    shared = {f.name for f in dataclasses.fields(cfg)}
    assert shared == {
        "eps", "layers", "mixers", "router", "routing", "routed",
        "held_first", "held_count", "per_token", "bins", "fraud_id",
        "legit_id", "shift", "residual", "residual_settings", "tied_head",
        "embed_scale", "logit_divisor", "expert_body", "norm_offset"}
    described = registry.get_history("hybrid_moe").describe(cfg)
    assert described["residual"] == cfg.residual
    assert set(described["kinds"]) == {name for name, _ in cfg.mixers} | (
        {cfg.router} if cfg.routing is not None else set()) | (
        {cfg.residual} if cfg.residual_settings is not None else set())
    json.dumps(described)


@pytest.mark.parametrize("change,match", [
    ({"model_type": "gpt2"}, "model_type"),
    ({"n_routed_experts": 16}, "n_routed_experts"),
    ({"first_k_dense_replace": 1}, "mistral4"),
    ({"n_group": 2}, "mistral4"),
    ({"norm_topk_prob": False}, "mistral4")])
def test_a_configuration_the_reader_cannot_serve_is_refused(small, change,
                                                            match):
    with pytest.raises(ValueError, match=match):
        hm.HybridConfig.from_dict(dict(small, **change))


def test_the_real_configuration_reads_at_its_published_widths():
    cfg = hm.HybridConfig.from_dict(_real_config())
    mla = cfg.mixer("mla")
    assert (mla.heads, mla.nope, mla.rope, mla.v_dim, mla.kv_rank,
            mla.q_rank) == (32, 64, 64, 128, 256, 1024)
    assert mla.yarn.original == 8192 and mla.interleaved
    assert cfg.layers == (("mla", "moe"),) * 6
    assert (cfg.routed, cfg.held_count, cfg.per_token) == (128, 32, 4)


# -- the layout and the served path -------------------------------------------------------

@pytest.mark.parametrize("stack", ["scanned", "listed"])
def test_the_layout_rules_cover_the_tree(small, params, stack):
    """Experts over the expert axis (behind the layers' axis where the
    tree is stacked), embedding rows and head columns too; both low-rank
    paths, their norms and the router whole on every chip."""
    from jax.sharding import PartitionSpec as P

    from ccfd_tpu.parallel import partition
    from ccfd_tpu.parallel.mesh import EXPERT_AXIS

    tree = params if stack == "scanned" else ref.make_params(
        dict(small, layer_stack="listed"))
    specs = partition.match_partition_rules(
        partition.hybrid_moe_rules(EXPERT_AXIS), tree)
    layer = specs["layers"] if stack == "scanned" else specs["layers"][2]
    experts = P(None, EXPERT_AXIS, None, None) if stack == "scanned" \
        else P(EXPERT_AXIS, None, None)
    for name in ("gate", "up", "down"):
        assert layer["ffn"]["experts"][name] == experts
        assert layer["ffn"]["shared"][name] == P()
    for name in ("wdq", "q_norm", "wuq", "wdkv", "c_norm", "wukv", "wo"):
        assert layer["mixer"][name] == P()
    assert layer["ffn"]["router"] == P()
    assert specs["embed"] == P(EXPERT_AXIS, None)
    assert specs["head"] == P(None, EXPERT_AXIS)
    assert partition.expert_share(32, 4, 0) == small["experts_held"]
    # a spec has as many entries as its leaf has axes, or none
    for leaf, spec in zip(jax.tree.leaves(tree), jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, P))):
        assert len(spec) in (0, leaf.ndim)


def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had, and the counters add up to
    four pairs a token and layer."""
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
    assert grid["model"] == "hybrid_moe" and grid["experts_held"] == [0, 8]
    assert grid["router"] == "top_k"
    assert grid["kinds"]["mla"]["q_rank"] == 32
    assert grid["kinds"]["top_k"]["score"] == "softmax"
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_pairs_absent_total", "moe_skipped_tokens_total",
        "moe_routed_tokens_total", "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice[..., :8].sum()
    assert total["moe_pairs_absent_total"] == choice[..., 8:].sum()
    assert total["moe_pairs_served_total"] + total[
        "moe_pairs_absent_total"] == total["moe_routed_tokens_total"] * 3 * 4
    assert total["moe_skipped_tokens_total"] == 0
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
