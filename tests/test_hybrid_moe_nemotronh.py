"""The ``hybrid_moe`` family's ``nemotron_h`` model (layers of ONE sublayer
each: a Mamba-2 mixer with several groups of B and C and an RMS norm inside
each group of gated values, grouped-query attention ``head_dim`` wide
without positions, or the expert layer: sigmoid scores with a choice bias,
experts of two matrices with relu squared and no gate, one shared expert;
an untied head; models/hybrid_moe.py) against its plain reference
(benchmark/reference/ssm_relu2_moe_f32.py: the recurrence a token at a
time) at the small preset, seeded weights, on the CPU: the whole model in
both precisions, each part alone, the grouped kernels under the
interpreter over a padded stack, a stack of single sublayers scanned
against listed, padding, the reader's refusals, the two shares of the
experts, the settings, the kernels at the cell's shapes, the served path
through ``SeqScorer``, and that the five accepted models hand back the
leaves they did."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssm_relu2_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.models import registry
from ccfd_tpu.ops import grouped_experts as ge
from ccfd_tpu.ops import kernels
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
LENGTH, COLS = 8, 30
HELD = 4  # of 8 routed experts at the small preset


def _config(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    return _config("tests", "benchmark", "nemotron3n_small_config.json")


@pytest.fixture(scope="module")
def params(small):
    return ref.make_params(small)


@pytest.fixture(scope="module")
def cfg(small):
    return hm.HybridConfig.from_dict(small)


@pytest.fixture(scope="module")
def rows():
    return table.surrogate_rows(4096, 7)[0]


def _real_config():
    return _config("benchmark", "configs", "kafka_history_nemotron3n.json")


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
    return jnp.asarray(x), jnp.asarray(real)


def _real_tokens(filled):
    return np.asarray(ref.shared.real_tokens(jnp.asarray(filled), LENGTH,
                                             COLS))


def _layer(params, small, letter):
    """The first kept layer of this letter of the pattern."""
    return ref.layer_of(params, small["hybrid_override_pattern"].index(
        letter))


# -- the whole model, and each part ------------------------------------------------

@pytest.mark.parametrize("dtype,worst,mean", [
    (F32, 2e-4, 2e-5),  # the reference's own precision: tight
    (jnp.bfloat16, None, 0.05),  # as served: a token near a tie may choose
    # another expert, so the widest gap is wide; the mean is not
])
def test_logits_and_routing_agree_with_the_reference_at_every_position(
        small, params, cfg, rows, dtype, worst, mean):
    """Pattern MEMEM*E, a row of three records and one of a single record
    padded on the left."""
    hist, filled = _windows(rows, [8, 3, 1])
    want, want_choice = ref.forward(params, small, hist, filled,
                                    every_position=True)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = _real_tokens(filled)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    assert int(aux["routed_tokens"]) == int(real.sum())
    expert_layers, per_token = 3, small["num_experts_per_tok"]
    assert cfg.moe_layers == expert_layers
    assert np.asarray(aux["pairs"]).shape == (expert_layers, HELD)
    assert np.asarray(aux["row_choice"]).shape == (3, expert_layers, 8)
    # every chosen pair is served here or is the other chip's
    assert int(aux["pairs_served"]) + int(aux["pairs_absent"]) == int(
        real.sum()) * expert_layers * per_token
    assert int(aux["pairs_served"]) == int(np.asarray(aux["pairs"]).sum())
    assert int(aux["pairs_absent"]) > 0 and int(aux["skipped_tokens"]) == 0
    assert float(aux["ssm_log_decay_min"]) < 0
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
        assert np.array_equal(np.asarray(aux["row_pairs"]),
                              want_choice[..., :HELD].sum((1, 2)))


@pytest.mark.parametrize("part", ["mamba2", "gqa", "route", "experts"])
def test_each_part_agrees_with_the_reference(small, params, cfg, part):
    """The chunked scan (a chunk of 32 against 100 tokens, 37 padding
    tokens on the left of one row, two groups of B and C, the norm inside
    each of two groups), attention 16 wide where hidden / heads is 8, the
    sigmoid router with its bias, and the relu-squared experts with the
    shared one, each alone in float32."""
    x, real = _inputs(small)
    keep = np.asarray(real)[..., None]
    with jax.default_matmul_precision("highest"):
        if part == "mamba2":
            p = _layer(params, small, "M")["mixer"]
            want = ref.mamba(p, x, real, small)
            got, low = hm.mamba2(p, x, real, cfg, F32)
            assert float(low) < 0
        elif part == "gqa":
            p = _layer(params, small, "*")["mixer"]
            want = ref.attention(p, x, real, small)
            got = hm.gqa(p, x, real, cfg, F32)
        else:
            p = _layer(params, small, "E")["ffn"]
            flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
            chosen, w = ref.route(p, flat, flat_real, small)
            got_chosen, got_w = hm.route(p, flat, flat_real, cfg)
            assert np.array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(got_chosen), -1))
            assert np.allclose(np.sort(np.asarray(w), -1),
                               np.sort(np.asarray(got_w), -1), atol=1e-6)
            live = np.asarray(w)[np.asarray(flat_real)]
            assert np.allclose(live.sum(-1), 2.5, atol=1e-5)
            if part == "route":
                return
            want, choice = ref.experts(p, x, real, small)
            got, _, counts = hm.moe(p, x, None, real, cfg, F32)
            assert int(counts["served"]) == int(choice[:, :HELD].sum())
            assert int(counts["absent"]) == int(choice[:, HELD:].sum())
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_gated_norm_norms_inside_each_group(groups):
    """Against a loop over the groups: each group's values are normed by
    their own root mean square, and at one group that is the norm over
    all."""
    rng = np.random.default_rng(groups)
    y, gate = (jnp.asarray(rng.normal(size=(2, 5, 64)), F32)
               for _ in range(2))
    weight = jnp.asarray(1.0 + 0.1 * rng.normal(size=64), F32)
    got = np.asarray(hm._gated_norm(y, gate, weight, 1e-5, groups))
    v = np.asarray(y * jax.nn.silu(gate), np.float64)
    size = 64 // groups
    for g in range(groups):
        part = v[..., g * size:(g + 1) * size]
        want = part / np.sqrt((part * part).mean(-1, keepdims=True) + 1e-5)
        assert np.allclose(got[..., g * size:(g + 1) * size],
                           want * np.asarray(weight)[g * size:(g + 1) * size],
                           atol=1e-5)
    if groups > 1:  # and not the norm over all of them
        assert not np.allclose(got, np.asarray(hm._gated_norm(
            y, gate, weight, 1e-5)), atol=1e-3)


# -- the relu-squared experts: the loop, and the kernels over a padded stack ----------

def _relu2_experts(held, hidden, width, seed=0):
    rng = np.random.default_rng(seed)
    return {"up": jnp.asarray(rng.normal(size=(held, hidden, width))
                              / hidden ** 0.5, F32),
            "down": jnp.asarray(rng.normal(size=(held, width, hidden))
                                / width ** 0.5, F32)}


def _padded(ex, stored):
    width = ex["up"].shape[-1]
    return {"up": jnp.pad(ex["up"], ((0, 0), (0, 0), (0, stored - width))),
            "down": jnp.pad(ex["down"], ((0, 0), (0, stored - width),
                                         (0, 0)))}


def test_the_plain_loop_is_a_loop_over_the_experts(cfg):
    """``held_experts`` with the relu-squared body (through XLA: 24 is no
    lane tile) against every held expert run in turn on every token,
    weighted where the token chose it."""
    rng = np.random.default_rng(5)
    n, k = 90, 3
    ex = _relu2_experts(HELD, 64, 24)
    z = jnp.asarray(rng.normal(size=(n, 64)), F32)
    chosen = np.stack([rng.permutation(8)[:k] for _ in range(n)])
    w = rng.uniform(0.1, 1.0, size=(n, k)).astype(np.float32)
    assert not ge.kernel_fits(ex["up"], F32, 1)
    with jax.default_matmul_precision("highest"):
        y, pairs, served = hm.held_experts(
            ex, z, jnp.asarray(chosen, jnp.int32), jnp.asarray(w), cfg, F32)
        want = np.zeros((n, 64))
        for e in range(HELD):
            h = np.square(np.maximum(np.asarray(z, np.float64)
                                     @ np.asarray(ex["up"][e]), 0.0))
            weight = np.where(chosen == e, w, 0.0).sum(-1)
            want += (h @ np.asarray(ex["down"][e])) * weight[:, None]
    assert int(served) == int(pairs.sum()) == int((chosen < HELD).sum())
    assert np.allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)


def test_the_kernels_over_the_padded_stack_equal_the_unpadded_loop(
        cfg, monkeypatch):
    """Experts 96 wide stored with 128 columns / rows, the further ones
    zeros: the stored stack is one the kernels admit (interpreted here),
    the published one is not; the kernels over the stored stack give the
    plain loop over the published one, and the plain loop gives the same
    bits over either (relu(0)^2 = 0, and a zero row of ``down`` adds
    nothing)."""
    monkeypatch.setattr(hm, "MOE_CHUNK", 128)
    rng = np.random.default_rng(9)
    n, k = 200, 3
    published = _relu2_experts(HELD, 128, 96, seed=1)
    stored = _padded(published, 128)
    z = jnp.asarray(rng.normal(size=(n, 128)), F32)
    real = rng.uniform(size=n) > 0.2
    chosen = np.stack([rng.permutation(8)[:k] for _ in range(n)])
    chosen[~real] = -1
    args = (z, jnp.asarray(chosen, jnp.int32), jnp.asarray(
        rng.uniform(0.1, 1.0, size=(n, k)), F32))
    assert ge.kernel_fits(stored["up"], F32, 1)
    assert not ge.kernel_fits(published["up"], F32, 1)

    def layer(ex, tile):
        return hm.held_experts(ex, *args, cfg, F32, tile=tile)

    assert kernels.kernels_of(lambda ex: layer(ex, 32), stored) == frozenset(
        ge.KERNELS)
    assert not kernels.kernels_of(lambda ex: layer(ex, 32), published)
    with jax.default_matmul_precision("highest"):
        y, pairs, served = layer(stored, 32)
        want, want_pairs, want_served = layer(published, 32)
        with monkeypatch.context() as m:
            m.setattr(ge, "kernel_fits", lambda *_: False)
            loop_stored, _, _ = layer(stored, 32)
    assert np.array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert int(served) == int(want_served) == int(
        ((chosen >= 0) & (chosen < HELD)).sum())
    # to the last bit of float32: padding changes no sum
    assert np.array_equal(np.asarray(loop_stored), np.asarray(want))
    assert np.array_equal(np.asarray(y), np.asarray(want))
    assert not np.asarray(y)[~real].any()


@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (jnp.bfloat16, 0.02)],
                         ids=["float32", "bfloat16"])
def test_the_relu2_kernels_equal_the_loop(cfg, monkeypatch, dtype, tol):
    """Lane-wide experts, several chunks of several tiles, a fifth of the
    tokens padding, the second of two shares: ``y`` to the rows' rounding,
    the counts exactly."""
    monkeypatch.setattr(hm, "MOE_CHUNK", 128)
    second = dataclasses.replace(cfg, held_first=HELD)
    rng = np.random.default_rng(13)
    n, k = 300, 3
    ex = jax.tree.map(lambda a: a.astype(dtype), _relu2_experts(
        HELD, 128, 256, seed=2))
    z = jnp.asarray(rng.normal(size=(n, 128)), F32)
    real = rng.uniform(size=n) > 0.2
    chosen = np.stack([rng.permutation(8)[:k] for _ in range(n)])
    chosen[~real] = -1
    args = (ex, z, jnp.asarray(chosen, jnp.int32), jnp.asarray(
        rng.uniform(0.1, 1.0, size=(n, k)), F32))
    y, pairs, served = hm.held_experts(*args, second, dtype, tile=32)
    with monkeypatch.context() as m:
        m.setattr(ge, "kernel_fits", lambda *_: False)
        want, want_pairs, want_served = hm.held_experts(
            *args, second, dtype, tile=32)
    assert np.array_equal(np.asarray(pairs), np.asarray(want_pairs))
    assert int(served) == int(want_served) == int((chosen >= HELD).sum())
    assert np.abs(np.asarray(y) - np.asarray(want)).max() <= tol * max(
        1.0, float(np.abs(np.asarray(want)).max()))


def test_the_whole_model_is_the_same_over_the_unpadded_stack(small, params,
                                                             cfg, rows):
    """The small preset stores its experts 32 wide where 24 are published:
    the tree cut back to 24 gives the same logits to the rounding of a
    float32 sum (the columns past 24 are zeros and add nothing; the CPU's
    product of 32 columns adds the same terms in another order than its
    product of 24, which the last bits show)."""
    hist, filled = _windows(rows, [8, 2])
    cut = dict(params, layers=[
        dict(p, ffn=dict(p["ffn"], experts={
            "up": p["ffn"]["experts"]["up"][..., :24],
            "down": p["ffn"]["experts"]["down"][:, :24]}))
        if "ffn" in p else p for p in params["layers"]])
    assert params["layers"][1]["ffn"]["experts"]["up"].shape == (HELD, 64, 32)
    for name, axis in (("up", 2), ("down", 1)):
        past = np.take(np.asarray(params["layers"][1]["ffn"]["experts"][
            name], np.float32), np.arange(24, 32), axis=axis)
        assert not past.any()
    with jax.default_matmul_precision("highest"):
        stored, aux = hm.logits_everywhere(params, hist, filled, cfg, F32)
        published, want = hm.logits_everywhere(cut, hist, filled, cfg, F32)
    assert np.allclose(np.asarray(stored), np.asarray(published), atol=5e-6,
                       rtol=0)
    assert np.array_equal(np.asarray(aux["row_choice"]),
                          np.asarray(want["row_choice"]))


# -- the stack -----------------------------------------------------------------------

def test_a_scanned_stack_of_single_sublayers_gives_what_the_listed_one_gives(
        small, rows):
    """Pattern MMEE*MM: alike neighbours arrive as one stacked tree each
    (two mixers, two expert layers, the attention layer alone, two mixers)
    and are scanned; listed, seven trees are unrolled. The same logits and
    the same counts, the expert layers' in their order."""
    model = dict(small, hybrid_override_pattern="MMEE*MM")
    cfg = hm.HybridConfig.from_dict(model)
    assert cfg.layers == (("mamba2", None),) * 2 + ((None, "moe"),) * 2 + (
        ("gqa", None),) + (("mamba2", None),) * 2
    listed = ref.make_params(dict(model, layer_stack="listed"))
    scanned = ref.make_params(dict(model, layer_stack="scanned"))
    assert [hm._stacked(p) for p in scanned["layers"]] == [2, 2, None, 2]
    assert [hm._stacked(p) for p in listed["layers"]] == [None] * 7
    hist, filled = _windows(rows, [8, 3])
    with jax.default_matmul_precision("highest"):
        want, want_aux = hm.logits_everywhere(listed, hist, filled, cfg, F32)
        got, aux = hm.logits_everywhere(scanned, hist, filled, cfg, F32)
        plain, choice = ref.forward(scanned, dict(
            model, layer_stack="scanned"), hist, filled, every_position=True)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    real = _real_tokens(filled)
    assert np.abs(np.asarray(got) - np.asarray(plain))[real].mean() < 2e-5
    for key in ("pairs", "row_choice", "row_pairs", "pairs_served",
                "pairs_absent"):
        assert np.array_equal(np.asarray(aux[key]), np.asarray(want_aux[key]))
    assert np.array_equal(np.asarray(aux["row_choice"]), choice)
    assert np.asarray(aux["pairs"]).shape == (2, HELD)


def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows):
    """Three records in a window of 8 and in a window of 4: padding on
    the left passes the state, attends to nothing and routes nowhere."""
    hist, filled = _windows(rows, [3])
    with jax.default_matmul_precision("highest"):
        wide, _ = hm.apply_serving(params, hist, filled, cfg, F32)
        narrow, _ = hm.apply_serving(params, hist[:, 4:], filled, cfg, F32)
    assert np.allclose(np.asarray(wide), np.asarray(narrow), atol=1e-6)


# -- the whole and its shares ------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(small):
    """What each of the 2 chips computes of one expert layer (experts 0-3
    and 4-7 of the 8), with the shared expert counted once, adds up to the
    reference's layer over all 8 experts; every chosen pair is served on
    exactly one chip, and each chip counts the other's as absent."""
    x, real = _inputs(small)
    whole = dict(small, n_routed_experts=8,
                 experts_held={"first": 0, "count": 8})
    p = _layer(ref.make_params(whole), whole, "E")["ffn"]
    n = int(np.asarray(real).sum())
    k = small["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        want, choice = ref.experts(p, x, real, whole)
        shared = ref.relu2(p["shared"], x, small[
            "moe_shared_expert_intermediate_size"])
        total, served = shared, 0
        for share in range(2):
            held = {"first": HELD * share, "count": HELD}
            mine = dict(p, experts={
                name: v[HELD * share:HELD * share + HELD]
                for name, v in p["experts"].items()})
            cfg = hm.HybridConfig.from_dict(dict(small, experts_held=held))
            got, _, counts = hm.moe(mine, x, None, real, cfg, F32)
            total = total + (got - shared)
            served += int(counts["served"])
            assert int(counts["served"]) + int(counts["absent"]) == k * n
            assert int(counts["served"]) == int(
                choice[:, HELD * share:HELD * share + HELD].sum())
    assert served == int(choice.sum()) == k * n
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(total) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


# -- the settings ----------------------------------------------------------------------------

def test_the_model_is_its_kinds_settings(small, cfg):
    assert cfg.mixers == (
        ("gqa", hm.Gqa(heads=8, kv_heads=2, head_dim=16, scale=0.25)),
        ("mamba2", hm.Mamba2(heads=8, head_dim=16, state=16, groups=2,
                             conv=4, chunk=32, norm_groups=2)))
    m, e, a = ("mamba2", None), (None, "moe"), ("gqa", None)
    assert cfg.layers == (m, e, m, e, m, a, e) and cfg.moe_layers == 3
    assert cfg.residual == "plain" and cfg.residual_settings is None
    assert (cfg.embed_scale, cfg.logit_divisor, cfg.tied_head) == (
        1.0, 1.0, False)
    assert cfg.routing == hm.TopK("sigmoid", True, 1, 1, 2.5)
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        8, 0, 4, 3)
    assert cfg.expert_body == "relu2" and cfg.eps == 1e-5
    assert registry.get_history("hybrid_moe").config_from(small) == cfg
    assert set(hm.EXPERT_BODIES) == set(ge.GROUPED) == {"swiglu", "relu2"}
    described = registry.get_history("hybrid_moe").describe(cfg)
    assert described["layers"] == [["mamba2"], ["moe"], ["mamba2"], ["moe"],
                                   ["mamba2"], ["gqa"], ["moe"]]
    assert described["expert_body"] == "relu2"
    assert described["kinds"]["mamba2"]["norm_groups"] == 2
    assert described["kinds"]["gqa"]["head_dim"] == 16
    json.dumps(described)
    # without the deployment's key the chunk is the published kernel's block
    bare = {k: v for k, v in small.items() if k != "scan_chunk"}
    assert hm.Mamba2.read(bare, 2).chunk == small["chunk_size"] == 128
    spec = registry.get_history("hybrid_moe")
    assert spec.scan_chunk(cfg, 240) == 32 and spec.scan_chunk(cfg, 30) == 30
    only = hm.HybridConfig.from_dict(dict(small, layers_kept=[1, 5]))
    assert [name for name, _ in only.mixers] == ["gqa"]
    assert spec.scan_chunk(only, 240) is None


@pytest.mark.parametrize("change,match", [
    ({"n_routed_experts": 5}, "n_routed_experts"),
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"n_groups": 3}, "n_groups"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"norm_eps": 1e-6}, "layer_norm_epsilon"),
    ({"hybrid_override_pattern": "ME-EM*E"}, "'-'"),
    ({"hybrid_override_pattern": "MEMEMXE"}, "'X'"),
    ({"scan_chunk": 0}, "scan_chunk"),
    ({"model_type": "nemotron"}, "model_type")])
def test_a_configuration_the_reader_cannot_serve_is_refused(small, change,
                                                            match):
    """Each by the name of the key that failed, in this family's own
    spelling."""
    with pytest.raises(ValueError, match=match):
        hm.HybridConfig.from_dict(dict(small, **change))


def test_the_real_configuration_reads_at_its_published_widths():
    real = _real_config()
    cfg = hm.HybridConfig.from_dict(real)
    m, a = cfg.mixer("mamba2"), cfg.mixer("gqa")
    assert (m.heads, m.head_dim, m.state, m.groups, m.conv,
            m.norm_groups) == (64, 64, 128, 8, 4, 8)
    assert 1920 % m.chunk == 0
    assert a == hm.Gqa(heads=32, kv_heads=2, head_dim=128, scale=128 ** -0.5)
    mix, exp, att = ("mamba2", None), (None, "moe"), ("gqa", None)
    assert cfg.layers == (mix, exp, mix, exp, mix, att, exp) * 2
    assert cfg.moe_layers == 6
    assert (cfg.routed, cfg.held_count, cfg.per_token) == (128, 64, 6)
    assert cfg.routing == hm.TopK("sigmoid", True, 1, 1, 2.5)
    assert cfg.expert_body == "relu2" and not cfg.tied_head
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    stored = sum(s.size for s in jax.tree.leaves(shapes)
                 if s.dtype == jnp.bfloat16)
    padding = 6 * 64 * 2 * 2688 * (1920 - 1856)
    assert stored - padding == 4_584_652_800  # 9.17 GB as published
    assert stored == 4_716_773_376  # 9.43 GB as stored
    assert [hm._stacked(p) for p in shapes["layers"]] == [None] * 14
    assert shapes["layers"][1]["ffn"]["experts"]["up"].shape == (
        64, 2688, 1920)
    assert set(shapes["layers"][0]) == {"norm1", "mixer"}
    assert set(shapes["layers"][1]) == {"norm2", "ffn"}


# -- the kernels at the cell's shapes ------------------------------------------------------------

@pytest.mark.parametrize("letter", ["M", "E", "*"])
def test_at_the_cells_shapes_a_layer_holds_the_kernels(letter):
    """One layer of the real configuration on 4 windows of 1,920 tokens,
    traced and not run: the Mamba-2 layer's jaxpr holds the ``ssd_scan``
    kernel (64 heads of 64 in 8 groups, a state of 128), the attention
    layer's the ``causal_attention`` kernel (32 : 2 heads of 128), the
    expert layer's the grouped expert kernels (2,688 x 1,920 as stored),
    and none holds another's."""
    from ccfd_tpu.ops import causal_attention, ssd_scan

    real = _real_config()
    real = dict(real, layers_kept=[real["hybrid_override_pattern"].index(
        letter)])
    cfg = hm.HybridConfig.from_dict(real)
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    held = kernels.kernels_of(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, jnp.bfloat16), shapes,
        jax.ShapeDtypeStruct((4, 64, 30), np.float32),
        jax.ShapeDtypeStruct((4,), np.int32))
    assert (set(ge.KERNELS) <= held) == (letter == "E")
    assert (causal_attention.KERNEL in held) == (letter == "*")
    assert (ssd_scan.KERNEL in held) == (letter == "M")


def test_the_tile_and_the_blocks_come_from_the_stored_width():
    """720 pairs an expert a dispatch of 8 windows: tiles of 256 rows; the
    stored 1,920 is 15 lane tiles and is multiplied in three blocks of 640
    (``down``: three of 896); the published 1,856 is 14.5 lane tiles and
    has no block."""
    assert ge.row_tile(8 * 1920 * 6 / 128) == 256
    assert ge.block_for(2688, 1920, 1, 2) == 640
    assert ge.block_for(1920, 2688, 1, 2) == 896
    assert ge.block_for(2688, 1856, 1, 2) is None
    assert ge.block_for(1856, 2688, 1, 2) is None


# -- the served path ---------------------------------------------------------------------------------

def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had; the counters add up to three
    pairs a token and expert layer; the grid says the expert body, the
    gated norm's groups and every executable's chunk and kernels."""
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
    assert grid["model"] == "hybrid_moe" and grid["experts_held"] == [0, HELD]
    assert grid["residual"] == "plain" and grid["expert_body"] == "relu2"
    assert grid["kinds"]["mamba2"]["norm_groups"] == 2
    assert grid["layers"][:2] == [["mamba2"], ["moe"]]
    for entry in grid["grid"]:  # a bucket shorter than the chunk is one chunk
        assert entry["scan_chunk"] == min(32, entry["l_bucket"] * COLS)
        # heads of 16, experts 32 wide: through XLA
        assert (entry["ssd_kernel"], entry["expert_kernel"],
                entry["attn_kernel"], entry["kda_kernel"],
                entry["gdn_kernel"]) == (False,) * 5
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_pairs_absent_total", "moe_routed_tokens_total",
        "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice[..., :HELD].sum()
    assert total["moe_pairs_absent_total"] == choice[..., HELD:].sum()
    assert total["moe_pairs_served_total"] + total[
        "moe_pairs_absent_total"] == total["moe_routed_tokens_total"] * 3 * 3
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
    assert reg.gauge("lm_ssm_log_decay_min").value() < -1


# -- the accepted models ------------------------------------------------------------------------------

LEAVES = {"pairs", "pairs_served", "pairs_absent", "routed_tokens",
          "skipped_tokens", "row_pairs", "row_choice", "logits"}


@pytest.mark.parametrize("preset,module,more,layers", [
    ("ling3", "hybrid_moe_f32", set(), None),
    ("zaya1", "cca_moe_f32", set(), [["cca", "moe"]]),
    ("mistral4", "mla_moe_f32", set(), [["mla", "moe"]]),
    ("xing4", "mhc_moe_f32", {"hc_defect"}, None),
    ("granite4h", "ssm_moe_f32", {"ssm_log_decay_min"}, [["mamba2", "moe"]]),
])
def test_an_accepted_model_hands_back_the_leaves_it_did(preset, module, more,
                                                        layers):
    """The five accepted models keep their trees (two norms a layer), their
    kinds (both halves named), their expert body and their ``aux``."""
    model = _config("tests", "benchmark", f"{preset}_small_config.json")
    cfg = hm.HybridConfig.from_dict(model)
    assert cfg.expert_body == "swiglu"
    assert all(mixer is not None and ffn in ("dense", "moe")
               for mixer, ffn in cfg.layers)
    described = registry.get_history("hybrid_moe").describe(cfg)
    assert all(len(kind) == 2 for kind in described["layers"])
    if layers is not None:
        assert layers[0] in described["layers"]
    shapes = jax.eval_shape(lambda: importlib.import_module(
        "benchmark.reference." + module).make_params(model))
    trees = shapes["layers"]
    for p in [trees] if isinstance(trees, dict) else trees:
        assert {"norm1", "norm2", "mixer", "ffn"} <= set(p)
    _, aux = jax.eval_shape(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, F32), shapes,
        jax.ShapeDtypeStruct((2, LENGTH, COLS), np.float32),
        jax.ShapeDtypeStruct((2,), np.int32))
    assert set(aux) == LEAVES | more
    assert aux["row_choice"].shape == (2, cfg.moe_layers, cfg.routed)
