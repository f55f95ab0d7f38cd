"""The ``hybrid_moe`` family's ``granitemoehybrid`` model (a Mamba-2
selective state-space mixer in most layers, grouped-query attention without
positions in the others, top-k by logit with a softmax over the chosen, one
shared expert, a tied head and four constant multipliers;
models/hybrid_moe.py) against its plain reference
(benchmark/reference/ssm_moe_f32.py: the recurrence a token at a time) at
the small preset, seeded weights, on the CPU: the whole model in both
precisions, each part alone, every chunk and both layouts of the stack,
padding, causality, what each named omission does to the answer, the
router, the two shares of the experts, the settings, the kernels at the
cell's shapes, and the served path through ``SeqScorer``."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssm_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.models import registry
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
LENGTH, COLS = 8, 30
HELD = 6  # of 12 routed experts at the small preset


def _config(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    return _config("tests", "benchmark", "granite4h_small_config.json")


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
    return _config("benchmark", "configs", "kafka_history_granite4h.json")


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


def _hidden(params, hist, filled, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.jit(hm.hidden_states, static_argnames=("cfg", "dtype"))(
            params, hist, filled, cfg=cfg, dtype=F32)


def _with_chunk(cfg, chunk):
    return dataclasses.replace(cfg, mixers=tuple(
        (name, dataclasses.replace(s, chunk=chunk) if name == "mamba2" else s)
        for name, s in cfg.mixers))


def _real_tokens(filled):
    return np.asarray(ref.shared.real_tokens(jnp.asarray(filled), LENGTH,
                                             COLS))


# -- the whole model, and each part ------------------------------------------------

@pytest.mark.parametrize("dtype,worst,mean", [
    (F32, 2e-4, 2e-5),  # the reference's own precision: tight
    (jnp.bfloat16, None, 0.05),  # as served: a token near a tie may choose
    # another expert, so the widest gap is wide; the mean is not
])
def test_logits_and_routing_agree_with_the_reference_at_every_position(
        small, params, cfg, rows, dtype, worst, mean):
    hist, filled = _windows(rows, [8, 3, 1])
    want, want_choice = ref.forward(params, small, hist, filled,
                                    every_position=True)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = _real_tokens(filled)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    assert int(aux["routed_tokens"]) == int(real.sum())
    layers, per_token = len(small["layers_kept"]), small["num_experts_per_tok"]
    # every chosen pair is served here or is the other chip's
    assert int(aux["pairs_served"]) + int(aux["pairs_absent"]) == int(
        real.sum()) * layers * per_token
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
    """The chunked scan (a chunk of 32 against 100 tokens: a first chunk
    part padding, 37 padding tokens on the left of one row, two groups of B
    and C) against the recurrence a token at a time; grouped queries
    without positions against the full masked softmax; the router (same
    experts, same weights); the tile loop and the shared expert against
    the loop over experts."""
    x, real = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    with jax.default_matmul_precision("highest"):
        if part == "mamba2":
            p = ref.layer_of(params, 1)["mixer"]
            want = ref.mamba(p, x, real, small)
            got, low = hm.mamba2(p, x, real, cfg, F32)
            assert -500 < float(low) < -1
        elif part == "gqa":
            p = ref.layer_of(params, 2)["mixer"]
            want = ref.attention(p, x, real, small)
            got = hm.gqa(p, x, real, cfg, F32)
        elif part == "route":
            p = ref.layer_of(params, 0)["ffn"]
            want_e, want, _ = ref.route(p, flat, flat_real, small)
            got_e, got = hm.route(p, flat, flat_real, cfg)
            assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
            assert np.allclose(np.asarray(got).sum(1)[np.asarray(flat_real)],
                               1.0, atol=1e-6)
        else:
            p = ref.layer_of(params, 3)["ffn"]
            want, choice = ref.experts(p, x, real, small)
            got, r, counts = hm.moe(p, x, None, real, cfg, F32)
            assert r is None
            assert np.array_equal(np.asarray(counts["row_choice"]), choice)
            assert int(counts["served"]) == int(choice[:, :HELD].sum())
            assert int(counts["absent"]) == int(choice[:, HELD:].sum())
    keep = np.asarray(real)[..., None] if np.ndim(got) == 3 else True
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_the_softmax_over_all_is_the_softmax_over_the_chosen_logits(
        small, params, cfg):
    """No new router: ``TopK("softmax", False, 1, 1, 1.0)``, softmax over
    all 12 renormalised over the chosen four, is the four largest logits
    and the softmax of those four, which is what the model states."""
    assert cfg.router == "top_k" and cfg.routing == hm.TopK(
        "softmax", False, 1, 1, 1.0)
    p = ref.layer_of(params, 0)["ffn"]
    x, real = _inputs(small)
    flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
    with jax.default_matmul_precision("highest"):
        chosen, w = hm.route(p, flat, flat_real, cfg)
        logits = flat @ p["router"].astype(F32)
    top, at = jax.lax.top_k(logits, small["num_experts_per_tok"])
    keep = np.asarray(flat_real)
    assert np.array_equal(np.asarray(chosen)[keep], np.asarray(at)[keep])
    assert np.allclose(np.asarray(w)[keep], np.asarray(
        jax.nn.softmax(top, axis=-1))[keep], atol=1e-6)
    assert (np.asarray(chosen)[~keep] == -1).all()
    assert not np.asarray(w)[~keep].any()


# -- chunks and layouts ------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 32, 64, 240, 256])
def test_every_chunk_gives_the_same_answer(small, params, cfg, rows, chunk):
    """240 tokens a window: 16 tiles it, 32 and 64 do not (the window is
    padded on the left to whole chunks), 240 is one chunk and 256, the
    published kernel's block, is cut to the window. The chunk is no part
    of the result; only the running log-decay it reports grows with it."""
    hist, filled = _windows(rows, [8, 5])
    base, base_aux = _hidden(params, hist, filled, _with_chunk(cfg, 16))
    x, aux = _hidden(params, hist, filled, _with_chunk(cfg, chunk))
    assert np.allclose(np.asarray(x), np.asarray(base), atol=2e-5)
    assert np.array_equal(np.asarray(aux["row_choice"]),
                          np.asarray(base_aux["row_choice"]))
    assert float(aux["ssm_log_decay_min"]) <= float(
        base_aux["ssm_log_decay_min"]) + 1e-3
    assert hm.Mamba2.read(dict(small, scan_chunk=chunk)).chunk_for(
        LENGTH * COLS) == min(chunk, 240)


def test_a_listed_stack_gives_what_the_scanned_one_gives(small, params, cfg,
                                                         rows):
    """``layer_stack`` ``scanned`` hands over [two ``mamba`` layers
    stacked, the ``attention`` layer, two ``mamba`` layers stacked]: the
    first stack whose list holds two stacks of alike layers around a single
    one; ``listed`` draws the same values as five trees, and the program
    unrolls the one where it scans the other."""
    hist, filled = _windows(rows, [8, 2])
    assert [hm._stacked(p) for p in params["layers"]] == [2, None, 2]
    listed = ref.make_params(dict(small, layer_stack="listed"))
    assert isinstance(listed["layers"], list) and len(listed["layers"]) == 5
    for i in range(5):
        for a, b in zip(jax.tree.leaves(ref.layer_of(params, i)),
                        jax.tree.leaves(listed["layers"][i])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    x, aux = _hidden(params, hist, filled, cfg)
    y, other = _hidden(listed, hist, filled, cfg)
    assert np.allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    assert set(aux) == set(other)
    for key in aux:
        assert np.allclose(np.asarray(aux[key]), np.asarray(other[key]),
                           atol=1e-4), key


# -- padding and causality ------------------------------------------------------------

@pytest.mark.parametrize("padding", ["zeros", "noise"])
def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows, padding):
    """One history of 5 records at windows of 8, 16 and 64 records gives
    one verdict and one routing, with other records where the padding is,
    too: a padding token has dt = 0 (the state passes it unchanged), sends
    zeros into the convolution, is masked as a key and routes nowhere."""
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
    assert float(aux["ssm_log_decay_min"]) == 0.0  # dt = 0 everywhere


def test_a_later_token_moves_no_earlier_hidden_state(params, cfg, rows):
    hist, filled = _windows(rows, [8, 6])
    other = hist.copy()
    other[:, -1] = rows[:2]  # the newest record of both rows
    x, _ = _hidden(params, hist, filled, cfg)
    y, _ = _hidden(params, other, filled, cfg)
    before = (LENGTH - 1) * COLS
    real = _real_tokens(filled)[:, :before]
    assert np.allclose(np.asarray(x)[:, :before][real],
                       np.asarray(y)[:, :before][real], atol=1e-5, rtol=0)
    assert np.abs(np.asarray(x)[:, before:] - np.asarray(y)[:, before:]
                  ).max() > 1e-3


# -- what each named omission does ----------------------------------------------------------

# an omission that is a vector of every Mamba-2 mixer read as zeros:
# the convolution's bias, the skip D, and softplus(dt) for softplus(dt +
# dt_bias)
ZEROED = {"conv_bias": "conv_b", "d": "d", "dt_bias": "dt_bias"}


def _zeroed(params, name):
    """The tree with every Mamba-2 mixer's ``name`` at zero."""
    out = dict(params)
    out["layers"] = [
        dict(p, mixer=dict(p["mixer"], **{name: jnp.zeros_like(
            p["mixer"][name])})) if name in p["mixer"] else p
        for p in params["layers"]]
    return out


@pytest.mark.parametrize("omission", [
    "conv_bias", "d", "dt_bias", "gate_order", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "attention_multiplier",
    "rotary"])
def test_a_program_that_drops_a_term_fails(small, params, cfg, rows,
                                           omission, monkeypatch):
    """Each of the model's own terms moves the logits' mean gap to the
    reference to fifty times and more the 2e-5 the whole model keeps: a
    program without the convolution's bias, the skip D, dt_bias, with the
    norm before the gate, without one of the four multipliers, or with a
    rotary on the attention layer's q and k, does not pass for the model."""
    hist, filled = _windows(rows, [8, 3])
    want, _ = ref.forward(params, small, hist, filled, every_position=True)
    tree, settings = params, cfg
    if omission in ZEROED:
        tree = _zeroed(params, ZEROED[omission])
    elif omission == "gate_order":  # the norm first, the gate after it
        monkeypatch.setattr(
            hm, "_gated_norm", lambda y, gate, weight, eps, groups=1:
            hm._rms(y, weight, eps) * jax.nn.silu(gate))
    elif omission == "embedding_multiplier":
        settings = dataclasses.replace(cfg, embed_scale=1.0)
    elif omission == "residual_multiplier":
        settings = dataclasses.replace(cfg, residual="plain",
                                       residual_settings=None)
    elif omission == "logits_scaling":
        settings = dataclasses.replace(cfg, logit_divisor=1.0)
    elif omission == "attention_multiplier":
        settings = dataclasses.replace(cfg, mixers=tuple(
            (name, dataclasses.replace(s, scale=s.head_dim ** -0.5)
             if name == "gqa" else s) for name, s in cfg.mixers))
    else:  # rotary: q and k of the attention layer turned by position
        kept = hm._causal_attention
        freq = hm._frequencies(1e4, small["hidden_size"] // small[
            "num_attention_heads"])

        def turned(q, k, v, real, scale, dtype):
            position = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
            b, t = q.shape[:2]
            q = hm._rotary(q.reshape(b, t, -1, q.shape[-1]), position,
                           freq).reshape(q.shape)
            return kept(q, hm._rotary(k, position, freq), v, real, scale,
                        dtype)

        monkeypatch.setattr(hm, "_causal_attention", turned)
    hm.logits_everywhere.clear_cache()
    try:
        with jax.default_matmul_precision("highest"):
            got, _ = hm.logits_everywhere(tree, hist, filled, settings, F32)
    finally:
        monkeypatch.undo()
        hm.logits_everywhere.clear_cache()
    real = _real_tokens(filled)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() > 1e-3, (omission, gap.mean())


# -- the whole and its shares ------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(small):
    """What each of the 2 chips computes of one expert layer (experts 0-5
    and 6-11 of the 12), with the shared expert counted once, adds up to
    the reference's layer over all 12 experts; every chosen pair is served
    on exactly one chip, and each chip counts the other's as absent."""
    x, real = _inputs(small)
    whole = dict(small, num_local_experts=12,
                 experts_held={"first": 0, "count": 12})
    p = ref.layer_of(ref.make_params(whole), 1)["ffn"]
    n = int(np.asarray(real).sum())
    k = small["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        want, choice = ref.experts(p, x, real, whole)
        shared = ref.shared.swiglu(p["shared"], x)
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
        ("gqa", hm.Gqa(heads=4, kv_heads=2, head_dim=16, scale=0.0625)),
        ("mamba2", hm.Mamba2(heads=8, head_dim=16, state=16, groups=2,
                             conv=4, chunk=32)))
    assert cfg.layers == (("mamba2", "moe"),) * 2 + (("gqa", "moe"),) + (
        ("mamba2", "moe"),) * 2 and cfg.moe_layers == 5
    assert cfg.residual == "multiplied" and cfg.residual_settings == \
        hm.Multiplied(0.22)
    assert (cfg.embed_scale, cfg.logit_divisor, cfg.tied_head) == (
        3.0, 4.0, True)
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        12, 0, 6, 4)
    assert registry.get_history("hybrid_moe").config_from(small) == cfg
    assert {"mamba2", "gqa"} <= set(hm.MIXERS) and set(hm.MIXERS) >= {
        "kda", "mla", "cca"}
    assert hm.MIXER_SCOPES == {"mamba2": "mamba"}
    described = registry.get_history("hybrid_moe").describe(cfg)
    assert described["kinds"]["mamba2"]["chunk"] == 32
    assert described["kinds"]["multiplied"] == {"multiplier": 0.22}
    json.dumps(described)
    # without the deployment's key the chunk is the published kernel's block
    bare = {k: v for k, v in small.items() if k != "scan_chunk"}
    assert hm.Mamba2.read(bare).chunk == small["mamba_chunk_size"] == 256
    # a model of attention layers alone has no Mamba-2 settings
    only = hm.HybridConfig.from_dict(dict(small, layers_kept=[2]))
    assert [name for name, _ in only.mixers] == ["gqa"]
    spec = registry.get_history("hybrid_moe")
    assert spec.scan_chunk(cfg, 240) == 32 and spec.scan_chunk(cfg, 30) == 30
    assert spec.scan_chunk(only, 240) is None


@pytest.mark.parametrize("change,match", [
    ({"num_local_experts": 5}, "num_local_experts"),
    ({"mamba_proj_bias": True}, "mamba2"),
    ({"mamba_conv_bias": False}, "mamba2"),
    ({"mamba_expand": 3}, "mamba2"),
    ({"mamba_n_groups": 3}, "mamba2"),
    ({"scan_chunk": 0}, "scan_chunk"),
    ({"position_embedding_type": "rope"}, "gqa"),
    ({"attention_bias": True}, "gqa"),
    ({"num_key_value_heads": 3}, "gqa"),
    ({"normalization_function": "layernorm"}, "granitemoehybrid")])
def test_a_configuration_the_reader_cannot_serve_is_refused(small, change,
                                                            match):
    with pytest.raises(ValueError, match=match):
        hm.HybridConfig.from_dict(dict(small, **change))


def test_the_real_configuration_reads_at_its_published_widths():
    real = _real_config()
    cfg = hm.HybridConfig.from_dict(real)
    m, a = cfg.mixer("mamba2"), cfg.mixer("gqa")
    assert (m.heads, m.head_dim, m.state, m.groups, m.conv) == (
        128, 64, 128, 1, 4)
    assert m.chunk in (64, 128, 384, 640) and 1920 % m.chunk == 0
    assert a == hm.Gqa(heads=32, kv_heads=8, head_dim=128, scale=1 / 128)
    assert cfg.layers == (("mamba2", "moe"),) * 5 + (("gqa", "moe"),) + (
        ("mamba2", "moe"),) * 4
    assert (cfg.routed, cfg.held_count, cfg.per_token) == (72, 36, 10)
    assert (cfg.embed_scale, cfg.logit_divisor) == (12.0, 16.0)
    assert cfg.residual_settings == hm.Multiplied(0.22) and cfg.tied_head
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    assert sum(s.size for s in jax.tree.leaves(shapes)
               if s.dtype == jnp.bfloat16) == 4_756_668_416  # 9.51 GB
    stacks = [hm._stacked(p) for p in shapes["layers"]]
    assert stacks in ([5, None, 4], [None] * 10)


# -- the kernels at the cell's shapes ------------------------------------------------------------

@pytest.mark.parametrize("kind", ["gqa", "mamba2"])
def test_at_the_cells_shapes_a_layer_holds_the_kernels(kind):
    """One layer of the real configuration on 2 windows of 1,920 tokens,
    traced and not run: the attention layer's jaxpr holds the
    ``causal_attention`` kernel (32 : 8 heads of 128, 1,920 tokens), the
    Mamba-2 layer's the ``ssd_scan`` kernel (128 heads of 64, a state of
    128, chunks of 640), every layer's the grouped expert kernels
    (4,096 x 768)."""
    from ccfd_tpu.ops import (causal_attention, grouped_experts, kernels,
                              ssd_scan)

    real = dict(_real_config(), layers_kept=[5 if kind == "gqa" else 0],
                layer_stack="listed")
    cfg = hm.HybridConfig.from_dict(real)
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    held = kernels.kernels_of(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, jnp.bfloat16), shapes,
        jax.ShapeDtypeStruct((2, 64, 30), np.float32),
        jax.ShapeDtypeStruct((2,), np.int32))
    assert set(grouped_experts.KERNELS) <= held
    assert (causal_attention.KERNEL in held) == (kind == "gqa")
    assert (ssd_scan.KERNEL in held) == (kind == "mamba2")


# -- the served path ---------------------------------------------------------------------------------

def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had; the counters add up to four
    pairs a token and layer; the grid gives every executable's chunk and
    ``seq.wait`` carries the running log-decay."""
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
    assert grid["residual"] == "multiplied"
    assert grid["kinds"]["mamba2"]["state"] == 16
    for entry in grid["grid"]:  # a bucket shorter than the chunk is one chunk
        assert entry["scan_chunk"] == min(32, entry["l_bucket"] * COLS)
        assert entry["ssd_kernel"] is False  # heads of 16: through XLA
    assert reg.counter("seq_ssd_kernel_dispatch_total").total() == 0
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_pairs_absent_total", "moe_routed_tokens_total",
        "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice[..., :HELD].sum()
    assert total["moe_pairs_absent_total"] == choice[..., HELD:].sum()
    assert total["moe_pairs_served_total"] + total[
        "moe_pairs_absent_total"] == total["moe_routed_tokens_total"] * 5 * 4
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
    assert reg.gauge("lm_ssm_log_decay_min").value() < -1


def test_a_model_without_the_mixer_hands_back_the_leaves_it_did():
    """``ssm_log_decay_min`` is a leaf of ``aux`` only where a layer mixes
    by ``mamba2``, and the grid names a chunk only there."""
    from benchmark.reference import mla_moe_f32

    other = _config("tests", "benchmark", "mistral4_small_config.json")
    cfg = hm.HybridConfig.from_dict(other)
    shapes = jax.eval_shape(lambda: mla_moe_f32.make_params(other))
    _, aux = jax.eval_shape(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, F32), shapes,
        jax.ShapeDtypeStruct((2, LENGTH, COLS), np.float32),
        jax.ShapeDtypeStruct((2,), np.int32))
    assert set(aux) == {"pairs", "pairs_served", "pairs_absent",
                        "routed_tokens", "skipped_tokens", "row_pairs",
                        "row_choice", "logits"}
    assert registry.get_history("hybrid_moe").scan_chunk(cfg, 240) is None
