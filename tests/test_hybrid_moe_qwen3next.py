"""The ``hybrid_moe`` family's ``qwen3_next`` model (Gated DeltaNet: a
delta rule with one scalar decay a value head, 2 value heads on every key
head, one convolution over q, k and v together, a per-head norm gated by
SiLU(z); gated grouped-query attention with an RMS norm on every query and
key head, a partial rotary turn and a sigmoid gate the query projection
carries; softmax top-k experts with a gated shared expert; norms that
multiply by 1 + w; models/hybrid_moe.py) against its plain reference
(benchmark/reference/gdn_moe_f32.py: the delta rule a token at a time) at
the small preset, seeded weights, on the CPU: the whole model in both
precisions, each part alone, the chunked scan alone at several chunks with
decays no strip of ``kda_scan`` could hold, a scanned stack against a
listed one, padding, the four shares of the experts, the settings and the
reader's refusals, the kernels at the cell's shapes, the served path
through ``SeqScorer``, and that ``gqa`` with no setting set and ``moe``
without a ``shared_gate`` trace to what the accepted models served."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gdn_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
from ccfd_tpu.models import registry
from ccfd_tpu.ops import kernels
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
LENGTH, COLS = 8, 30
HELD, ROUTED, PER_TOKEN = 4, 16, 4  # a quarter of the experts, as served


def _config(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    return _config("tests", "benchmark", "qwen3next_small_config.json")


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
    return _config("benchmark", "configs", "kafka_history_qwen3next.json")


def _windows(rows, filled, length=LENGTH, seed=0):
    rng = np.random.default_rng(seed)
    hist = np.zeros((len(filled), length, rows.shape[1]), np.float32)
    for i, k in enumerate(filled):
        hist[i, length - k:] = rows[rng.integers(0, len(rows), k)]
    return hist, np.asarray(filled, np.int32)


def _inputs(small, n=2, t=100, pad=(0, 37), seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, small["hidden_size"])).astype(np.float32)
    pad = np.asarray(pad)[:, None]
    real = np.arange(t)[None, :] >= pad
    position = np.maximum(np.arange(t)[None, :] - pad, 0)
    return jnp.asarray(x), jnp.asarray(real), jnp.asarray(position)


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
    """Three Gated DeltaNet layers and the attention layer, a full window,
    one of three records and one of a single record padded on the left
    (240 tokens are 7.5 chunks of 32)."""
    hist, filled = _windows(rows, [8, 3, 1])
    want, want_choice = ref.forward(params, small, hist, filled,
                                    every_position=True)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = _real_tokens(filled)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    assert int(aux["routed_tokens"]) == int(real.sum())
    layers = len(small["layers_kept"])
    assert cfg.moe_layers == layers
    assert np.asarray(aux["pairs"]).shape == (layers, HELD)
    assert np.asarray(aux["row_choice"]).shape == (3, layers, ROUTED)
    # every chosen pair is served here or is another chip's
    assert int(aux["pairs_served"]) + int(aux["pairs_absent"]) == int(
        real.sum()) * layers * PER_TOKEN
    assert int(aux["pairs_served"]) == int(np.asarray(aux["pairs"]).sum())
    assert int(aux["pairs_absent"]) > 0 and int(aux["skipped_tokens"]) == 0
    assert float(aux["gdn_log_decay_min"]) < -1
    assert "ssm_log_decay_min" not in aux and "hc_defect" not in aux
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
        assert np.array_equal(np.asarray(aux["row_pairs"]),
                              want_choice[..., :HELD].sum((1, 2)))


@pytest.mark.parametrize("part", ["gdn", "gqa", "route", "experts"])
def test_each_part_agrees_with_the_reference(small, params, cfg, part):
    """The chunked scan (a chunk of 32 against 100 tokens, 37 padding
    tokens on the left of one row, 2 value heads a key head), the gated
    attention (normed heads, a quarter rotary counted from the row's first
    real token), the softmax router and the experts with the gated shared
    one, each alone in float32."""
    x, real, position = _inputs(small)
    keep = np.asarray(real)[..., None]
    with jax.default_matmul_precision("highest"):
        if part == "gdn":
            p = ref.layer_of(params, 0)["mixer"]
            want = ref.gdn(p, x, real, small)
            got, low = hm.gdn(p, x, real, cfg, F32)
            assert float(low) < 0
        elif part == "gqa":
            p = ref.layer_of(params, 3)["mixer"]
            want = ref.attention(p, x, real, position, small)
            got = hm.gqa(p, x, real, cfg, F32, position)
        else:
            p = ref.layer_of(params, 0)["ffn"]
            flat, flat_real = x.reshape(-1, x.shape[-1]), real.reshape(-1)
            chosen, w = ref.route(p, flat, flat_real, small)
            got_chosen, got_w = hm.route(p, flat, flat_real, cfg)
            assert np.array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(got_chosen), -1))
            assert np.allclose(np.sort(np.asarray(w), -1),
                               np.sort(np.asarray(got_w), -1), atol=1e-6)
            live = np.asarray(w)[np.asarray(flat_real)]
            assert np.allclose(live.sum(-1), 1.0, atol=1e-5)  # no scale
            if part == "route":
                return
            want, choice = ref.experts(p, x, real, small)
            got, _, counts = hm.moe(p, x, None, real, cfg, F32)
            assert int(counts["served"]) == int(choice[:, :HELD].sum())
            assert int(counts["absent"]) == int(choice[:, HELD:].sum())
    assert np.allclose(np.asarray(got) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


def test_the_shared_experts_gate_is_one_scalar_a_token(small, params, cfg):
    """Without the leaf the shared expert is added whole (every accepted
    model); with it, times sigmoid(u . w_sg): the two layers differ by
    exactly (1 - gate) x the shared expert."""
    x, real, _ = _inputs(small)
    p = ref.layer_of(params, 0)["ffn"]
    bare = {k: v for k, v in p.items() if k != "shared_gate"}
    with jax.default_matmul_precision("highest"):
        gated, _, _ = hm.moe(p, x, None, real, cfg, F32)
        whole, _, _ = hm.moe(bare, x, None, real, cfg, F32)
        shared = ref.shared.swiglu(p["shared"], x)
        gate = jax.nn.sigmoid(x @ p["shared_gate"].astype(F32))
    assert gate.shape == x.shape[:2] + (1,)
    assert 0.05 < float(gate.min()) and float(gate.max()) < 0.95
    assert np.allclose(np.asarray(whole - gated),
                       np.asarray((1.0 - gate) * shared), atol=2e-5)


def test_the_norm_offset_is_added_to_the_weight():
    """N(x) = x / rms(x) x (1 + w) under ``norm_offset`` 1, x w without."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 5, 32)), F32)
    w = jnp.asarray(0.1 * rng.normal(size=32), F32)
    unit = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(
        -1, keepdims=True) + 1e-6)
    assert np.allclose(hm._rms(x, w, 1e-6, 1.0), unit * (1.0 + np.asarray(w)),
                       atol=1e-6)
    assert np.allclose(hm._rms(x, w, 1e-6), unit * np.asarray(w), atol=1e-6)
    assert hm.HybridConfig.__dataclass_fields__["norm_offset"].default == 0.0


# -- the chunked scalar-decay scan alone -------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """The delta rule a token at a time in float64: q, k (B, T, Hk, d), v
    (B, T, Hv, d), g and beta (B, T, Hv); value head j on key head j //
    (Hv / Hk)."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    b, t, hv, dv = v.shape
    per = hv // q.shape[2]
    q, k = np.repeat(q, per, 2), np.repeat(k, per, 2)
    state = np.zeros((b, hv, q.shape[-1], dv))
    out = np.zeros_like(v)
    for i in range(t):
        state = state * np.exp(g[:, i])[..., None, None]
        seen = np.einsum("bhk,bhkv->bhv", k[:, i], state)
        state = state + (beta[:, i][..., None, None] * k[:, i][..., None]
                         * (v[:, i] - seen)[..., None, :])
        out[:, i] = np.einsum("bhk,bhkv->bhv", q[:, i], state)
    return out


@pytest.mark.parametrize("chunk,tokens", [
    (16, 100), (32, 100), (64, 100),  # 6.25, 3.125 and 1.5625 chunks
    (64, 128), (128, 130), (8, 23)])
def test_the_chunked_scan_is_the_recurrence_a_token_at_a_time(chunk, tokens):
    """Log-decays drawn down to -40 a token, far past the -5.3 at which a
    strip of 16 rows of ``kda_scan`` leaves float32: the pairwise factor is
    the exponential of a masked difference, so nothing overflows and the
    chunks agree with the recurrence to float32's rounding at every chunk
    length, also where the window is no whole number of chunks (padded on
    the left: g = beta = 0 passes the state) and where a row starts with
    padding of its own."""
    rng = np.random.default_rng(chunk + tokens)
    b, hk, hv, d = 2, 2, 4, 16

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.normal(size=(b, tokens, hk, d))) * d ** -0.5
    k = unit(rng.normal(size=(b, tokens, hk, d)))
    v = rng.normal(size=(b, tokens, hv, d))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(40.0),
                            size=(b, tokens, hv)))
    beta = rng.uniform(0.0, 1.0, size=(b, tokens, hv))
    g[1, :9], beta[1, :9] = 0.0, 0.0  # a row's own padding
    assert g.min() < -30 and (g < -5.3).mean() > 0.1
    args = [jnp.asarray(a, F32) for a in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        got = hm._scalar_delta_scan(*args, chunk)
    want = _recurrence(q, k, v, g, beta)
    assert got.shape == (b, tokens, hv, d) and got.dtype == F32
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * max(
        1.0, np.abs(want).max())


def test_two_value_heads_share_their_key_heads_products():
    """A chunk's K K^T and Q K^T are formed once a key head: the jaxpr of
    one chunk holds the two products over (B, Hk, C, C), none over the
    value heads."""
    b, c, hk, per, d = 1, 16, 2, 2, 16
    shapes = (jax.ShapeDtypeStruct((b, hk, per, d, d), F32), (
        jax.ShapeDtypeStruct((b, c, hk, d), F32),
        jax.ShapeDtypeStruct((b, c, hk, d), F32),
        jax.ShapeDtypeStruct((b, c, hk, per, d), F32),
        jax.ShapeDtypeStruct((b, c, hk, per), F32),
        jax.ShapeDtypeStruct((b, c, hk, per), F32)))
    text = str(jax.make_jaxpr(hm._gdn_chunk)(*shapes))
    pairwise = [line for line in text.splitlines()
                if "dot_general" in line and f"f32[{b},{hk},{c},{c}]" in line]
    assert len(pairwise) == 2


# -- the stack -----------------------------------------------------------------------

def test_a_scanned_stack_gives_what_the_listed_one_gives(small, rows):
    """Eight layers: scanned, three Gated DeltaNet layers arrive as one
    stacked tree, the attention layer alone, twice; listed, eight trees
    are unrolled. The same logits, the same counts and the same lowest
    log-decay, the layers in their order."""
    model = dict(small, layers_kept=list(range(8)), num_hidden_layers=8)
    cfg = hm.HybridConfig.from_dict(model)
    gdn, gqa = ("gdn", "moe"), ("gqa", "moe")
    assert cfg.layers == (gdn, gdn, gdn, gqa) * 2
    listed = ref.make_params(dict(model, layer_stack="listed"))
    scanned = ref.make_params(dict(model, layer_stack="scanned"))
    assert [hm._stacked(p) for p in scanned["layers"]] == [3, None, 3, None]
    assert [hm._stacked(p) for p in listed["layers"]] == [None] * 8
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
                "pairs_absent", "gdn_log_decay_min"):
        assert np.array_equal(np.asarray(aux[key]), np.asarray(want_aux[key]))
    assert np.array_equal(np.asarray(aux["row_choice"]), choice)
    assert np.asarray(aux["pairs"]).shape == (8, HELD)


@pytest.mark.parametrize("records,window", [(3, 4), (3, 5), (3, 8), (1, 1)])
def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows, records, window):
    """Three records in a window of 8, of 5 (150 tokens: no whole number
    of chunks) and of 4, and one record in a window of one (30 tokens:
    less than a chunk, which the scan pads to one): padding on the left
    passes the delta rule's state, sends zeros into the convolution, counts
    no position, attends to nothing and routes nowhere."""
    hist, filled = _windows(rows, [records])
    with jax.default_matmul_precision("highest"):
        wide, _ = hm.apply_serving(params, hist, filled, cfg, F32)
        narrow, _ = hm.apply_serving(params, hist[:, LENGTH - window:],
                                     filled, cfg, F32)
        want, _ = ref.forward(params, small, hist, filled)
    assert np.allclose(np.asarray(wide), np.asarray(narrow), atol=1e-6)
    p = 1.0 / (1.0 + np.exp(-np.asarray(ref.verdict_logit(
        np.asarray(want), small), np.float64)))
    assert np.allclose(np.asarray(narrow), p, rtol=2e-3, atol=1e-6)


# -- the whole and its shares ------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(small):
    """What each of the 4 chips computes of one expert layer (experts
    0-3, 4-7, 8-11 and 12-15 of the 16), with the gated shared expert
    counted once, adds up to the reference's layer over all 16 experts;
    every chosen pair is served on exactly one chip, and each chip counts
    the others' as absent."""
    x, real, _ = _inputs(small)
    whole = dict(small, num_experts=ROUTED,
                 experts_held={"first": 0, "count": ROUTED})
    p = ref.layer_of(ref.make_params(whole), 0)["ffn"]
    n = int(np.asarray(real).sum())
    with jax.default_matmul_precision("highest"):
        want, choice = ref.experts(p, x, real, whole)
        shared = ref._shared_expert(p, x.reshape(-1, x.shape[-1])).reshape(
            x.shape)
        total, served = shared, 0
        for share in range(ROUTED // HELD):
            held = {"first": HELD * share, "count": HELD}
            mine = dict(p, experts={
                name: v[HELD * share:HELD * share + HELD]
                for name, v in p["experts"].items()})
            cfg = hm.HybridConfig.from_dict(dict(small, experts_held=held))
            got, _, counts = hm.moe(mine, x, None, real, cfg, F32)
            total = total + (got - shared)
            served += int(counts["served"])
            assert int(counts["served"]) + int(
                counts["absent"]) == PER_TOKEN * n
            assert int(counts["served"]) == int(
                choice[:, HELD * share:HELD * share + HELD].sum())
    assert served == int(choice.sum()) == PER_TOKEN * n
    keep = np.asarray(real)[..., None]
    assert np.allclose(np.asarray(total) * keep, np.asarray(want) * keep,
                       atol=2e-4, rtol=2e-4)


# -- the settings ----------------------------------------------------------------------------

def test_the_model_is_its_kinds_settings(small, cfg):
    assert cfg.mixers == (
        ("gdn", hm.Gdn(key_heads=2, value_heads=4, key_dim=16, value_dim=16,
                       conv=4, chunk=32)),
        ("gqa", hm.Gqa(heads=4, kv_heads=2, head_dim=16, scale=0.25,
                       rotary_dim=4, theta=1e7, qk_norm=True, gated=True)))
    assert cfg.layers == (("gdn", "moe"),) * 3 + (("gqa", "moe"),)
    assert cfg.residual == "plain" and cfg.residual_settings is None
    assert (cfg.embed_scale, cfg.logit_divisor, cfg.tied_head) == (
        1.0, 1.0, False)
    assert cfg.routing == hm.TopK("softmax", False, 1, 1, 1.0)
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        ROUTED, 0, HELD, PER_TOKEN)
    assert cfg.expert_body == "swiglu" and cfg.eps == 1e-6
    assert cfg.norm_offset == 1.0
    spec = registry.get_history("hybrid_moe")
    assert spec.config_from(small) == cfg
    assert spec.scan_chunk(cfg, 240) is None  # mamba2's alone
    described = spec.describe(cfg)
    assert described["layers"] == [["gdn", "moe"]] * 3 + [["gqa", "moe"]]
    assert described["kinds"]["gdn"]["chunk"] == 32
    assert described["kinds"]["gqa"]["rotary_dim"] == 4
    json.dumps(described)
    # without the deployment's key the chunk is 64
    bare = {k: v for k, v in small.items() if k != "gdn_chunk"}
    assert hm.Gdn.read(bare).chunk == 64
    only = hm.HybridConfig.from_dict(dict(small, layers_kept=[3]))
    assert [name for name, _ in only.mixers] == ["gqa"]
    # what the accepted models' gqa reads is plain
    assert hm.Gqa(8, 2, 16, 0.25) == hm.Gqa(
        8, 2, 16, 0.25, rotary_dim=0, theta=0.0, qk_norm=False, gated=False)


@pytest.mark.parametrize("change,match", [
    ({"num_experts": 5}, "num_experts"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"linear_num_value_heads": 3}, "linear_num_value_heads"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"attention_bias": True}, "attention_bias"),
    ({"gdn_chunk": 48}, "gdn_chunk"),
    ({"gdn_chunk": 4}, "gdn_chunk"),
    ({"model_type": "qwen3_moe"}, "model_type")])
def test_a_configuration_the_reader_cannot_serve_is_refused(small, change,
                                                            match):
    """Each by the name of the key that failed."""
    with pytest.raises(ValueError, match=match):
        hm.HybridConfig.from_dict(dict(small, **change))


def test_the_real_configuration_reads_at_its_published_widths():
    real = _real_config()
    cfg = hm.HybridConfig.from_dict(real)
    m, a = cfg.mixer("gdn"), cfg.mixer("gqa")
    assert (m.key_heads, m.value_heads, m.key_dim, m.value_dim, m.conv) == (
        16, 32, 128, 128, 4)
    assert m.chunk in (16, 32, 64, 128)
    assert a == hm.Gqa(heads=16, kv_heads=2, head_dim=256, scale=256 ** -0.5,
                       rotary_dim=64, theta=1e7, qk_norm=True, gated=True)
    gdn, gqa = ("gdn", "moe"), ("gqa", "moe")
    assert cfg.layers == (gdn, gdn, gdn, gqa) * (len(cfg.layers) // 4)
    assert cfg.moe_layers == len(real["layers_kept"]) in (8, 12)
    assert (cfg.routed, cfg.held_count, cfg.per_token) == (512, 128, 10)
    assert cfg.routing == hm.TopK("softmax", False, 1, 1, 1.0)
    assert cfg.norm_offset == 1.0 and not cfg.tied_head
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    stored = sum(s.size for s in jax.tree.leaves(shapes)
                 if s.dtype == jnp.bfloat16)
    mixer = {"gdn": 2048 * 12288 + 2048 * 64 + 4096 * 2048,
             "gqa": 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048}
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 128 * 3 * 2048 * 512
    assert stored == sum(mixer[kind] + ffn for kind, _ in cfg.layers) + (
        2 * 37984 * 2048)
    if len(cfg.layers) == 12:
        assert stored == 5_422_735_360  # 10.85 GB in bfloat16
    layer = shapes["layers"][0]
    assert layer["mixer"]["w_qkvz"].shape == (2048, 12288)
    assert layer["mixer"]["w_ba"].shape == (2048, 64)
    assert layer["mixer"]["conv"].shape == (4, 8192)
    assert layer["mixer"]["norm"].shape == (128,)
    assert layer["ffn"]["shared_gate"].shape == (2048, 1)
    assert layer["ffn"]["experts"]["up"].shape == (128, 2048, 512)
    assert shapes["layers"][3]["mixer"]["wq"].shape == (2048, 8192)
    assert shapes["layers"][3]["mixer"]["q_norm"].shape == (256,)


# -- the kernels at the cell's shapes ------------------------------------------------------------

@pytest.mark.parametrize("layer,mixer", [(0, "gdn"), (3, "gqa")])
def test_at_the_cells_shapes_a_layer_holds_the_kernels(layer, mixer):
    """One layer of the real configuration on 8 windows of 1,920 tokens,
    traced and not run: the Gated DeltaNet layer's jaxpr holds the
    ``short_conv`` kernel (q | k | v are 64 lane tiles at offset 0 of the
    12,288-wide projection) and, since PR 53, the ``gdn_scan`` kernel and
    no other scan's (``kda_scan``'s decays are a vector a head), the attention
    layer's the ``causal_attention`` kernel (16 : 2 heads of 256), both the
    grouped expert kernels (2,048 x 512, 300 pairs an expert: tiles of
    128)."""
    from ccfd_tpu.ops import (causal_attention, cca_conv, gdn_scan,
                              grouped_experts, kda_scan, short_conv, ssd_scan)

    real = dict(_real_config(), layers_kept=[layer])
    cfg = hm.HybridConfig.from_dict(real)
    assert cfg.layers == ((mixer, "moe"),)
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    held = kernels.kernels_of(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, jnp.bfloat16), shapes,
        jax.ShapeDtypeStruct((8, 64, 30), np.float32),
        jax.ShapeDtypeStruct((8,), np.int32))
    assert set(grouped_experts.KERNELS) <= held
    assert (short_conv.KERNEL in held) == (mixer == "gdn")
    assert (gdn_scan.KERNEL in held) == (mixer == "gdn")
    assert (causal_attention.KERNEL in held) == (mixer == "gqa")
    assert not held & {kda_scan.KERNEL, ssd_scan.KERNEL, cca_conv.KERNEL}
    assert grouped_experts.row_tile(8 * 1920 * 10 / 512) == 128


# -- the served path ---------------------------------------------------------------------------------

def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had; the counters add up to four
    pairs a token and layer; the grid says the mixers' settings and every
    executable's kernels; the lowest log-decay is on the gauge."""
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
    assert grid["residual"] == "plain" and grid["expert_body"] == "swiglu"
    assert grid["kinds"]["gdn"]["chunk"] == 32
    assert grid["kinds"]["gqa"]["gated"] is True
    assert grid["layers"][0] == ["gdn", "moe"]
    for entry in grid["grid"]:  # heads of 16, experts 32 wide: through XLA
        assert "scan_chunk" not in entry
        assert (entry["conv_kernel"], entry["expert_kernel"],
                entry["attn_kernel"], entry["kda_kernel"],
                entry["ssd_kernel"], entry["cca_kernel"],
                entry["gdn_kernel"]) == (False,) * 7
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_pairs_absent_total", "moe_routed_tokens_total",
        "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice[..., :HELD].sum()
    assert total["moe_pairs_absent_total"] == choice[..., HELD:].sum()
    assert total["moe_pairs_served_total"] + total[
        "moe_pairs_absent_total"] == total[
            "moe_routed_tokens_total"] * PER_TOKEN * 4
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
    assert reg.gauge("lm_gdn_log_decay_min").value() < -1
    assert reg.gauge("lm_ssm_log_decay_min").value() == 0.0


# -- the accepted models ------------------------------------------------------------------------------

def _gqa_as_accepted(p, z, real, cfg, dtype):
    """``gqa`` as PR 51 served it: no positions, no norm, no gate."""
    b, t, _ = z.shape
    s = cfg.mixer("gqa")
    h, g, hd = s.heads, s.kv_heads, s.head_dim
    with jax.named_scope("gqa.project"):
        q = hm._mm(z, p["wq"], dtype).reshape(
            b, t, g, h // g, hd).astype(dtype)
        k = hm._mm(z, p["wk"], dtype).reshape(b, t, g, hd).astype(dtype)
        v = hm._mm(z, p["wv"], dtype).reshape(b, t, g, hd).astype(dtype)
    with jax.named_scope("gqa.attend"):
        o = hm._causal_attention(q, k, v, real, s.scale, dtype)
    with jax.named_scope("gqa.project"):
        return hm._mm(o.reshape(b, t, h * hd), p["wo"], dtype)


def _moe_as_accepted(p, z, r, real, cfg, dtype):
    """``moe`` as PR 51 served it: the shared expert added whole."""
    b, t, d = z.shape
    flat = z.reshape(b * t, d)
    with jax.named_scope("moe.route"):
        chosen, w, r = hm.ROUTERS[cfg.router](p, flat, r, real.reshape(-1),
                                              cfg)
    with jax.named_scope("moe.experts"):
        y, pairs, served = hm.held_experts(p["experts"], flat, chosen, w,
                                           cfg, dtype)
    if "shared" in p:
        _, body = hm.EXPERT_BODIES[cfg.expert_body]
        with jax.named_scope("moe.shared"):
            y = y + body(p["shared"], flat, dtype)
    local = chosen - cfg.held_first
    mine = (local >= 0) & (local < cfg.held_count)
    counts = {
        "pairs": pairs, "served": served,
        "absent": jnp.sum((chosen >= 0) & ~mine, dtype=jnp.int32),
        "row_pairs": jnp.sum(mine.reshape(b, -1), axis=1, dtype=jnp.int32),
        "skipped": jnp.zeros((), jnp.int32),
        "row_choice": jnp.sum(
            chosen.reshape(b, -1, 1) == jnp.arange(cfg.routed), axis=1,
            dtype=jnp.int32)}
    return y.reshape(b, t, d), r, counts


@pytest.mark.parametrize("part", ["gqa", "moe"])
@pytest.mark.parametrize("preset,module", [
    ("granite4h", "ssm_moe_f32"), ("nemotron3n", "ssm_relu2_moe_f32")])
def test_an_accepted_model_traces_to_what_it_served(preset, module, part):
    """``gqa`` with no setting set and ``moe`` without a ``shared_gate``
    give, equation for equation, the jaxpr of the functions as they stood
    before either learnt this model's settings (written out above), at
    the two small configurations whose layers they are."""
    model = dict(_config("tests", "benchmark", f"{preset}_small_config.json"),
                 layer_stack="listed")  # a tree a layer
    cfg = hm.HybridConfig.from_dict(model)
    assert cfg.norm_offset == 0.0
    assert cfg.mixer("gqa") == hm.Gqa(*dataclasses.astuple(
        cfg.mixer("gqa"))[:4])
    shapes = jax.eval_shape(lambda: importlib.import_module(
        "benchmark.reference." + module).make_params(model))
    trees = shapes["layers"]
    z = jax.ShapeDtypeStruct((2, 240, model["hidden_size"]), F32)
    real = jax.ShapeDtypeStruct((2, 240), bool)
    position = jax.ShapeDtypeStruct((2, 240), jnp.int32)
    if part == "gqa":
        p = [t["mixer"] for t in trees if "wq" in t.get("mixer", {})][0]
        now = jax.make_jaxpr(lambda p, z, real, position: hm.gqa(
            p, z, real, cfg, jnp.bfloat16, position))(p, z, real, position)
        was = jax.make_jaxpr(lambda p, z, real, position: _gqa_as_accepted(
            p, z, real, cfg, jnp.bfloat16))(p, z, real, position)
    else:
        p = [t["ffn"] for t in trees if "ffn" in t][0]
        assert "shared" in p and "shared_gate" not in p
        now = jax.make_jaxpr(lambda p, z, real: hm.moe(
            p, z, None, real, cfg, jnp.bfloat16))(p, z, real)
        was = jax.make_jaxpr(lambda p, z, real: _moe_as_accepted(
            p, z, None, real, cfg, jnp.bfloat16))(p, z, real)
    assert str(now) == str(was)


LEAVES = {"pairs", "pairs_served", "pairs_absent", "routed_tokens",
          "skipped_tokens", "row_pairs", "row_choice", "logits"}


@pytest.mark.parametrize("preset,module,more", [
    ("ling3", "hybrid_moe_f32", set()),
    ("zaya1", "cca_moe_f32", set()),
    ("mistral4", "mla_moe_f32", set()),
    ("xing4", "mhc_moe_f32", {"hc_defect"}),
    ("granite4h", "ssm_moe_f32", {"ssm_log_decay_min"}),
    ("nemotron3n", "ssm_relu2_moe_f32", {"ssm_log_decay_min"}),
    ("qwen3next", "gdn_moe_f32", {"gdn_log_decay_min"}),
])
def test_a_model_hands_back_the_leaves_of_its_mixers(preset, module, more):
    """The six accepted models keep their ``aux`` (a ``mamba2`` layer's
    report is ``ssm_log_decay_min``, nobody else's), and the seventh adds
    ``gdn_log_decay_min`` alone; none multiplies its norms by 1 + w but the
    seventh."""
    model = _config("tests", "benchmark", f"{preset}_small_config.json")
    cfg = hm.HybridConfig.from_dict(model)
    assert cfg.norm_offset == (1.0 if preset == "qwen3next" else 0.0)
    shapes = jax.eval_shape(lambda: importlib.import_module(
        "benchmark.reference." + module).make_params(model))
    _, aux = jax.eval_shape(
        lambda p, h, f: hm.apply_serving(p, h, f, cfg, F32), shapes,
        jax.ShapeDtypeStruct((2, LENGTH, COLS), np.float32),
        jax.ShapeDtypeStruct((2,), np.int32))
    assert set(aux) == LEAVES | more
    assert aux["row_choice"].shape == (2, cfg.moe_layers, cfg.routed)
