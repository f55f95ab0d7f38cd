"""The ``hybrid_moe`` family's ``xing4_0`` model (a residual path of four
streams mixed by Sinkhorn-normalised maps around every sublayer, MLA with
low-rank queries and YaRN, a leading dense layer, sigmoid top 4 with a bias
over 16 experts, all held, one shared expert, untied head;
models/hybrid_moe.py) against its plain reference
(benchmark/reference/mhc_moe_f32.py) at the small preset, seeded weights, on
the CPU: the whole model in both precisions, listed and scanned, at both
dispatch sizes; the maps alone; what a dropped term or a Sinkhorn cut short
does; padding and causality; the rule tied to the table's other entries;
the reader and what it refuses; the published parameter count; the three
accepted models' programs, which the table leaves as they were; and the
served path through ``SeqScorer`` with what it reports."""

import dataclasses
import json
import os
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cca_moe_f32, hybrid_moe_f32, mla_moe_f32
from benchmark.reference import mhc_moe_f32 as ref
from benchmark.reference import table
from ccfd_tpu.models import hybrid_moe as hm
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
    return _config("tests", "benchmark", "xing4_small_config.json")


@pytest.fixture(scope="module")
def trees(small):
    """The one draw, as the program scans it and as it unrolls it."""
    return {stack: ref.make_params(dict(small, layer_stack=stack))
            for stack in ("scanned", "listed")}


@pytest.fixture(scope="module")
def params(trees):
    return trees["scanned"]


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


def _streams(small, n=2, t=100, pad=(0, 37), seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, small["hc_mult"], small["hidden_size"]))
    real = np.arange(t)[None, :] >= np.asarray(pad)[:, None]
    return jnp.asarray(x, F32), jnp.asarray(real)


# -- the whole model --------------------------------------------------------------

@pytest.mark.parametrize("dtype,worst,mean", [
    (F32, 2e-4, 2e-5),  # the reference's own precision: tight
    (jnp.bfloat16, None, 0.05),  # as served: a token near a tie may choose
    # another expert, so the widest gap is wide; the mean is not
])
@pytest.mark.parametrize("stack", ["scanned", "listed"])
@pytest.mark.parametrize("windows", [4, 8])
def test_logits_agree_with_the_reference_at_every_position(
        small, trees, cfg, rows, dtype, worst, mean, stack, windows):
    """Both forms of the stack at both dispatch sizes (the benchmark serves
    programs of 4 and of 8 windows), rows of every depth among them."""
    params = trees[stack]
    hist, filled = _windows(rows, [8, 3, 1, 6, 8, 2, 5, 7][:windows])
    want, want_choice, want_defect = ref.forward(
        params, small, hist, filled, every_position=True, with_defect=True)
    with jax.default_matmul_precision("highest"):
        got, aux = hm.logits_everywhere(params, hist, filled, cfg, dtype)
    real = np.asarray(hybrid_moe_f32.real_tokens(
        jnp.asarray(filled), LENGTH, COLS))
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.mean() < mean
    assert int(aux["routed_tokens"]) == int(real.sum())
    # every expert is held: four pairs a token and expert layer, none absent
    assert int(aux["pairs_served"]) == int(real.sum()) * 3 * 4
    assert int(aux["pairs_absent"]) == int(aux["skipped_tokens"]) == 0
    assert np.asarray(aux["pairs"]).shape == (3, 16)
    if worst is not None:
        assert gap.max() < worst
        assert np.array_equal(np.asarray(aux["row_choice"]), want_choice)
        assert float(aux["hc_defect"]) == pytest.approx(want_defect,
                                                        rel=1e-3)


def test_the_listed_and_the_scanned_tree_hold_the_same_values(small, trees):
    """``scanned``: the dense layer's tree, then one tree of the three
    expert layers; ``listed``: four trees; leaf for leaf the same draw."""
    scanned, listed = trees["scanned"]["layers"], trees["listed"]["layers"]
    assert len(scanned) == 2 and len(listed) == 4
    assert hm._stacked(scanned[0]) is None and hm._stacked(scanned[1]) == 3
    assert all(hm._stacked(p) is None for p in listed)
    for i in range(4):
        a = ref.layer_of(trees["scanned"], i)
        b = ref.layer_of(trees["listed"], i)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    assert "experts" not in listed[0]["ffn"] and "experts" in listed[1]["ffn"]
    assert listed[1]["res1"]["phi"].shape == (4 * 64, 24)


def test_a_stack_of_layers_of_two_kinds_is_refused(trees, cfg, rows):
    """One tree holds alike layers: the dense layer and an expert layer
    cannot be scanned as one."""
    hist, filled = _windows(rows, [8])
    wrong = dataclasses.replace(
        cfg, layers=(("mla", "dense"), ("mla", "moe"), ("mla", "dense"),
                     ("mla", "moe")))
    with pytest.raises(ValueError, match="one kind"):
        hm.logits_everywhere(trees["scanned"], hist, filled, wrong, F32)


# -- the maps ----------------------------------------------------------------------

def test_the_maps_agree_with_the_reference_and_are_doubly_stochastic(
        small, params, cfg):
    """h_pre in (0, 1), h_post in (0, 2), H_res positive with every row
    and column summing to 1 up to the 20 steps' residue: the columns to
    eps (they were normalised last), the rows to what the last column step
    moved them by; and the defect the program reports is that residue."""
    p = ref.layer_of(params, 1)["res2"]
    x, real = _streams(small)
    with jax.default_matmul_precision("highest"):
        got = hm.mhc_maps(p, x, cfg.residual_settings, cfg.eps)
        want = ref.maps(p, x, small)
    for a, b in zip(got, want):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
    h_pre, h_post, h_res = (np.asarray(a, np.float64) for a in got)
    assert (0 < h_pre).all() and (h_pre < 1).all()
    assert (0 < h_post).all() and (h_post < 2).all()
    assert (h_res > 0).all() and h_res.shape == (2, 100, 4, 4)
    assert np.abs(h_res.sum(-2) - 1).max() < 1e-5  # columns: the last step
    rows_off = np.abs(h_res.sum(-1) - 1).max()
    assert 1e-6 < rows_off < 0.1  # rows: the residue, small and not zero
    assert ref.defect_of(got[2], jnp.ones_like(real)) == pytest.approx(
        rows_off, rel=1e-3)
    # the dynamic terms are of the size of the static ones: the maps differ
    # from token to token by as much as from the mean
    assert h_res.std(axis=(0, 1)).mean() > 0.05


@pytest.mark.parametrize("fault", ["no_dynamic_terms", "five_sinkhorn_steps",
                                   "no_clamp_no_matter"])
def test_a_term_left_out_or_a_sinkhorn_cut_short_shows(small, params, cfg,
                                                       rows, fault):
    """What the weights' draw is for (``assumed.weights``): a program
    without the dynamic terms alpha (u phi), or with 5 Sinkhorn steps of
    the 20, answers far from the reference; one whose clamp is wider
    answers the same (no logit of this draw reaches 30)."""
    hist, filled = _windows(rows, [8, 5])
    want, _ = ref.forward(params, small, hist, filled)
    tree, settings = params, cfg.residual_settings
    if fault == "no_dynamic_terms":
        def zero(p):
            return dict(p, res1=dict(p["res1"], alpha=p["res1"]["alpha"] * 0),
                        res2=dict(p["res2"], alpha=p["res2"]["alpha"] * 0))
        tree = dict(params, layers=[zero(p) for p in params["layers"]])
    elif fault == "five_sinkhorn_steps":
        settings = dataclasses.replace(settings, sinkhorn_iters=5)
    else:
        settings = dataclasses.replace(settings, clamp=(-80.0, 80.0))
    other = dataclasses.replace(cfg, residual_settings=settings)
    with jax.default_matmul_precision("highest"):
        proba, aux = hm.apply_serving(tree, hist, filled, other, F32)
    gap = np.abs(np.asarray(aux["logits"]) - np.asarray(want))
    if fault == "no_clamp_no_matter":
        assert gap.max() < 2e-4
    else:
        assert gap.mean() > 2e-3  # a hundred times the float32 agreement
    if fault == "five_sinkhorn_steps":  # and the program's own number shows it
        with jax.default_matmul_precision("highest"):
            _, whole = hm.apply_serving(params, hist, filled, cfg, F32)
        assert float(aux["hc_defect"]) > 3 * float(whole["hc_defect"])


def test_one_stream_with_unit_maps_is_the_plain_rule(small, params, cfg):
    """n = 1, h_pre = 1, h_post = 1, H_res = 1: X' = X + f(X). With alpha =
    0 the maps are their biases': sigmoid(40) = 1, 2 sigmoid(0) = 1, and
    Sinkhorn of one value is 1 / (1 + eps) twice."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 50, 1, 64)), F32)
    real = jnp.ones((2, 50), bool)
    w = jnp.asarray(rng.normal(size=(64, 64)) / 8.0, F32)

    def sublayer(z):
        return jnp.tanh(z @ w), "extra"

    p = {"phi": jnp.asarray(rng.normal(size=(64, 3)), jnp.bfloat16),
         "alpha": jnp.zeros((3,), F32), "b": jnp.asarray([40.0, 0.0, 0.7])}
    one = dataclasses.replace(cfg, residual_settings=dataclasses.replace(
        cfg.residual_settings, streams=1))
    with jax.default_matmul_precision("highest"):
        got, extra, defect = hm.RESIDUALS["mhc"](p, x, sublayer, real, one)
        want, also, none = hm.RESIDUALS["plain"](None, x[:, :, 0], sublayer,
                                                 real, one)
    assert extra == also == "extra" and none is None
    assert np.allclose(np.asarray(got)[:, :, 0], np.asarray(want), atol=2e-5)
    assert float(defect) < 1e-5
    # and the table's third entry with unit scales and no biases
    unit = {"s_r": jnp.ones(64), "b_r": jnp.zeros(64), "s_o": jnp.ones(64),
            "b_o": jnp.zeros(64)}
    scaled, _, _ = hm.RESIDUALS["scaled"](unit, x[:, :, 0], sublayer, real,
                                          one)
    assert np.allclose(np.asarray(scaled), np.asarray(want), atol=1e-6)
    assert set(hm.RESIDUALS) == {"plain", "multiplied", "scaled", "mhc"}


# -- padding and causality ------------------------------------------------------------

@pytest.mark.parametrize("padding", ["zeros", "noise"])
def test_a_verdict_is_the_same_at_every_window_that_holds_its_history(
        small, params, cfg, rows, padding):
    """One history of 5 records at windows of 8, 16 and 64 records gives
    one verdict, one routing and one defect, with other records where the
    padding is, too: the rule is a token's own, and the defect is taken
    over real tokens."""
    hist, _ = _windows(rows, [5], 5)
    rng = np.random.default_rng(9)
    verdicts, choices, defects = [], [], []
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
        defects.append(float(aux["hc_defect"]))
        assert int(aux["routed_tokens"]) == 5 * COLS
    assert np.allclose(verdicts, verdicts[0], rtol=1e-4, atol=1e-7)
    assert all(np.array_equal(c, choices[0]) for c in choices)
    assert np.allclose(defects, defects[0], rtol=1e-3)
    want, _ = ref.forward(params, small, hist, np.array([5], np.int32))
    p_want = 1.0 / (1.0 + np.exp(-float(ref.verdict_logit(
        np.asarray(want), small)[0])))
    assert verdicts[0] == pytest.approx(p_want, rel=1e-3)


def test_a_window_of_padding_alone_routes_nowhere_and_has_no_defect(params,
                                                                    cfg):
    _, aux = hm.apply_serving(params, np.zeros((2, LENGTH, COLS), np.float32),
                              np.zeros(2, np.int32), cfg, F32)
    assert int(aux["pairs_served"]) == int(aux["routed_tokens"]) == 0
    assert float(aux["hc_defect"]) == 0.0


def test_a_later_token_moves_no_earlier_stream(params, cfg, rows):
    hist, filled = _windows(rows, [8, 6])
    other = hist.copy()
    other[:, -1] = rows[:2]  # the newest record of both rows

    def streams(h):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(
                hm.hidden_states, static_argnames=("cfg", "dtype"))(
                    params, h, filled, cfg=cfg, dtype=F32)[0])

    x, y = streams(hist), streams(other)
    assert x.shape == (2, LENGTH * COLS, 4, 64)
    before = (LENGTH - 1) * COLS
    real = np.asarray(hybrid_moe_f32.real_tokens(
        jnp.asarray(filled), LENGTH, COLS))[:, :before]
    assert np.allclose(x[:, :before][real], y[:, :before][real], atol=1e-5,
                       rtol=0)
    assert np.abs(x[:, before:] - y[:, before:]).max() > 1e-3


# -- the reader ------------------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    ({"n_group": 2, "topk_group": 2}, "xing4_0"),
    ({"n_shared_experts": 0}, "xing4_0"),
    ({"scoring_func": "softmax"}, "xing4_0"),
    ({"norm_topk_prob": False}, "xing4_0"),
    ({"n_routed_experts": 8}, "n_routed_experts"),
    ({"hc_mult": None}, "hc_mult"),
    ({"hc_sinkhorn_iters": None}, "hc_sinkhorn_iters"),
    ({"mhc_h_res_clamp_max": None}, "mhc_h_res_clamp"),
    ({"rope_scaling": None}, "rope_scaling")])
def test_a_configuration_the_reader_cannot_serve_is_refused(small, change,
                                                            match):
    """Groups, a layer without its shared expert, another scoring, and a
    missing key of the residual rule or of the rotary (``None`` here: the
    key taken out)."""
    model = {k: v for k, v in dict(small, **change).items() if v is not None}
    with pytest.raises((ValueError, KeyError), match=match):
        hm.HybridConfig.from_dict(model)


def test_the_real_configuration_reads_at_its_published_widths():
    real = _config("benchmark", "configs", "kafka_history_xing4.json")
    cfg = hm.HybridConfig.from_dict(real)
    mla = cfg.mixer("mla")
    assert (mla.heads, mla.nope, mla.rope, mla.v_dim, mla.kv_rank,
            mla.q_rank) == (32, 128, 64, 128, 512, 768)
    assert mla.yarn.factor == 64 and mla.yarn.original == 4096
    assert mla.yarn.query_beta == 0 and mla.interleaved
    assert not mla.part_norms and mla.theta == 1e4
    m = 0.1 * np.log(64.0) + 1.0
    assert mla.scale == pytest.approx(192 ** -0.5 * m * m)
    assert mla.turn_scale == 1.0
    assert ref.mla_dims(real)["sigma"] == pytest.approx(mla.scale)
    assert cfg.residual_settings == hm.Mhc(4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.layers[0] == ("mla", "dense")
    assert cfg.layers[1:] == (("mla", "moe"),) * (len(cfg.layers) - 1)
    assert len(cfg.layers) in (6, 7) and cfg.moe_layers == len(cfg.layers) - 1
    assert (cfg.routed, cfg.held_first, cfg.held_count, cfg.per_token) == (
        64, 0, 64, 4)
    assert cfg.routing == hm.TopK("sigmoid", True, 1, 1, 2.0)


@pytest.mark.parametrize("windows", [4, 8])
def test_the_real_programs_hold_the_expert_kernels_and_the_attention_kernel(
        windows):
    """What ``executable_grid()`` reads back for both served programs, from
    their own jaxprs at the published shapes (nothing is drawn or run):
    hidden 3,584 and expert width 1,024 fill lane tiles, so the held
    experts multiply through the grouped kernels; a query-key width of 128
    + 64 = 192 is a lane tile and a half against values of 128, so every
    layer attends through the causal-attention kernel (the plain path
    until PR 42), and none through ``seq``'s."""
    from ccfd_tpu.ops import grouped_experts, kernels

    real = _config("benchmark", "configs", "kafka_history_xing4.json")
    cfg = hm.HybridConfig.from_dict(real)
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    hist = jax.ShapeDtypeStruct((windows, 64, 30), F32)
    filled = jax.ShapeDtypeStruct((windows,), jnp.int32)

    def program(p, h, f):
        return hm.apply_serving(p, h, f, cfg, jnp.bfloat16)

    assert kernels.kernels_of(program, shapes, hist, filled) == {
        "causal_attention", *grouped_experts.KERNELS}


def _count(shapes) -> int:
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


@pytest.mark.parametrize("which", ["published", "cut"])
def test_the_parameter_count_from_the_shapes(which):
    """Published: 40 layers, 2 dense of 128.2 M + 38 expert layers of 745.0
    M + 939.5 M of embedding and head = 29.5 B (the next-token block
    apart). The cut: the file's kept layers + the whole vocabulary, 11.08
    GB at 2 bytes (five expert layers: 9.59). Shapes only: nothing is
    drawn."""
    real = _config("benchmark", "configs", "kafka_history_xing4.json")
    if which == "published":
        real = dict(real, layers_kept=list(range(40)), layer_stack="scanned")
    hm.HybridConfig.from_dict(real)  # the reader takes it
    shapes = jax.eval_shape(lambda: ref.make_params(real))
    layers = shapes["layers"]
    outside = _count({k: v for k, v in shapes.items()
                      if k not in ("layers", "edges")})
    assert outside == 2 * 131072 * 3584 + 3584  # 939.5 M and the final norm
    mla = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 4096 * 3584
    maps = 2 * (14336 * 24 + 24 + 3)
    norms = 2 * 3584 + 768 + 512
    dense = mla + maps + norms + 3 * 3584 * 9216
    sparse = mla + maps + norms + 3584 * 64 + 64 + 65 * 3 * 3584 * 1024
    assert 128.1e6 < dense < 128.3e6 and 744.9e6 < sparse < 745.1e6
    if which == "published":
        assert [hm._stacked(p) for p in layers] == [None, None, 38]
        assert _count(layers) == 2 * dense + 38 * sparse
        assert 29.4e9 < _count(layers) + outside < 29.6e9
    else:
        n = len(real["layers_kept"]) - 1
        assert _count(layers) == dense + n * sparse
        total = 2 * (_count(layers) + outside)
        assert total == pytest.approx({6: 11.08e9, 5: 9.59e9}[n], rel=2e-3)


# -- what the table leaves as it was ---------------------------------------------------

def _plain_loop(params, hist, filled, cfg, dtype):
    """The family's forward pass as it stood before the residual rules
    were a table: x + f(norm(x)) or the scaled sum, a scan over one tree
    or a loop over a list. Everything but the layer loop is the
    program's."""
    b, length, cols = hist.shape
    t = length * cols
    filled = filled.astype(jnp.int32)
    at = jnp.arange(t, dtype=jnp.int32)
    real = (at // cols)[None, :] >= (length - filled)[:, None]
    position = jnp.maximum(at[None, :] - ((length - filled) * cols)[:, None],
                           0)
    with jax.named_scope("lm.embed"):
        ids = hm.tokenise(params["edges"], hist.astype(F32), cfg.bins)
        x = params["embed"][ids].astype(F32)

    def add(scaling, x, y):
        if scaling is None:
            return x + y
        return scaling["s_r"] * (x + scaling["b_r"]) + scaling["s_o"] * (
            y + scaling["b_o"])

    def layer(p, x, r, kind):
        mixer, ffn = kind
        z = hm._rms(x, p["norm1"], cfg.eps)
        with jax.named_scope(mixer):
            y, _ = hm.MIXERS[mixer](p["mixer"], z, real, position, cfg,
                                    dtype)
        x = add(p.get("res1"), x, y)
        z = hm._rms(x, p["norm2"], cfg.eps)
        if ffn == "dense":
            with jax.named_scope("dense_ffn"):
                return add(p.get("res2"), x, hm._swiglu(p["ffn"], z,
                                                        dtype)), r, None
        y, r, counts = hm.moe(p["ffn"], z, r, real, cfg, dtype)
        return add(p.get("res2"), x, y), r, counts

    layers = params["layers"]
    r = None
    if cfg.router == "carried_mlp":
        r = jnp.zeros((b * t, hm._router_width(layers)), F32)
    if isinstance(layers, Mapping):
        def step(carry, p):
            x, r, counts = layer(p, *carry, cfg.layers[0])
            return (x, r), counts

        (x, r), counts = jax.lax.scan(step, (x, r), layers)
    else:
        each = []
        for kind, p in zip(cfg.layers, layers):
            x, r, one = layer(p, x, r, kind)
            if one is not None:
                each.append(one)
        counts = jax.tree.map(lambda *leaves: jnp.stack(leaves), *each)
    aux = {"pairs": counts["pairs"],
           "pairs_served": counts["served"].sum(dtype=jnp.int32),
           "pairs_absent": counts["absent"].sum(dtype=jnp.int32),
           "routed_tokens": jnp.sum(real, dtype=jnp.int32),
           "skipped_tokens": counts["skipped"].sum(dtype=jnp.int32),
           "row_pairs": counts["row_pairs"].sum(0, dtype=jnp.int32),
           "row_choice": jnp.swapaxes(counts["row_choice"], 0, 1)}
    z = hm.slice_logits(params, x[:, -1], cfg, dtype)
    aux["logits"] = z
    verdict = z[:, cfg.fraud_id] - z[:, cfg.legit_id] + cfg.shift
    return jax.nn.sigmoid(verdict), aux


ACCEPTED = {  # model -> (small preset, reference, the stacks it can draw)
    "ling3": ("ling3_small_config.json", hybrid_moe_f32),
    "zaya1": ("zaya1_small_config.json", cca_moe_f32),
    "mistral4": ("mistral4_small_config.json", mla_moe_f32)}


@pytest.mark.parametrize("model,stack", [
    ("ling3", None), ("zaya1", None), ("mistral4", "scanned"),
    ("mistral4", "listed")])
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_an_accepted_models_program_lowers_to_the_text_it_lowered_to(
        model, stack, dtype):
    """The residual table, the stacks inside a list and the rule's extra
    output add no operation to, and move none in, the three accepted
    programs: ``apply_serving`` lowers to the text the plain loop lowers
    to, listed (``ling3``, ``mistral4``) and scanned (``zaya1``,
    ``mistral4``), and its ``aux`` keeps eight leaves. (Against the parent
    commit's own text the builder's run found the same: PERF.md, PR 41.)"""
    preset, reference = ACCEPTED[model]
    small = _config("tests", "benchmark", preset)
    if stack is not None:
        small = dict(small, layer_stack=stack)
    params = reference.make_params(small)
    cfg = hm.HybridConfig.from_dict(small)
    hist = jnp.zeros((16, LENGTH, COLS), F32)
    filled = jnp.ones((16,), jnp.int32)

    def text(fn):
        return jax.jit(lambda p, h, f: fn(p, h, f, cfg, dtype)).lower(
            params, hist, filled).as_text()

    ours = text(hm.apply_serving.__wrapped__)
    assert ours == text(_plain_loop)
    assert "hc" not in ours.split("func.func")[0]
    shapes = jax.eval_shape(lambda p, h, f: hm.apply_serving(
        p, h, f, cfg, dtype), params, hist, filled)
    assert len(shapes[1]) == 8 and "hc_defect" not in shapes[1]


# -- the served path ----------------------------------------------------------------------

def test_a_keyed_stream_through_the_scorer_equals_the_reference(
        small, params, cfg, rows):
    """Records of a few customers through ``HistoryStore`` + ``SeqScorer``
    (family by name, buckets, repeated keys inside a batch, histories
    shorter and longer than the window): record for record the reference's
    verdict on the history that customer had; the counters add up to four
    pairs a token and expert layer with none absent; ``executable_grid``
    names the residual rule and its settings; the family's ``aux`` has one
    leaf more than the other models', which ``seq.wait`` and a gauge
    carry."""
    from ccfd_tpu.metrics.prom import Registry

    reg = Registry()
    scorer = SeqScorer(params, length=LENGTH, batch_sizes=(4, 16),
                       compute_dtype="float32", registry=reg,
                       family="hybrid_moe", family_config=cfg)
    rng = np.random.default_rng(11)
    customers = rng.choice([3, 5, 8, 13], size=37, p=[0.55, 0.25, 0.15, 0.05])
    sent = rows[rng.integers(0, len(rows), len(customers))]
    seen = []
    scorer.aux_tap = lambda idx, m, aux: seen.append(aux)
    served = np.concatenate([
        scorer.score(sent[lo:lo + 9], [int(c) for c in customers[lo:lo + 9]])
        for lo in range(0, len(customers), 9)])
    hist, filled = ref.histories(
        customers, np.arange(len(customers)), sent,
        np.arange(len(customers)), LENGTH, np.full((14, 1), -1, np.int64))
    logits, choice, defect = ref.forward(params, small, hist, filled,
                                         with_defect=True)
    want = 1.0 / (1.0 + np.exp(-np.asarray(ref.verdict_logit(
        np.asarray(logits), small), np.float64)))
    assert np.allclose(served, want, rtol=2e-3, atol=1e-6)
    grid = scorer.executable_grid()
    assert grid["model"] == "hybrid_moe" and grid["experts_held"] == [0, 16]
    assert grid["router"] == "top_k" and grid["residual"] == "mhc"
    assert dict(grid["kinds"]["mhc"], clamp=list(
        grid["kinds"]["mhc"]["clamp"])) == {
            "streams": 4, "sinkhorn_iters": 20, "eps": 1e-6,
            "clamp": [-30.0, 30.0]}
    assert grid["kinds"]["top_k"]["score"] == "sigmoid"
    assert grid["kinds"]["mla"]["q_rank"] == 32
    json.dumps(grid)
    total = {k: reg.counter(k).total() for k in (
        "moe_pairs_served_total", "moe_pairs_routed_total",
        "moe_pairs_absent_total", "moe_routed_tokens_total",
        "lm_tokens_total")}
    assert total["moe_pairs_served_total"] == total[
        "moe_pairs_routed_total"] == choice.sum()
    assert total["moe_pairs_absent_total"] == 0
    assert total["moe_pairs_served_total"] == total[
        "moe_routed_tokens_total"] * 3 * 4
    assert total["lm_tokens_total"] == int(filled.sum()) * COLS
    # the ninth leaf, and where it goes
    assert all(len(aux) == 9 and "hc_defect" in aux for aux in seen)
    worst = max(float(aux["hc_defect"]) for aux in seen)
    assert worst == pytest.approx(defect, rel=1e-3)
    assert reg.gauge("lm_hc_defect_max").value() == pytest.approx(worst)
    observe = hm.make_observer(Registry())
    stats = observe({k: np.asarray(v) for k, v in seen[0].items()})
    assert stats["hc_defect"] == pytest.approx(float(seen[0]["hc_defect"]))
    assert set(stats) == {"pairs_served", "pairs_absent", "skipped_tokens",
                          "routed_tokens", "max_expert_pairs", "hc_defect"}


def test_the_family_describes_the_rule_of_every_model():
    described = {}
    for preset in ("ling3", "zaya1", "mistral4", "xing4"):
        model = _config("tests", "benchmark", f"{preset}_small_config.json")
        spec = registry.get_history("hybrid_moe")
        described[preset] = spec.describe(spec.config_from(model))
    assert [described[m]["residual"] for m in described] == [
        "plain", "scaled", "plain", "mhc"]
    assert "mhc" in described["xing4"]["kinds"]
    assert not {"plain", "scaled", "mhc"} & set(described["zaya1"]["kinds"])
