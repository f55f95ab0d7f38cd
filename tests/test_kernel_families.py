"""The kernels' seam (ops/kernels.py): the table of families against the
kernel modules, the names a dashboard reads, what the eight ``kernel_fits``
answer at the shapes the kernel tests carry, and that a row of the table is
all a family's key and counter take to appear."""

import ast
import contextlib
import importlib
import inspect
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ccfd_tpu.ops
from ccfd_tpu.analysis.rules import metric_name_ok
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq
from ccfd_tpu.ops import (causal_attention, cca_conv, gdn_scan,
                          grouped_experts, kda_scan, kernels, seq_attention,
                          short_conv, ssd_scan)
from ccfd_tpu.serving import history

BF16, F32, F16 = jnp.bfloat16, jnp.float32, jnp.float16


# -- the table against the modules -----------------------------------------------------

def _named(module) -> tuple:
    return getattr(module, "KERNELS", None) or (
        (module.KERNEL,) if hasattr(module, "KERNEL") else ())


def test_every_module_that_names_a_kernel_has_a_row_and_every_row_a_module():
    naming = {info.name for info in pkgutil.iter_modules(ccfd_tpu.ops.__path__)
              if _named(importlib.import_module(f"ccfd_tpu.ops.{info.name}"))}
    rows = [name for family in kernels.FAMILIES for name in family.modules]
    assert sorted(rows) == sorted(naming)  # each once: a module has one row


def test_every_kernel_name_belongs_to_exactly_one_family():
    claimed = [name for family in kernels.FAMILIES for name in family.names]
    assert len(claimed) == len(set(claimed)) == 10
    assert all(isinstance(name, str) and name for name in claimed)
    for family in kernels.FAMILIES:
        assert set(family.names) == {
            name for module in family.modules for name in _named(
                importlib.import_module(f"ccfd_tpu.ops.{module}"))}


@pytest.mark.parametrize("module", [seq_attention, causal_attention,
                                    grouped_experts, ssd_scan, kda_scan,
                                    short_conv, cca_conv, gdn_scan],
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_a_module_hands_pallas_call_no_name_but_those_it_declares(module):
    """The ``name=`` keywords of the module's calls, read from its source:
    its declared constants and nothing spelt in place, so a second kernel
    in a module cannot run under a name that no family counts."""
    given = set()
    for node in ast.walk(ast.parse(inspect.getsource(module))):
        for kw in getattr(node, "keywords", ()) if isinstance(
                node, ast.Call) else ():
            if kw.arg != "name":
                continue
            assert not isinstance(kw.value, ast.Constant), kw.value.value
            constant = getattr(module, getattr(kw.value, "id", ""), None)
            if isinstance(constant, str):
                given.add(constant)
    assert given == set(_named(module))


def test_the_names_on_the_wire_are_the_ones_dashboards_read():
    assert [(f.key, f.counter, f.names) for f in kernels.FAMILIES] == [
        ("attn_kernel", "seq_attention_kernel_dispatch_total",
         ("seq_attention", "causal_attention")),
        ("expert_kernel", "seq_expert_kernel_dispatch_total",
         ("expert_up", "expert_down", "expert_rows")),
        ("ssd_kernel", "seq_ssd_kernel_dispatch_total", ("ssd_scan",)),
        ("kda_kernel", "seq_kda_kernel_dispatch_total", ("kda_scan",)),
        ("conv_kernel", "seq_conv_kernel_dispatch_total", ("short_conv",)),
        ("cca_kernel", "seq_cca_kernel_dispatch_total", ("cca_conv",)),
        ("gdn_kernel", "seq_gdn_kernel_dispatch_total", ("gdn_scan",))]
    assert kernels.held(lambda x: x, 1.0) == {
        "attn_kernel": 0, "expert_kernel": 0, "ssd_kernel": 0,
        "kda_kernel": 0, "conv_kernel": 0, "cca_kernel": 0, "gdn_kernel": 0}
    assert kernels.FAMILIES[0].help == (
        "seq dispatches of executables whose attention holds a kernel that "
        "keeps the scores on the chip (beside seq_bucket_dispatch_total: "
        "the rest attended through XLA)")


@pytest.mark.parametrize("family", kernels.FAMILIES, ids=lambda f: f.key)
def test_a_familys_counter_keeps_the_naming_rule(family):
    """``analysis/rules.py`` reads literal names at ``registry.counter``
    sites; these come from the table, so they are held to the rule here."""
    assert metric_name_ok("counter", family.counter) is None
    assert family.counter.startswith("seq_") and family.key.endswith("_kernel")
    assert family.help.count("seq_bucket_dispatch_total") == 1


# -- what the eight kernel_fits answer ----------------------------------------------------

def _shape(dims, dtype, mesh=None):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.ShapeDtypeStruct(dims, dtype, sharding=mesh and NamedSharding(
        mesh, PartitionSpec()))


def _seq(dims, dtype, mesh):
    q, k = dims
    return seq_attention.kernel_fits(q, k, dtype)


def _causal(dims, dtype, mesh):
    return causal_attention.kernel_fits(*dims, dtype)


def _experts(dims, dtype, mesh):
    return grouped_experts.kernel_fits(_shape(dims, dtype, mesh), dtype)


def _ssd(dims, dtype, mesh):
    x, b, chunk = dims
    return ssd_scan.kernel_fits(_shape(x, dtype, mesh), _shape(b, dtype),
                                chunk)


def _kda(dims, dtype, mesh):
    return kda_scan.kernel_fits(_shape(dims, dtype), _shape(dims, dtype, mesh),
                                64, 16)


def _conv(dims, dtype, mesh):
    proj, at, widths = dims
    return short_conv.kernel_fits(
        _shape(proj, dtype, mesh), _shape((4, sum(widths)), jnp.float32), at,
        widths)


def _cca(dims, dtype, mesh):
    tokens, hidden, heads, groups, head = dims
    return cca_conv.kernel_fits(
        _shape(tokens + (hidden,), jnp.float32, mesh),
        _shape((hidden, heads * head), dtype), _shape((hidden, groups * head),
                                                      dtype),
        _shape((2, (heads + groups) * head), jnp.float32),
        _shape((2, heads + groups, head, head), dtype), dtype)


def _gdn(dims, dtype, mesh):
    q, v, chunk = dims
    return gdn_scan.kernel_fits(_shape(q, dtype), _shape(v, dtype, mesh),
                                chunk)


# (family, which shape, its dimensions, whether the parent's ``kernel_fits``
# took it in bfloat16 and float32 on one device): written out from the
# parent's code before ``ops/kernels.py`` took over the common part
FITS = [
    (_seq, "served", ((1024, 4, 512, 32),) * 2, True),
    (_seq, "rung_128", ((3, 4, 128, 32),) * 2, True),
    (_seq, "rung_64", ((2, 4, 64, 32),) * 2, False),
    (_seq, "readout", ((2, 4, 1, 32), (2, 4, 512, 32)), False),
    (_causal, "served", ((8, 32, 1920, 128),) * 3, True),
    (_causal, "served_192", ((8, 32, 1920, 192),) * 2 + (
        (8, 32, 1920, 128),), True),
    (_causal, "lane_wide", ((2, 4, 256, 128), (2, 2, 256, 128),
                            (2, 2, 256, 128)), True),
    (_causal, "small", ((2, 4, 240, 16),) * 3, False),
    (_experts, "served", (32, 4096, 2048), True),
    (_experts, "lane_wide", (8, 256, 384), True),
    (_experts, "small", (8, 64, 32), False),
    (_ssd, "served", ((8, 1920, 128, 64), (8, 1920, 1, 128), 384), True),
    (_ssd, "lane_wide", ((2, 240, 8, 64), (2, 240, 1, 128), 128), True),
    (_ssd, "small", ((3, 240, 8, 16), (3, 240, 2, 16), 32), False),
    (_kda, "served", (8, 1920, 32, 128), True),
    (_kda, "lane_wide", (2, 240, 2, 128), True),
    (_kda, "small", (3, 240, 4, 16), False),
    # new with its row (PR 50): by the module's own rules, no parent to copy
    (_conv, "served", ((4, 1920, 16768), 8192, (8192, 128, 128)), True),
    (_conv, "served_8_groups", ((8, 1920, 10304), 4096, (4096, 1024, 1024)),
     True),
    (_conv, "lane_wide", ((2, 240, 1288), 512, (512, 128, 128)), True),
    (_conv, "small", ((3, 240, 328), 128, (128, 32, 32)), False),
    # and PR 51's
    (_cca, "served", ((8, 1920), 2048, 8, 2, 128), True),
    (_cca, "lane_wide", ((3, 240), 128, 4, 2, 128), True),
    (_cca, "small", ((3, 240), 64, 8, 2, 16), False),
    # and PR 53's
    (_gdn, "served", ((8, 1920, 16, 128), (8, 1920, 32, 128), 16), True),
    (_gdn, "lane_wide", ((2, 240, 1, 128), (2, 240, 2, 128), 32), True),
    (_gdn, "small", ((3, 240, 2, 16), (3, 240, 4, 16), 32), False),
]
ASKS_ABOUT_A_MESH = (_experts, _ssd, _kda, _conv, _cca, _gdn)


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]), ("data",))


@pytest.mark.parametrize("fits,which,dims,taken", FITS, ids=[
    f"{fits.__name__[1:]}_{which}" for fits, which, *_ in FITS])
def test_kernel_fits_answers_as_the_parents_did(fits, which, dims, taken,
                                                mesh):
    """By dtype, with an operand on a mesh and under an abstract mesh: the
    grouped experts, the three scans and the two convolutions refuse either mesh; the two
    attentions never ask (``SeqScorer`` hands each device its rows under
    ``shard_map`` itself) and answer under an abstract mesh as without."""
    meshed = fits in ASKS_ABOUT_A_MESH
    for dtype in (BF16, F32, F16):
        want = taken and dtype != F16
        for under in (contextlib.nullcontext(),
                      jax.sharding.use_abstract_mesh(mesh.abstract_mesh)):
            inside = not isinstance(under, contextlib.nullcontext)
            with under:
                assert fits(dims, dtype, None) is (
                    want and not (meshed and inside)), (dtype, inside)
                if meshed:
                    assert fits(dims, dtype, mesh) is False, (dtype, inside)


def test_a_backend_without_pallas_takes_no_kernel(monkeypatch):
    assert kernels.backend_runs_pallas() and kernels.interpreted()  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not kernels.backend_runs_pallas()
    for fits, _, dims, _ in FITS:
        assert fits(dims, BF16, None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.backend_runs_pallas() and not kernels.interpreted()
    assert all(fits(dims, BF16, None) is taken
               for fits, _, dims, taken in FITS)


# -- a row is all it takes -----------------------------------------------------------------

def test_a_further_row_brings_its_key_and_its_counter_with_no_other_edit(
        monkeypatch):
    """A table of the test's own, with one more family that counts ``seq``'s
    kernel alone: the inventory, the ``seq.enqueue`` phase and the registry
    carry it beside the seven, and ``serving/history.py`` was not told."""
    fifth = kernels.Family(
        "own_kernel", "seq_own_kernel_dispatch_total", "attention is seq's",
        "have another", ("seq_attention",))
    monkeypatch.setattr(kernels, "FAMILIES", kernels.FAMILIES + (fifth,))
    enqueued = []
    phase = history.phase

    def recorded(name, **stats):
        if name == "seq.enqueue":
            enqueued.append(stats)
        return phase(name, **stats)

    monkeypatch.setattr(history, "phase", recorded)
    reg = Registry()
    scorer = history.SeqScorer(seq.init(jax.random.PRNGKey(0)), length=128,
                               batch_sizes=(4,), registry=reg)
    scorer.score(np.zeros((3, 30), np.float32), ids=["a", "b", "c"])
    want = {"attn_kernel": 1, "expert_kernel": 0, "ssd_kernel": 0,
            "kda_kernel": 0, "conv_kernel": 0, "cca_kernel": 0,
            "gdn_kernel": 0, "own_kernel": 1}
    (stats,) = enqueued
    assert {key: stats[key] for key in want} == want
    assert list(stats)[list(stats).index("tokens") + 1:][:len(want) + 1] == [
        *want, "flat_wire"]  # where they stood, in the table's order
    (entry,) = scorer.executable_grid()["grid"]
    assert {key: entry[key] for key in want} == {
        key: bool(on) for key, on in want.items()}
    assert reg.counter(fifth.counter).total() == 1
    assert reg.counter("seq_attention_kernel_dispatch_total").total() == 1
    assert reg.counter("seq_expert_kernel_dispatch_total").total() == 0
    assert fifth.help in reg.render()
    # a stand-in for the program holds none of the eight
    real = scorer._apply
    scorer._apply = lambda p, xs: real(p, xs)
    (entry,) = scorer.executable_grid()["grid"]
    assert not any(entry[key] for key in want)


# -- the CCA family's row, end to end ------------------------------------------------------

def _small_config(name):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tests", "benchmark",
                           name + "_small_config.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("widths,held", [
    ({"head_dim": 128, "num_attention_heads": 4, "hidden_size": 128}, 1),
    ({}, 0)], ids=["lane_wide", "small"])
def test_the_cca_row_is_on_the_enqueue_phase_in_the_grid_and_counted(
        monkeypatch, widths, held):
    """A ``zaya`` program served through ``SeqScorer``: with heads of 128
    its CCA mixers hold ``ops/cca_conv.py``'s kernel, and ``cca_kernel`` on
    every ``seq.enqueue``, the executable's entry in ``executable_grid()``
    and ``seq_cca_kernel_dispatch_total`` beside
    ``seq_bucket_dispatch_total`` say so; with the small preset's heads of
    16 they say 0 of the same dispatches."""
    from benchmark.reference import cca_moe_f32
    from ccfd_tpu.models import hybrid_moe

    config = {**_small_config("zaya1"), **widths}
    enqueued = []
    phase = history.phase

    def recorded(name, **stats):
        if name == "seq.enqueue":
            enqueued.append(stats)
        return phase(name, **stats)

    monkeypatch.setattr(history, "phase", recorded)
    reg = Registry()
    scorer = history.SeqScorer(
        cca_moe_f32.make_params(config), length=8, batch_sizes=(4,),
        compute_dtype="float32", registry=reg, family="hybrid_moe",
        family_config=hybrid_moe.HybridConfig.from_dict(config))
    for _ in range(2):
        scorer.score(np.zeros((3, 30), np.float32), ids=["a", "b", "a"])
    assert [e["cca_kernel"] for e in enqueued] == [held] * 2
    assert [e["conv_kernel"] for e in enqueued] == [0] * 2
    (entry,) = scorer.executable_grid()["grid"]
    assert entry["cca_kernel"] is bool(held) and entry["dispatches"] == 2
    assert reg.counter("seq_bucket_dispatch_total").total() == 2
    assert reg.counter("seq_cca_kernel_dispatch_total").total() == 2 * held
    assert reg.counter("seq_conv_kernel_dispatch_total").total() == 0


@pytest.mark.parametrize("name,module", [
    ("ling3", "hybrid_moe_f32"), ("mistral4", "mla_moe_f32"),
    ("xing4", "mhc_moe_f32"), ("granite4h", "ssm_moe_f32"),
    ("nemotron3n", "ssm_relu2_moe_f32"), ("seq", None)])
def test_a_program_without_the_mixer_holds_no_cca_kernel(name, module):
    """The five other ``hybrid_moe`` models at their small presets, and
    ``seq`` at a rung that holds its attention kernel."""
    if module is None:
        params = seq.init(jax.random.PRNGKey(0))
        held = kernels.held(
            lambda p, h: seq.apply_serving(p, h), params,
            jax.ShapeDtypeStruct((4, 128, 30), np.float32))
        assert held["attn_kernel"] == 1
    else:
        from ccfd_tpu.models import hybrid_moe

        config = _small_config(name)
        ref = importlib.import_module("benchmark.reference." + module)
        cfg = hybrid_moe.HybridConfig.from_dict(config)
        held = kernels.held(
            lambda p, h, f: hybrid_moe.apply_serving(p, h, f, cfg, F32),
            jax.eval_shape(lambda: ref.make_params(config)),
            jax.ShapeDtypeStruct((2, 8, 30), np.float32),
            jax.ShapeDtypeStruct((2,), np.int32))
    assert held["cca_kernel"] == held["gdn_kernel"] == 0


# -- the Gated DeltaNet family's row, end to end -------------------------------------------

@pytest.mark.parametrize("widths,held", [
    ({"linear_num_key_heads": 1, "linear_num_value_heads": 2,
      "linear_key_head_dim": 128, "linear_value_head_dim": 128}, 1),
    ({}, 0)], ids=["lane_wide", "small"])
def test_the_gdn_row_is_on_the_enqueue_phase_in_the_grid_and_counted(
        monkeypatch, widths, held):
    """A ``qwen3_next`` program served through ``SeqScorer``: with heads of
    128 its Gated DeltaNet mixers hold ``ops/gdn_scan.py``'s kernel (and
    ``ops/short_conv.py``'s in front of it), and ``gdn_kernel`` on every
    ``seq.enqueue``, the executable's entry in ``executable_grid()`` and
    ``seq_gdn_kernel_dispatch_total`` beside ``seq_bucket_dispatch_total``
    say so; with the small preset's heads of 16 they say 0 of the same
    dispatches."""
    from benchmark.reference import gdn_moe_f32
    from ccfd_tpu.models import hybrid_moe

    config = {**_small_config("qwen3next"), **widths, "layers_kept": [0, 3]}
    enqueued = []
    phase = history.phase

    def recorded(name, **stats):
        if name == "seq.enqueue":
            enqueued.append(stats)
        return phase(name, **stats)

    monkeypatch.setattr(history, "phase", recorded)
    reg = Registry()
    scorer = history.SeqScorer(
        gdn_moe_f32.make_params(config), length=8, batch_sizes=(4,),
        compute_dtype="float32", registry=reg, family="hybrid_moe",
        family_config=hybrid_moe.HybridConfig.from_dict(config))
    for _ in range(2):
        scorer.score(np.zeros((3, 30), np.float32), ids=["a", "b", "a"])
    assert [e["gdn_kernel"] for e in enqueued] == [held] * 2
    assert [e["conv_kernel"] for e in enqueued] == [held] * 2
    assert [e["kda_kernel"] for e in enqueued] == [0] * 2
    (entry,) = scorer.executable_grid()["grid"]
    assert entry["gdn_kernel"] is bool(held) and entry["dispatches"] == 2
    assert reg.counter("seq_bucket_dispatch_total").total() == 2
    assert reg.counter("seq_gdn_kernel_dispatch_total").total() == 2 * held
    assert reg.counter("seq_kda_kernel_dispatch_total").total() == 0


@pytest.mark.parametrize("name,module", [
    ("ling3", "hybrid_moe_f32"), ("mistral4", "mla_moe_f32"),
    ("zaya1", "cca_moe_f32"), ("xing4", "mhc_moe_f32"),
    ("granite4h", "ssm_moe_f32"), ("nemotron3n", "ssm_relu2_moe_f32")])
def test_a_program_without_the_mixer_holds_no_gdn_kernel(name, module):
    """The six other ``hybrid_moe`` models at their small presets."""
    from ccfd_tpu.models import hybrid_moe

    config = _small_config(name)
    ref = importlib.import_module("benchmark.reference." + module)
    cfg = hybrid_moe.HybridConfig.from_dict(config)
    held = kernels.held(
        lambda p, h, f: hybrid_moe.apply_serving(p, h, f, cfg, F32),
        jax.eval_shape(lambda: ref.make_params(config)),
        jax.ShapeDtypeStruct((2, 8, 30), np.float32),
        jax.ShapeDtypeStruct((2,), np.int32))
    assert held["gdn_kernel"] == 0
