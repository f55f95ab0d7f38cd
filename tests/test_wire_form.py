"""The wire form of a dispatch's history batch (``serving/history.py::
_Program``): where ``L * F`` fills whole (8, 128) float32 tiles and the
scorer has no mesh, the batch crosses as the (B, L * F / 128, 128) view of
the same memory and the one served program restores (B, L, F) first; any
other shape, and a mesh, keep (B, L, F). On the CPU: the probabilities are
the family's own bit for bit, the host copies nothing, the seam's readers
(inventory, counters, the kernel question) tell the truth."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.ops import seq_quant
from ccfd_tpu.serving.history import SeqScorer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = {
    "seq": (lambda p: p, seq_mod.apply_serving),
    "seq_q8": (seq_quant.quantize_seq, seq_quant.apply_serving),
}


def _histories(b, length, seed=0):
    return np.random.default_rng(seed).normal(
        size=(b, length, 30)).astype(np.float32)


def _rows(n, seed=0):
    return _histories(n, 1, seed)[:, 0]


def _wires(scorer) -> list:
    """Record every host array the flat program is handed (the kernel
    question traces it with shapes: no batch, not recorded)."""
    real, seen = scorer._apply.flat, []

    def flat(params, wire, *extra):
        if isinstance(wire, np.ndarray):
            seen.append(wire)
        return real(params, wire, *extra)

    scorer._apply.flat = flat
    return seen


@pytest.mark.parametrize("bucket", [4, 256])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_flat_wire_serves_the_familys_own_probabilities(family, bucket):
    """(4, 512, 30) and the benchmark's 256-row rung, both served families:
    through the seam the batch crosses flat, and what comes back is
    ``apply_serving`` on the (B, L, F) batch, bit for bit."""
    to_tree, direct = FAMILIES[family]
    params = to_tree(seq_mod.init(jax.random.PRNGKey(1)))
    scorer = SeqScorer(params, length=512, batch_sizes=(bucket,))
    assert scorer.executable_grid()["model"] == family
    seen = _wires(scorer)
    hist = _histories(bucket, 512)
    served = np.asarray(scorer._apply(scorer.params, hist))
    want = np.asarray(direct(params, hist, jnp.bfloat16, pos_length=512))
    assert np.array_equal(served, want)
    (wire,) = seen
    assert wire.shape == (bucket, 120, 128) and wire.dtype == np.float32
    assert np.shares_memory(wire, hist)
    assert np.array_equal(wire.reshape(hist.shape), hist)


def test_a_short_chunk_is_padded_as_before_and_crosses_as_a_view():
    """Three records in a bucket of four, twice: the wire is a view of the
    staging batch (nothing copied on the host), its padding row is zero,
    and each verdict is the family's on the history its customer had."""
    reg = Registry()
    params = seq_mod.init(jax.random.PRNGKey(2))
    scorer = SeqScorer(params, length=512, batch_sizes=(4,), registry=reg)
    seen = _wires(scorer)
    x = _rows(6, seed=3)
    first = scorer.score(x[:3], ids=["a", "b", "c"])
    second = scorer.score(x[3:], ids=["a", "a", "b"])
    assert len(seen) == 2
    (stage,) = scorer._staging  # recycled: both batches were staged in it
    for wire in seen:
        assert wire.shape == (4, 120, 128)
        assert np.shares_memory(wire, stage.hist)
        assert not wire[3].any()
    hist = np.zeros((2, 4, 512, 30), np.float32)  # the two padded batches
    hist[0, :3, -1] = x[:3]
    hist[1, 0, -2:] = x[[0, 3]]        # a: its first, then this one
    hist[1, 1, -3:] = x[[0, 3, 4]]     # a again, inside the same chunk
    hist[1, 2, -2:] = x[[1, 5]]
    for served, batch in zip((first, second), hist):
        want = np.asarray(seq_mod.apply_serving(params, batch, jnp.bfloat16,
                                                pos_length=512))
        assert np.array_equal(served, want[:3])
    assert reg.counter("seq_flat_wire_dispatch_total").total() == 2
    assert reg.counter("seq_bucket_dispatch_total").total() == 2


def test_a_ladder_window_is_copied_once_and_crosses_flat():
    """The L-bucket ladder's right-aligned window is the copy that fancy
    indexing makes; the flat form is a view of that copy, not a second."""
    reg = Registry()
    scorer = SeqScorer(seq_mod.init(jax.random.PRNGKey(3)), length=1024,
                       batch_sizes=(4,), len_buckets=(512,), registry=reg)
    assert scorer.len_buckets == (512, 1024)
    seen = _wires(scorer)
    scorer.score(_rows(4, seed=4), ids=[1, 2, 3, 4])
    (wire,) = seen
    assert wire.shape == (4, 120, 128)
    assert not wire.flags.owndata and wire.base is not None
    (stage,) = scorer._staging
    assert not np.shares_memory(wire, stage.hist)
    assert [(g["l_bucket"], g["flat_wire"], g["dispatches"])
            for g in scorer.executable_grid()["grid"]] == [
        (512, True, 1), (1024, True, 0)]


def _hybrid_moe_scorer(registry):
    from benchmark.reference import hybrid_moe_f32 as ref
    from ccfd_tpu.models import hybrid_moe as hm

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "ling3_small_config.json")) as f:
        small = json.load(f)
    params = ref.make_params(small)
    # KDA + dense, KDA + experts, MLA + experts: every layer kind
    model = dict(small, layers_kept=[0, 2, 5], num_hidden_layers=3)
    params = dict(params, layers=[params["layers"][i] for i in (0, 1, 4)])
    return SeqScorer(params, length=64, batch_sizes=(8,),
                     compute_dtype="float32", registry=registry,
                     family="hybrid_moe",
                     family_config=hm.HybridConfig.from_dict(model))


def _seq_scorer(length, bucket, registry, **kw):
    return SeqScorer(seq_mod.init(jax.random.PRNGKey(4)), length=length,
                     batch_sizes=(bucket,), registry=registry, **kw)


def _meshed_scorer(registry):
    from ccfd_tpu.parallel.multihost import make_global_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = make_global_mesh(model_parallel=1, devices=jax.devices()[:8])
    return _seq_scorer(512, 8, registry, mesh=mesh)


@pytest.mark.parametrize("build,flat", [
    (lambda reg: _seq_scorer(512, 4, reg), True),
    (lambda reg: _seq_scorer(128, 4, reg), False),   # 3,840 values: 3.75 tiles
    (lambda reg: _seq_scorer(64, 8, reg), False),    # 1,920 values
    (_hybrid_moe_scorer, False),                     # (8, 64, 30) windows
    (_meshed_scorer, False),                         # L = 512, rows placed
], ids=["seq_512", "seq_128", "seq_64", "hybrid_moe_64", "seq_512_mesh"])
def test_the_wire_is_chosen_by_shape_and_mesh_and_the_inventory_says_so(
        build, flat):
    """Off the rule and over a mesh the batch crosses as (B, L, F), as it
    always did; the inventory, the counter and what the flat program was
    handed agree on which it was."""
    reg = Registry()
    scorer = build(reg)
    seen = _wires(scorer) if scorer._apply.flat is not None else []
    assert (scorer._apply.flat is None) == (scorer.mesh is not None)
    scorer.score(_rows(3, seed=5), ids=[1, 2, 3])
    grid = scorer.executable_grid()["grid"]
    assert [g["flat_wire"] for g in grid] == [flat]
    assert [g["dispatches"] for g in grid] == [1]
    assert len(seen) == int(flat)
    assert reg.counter("seq_bucket_dispatch_total").total() == 1
    assert reg.counter("seq_flat_wire_dispatch_total").total() == int(flat)


def test_the_kernel_question_is_asked_at_the_dispatched_shape():
    """Warm-up, a dispatch and the inventory leave the served program with
    one trace a rung: ``kernels_held`` looks up the executable that
    runs and traces no second one beside it."""
    reg = Registry()
    scorer = SeqScorer(seq_mod.init(jax.random.PRNGKey(5)), length=512,
                       batch_sizes=(4, 8), registry=reg)
    program = scorer._apply
    scorer.warmup()
    assert program.flat._cache_size() == 2
    scorer.score(_rows(11, seed=6), ids=list(range(11)))  # 8 + 3 rows
    grid = scorer.executable_grid()["grid"]
    assert [(g["b_bucket"], g["attn_kernel"], g["flat_wire"], g["dispatches"])
            for g in grid] == [(4, True, True, 1), (8, True, True, 1)]
    assert program.flat._cache_size() == 2
    assert reg.counter("seq_attention_kernel_dispatch_total").total() == 2


def test_a_batch_on_the_device_already_has_no_wire_to_cross():
    scorer = SeqScorer(seq_mod.init(jax.random.PRNGKey(6)), length=512,
                       batch_sizes=(4,))
    seen = _wires(scorer)
    hist = _histories(4, 512, seed=7)
    on_device = np.asarray(scorer._apply(scorer.params, jnp.asarray(hist)))
    assert not seen
    assert np.array_equal(on_device,
                          np.asarray(scorer._apply(scorer.params, hist)))
    assert len(seen) == 1


def test_a_promoted_variant_is_warmed_through_the_wire_it_will_serve():
    """``swap_params`` to ``seq_q8`` compiles the new program's rungs
    before it publishes, in the form the dispatches will take."""
    scorer = SeqScorer(seq_mod.init(jax.random.PRNGKey(7)), length=512,
                       batch_sizes=(4,))
    scorer.warmup()
    before = scorer._apply
    scorer.swap_params(seq_quant.quantize_seq(scorer.params))
    program = scorer._apply
    assert program is not before and program.flat._cache_size() == 1
    scorer.score(_rows(2, seed=8), ids=["a", "b"])
    assert program.flat._cache_size() == 1
    assert scorer.executable_grid()["grid"][0]["flat_wire"]
