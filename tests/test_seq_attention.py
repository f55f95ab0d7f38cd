"""The seq family's attention kernel (ops/seq_attention.py): its numbers
against ``reference_attention``, which shapes get it, and what the scorer's
inventory and counters say of it. On the CPU the kernel runs under the
interpreter (``ops/kernels.py::interpreted``)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq
from ccfd_tpu.ops import kernels, seq_quant
from ccfd_tpu.ops.ring_attention import reference_attention
from ccfd_tpu.ops.seq_attention import (KERNEL, attention, fused_attention,
                                        kernel_fits, query_block)
from ccfd_tpu.serving.history import SeqScorer

held_by = partial(kernels.held_by, names=(KERNEL,))


def _qkv(shape, dtype, seed=0):
    """Operands with what a served batch holds: a row of zeros (a short
    history's padding attends and is attended), a head whose first rows
    are zero, and one query whose score towers over its row's others."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (np.array(jax.random.normal(kk, shape, jnp.float32))
               for kk in keys)
    for t in (q, k, v):
        t[0] = 0.0           # a whole history row of zeros
        t[-1, 0, :5] = 0.0   # zero positions inside a live row
    q[-1, -1, 7] = 40.0 * k[-1, -1, 3]  # one score far above the rest
    return tuple(jnp.asarray(t, dtype) for t in (q, k, v))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 0.03)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(2, 4, 512, 32), (3, 4, 128, 32),
                                   (1, 4, 256, 32), (5, 2, 512, 64)],
                         ids=lambda s: "x".join(map(str, s)))
def test_kernel_matches_reference_attention(shape, dtype, tol):
    q, k, v = _qkv(shape, dtype)
    assert kernel_fits(q.shape, k.shape, q.dtype)
    got = fused_attention(q, k, v)
    want = reference_attention(q, k, v)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # the row of zeros averages its values: zeros
    assert not got[0].any()


def _struct(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("q_shape,k_shape,dtype,why", [
    ((2, 4, 1, 32), (2, 4, 512, 32), jnp.bfloat16,
     "the readout block's single query"),
    ((2, 4, 96, 32), (2, 4, 96, 32), jnp.bfloat16,
     "a length the kernel does not tile"),
    ((2, 4, 64, 32), (2, 4, 64, 32), jnp.bfloat16,
     "a short window of the ladder"),
    ((1, 4, 8192, 32), (1, 4, 8192, 32), jnp.bfloat16,
     "a row of scores over the VMEM budget"),
    ((2, 8, 512, 32), (2, 8, 512, 32), jnp.bfloat16,
     "heads wider than one lane tile"),
    ((2, 4, 512, 32), (2, 4, 512, 32), jnp.float16,
     "a dtype the kernel was not written for"),
], ids=["single_query", "untiled_length", "short_window", "over_budget",
        "two_lane_tiles", "float16"])
def test_shapes_the_kernel_does_not_take_get_reference_attention(
        q_shape, k_shape, dtype, why):
    assert not kernel_fits(q_shape, k_shape, dtype), why
    # traced on shapes alone: the over-budget case would be 4 GB of scores
    assert not held_by(attention, _struct(q_shape, dtype),
                       _struct(k_shape, dtype), _struct(k_shape, dtype)), why
    if k_shape[2] <= 512:
        q = jnp.ones(q_shape, dtype)
        k, v = jnp.ones(k_shape, dtype), jnp.ones(k_shape, dtype)
        np.testing.assert_array_equal(
            np.asarray(attention(q, k, v), np.float32),
            np.asarray(reference_attention(q, k, v), np.float32))


def test_the_kernel_is_taken_where_it_fits_and_blocks_stay_in_budget():
    assert held_by(attention, *[_struct((3, 4, 512, 32))] * 3)
    assert held_by(attention, *[_struct((3, 4, 128, 32), jnp.float32)] * 3)
    assert [query_block(n) for n in (128, 512, 1024, 2048, 4096, 8192)] == [
        128, 512, 512, 256, 128, None]


def _history(n, length=512, seed=3):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, length, 30)), jnp.float32)


@pytest.mark.parametrize("family,logit_tol,proba_tol", [
    ("seq", 1e-5, 5e-3), ("seq_q8", 2e-2, 1e-2)])
def test_served_programs_agree_with_themselves_on_reference_attention(
        family, logit_tol, proba_tol):
    """(4, 512, 30): the programs as served (the kernel in the full block)
    against the same programs forced onto ``reference_attention``. ``seq``
    to the tolerances tests/test_seq.py holds the readout program to.
    ``seq_q8`` rounds every token's activations to 127 steps after the
    attention, so a last-place difference there moves a whole step (one
    logit in four by 0.013 in float32): held to the int8 grid's own noise
    (PERF.md section 2: 0.013-0.017 mean against float32)."""
    params = seq.init(jax.random.PRNGKey(11))
    if family == "seq":
        logits, served = seq.logits_readout, seq.apply_serving
    else:
        params = seq_quant.quantize_seq(params)
        logits, served = seq_quant.logits, seq_quant.apply_serving
    x = _history(4)
    shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
    assert held_by(served, params, shape)

    def forced(dtype):
        return jax.jit(lambda p, xs: logits(
            p, xs, dtype, attention_fn=reference_attention))

    assert not held_by(forced(jnp.bfloat16), params, shape)
    np.testing.assert_allclose(
        np.asarray(logits(params, x, jnp.float32)),
        np.asarray(forced(jnp.float32)(params, x)),
        rtol=logit_tol, atol=logit_tol)
    np.testing.assert_allclose(
        np.asarray(served(params, x)),
        np.asarray(jax.nn.sigmoid(forced(jnp.bfloat16)(params, x))),
        atol=proba_tol)


def test_training_holds_no_kernel_and_still_differentiates():
    params = seq.init(jax.random.PRNGKey(2))
    x, y = _history(2, length=128), jnp.asarray([0.0, 1.0])
    grad = jax.grad(seq.loss_fn)
    assert not held_by(grad, params, x, y)
    assert not held_by(seq.apply, params, x)
    g = grad(params, x, y)
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(g))
    assert float(jnp.abs(g["blocks"][0]["qkv"]["w"]).sum()) > 0.0


def _scorer(length, registry, **kw):
    return SeqScorer(seq.init(jax.random.PRNGKey(0)), length=length,
                     batch_sizes=(4, 16), registry=registry, **kw)


@pytest.mark.parametrize("length,len_buckets,held", [
    (128, None, {(128, 4): True, (128, 16): True}),
    (96, None, {(96, 4): False, (96, 16): False}),
    (128, (32,), {(32, 4): False, (32, 16): False,
                  (128, 4): True, (128, 16): True}),
], ids=["fits", "untiled", "ladder"])
def test_inventory_and_counter_say_which_executables_hold_the_kernel(
        length, len_buckets, held):
    reg = Registry()
    scorer = _scorer(length, reg, len_buckets=len_buckets)
    rows = np.random.default_rng(1).normal(size=(20, 30)).astype(np.float32)
    scorer.score(rows, ids=[f"c{i}" for i in range(20)])  # 16 + 4 rows
    grid = scorer.executable_grid()["grid"]
    assert {(g["l_bucket"], g["b_bucket"]): g["attn_kernel"]
            for g in grid} == held
    with_kernel = sum(g["dispatches"] for g in grid if g["attn_kernel"])
    assert sum(g["dispatches"] for g in grid) == 2
    assert reg.counter("seq_attention_kernel_dispatch_total").total() \
        == with_kernel
    assert reg.counter("seq_bucket_dispatch_total").total() == 2


def test_a_variant_swap_reads_the_new_programs_trace():
    """``seq_q8`` promoted over ``seq``: the inventory describes the
    program that serves now, and a stand-in for the program (a drill's
    gate) holds no kernel and is not traced."""
    reg = Registry()
    scorer = _scorer(128, reg)
    before = scorer._apply
    assert before.kernels_held(scorer.params, 128, 4)["attn_kernel"]
    scorer.swap_params(seq_quant.quantize_seq(scorer.params))
    assert scorer._apply is not before
    assert all(g["attn_kernel"] for g in scorer.executable_grid()["grid"])
    calls = []
    real = scorer._apply
    scorer._apply = lambda p, xs: calls.append(type(xs)) or real(p, xs)
    assert not any(g["attn_kernel"]
                   for g in scorer.executable_grid()["grid"])
    scorer.score(np.zeros((3, 30), np.float32), ids=["a", "b", "c"])
    assert calls == [np.ndarray]
    assert reg.counter("seq_attention_kernel_dispatch_total").total() == 0


@pytest.mark.parametrize("mesh_kw,seq_parallel,sharded", [
    ({}, "none", False),                      # rows over 8 devices
    ({"model_parallel": 8}, "ulysses", False),  # 4 heads over 8: falls back
    ({"model_parallel": 2}, "ring", True),    # the operator's choice wins
], ids=["data_parallel", "ulysses_cannot_shard", "ring_shards"])
def test_a_mesh_executable_attends_as_one_chip_does_on_each_devices_rows(
        mesh_kw, seq_parallel, sharded):
    """Over a mesh the default runs under ``shard_map`` (a kernel is not
    partitioned for us): every device takes the kernel for its own rows,
    and so does a shape the sequence-parallel axis cannot shard; where
    ring or ulysses shards, it wins and the executable holds no kernel."""
    from ccfd_tpu.parallel.mesh import make_mesh

    params = seq.init(jax.random.PRNGKey(4))
    rows = np.random.default_rng(2).normal(size=(16, 30)).astype(np.float32)
    ids = [f"c{i % 5}" for i in range(16)]
    mk = lambda **kw: SeqScorer(  # noqa: E731
        params, length=128, batch_sizes=(16,), compute_dtype="float32", **kw)
    single = mk()
    meshed = mk(mesh=make_mesh(**mesh_kw), seq_parallel=seq_parallel)
    for s in (single, meshed):
        s.score(rows, ids)
    np.testing.assert_allclose(single.score(rows, ids),
                               meshed.score(rows, ids), rtol=1e-4, atol=1e-5)
    grid = meshed.executable_grid()
    assert [g["attn_kernel"] for g in grid["grid"]] == [not sharded]
    if seq_parallel != "none":
        assert grid["seq_parallel_engaged"] is sharded
