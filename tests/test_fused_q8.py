"""Fused int8 Pallas kernel (ops/fused_mlp_q8.py): exact parity with the
served XLA ``mlp_q8`` graph, Scorer integration by name, and the warmup
fallback that keeps serving alive if Mosaic lowering fails on real TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ccfd_tpu.data.ccfd import synthetic_dataset
from ccfd_tpu.models import mlp
from ccfd_tpu.ops import fused_mlp_q8, quant
from ccfd_tpu.serving.scorer import Scorer


def _quantized_params(seed=0):
    ds = synthetic_dataset(n=1024, fraud_rate=0.1, seed=seed)
    params = mlp.init(jax.random.PRNGKey(seed))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    return quant.quantize_mlp(params), ds


def test_kernel_matches_xla_q8_graph_exactly():
    """f32 rows in both paths -> the kernel re-implements quant.logits'
    exact integer math; only float-associativity noise remains (~1e-7)."""
    qp, ds = _quantized_params()
    kp = fused_mlp_q8.fold_for_kernel(qp)
    x = jnp.asarray(ds.X[:512])
    ref = np.asarray(quant.apply(qp, x))
    out = np.asarray(
        fused_mlp_q8.fused_mlp_q8_score(kp, x, tile=256, interpret=True)
    )
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_kernel_parity_survives_large_magnitude_normalizers():
    """Regression: normalizing with a reciprocal MULTIPLY instead of the
    XLA graph's division differs in the last ulp and flipped quantization
    steps on large-magnitude normalizers (measured 4e-3 prob delta). The
    kernel, the preq host path, and the C++ tier all DIVIDE now."""
    ds = synthetic_dataset(n=512, fraud_rate=0.1, seed=12)
    p = mlp.init(jax.random.PRNGKey(12))
    # Time-column-like scale: huge mu, doubled sigma
    p = mlp.set_normalizer(p, ds.X.mean(0) + 3.0, ds.X.std(0) * 2.0)
    qp = quant.quantize_mlp(p)
    kp = fused_mlp_q8.fold_for_kernel(qp)
    x = ds.X[:256]
    ref = np.asarray(quant.apply(qp, jnp.asarray(x)))
    full = np.asarray(fused_mlp_q8.fused_mlp_q8_score(
        kp, jnp.asarray(x), tile=256, interpret=True))
    np.testing.assert_allclose(full, ref, atol=1e-5)
    q, s = fused_mlp_q8.prequantize_rows_numpy(kp, x)
    preq = np.asarray(fused_mlp_q8.fused_mlp_q8_score_preq(
        kp, jnp.asarray(q), jnp.asarray(s), tile=256, interpret=True))
    np.testing.assert_allclose(preq, ref, atol=1e-5)


def test_padded_features_contribute_nothing():
    """Zero-padded feature columns (30 -> 128) must not shift any
    probability: mu=0 / sigma=1 in padding makes them normalize to 0, and
    w1q's padded rows are 0."""
    qp, ds = _quantized_params(seed=1)
    kp = fused_mlp_q8.fold_for_kernel(qp)
    assert int(np.asarray(kp["w1q"])[30:].max()) == 0
    assert np.all(np.asarray(kp["sigma"])[30:] == 1.0)
    assert np.all(np.asarray(kp["mu"])[30:] == 0.0)
    x = jnp.asarray(ds.X[:256])
    ref = np.asarray(quant.apply(qp, x))
    out = np.asarray(
        fused_mlp_q8.fused_mlp_q8_score(kp, x, tile=256, interpret=True)
    )
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_preq_wire_path_matches_full_kernel_and_xla():
    """int8-at-the-edge: host normalize+rowquant (the model's OWN first
    requantization, moved across the wire) -> kernel starting at the first
    MXU matmul. Bit-identical to both the full kernel and the XLA graph."""
    qp, ds = _quantized_params(seed=5)
    kp = fused_mlp_q8.fold_for_kernel(qp)
    x = ds.X[:512]
    q, s = fused_mlp_q8.prequantize_rows_numpy(kp, x)
    assert q.dtype == np.int8 and q.shape == (512, 30)  # unpadded wire rows
    assert s.shape == (512, 1)
    out = np.asarray(fused_mlp_q8.fused_mlp_q8_score_preq(
        kp, jnp.asarray(q), jnp.asarray(s), tile=256, interpret=True
    ))
    ref = np.asarray(quant.apply(qp, jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    full = np.asarray(fused_mlp_q8.fused_mlp_q8_score(
        kp, jnp.asarray(x), tile=256, interpret=True
    ))
    np.testing.assert_allclose(out, full, atol=1e-6)


def test_fold_rejects_unquantized_or_wrong_depth_trees():
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(
        params, np.zeros(30, np.float32), np.ones(30, np.float32)
    )
    with pytest.raises(KeyError):
        fused_mlp_q8.fold_for_kernel(params)  # f32 tree, no "wq"
    qp, _ = _quantized_params()
    two = {"norm": qp["norm"], "layers": list(qp["layers"])[:2]}
    with pytest.raises(KeyError):
        fused_mlp_q8.fold_for_kernel(two)


def test_scorer_fused_q8_matches_xla_scorer():
    """Scorer(model_name='mlp_q8', use_fused=True) serves the identical
    probabilities as the XLA q8 scorer through the full bucket/pad path."""
    qp, ds = _quantized_params(seed=2)
    fused = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=True)
    plain = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=False)
    assert fused.fused and not plain.fused
    # the q8 kernel's wire format is f32 — exact parity, unlike bf16
    assert fused._fused_in_dtype == np.float32
    x = ds.X[:100]  # full 64 bucket + padded 256 bucket
    np.testing.assert_allclose(fused.score(x), plain.score(x), atol=1e-5)
    np.testing.assert_allclose(
        fused.score_pipelined(x, depth=2), plain.score(x), atol=1e-5
    )


def test_preq_wire_is_the_default_serving_path(monkeypatch):
    """The int8 wire is the q8 fused scorer's default: _fused_dispatch
    ships int8 rows + per-row scales, and the probabilities stay identical
    to the XLA graph. CCFD_Q8_WIRE=f32 opts out."""
    monkeypatch.delenv("CCFD_Q8_WIRE", raising=False)
    qp, ds = _quantized_params(seed=9)
    fused = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=True)
    assert fused._preq_wire
    plain = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=False)
    x = ds.X[:100]
    np.testing.assert_allclose(fused.score(x), plain.score(x), atol=1e-5)

    monkeypatch.setenv("CCFD_Q8_WIRE", "f32")
    f32wire = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64,),
                     use_fused=True)
    assert not f32wire._preq_wire
    np.testing.assert_allclose(f32wire.score(ds.X[:64]),
                               plain.score(ds.X[:64]), atol=1e-5)


def test_preq_wire_swap_refreshes_quantization_grid():
    """A retrain swap must re-pair the host-side quantization grid with
    the new kernel weights — quantizing on the OLD normalizer against new
    weights would corrupt every score."""
    qp, ds = _quantized_params(seed=10)
    scorer = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64,),
                    use_fused=True)
    assert scorer._preq_wire
    # new params with a DIFFERENT normalizer (shifted mu, scaled sigma)
    ds2 = synthetic_dataset(n=1024, fraud_rate=0.1, seed=11)
    p2 = mlp.init(jax.random.PRNGKey(11))
    p2 = mlp.set_normalizer(p2, ds2.X.mean(0) + 3.0, ds2.X.std(0) * 2.0)
    qp2 = quant.quantize_mlp(p2)
    scorer.swap_params(qp2)
    ref = Scorer(model_name="mlp_q8", params=qp2, batch_sizes=(64,),
                 use_fused=False).score(ds.X[:64])
    np.testing.assert_allclose(scorer.score(ds.X[:64]), ref, atol=1e-5)


def test_mesh_sharded_fused_q8_matches_xla():
    """The q8 kernel composes through the same shard_map data-axis path as
    the bf16 kernel: row shards per device, replicated int8 weights."""
    from ccfd_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    qp, ds = _quantized_params(seed=8)
    mesh = make_mesh()
    fused = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=True, mesh=mesh)
    plain = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 256),
                   use_fused=False)
    assert fused.fused
    x = ds.X[:200]  # padded 256 bucket split over the data axis
    np.testing.assert_allclose(fused.score(x), plain.score(x), atol=1e-5)


def test_warmup_kernel_failure_raises(monkeypatch):
    """A kernel the compiler refuses at first call must fail warmup — on
    BOTH device entry points (the q8 scorer serves through the int8-wire
    path by default) — and leave the scorer on the fused path: the XLA
    graph is never selected because an exception was raised."""
    qp, ds = _quantized_params(seed=3)
    scorer = Scorer(model_name="mlp_q8", params=qp, batch_sizes=(64, 128),
                    use_fused=True)
    assert scorer.fused

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(scorer._fused_mod, "fused_score", boom)
    monkeypatch.setattr(scorer._fused_mod, "fused_mlp_q8_score_preq", boom)
    with pytest.raises(RuntimeError, match="Mosaic"):
        scorer.warmup()
    assert scorer.fused
    # a retrain publish re-folds (pure layout) and stays fused; the
    # failure keeps surfacing at dispatch instead of being papered over
    qp2, _ = _quantized_params(seed=4)
    scorer.swap_params(qp2)
    assert scorer.fused
    with pytest.raises(RuntimeError, match="Mosaic"):
        scorer.score(ds.X[:64])


def test_fold_rejects_wide_last_layer_beyond_f32_exact_bound():
    """hidden > 1040 breaks the last layer's integer-exact f32 accumulate
    (127^2 * 1040 < 2^24 <= 127^2 * 1041); the C++ front refuses such
    models at install and fold_for_kernel must mirror that guard instead
    of silently breaking bit-parity with the XLA int32 path (ADVICE r4)."""
    qp, _ = _quantized_params()
    wide = 1152  # the smallest legal multiple-of-128 hidden over the bound
    layers = [dict(l) for l in qp["layers"]]
    layers[2] = dict(layers[2])
    layers[2]["wq"] = np.ones((wide, 1), np.int8)
    bad = {"norm": qp["norm"], "layers": layers}
    with pytest.raises(ValueError, match="1040"):
        fused_mlp_q8.fold_for_kernel(bad)


def test_bf16_rows_are_widened_to_f32_not_fast_pathed():
    """bf16 input must hit the same f32 wire as every other dtype: the
    widening is lossless, and a bf16 fast path would silently ship the
    degraded-accuracy behavior the module docstring warns against."""
    qp, ds = _quantized_params()
    kp = fused_mlp_q8.fold_for_kernel(qp)
    x = jnp.asarray(ds.X[:256])
    tile = fused_mlp_q8.fit_tile(256)
    ref = fused_mlp_q8.fused_mlp_q8_score(kp, x, tile=tile, interpret=True)
    got = fused_mlp_q8.fused_mlp_q8_score(
        kp, x.astype(jnp.bfloat16), tile=tile, interpret=True)
    # parity with the f32 path on the SAME (bf16-rounded) values: widen
    # bf16->f32 first, then it must equal feeding those f32 values directly
    same = fused_mlp_q8.fused_mlp_q8_score(
        kp, x.astype(jnp.bfloat16).astype(jnp.float32), tile=tile,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    assert np.max(np.abs(np.asarray(got) - np.asarray(ref))) < 0.06
