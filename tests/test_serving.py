"""Golden tests for the Seldon REST contract (SURVEY.md §4)."""

import json
import urllib.request

import numpy as np
import pytest

from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.serving.client import SeldonClient
from ccfd_tpu.serving.scorer import Scorer
from ccfd_tpu.serving.server import PredictionServer


@pytest.fixture(scope="module")
def server():
    scorer = Scorer(model_name="logreg", batch_sizes=(16, 64), compute_dtype="float32")
    srv = PredictionServer(scorer, Config())
    port = srv.start(host="127.0.0.1", port=0)
    yield srv, port
    srv.stop()


def _post(port, path, body, token=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}
        | ({"Authorization": f"Bearer {token}"} if token else {}),
        method="POST",
    )
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_predictions_contract_shape(server):
    srv, port = server
    rows = [[0.0] * 30, [1.0] * 30]
    code, out = _post(port, "/api/v0.1/predictions",
                      {"data": {"names": list(FEATURE_NAMES), "ndarray": rows}})
    assert code == 200
    assert out["data"]["names"] == ["proba_0", "proba_1"]
    nd = out["data"]["ndarray"]
    assert len(nd) == 2 and all(len(r) == 2 for r in nd)
    for p0, p1 in nd:
        assert abs(p0 + p1 - 1.0) < 1e-5
        assert 0.0 <= p1 <= 1.0


def test_predict_endpoint_alias(server):
    srv, port = server
    code, out = _post(port, "/predict", {"data": {"ndarray": [[0.5] * 30]}})
    assert code == 200 and len(out["data"]["ndarray"]) == 1


def test_names_reordering(server):
    """Feature values are mapped by name when names are shuffled."""
    srv, port = server
    names = list(FEATURE_NAMES)[::-1]
    row = list(np.arange(30, dtype=float))[::-1]
    code, out = _post(port, "/api/v0.1/predictions",
                      {"data": {"names": names, "ndarray": [row]}})
    code2, out2 = _post(port, "/api/v0.1/predictions",
                        {"data": {"names": list(FEATURE_NAMES),
                                  "ndarray": [list(np.arange(30, dtype=float))]}})
    assert out["data"]["ndarray"] == out2["data"]["ndarray"]


def test_malformed_body_400(server):
    srv, port = server
    code, out = _post(port, "/api/v0.1/predictions", {"nope": 1})
    assert code == 400
    code, _ = _post(port, "/api/v0.1/predictions", {"data": {"ndarray": "x"}})
    assert code == 400


def test_unknown_route_404(server):
    srv, port = server
    code, _ = _post(port, "/api/v9/bogus", {})
    assert code == 404


def test_health_and_metrics(server):
    srv, port = server
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/health/status") as r:
        assert json.loads(r.read())["status"] == "ok"
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/prometheus") as r:
        body = r.read().decode()
    assert "seldon_api_executor_client_requests_seconds" in body
    assert "proba_1" in body


def test_token_auth():
    scorer = Scorer(model_name="logreg", batch_sizes=(16,), compute_dtype="float32")
    srv = PredictionServer(scorer, Config(seldon_token="sekrit"))
    port = srv.start(host="127.0.0.1", port=0)
    try:
        code, _ = _post(port, "/predict", {"data": {"ndarray": [[0.0] * 30]}})
        assert code == 401
        code, _ = _post(port, "/predict", {"data": {"ndarray": [[0.0] * 30]}},
                        token="sekrit")
        assert code == 200
    finally:
        srv.stop()


def test_seldon_client_roundtrip(server):
    srv, port = server
    cfg = Config(
        seldon_url=f"http://127.0.0.1:{port}",
        seldon_endpoint="api/v0.1/predictions",
        seldon_pool_size=2,
    )
    client = SeldonClient(cfg)
    x = np.random.default_rng(0).normal(size=(5, 30)).astype(np.float32)
    proba = client.score(x)
    assert proba.shape == (5,)
    direct = srv.scorer.score(x)
    np.testing.assert_allclose(proba, direct, atol=1e-6)
    client.close()


def test_keepalive_survives_401_then_succeeds():
    """Pooled HTTP/1.1 connection must stay in sync after an auth failure."""
    import http.client

    scorer = Scorer(model_name="logreg", batch_sizes=(16,), compute_dtype="float32")
    srv = PredictionServer(scorer, Config(seldon_token="tok"))
    port = srv.start(host="127.0.0.1", port=0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        body = json.dumps({"data": {"ndarray": [[0.0] * 30]}})
        conn.request("POST", "/predict", body, {"Content-Type": "application/json"})
        r1 = conn.getresponse(); r1.read()
        assert r1.status == 401
        # same connection, now with the token: must parse cleanly
        conn.request("POST", "/predict", body,
                     {"Content-Type": "application/json",
                      "Authorization": "Bearer tok"})
        r2 = conn.getresponse(); out = json.loads(r2.read())
        assert r2.status == 200 and len(out["data"]["ndarray"]) == 1
        conn.close()
    finally:
        srv.stop()


class TestFusedScorerPath:
    """Pallas fused kernel wired into the serving Scorer (interpret on CPU)."""

    def _trained_params(self):
        import jax

        from ccfd_tpu.data.ccfd import synthetic_dataset
        from ccfd_tpu.models import mlp

        ds = synthetic_dataset(n=512, seed=5)
        params = mlp.init(jax.random.PRNGKey(0))
        return mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0)), ds

    def test_fused_matches_unfused(self):
        params, ds = self._trained_params()
        fused = Scorer(model_name="mlp", params=params, batch_sizes=(64, 256),
                       use_fused=True)
        plain = Scorer(model_name="mlp", params=params, batch_sizes=(64, 256),
                       compute_dtype="float32", use_fused=False)
        assert fused.fused and not plain.fused
        x = ds.X[:100]  # spans a full 64 bucket + padded 256 bucket
        np.testing.assert_allclose(
            fused.score(x), plain.score(x), atol=2e-2
        )  # bf16 matmuls in the kernel vs f32 reference

    def test_swap_params_refolds_kernel_weights(self):
        import jax

        from ccfd_tpu.models import mlp

        params, ds = self._trained_params()
        scorer = Scorer(model_name="mlp", params=params, batch_sizes=(64,),
                        use_fused=True)
        x = ds.X[:64]
        before = scorer.score(x)
        new_params = mlp.init(jax.random.PRNGKey(42))
        new_params = mlp.set_normalizer(new_params, ds.X.mean(0), ds.X.std(0))
        scorer.swap_params(new_params)
        after = scorer.score(x)
        assert not np.allclose(before, after)
        ref = Scorer(model_name="mlp", params=new_params, batch_sizes=(64,),
                     compute_dtype="float32", use_fused=False).score(x)
        np.testing.assert_allclose(after, ref, atol=2e-2)

    def test_swap_params_unfoldable_tree_drops_to_xla_path(self):
        import jax

        from ccfd_tpu.models import mlp

        params, ds = self._trained_params()
        scorer = Scorer(model_name="mlp", params=params, batch_sizes=(64,),
                        use_fused=True)
        assert scorer.fused
        x = ds.X[:64]
        # a 2-layer tree: fold_for_kernel only accepts the 3-layer flagship
        odd = mlp.init(jax.random.PRNGKey(3), depth=2)
        odd = mlp.set_normalizer(odd, ds.X.mean(0), ds.X.std(0))
        scorer.swap_params(odd)
        assert not scorer.fused  # stale fused weights must not keep serving
        ref = Scorer(model_name="mlp", params=odd, batch_sizes=(64,),
                     compute_dtype="float32", use_fused=False).score(x)
        np.testing.assert_allclose(scorer.score(x), ref, atol=2e-2)
        # a later foldable tree re-enables the kernel path
        scorer.swap_params(params)
        assert scorer.fused
        ref2 = Scorer(model_name="mlp", params=params, batch_sizes=(64,),
                      compute_dtype="float32", use_fused=False).score(x)
        np.testing.assert_allclose(scorer.score(x), ref2, atol=2e-2)

    def test_odd_bucket_sizes_fall_back_to_smaller_tiles(self):
        params, ds = self._trained_params()
        scorer = Scorer(model_name="mlp", params=params, batch_sizes=(48,),
                        use_fused=True)
        proba = scorer.score(ds.X[:48])
        assert proba.shape == (48,)
        assert np.all((proba >= 0) & (proba <= 1))

    def test_score_pipelined_matches_score(self):
        params, ds = self._trained_params()
        for fused in (True, False):
            scorer = Scorer(model_name="mlp", params=params,
                            batch_sizes=(64, 128), use_fused=fused,
                            compute_dtype="float32" if not fused else "bfloat16")
            x = ds.X[:300]  # 2 full 128-buckets + padded tail, > depth chunks
            np.testing.assert_allclose(
                scorer.score_pipelined(x, depth=3), scorer.score(x), atol=1e-6
            )


def test_host_tier_parity_and_routing():
    """Small batches score on the host tier (numpy, no device dispatch);
    results match the device path within bf16 tolerance; bulk stays on
    the device path."""
    import jax as _jax

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import mlp
    from ccfd_tpu.serving.scorer import Scorer

    ds = synthetic_dataset(n=1024, fraud_rate=0.2, seed=5)
    params = mlp.init(_jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    s = Scorer(model_name="mlp", params=params, batch_sizes=(16, 128, 1024),
               compute_dtype="bfloat16", host_tier_rows=256)
    s.warmup()
    assert s.host_tier_rows == 256
    x = ds.X[:64]
    # host tier result vs forced-device result
    host = s.score(x)
    device = s.score_pipelined(x, depth=1)
    assert host.shape == (64,)
    assert np.allclose(host, device, atol=2e-2), np.abs(host - device).max()
    # routing: above the threshold the device path runs (spy on it)
    calls = {"device": 0}
    orig = s.score_pipelined

    def spy(xx, depth=2):
        calls["device"] += 1
        return orig(xx, depth=depth)

    s.score_pipelined = spy
    s.score(ds.X[:64])
    assert calls["device"] == 0  # host tier
    s.score(ds.X[:512])
    assert calls["device"] == 1  # device path
    s.score_pipelined = orig

    # swap_params publishes to the host tier too
    import jax.numpy as _jnp

    p2 = dict(params)
    p2["layers"] = [dict(l) for l in params["layers"]]
    p2["layers"][-1] = dict(p2["layers"][-1])
    p2["layers"][-1]["b"] = _jnp.asarray([9.0], _jnp.float32)
    s.swap_params(p2)
    shifted = s.score(x)
    assert (shifted > host).all()  # +9 logit bias must show through the tier


def test_host_tier_auto_is_zero(monkeypatch):
    """Auto resolves to 0 whatever the backend says it is — the default
    serving path always reaches the device — and warmup never moves it;
    an explicit positive value still works and survives warmup."""
    import jax as _jax

    from ccfd_tpu.serving.scorer import Scorer

    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(_jax, "default_backend", lambda b=backend: b)
        s = Scorer(model_name="mlp", batch_sizes=(16,), use_fused=False,
                   dispatch_deadline_ms=0)
        assert s.host_tier_rows == 0, backend
        s.warmup()
        assert s.host_tier_rows == 0, backend
    monkeypatch.undo()
    s = Scorer(model_name="mlp", batch_sizes=(16,), host_tier_rows=256)
    s.warmup()
    assert s.host_tier_rows == 256


def test_fused_kernel_exception_in_warmup_propagates(monkeypatch):
    """A fused kernel that fails at warm-up is a bug to fix in the kernel:
    warmup raises and the scorer stays on the fused path — it never
    drops to the XLA graph because an exception was raised."""
    import pytest

    from ccfd_tpu.serving.scorer import Scorer

    s = Scorer(model_name="mlp", batch_sizes=(16, 128), use_fused=True)
    assert s.fused

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(s._fused_mod, "fused_score", boom)
    with pytest.raises(RuntimeError, match="Mosaic"):
        s.warmup()
    assert s.fused
    with pytest.raises(RuntimeError, match="Mosaic"):
        s.score(np.zeros((16, 30), np.float32))
    assert s.fused


def test_host_tier_gbt_small_batch_scores():
    """ADVICE r2 (high): the host-params copy must keep the tree family's
    integer gather indices integer — a uniform f32 cast made
    ``trees.apply_numpy`` raise IndexError on any host-tier batch, crashing
    serve/router warmup for CCFD_MODEL=gbt on accelerator backends. Calls the
    Scorer directly (no native front) so the numpy path itself is exercised."""
    import jax as _jax

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import trees
    from ccfd_tpu.serving.scorer import Scorer

    from sklearn.ensemble import GradientBoostingClassifier

    ds = synthetic_dataset(n=512, fraud_rate=0.2, seed=7)
    clf = GradientBoostingClassifier(
        n_estimators=8, max_depth=3, random_state=3
    ).fit(ds.X, ds.y)
    params = trees.from_sklearn_gbt(clf)
    for name in ("gbt", "gbt_mxu"):
        s = Scorer(model_name=name, params=params,
                   batch_sizes=(16, 128), host_tier_rows=64)
        assert s._host_params is not None
        feat = s._host_params["feature"]
        assert np.issubdtype(np.asarray(feat).dtype, np.integer)
        small = s.score(ds.X[:16])  # <= host_tier_rows: numpy path
        dev = s.score_pipelined(ds.X[:16], depth=1)
        assert small.shape == (16,)
        assert np.allclose(small, dev, atol=2e-2)
        # swap keeps the tier alive (and integer) too
        clf2 = GradientBoostingClassifier(
            n_estimators=8, max_depth=3, random_state=4
        ).fit(ds.X, 1 - ds.y)
        s.swap_params(trees.from_sklearn_gbt(clf2))
        assert np.issubdtype(
            np.asarray(s._host_params["feature"]).dtype, np.integer
        )
        s.score(ds.X[:16])


def test_swap_listener_ordering_under_concurrent_swaps():
    """ADVICE r2 (low): listener delivery is generation-ordered — a slower,
    older swap must not overwrite a newer swap's params in listener copies."""
    import jax as _jax

    from ccfd_tpu.models import mlp
    from ccfd_tpu.serving.scorer import Scorer

    params = mlp.init(_jax.random.PRNGKey(0))
    s = Scorer(model_name="mlp", params=params, batch_sizes=(16,),
               host_tier_rows=16)
    seen = []
    s.add_swap_listener(lambda tree: seen.append(float(tree["layers"][-1]["b"][0])))

    def bumped(v):
        p = dict(params)
        p["layers"] = [dict(l) for l in params["layers"]]
        p["layers"][-1] = dict(p["layers"][-1])
        p["layers"][-1]["b"] = np.asarray([v], np.float32)
        return p

    # simulate the race: swap A claims its generation, then swap B fully
    # lands (newer gen, delivered); A's delivery must then be skipped
    with s._lock:
        s._swap_gen += 1
        gen_a = s._swap_gen
    s.swap_params(bumped(2.0))  # B: newer generation, delivers
    assert seen == [2.0]
    # replay A's delivery attempt the way swap_params would
    with s._notify_lock:
        stale = gen_a <= s._swap_delivered_gen
    assert stale  # A would be (correctly) dropped
    assert float(s._host_params["layers"][-1]["b"][0]) == 2.0


def test_host_tier_logreg_numpy_matches_jax():
    import jax as _jax

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import logreg

    ds = synthetic_dataset(n=128, fraud_rate=0.3, seed=2)
    params = logreg.init(_jax.random.PRNGKey(1))
    a = np.asarray(logreg.apply(params, ds.X))
    b = logreg.apply_numpy(
        {"w": np.asarray(params["w"]), "b": np.asarray(params["b"])}, ds.X
    )
    assert np.allclose(a, b, atol=1e-6)
