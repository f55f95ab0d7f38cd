"""History-aware streaming scoring: HistoryStore semantics and the seq
scorer through the real router loop (serving/history.py).

The seq model family (models/seq.py) is the long-context member of the
zoo; this is the PRODUCT path that serves it: per-customer ring-buffer
histories live in the routing tier (where the stream is), assembled into
static (bucket, L, F) batches for one jit dispatch per poll."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.process.fraud import build_engine
from ccfd_tpu.router.router import Router
from ccfd_tpu.serving.history import HistoryStore, SeqScorer


def test_ring_buffer_newest_last_and_cold_padding():
    st = HistoryStore(length=4, num_features=3)
    rows = np.arange(9, dtype=np.float32).reshape(3, 3)
    out, staged = st.prepare(["a", "a", "a"], rows)
    st.commit(staged)
    # after the 3rd append: zeros pad on the LEFT, newest is row L-1
    assert np.all(out[2, 0] == 0.0)
    assert np.allclose(out[2, 1], rows[0])
    assert np.allclose(out[2, 2], rows[1])
    assert np.allclose(out[2, 3], rows[2])
    # same-batch earlier rows are visible to later rows (arrival order)
    assert np.allclose(out[1, 3], rows[1]) and np.allclose(out[1, 2], rows[0])


def test_ring_buffer_wraps_and_keeps_depth():
    st = HistoryStore(length=3, num_features=2)
    rows = np.arange(12, dtype=np.float32).reshape(6, 2)
    out, staged = st.prepare(["c"] * 6, rows)
    st.commit(staged)
    assert np.allclose(out[-1], rows[3:6])  # only the newest 3 remain


def test_customers_are_isolated_and_capped():
    st = HistoryStore(length=2, num_features=1, max_customers=3)
    st.commit(st.prepare(list("abcd"), np.ones((4, 1), np.float32))[1])
    assert len(st) == 3  # coldest ("a") evicted at the cap
    out, staged = st.prepare(["b"], np.full((1, 1), 5.0, np.float32))
    st.commit(staged)
    assert out[0, 0, 0] == 1.0 and out[0, 1, 0] == 5.0  # b kept its history


def test_seq_scorer_history_changes_the_score():
    """The same transaction must score differently for a customer with
    history than for a cold one — the model actually reads the context."""
    params = seq_mod.init(jax.random.PRNGKey(0))
    s = SeqScorer(params, length=8, batch_sizes=(4,),
                  compute_dtype="float32")
    rng = np.random.default_rng(0)
    row = rng.normal(size=(1, 30)).astype(np.float32)
    history_rows = rng.normal(size=(6, 30)).astype(np.float32) * 3.0
    cold = s.score(row, ids=["fresh"])
    s.score(history_rows, ids=["warm"] * 6)
    warm = s.score(row, ids=["warm"])
    assert cold.shape == warm.shape == (1,)
    assert 0.0 <= cold[0] <= 1.0 and 0.0 <= warm[0] <= 1.0
    assert abs(float(cold[0]) - float(warm[0])) > 1e-6


def test_seq_scorer_bucket_padding_matches_unpadded():
    params = seq_mod.init(jax.random.PRNGKey(1))
    s = SeqScorer(params, length=4, batch_sizes=(8,),
                  compute_dtype="float32")
    x = np.random.default_rng(1).normal(size=(3, 30)).astype(np.float32)
    got = s.score(x, ids=["p", "q", "r"])
    s2 = SeqScorer(params, length=4, batch_sizes=(4,),
                   compute_dtype="float32")
    want = s2.score(x, ids=["p", "q", "r"])
    assert np.allclose(got, want, atol=1e-5)


def test_router_serves_the_seq_scorer_end_to_end():
    """CCFD's streaming tier with a history-aware model: records flow
    bus -> router -> SeqScorer (per-customer context) -> engine."""
    cfg = Config(fraud_threshold=0.99)
    broker = Broker()
    engine = build_engine(cfg, broker, Registry())
    params = seq_mod.init(jax.random.PRNGKey(2))
    scorer = SeqScorer(params, length=8, batch_sizes=(16, 128),
                       compute_dtype="float32", registry=Registry())
    router = Router(cfg, broker, scorer, engine, Registry())
    rows = [
        {FEATURE_NAMES[j]: float(j % 5) for j in range(30)}
        | {"id": i % 4, "customer_id": i % 4}
        for i in range(32)
    ]
    broker.produce_batch(cfg.kafka_topic, rows)
    routed = router.step()
    assert routed == 32
    # 4 customers, 8 transactions each: histories accumulated
    assert len(scorer.store) == 4
    counts = scorer.store.snapshot_counts()
    assert counts["customers"] == 4 and counts["length"] == 8


def test_prepare_without_commit_leaves_store_untouched():
    """A failed dispatch drops the batch; the store must keep matching
    the routed stream exactly."""
    st = HistoryStore(length=3, num_features=2)
    st.commit(st.prepare(["k"], np.ones((1, 2), np.float32))[1])
    before = st.snapshot()
    st.prepare(["k", "k"], np.full((2, 2), 9.0, np.float32))  # no commit
    after = st.snapshot()
    assert [c[0] for c in after["customers"]] == [c[0] for c in before["customers"]]
    assert np.allclose(after["customers"][0][1], before["customers"][0][1])


def test_anonymous_rows_score_cold_and_are_not_stored():
    st = HistoryStore(length=3, num_features=2, max_customers=2)
    out, staged = st.prepare([None, None, "real"],
                             np.ones((3, 2), np.float32))
    st.commit(staged)
    assert len(st) == 1  # only "real" tracked — no cap pollution
    assert np.all(out[0, :2] == 0.0) and np.all(out[0, 2] == 1.0)


def test_snapshot_restore_round_trip_and_reset():
    st = HistoryStore(length=2, num_features=2)
    st.commit(st.prepare(["a", "b"], np.ones((2, 2), np.float32))[1])
    snap = st.snapshot()
    st.commit(st.prepare(["c"], np.ones((1, 2), np.float32))[1])
    st.restore(snap)
    assert len(st) == 2
    st.restore(None)  # genesis reset
    assert len(st) == 0


def test_history_rides_the_recovery_cut():
    """The corruption this exists to prevent: after a crash restore, the
    rewound bus REPLAYS records — without resetting histories to the
    cut, every replayed transaction would append a second time."""
    from ccfd_tpu.runtime.recovery import CheckpointCoordinator

    cfg = Config(fraud_threshold=0.99)
    broker = Broker()
    reg = Registry()
    factory = lambda: build_engine(cfg, broker, reg)  # noqa: E731
    params = seq_mod.init(jax.random.PRNGKey(3))
    scorer = SeqScorer(params, length=8, batch_sizes=(16,),
                       compute_dtype="float32")
    router = Router(cfg, broker, scorer, factory(), Registry())
    coord = CheckpointCoordinator(router, broker, factory, interval_s=999.0)
    coord.register_state("history", scorer.store.snapshot,
                         scorer.store.restore)
    t = router.start(poll_timeout_s=0.01)
    try:
        def feed(lo, hi):
            # keyed by customer: per-key ordering is the bus's (and
            # Kafka's) contract, and history order depends on it
            broker.produce_batch(
                cfg.kafka_topic,
                [{FEATURE_NAMES[j]: float(i) for j in range(30)}
                 | {"id": "cust", "customer_id": "cust"}
                 for i in range(lo, hi)],
                keys=["cust"] * (hi - lo),
            )

        feed(0, 4)
        deadline = time.time() + 10
        while router._c_in.value() < 4 and time.time() < deadline:
            time.sleep(0.02)
        assert coord.checkpoint() is not None
        hist_at_cut = scorer.store.snapshot()
        feed(4, 7)  # post-cut appends (doomed epoch)
        deadline = time.time() + 10
        while router._c_in.value() < 7 and time.time() < deadline:
            time.sleep(0.02)
        coord.restore(reason="test")
        deadline = time.time() + 10
        while router._c_in.value() < 10 and time.time() < deadline:
            time.sleep(0.02)  # 3 replayed
        router.pause(5.0)
        final = scorer.store.snapshot()
        # exactly ONE copy of each replayed row: depth == 7 appends total
        (key, buf, filled), = final["customers"]
        assert key == "cust" and filled == 7
        # newest-last ordering preserved: last row is transaction 6
        assert buf[-1][0] == 6.0 and buf[-2][0] == 5.0
        assert hist_at_cut["customers"][0][2] == 4
    finally:
        router.resume()
        router.stop()
        t.join(timeout=5)


def test_stale_generation_commit_is_dropped():
    """A dispatch in flight across a restore (unacked-barrier path) must
    not land doomed-epoch rows on the restored state — the replayed
    records would then append them a second time."""
    st = HistoryStore(length=3, num_features=2)
    st.commit(st.prepare(["k"], np.ones((1, 2), np.float32))[1])
    snap = st.snapshot()
    _, token = st.prepare(["k"], np.full((1, 2), 9.0, np.float32))
    st.restore(snap)  # crash restore lands while the dispatch is in flight
    assert st.commit(token) is False  # stale: dropped
    final = st.snapshot()
    assert final["customers"][0][2] == 1  # still exactly the cut's state


def test_multichunk_batch_commits_once_with_cross_chunk_visibility():
    params = seq_mod.init(jax.random.PRNGKey(4))
    s = SeqScorer(params, length=8, batch_sizes=(2,), compute_dtype="float32")
    x = np.arange(5 * 30, dtype=np.float32).reshape(5, 30)
    s.score(x, ids=["c"] * 5)  # 3 chunks of <=2 rows, one customer
    snap = s.store.snapshot()
    (key, buf, filled), = snap["customers"]
    assert filled == 5  # every chunk's rows landed exactly once, in order
    assert np.allclose(np.asarray(buf)[-1], x[4])
    assert np.allclose(np.asarray(buf)[-5], x[0])


def test_seq_scorer_mesh_dispatch_matches_single_device():
    """SeqScorer(mesh=...): history batches split over every mesh device
    with replicated params — same probabilities as the single-device
    scorer on the same (warm) store contents, buckets rounded to
    device-count multiples (round 5; SURVEY §7 stage 6 for the seq
    family)."""
    import jax
    import numpy as np

    from ccfd_tpu.models import seq as seq_mod
    from ccfd_tpu.parallel.multihost import make_global_mesh
    from ccfd_tpu.serving.history import SeqScorer

    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs the 8-device CPU mesh")
    mesh = make_global_mesh(model_parallel=2, devices=jax.devices()[:8])
    params = seq_mod.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(40, 30)).astype(np.float32)
    ids = [i % 10 for i in range(40)]

    meshed = SeqScorer(params, length=8, batch_sizes=(16,), mesh=mesh,
                       max_customers=64)
    assert all(b % 8 == 0 for b in meshed.batch_sizes)
    meshed.warmup()
    single = SeqScorer(params, length=8, batch_sizes=(16,), max_customers=64)

    p_mesh = meshed.score(rows, ids)
    p_single = single.score(rows, ids)
    assert p_mesh.shape == (40,)
    np.testing.assert_allclose(p_mesh, p_single, atol=5e-3)
    # both stores saw identical appends
    assert len(meshed.store) == len(single.store) == 10
    # online-retrain surface keeps the mesh placement
    meshed.swap_params(params)
    np.testing.assert_allclose(meshed.score(rows, ids),
                               single.score(rows, ids), atol=5e-3)


# -- round 11: striped store, fast paths, L buckets, overlapped dispatch ----


def test_anonymous_only_prepare_stages_nothing_and_skips_the_store():
    """Cold REST scoring (every id None) must not touch stripe locks or
    the cap: empty staged dict, store untouched, commit a no-op."""
    st = HistoryStore(length=3, num_features=2, max_customers=2)
    out, token = st.prepare([None, None, None], np.ones((3, 2), np.float32))
    gen, staged = token[0], token[1]
    assert staged == {}
    assert np.all(out[:, :2] == 0.0) and np.all(out[:, 2] == 1.0)
    assert st.commit(token) is True
    assert len(st) == 0


def test_seq_scorer_counts_anonymous_fast_path_rows():
    from ccfd_tpu.metrics.prom import Registry

    reg = Registry()
    params = seq_mod.init(jax.random.PRNGKey(0))
    s = SeqScorer(params, length=4, batch_sizes=(8,),
                  compute_dtype="float32", registry=reg)
    s.score(np.zeros((5, 30), np.float32))  # no ids at all
    assert reg.counter("seq_anonymous_rows_total", "").value() == 5.0
    assert len(s.store) == 0


def test_striped_store_keeps_global_lru_exact():
    """Eviction order is GLOBAL commit recency, not per-stripe: with many
    stripes and a tiny cap, the coldest keys fall regardless of which
    stripe they hash to."""
    st = HistoryStore(length=2, num_features=1, max_customers=3, stripes=7)
    for key in "abcde":
        st.commit(st.prepare([key], np.ones((1, 1), np.float32))[1])
    assert len(st) == 3
    snap_keys = [c[0] for c in st.snapshot()["customers"]]
    assert sorted(snap_keys) == ["c", "d", "e"]
    # snapshot order is coldest-first (stamp order) for faithful restore
    assert snap_keys == ["c", "d", "e"]
    # touching "c" (re-commit) makes "d" the next victim
    st.commit(st.prepare(["c"], np.ones((1, 1), np.float32))[1])
    st.commit(st.prepare(["f"], np.ones((1, 1), np.float32))[1])
    assert sorted(c[0] for c in st.snapshot()["customers"]) == ["c", "e", "f"]


def test_lru_cap_holds_under_interleaved_workers():
    """Satellite: concurrent prepare/commit across threads (the
    ParallelRouter shape) never overshoots the cap and keeps per-key
    histories intact."""
    import threading

    st = HistoryStore(length=4, num_features=2, max_customers=64, stripes=8)
    errors: list = []

    def worker(wid: int) -> None:
        try:
            rng = np.random.default_rng(wid)
            for it in range(30):
                keys = [f"w{wid}-k{int(k)}" for k in
                        rng.integers(0, 40, size=16)]
                out, token = st.prepare(keys, rng.normal(
                    size=(16, 2)).astype(np.float32))
                assert out.shape == (16, 4, 2)
                assert st.commit(token) is True
                assert len(st) <= 64
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert 0 < len(st) <= 64
    # survivors carry well-formed ring buffers
    for key, buf, filled in st.snapshot()["customers"]:
        assert np.asarray(buf).shape == (4, 2)
        assert 1 <= filled <= 4


def test_duplicate_keys_across_chunks_see_overlay_and_same_chunk_rows():
    """Satellite: overlay visibility with duplicate keys BOTH within a
    chunk and across chunks of one router batch (batch_sizes=(2,) forces
    3 chunks over 6 rows of two interleaved customers)."""
    params = seq_mod.init(jax.random.PRNGKey(5))
    s = SeqScorer(params, length=8, batch_sizes=(2,), compute_dtype="float32")
    x = np.arange(6 * 30, dtype=np.float32).reshape(6, 30)
    ids = ["a", "b", "a", "b", "a", "a"]
    s.score(x, ids=ids)
    snap = {c[0]: (np.asarray(c[1]), c[2]) for c in
            s.store.snapshot()["customers"]}
    buf_a, filled_a = snap["a"]
    buf_b, filled_b = snap["b"]
    assert filled_a == 4 and filled_b == 2
    # a's ring holds rows 0, 2, 4, 5 newest-last
    assert np.allclose(buf_a[-1], x[5]) and np.allclose(buf_a[-2], x[4])
    assert np.allclose(buf_a[-3], x[2]) and np.allclose(buf_a[-4], x[0])
    assert np.allclose(buf_b[-1], x[3]) and np.allclose(buf_b[-2], x[1])


def test_stale_generation_commit_after_restore_races_async_dispatch():
    """Satellite: a crash restore landing while an ASYNC dispatch is in
    flight must not let that batch's commit land on the restored state —
    the rewound bus re-drives those records. The dispatch is held open on
    an event; restore() fires mid-flight; the resolved batch still
    returns scores but its commit is a counted no-op."""
    import threading

    from ccfd_tpu.metrics.prom import Registry

    reg = Registry()
    params = seq_mod.init(jax.random.PRNGKey(6))
    s = SeqScorer(params, length=4, batch_sizes=(4,),
                  compute_dtype="float32", inflight=2, registry=reg)
    s.score(np.ones((1, 30), np.float32), ids=["k"])
    snap = s.store.snapshot()

    real_apply = s._apply
    entered = threading.Event()
    release = threading.Event()

    def blocking_apply(p, xs):
        entered.set()
        assert release.wait(timeout=10)
        return real_apply(p, xs)

    s._apply = blocking_apply
    result: dict = {}

    def run():
        result["proba"] = s.score(
            np.full((2, 30), 9.0, np.float32), ids=["k", "k2"])

    t = threading.Thread(target=run)
    t.start()
    assert entered.wait(timeout=10)
    s.store.restore(snap)  # crash restore while the dispatch is in flight
    release.set()
    t.join(timeout=30)
    assert result["proba"].shape == (2,)
    # the doomed-epoch commit was dropped: store is exactly the cut
    final = s.store.snapshot()
    assert [c[0] for c in final["customers"]] == ["k"]
    assert final["customers"][0][2] == 1
    assert reg.counter("seq_stale_commits_total", "").value() == 1.0


def test_len_bucket_ladder_routes_cold_rows_to_short_executables():
    """Cold rows (filled << L) dispatch through the short-L executable;
    a customer whose history outgrows the bucket moves up the ladder.
    Hit counters record the (L, B) mix."""
    from ccfd_tpu.metrics.prom import Registry

    reg = Registry()
    params = seq_mod.init(jax.random.PRNGKey(7))
    s = SeqScorer(params, length=16, batch_sizes=(4,),
                  compute_dtype="float32", len_buckets=(4,), registry=reg)
    assert s.len_buckets == (4, 16)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 30)).astype(np.float32)
    p1 = s.score(x, ids=["c", "c", "c"])  # filled <= 3: short bucket
    c = reg.counter("seq_bucket_rows_total", "")
    assert c.value(labels={"l_bucket": "4"}) == 3.0
    assert c.value(labels={"l_bucket": "16"}) == 0.0
    # two more appends: the 4th row still fits the short bucket, the 5th
    # (filled=5 > 4) moves up to the full-L executable
    p2 = s.score(x[:2], ids=["c", "c"])
    assert c.value(labels={"l_bucket": "4"}) == 4.0
    assert c.value(labels={"l_bucket": "16"}) == 1.0
    assert np.all((p1 >= 0) & (p1 <= 1)) and np.all((p2 >= 0) & (p2 <= 1))


def test_len_bucket_short_dispatch_keeps_full_l_token_positions():
    """The short-bucket executable scores the right-aligned window with
    positional encodings anchored at the FULL length (pos_length=L): a
    cold row's tokens keep the positions the full-L path gives them, so
    scores don't jump at ladder crossovers. Pinned by direct equality
    with the documented serving function."""
    import jax.numpy as jnp

    params = seq_mod.init(jax.random.PRNGKey(8))
    L = 16
    bucketed = SeqScorer(params, length=L, batch_sizes=(4,),
                         compute_dtype="float32", len_buckets=(4,))
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 30)).astype(np.float32)
    got = bucketed.score(x, ids=["p", "q"])  # filled=1 -> lb=4 window
    w = np.zeros((2, 4, 30), np.float32)
    w[:, -1] = x
    w = np.concatenate([w, np.zeros((2, 4, 30), np.float32)])  # B bucket 4
    want = np.asarray(seq_mod.apply_serving(
        params, w[:4], jnp.float32, pos_length=L))[:2]
    np.testing.assert_allclose(got, want, atol=1e-6)
    # anchoring is a real offset: the un-anchored forward differs
    unanchored = np.asarray(seq_mod.apply_serving(
        params, w[:4], jnp.float32))[:2]
    assert not np.allclose(got, unanchored, atol=1e-6)


def test_async_overlapped_scores_match_synchronous():
    """inflight > 0 (overlapped) and inflight=0 (synchronous) run the
    same executables over the same assemblies — identical probabilities,
    identical store contents."""
    params = seq_mod.init(jax.random.PRNGKey(9))
    rng = np.random.default_rng(2)
    x = rng.normal(size=(40, 30)).astype(np.float32)
    ids = [i % 7 for i in range(40)]
    sync = SeqScorer(params, length=8, batch_sizes=(16,),
                     compute_dtype="float32", inflight=0)
    over = SeqScorer(params, length=8, batch_sizes=(16,),
                     compute_dtype="float32", inflight=3)
    p_sync = sync.score(x, ids)
    p_over = over.score(x, ids)
    np.testing.assert_allclose(p_over, p_sync, atol=1e-6)
    a = {c[0]: np.asarray(c[1]) for c in sync.store.snapshot()["customers"]}
    b = {c[0]: np.asarray(c[1]) for c in over.store.snapshot()["customers"]}
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_snapshot_copies_out_of_the_mutable_rings():
    """Rings in the slab are appended to in place, so a snapshot shares
    nothing with the live store (PR 25; the immutable buffers it used to
    share are gone): each call hands out its own linearised copies, and a
    later commit moves neither an older snapshot nor the untouched key."""
    st = HistoryStore(length=2, num_features=2, max_customers=8, stripes=4)
    st.commit(st.prepare(["a", "b"], np.ones((2, 2), np.float32))[1])
    s1 = st.snapshot()
    s2 = st.snapshot()
    bufs1 = {c[0]: c[1] for c in s1["customers"]}
    bufs2 = {c[0]: c[1] for c in s2["customers"]}
    assert all(bufs1[k] is not bufs2[k] for k in bufs1)  # copies
    assert all(np.array_equal(bufs1[k], bufs2[k]) for k in bufs1)
    st.commit(st.prepare(["a"], np.full((1, 2), 2.0, np.float32))[1])
    s3 = st.snapshot()
    bufs3 = {c[0]: c[1] for c in s3["customers"]}
    assert np.all(np.asarray(bufs3["a"])[-1] == 2.0)  # touched: appended
    assert np.array_equal(bufs3["b"], bufs1["b"])     # untouched: equal
    # and the older snapshots were not corrupted by the later commit
    assert np.all(np.asarray(bufs1["a"])[-1] == 1.0)
    assert np.all(np.asarray(bufs1["a"])[0] == 0.0)


def test_quantized_swap_rebinds_the_serving_graph():
    """swap_params with an int8 seq_q8 tree (the lifecycle promotion
    path) re-binds the jitted apply by sniffing the params — scores keep
    flowing, close to the f32 champion's."""
    from ccfd_tpu.ops.seq_quant import is_quantized, quantize_seq

    params = seq_mod.init(jax.random.PRNGKey(10))
    s = SeqScorer(params, length=8, batch_sizes=(8,),
                  compute_dtype="float32")
    rng = np.random.default_rng(3)
    x = rng.normal(size=(6, 30)).astype(np.float32)
    before = s.score(x, ids=list(range(6)))
    s.swap_params(quantize_seq(params))
    assert is_quantized(s.params)
    # fresh customer ids: re-scoring ids 0..5 would append x to histories
    # that already hold it, and the band would then compare two different
    # inputs ([x] vs [x, x]) as well as two precisions
    after = s.score(x, ids=list(range(6, 12)))
    assert after.shape == (6,)
    np.testing.assert_allclose(after, before, atol=0.06)


def test_batch_commit_evicts_by_arrival_order_not_stripe_group():
    """Regression (found by the live replay drill): stamps must follow
    the batch's ARRIVAL order. Assigning them during the per-stripe
    insertion pass made whole stripe-groups 'newest' within a batch, so
    eviction at the cap systematically kept one hash class per batch —
    and a crash-replay with different batch boundaries rebuilt a
    DISJOINT survivor set."""
    st = HistoryStore(length=2, num_features=1, max_customers=4, stripes=4)
    keys = list(range(12))  # unique customers, one batch, cap binds hard
    st.commit(st.prepare(keys, np.ones((12, 1), np.float32))[1])
    survivors = sorted(c[0] for c in st.snapshot()["customers"])
    assert survivors == [8, 9, 10, 11], survivors  # the arrival tail


def test_restore_between_chunk_prepares_dooms_the_whole_batch():
    """Regression: the batch commits with the FIRST chunk's generation.
    A restore landing BETWEEN chunk prepares must drop the whole batch —
    committing with a later chunk's fresh generation would publish the
    earlier chunks' pre-restore staging onto the restored state, and the
    rewound bus would then double-append those records."""
    import threading

    params = seq_mod.init(jax.random.PRNGKey(12))
    s = SeqScorer(params, length=4, batch_sizes=(2,),
                  compute_dtype="float32", inflight=0)
    s.score(np.ones((1, 30), np.float32), ids=["k"])
    snap = s.store.snapshot()

    real_apply = s._apply
    calls = {"n": 0}
    first_done = threading.Event()
    resume = threading.Event()

    def chunked_apply(p, xs):
        calls["n"] += 1
        if calls["n"] == 1:  # park AFTER chunk 1's prepare+dispatch
            first_done.set()
            assert resume.wait(timeout=10)
        return real_apply(p, xs)

    s._apply = chunked_apply
    result = {}

    def run():
        # 4 rows, batch_sizes=(2,): two chunks, two prepares
        result["p"] = s.score(np.full((4, 30), 2.0, np.float32),
                              ids=["k", "k2", "k3", "k4"])

    t = threading.Thread(target=run)
    t.start()
    assert first_done.wait(timeout=10)
    s.store.restore(snap)  # lands between chunk 1 and chunk 2 prepares
    resume.set()
    t.join(timeout=30)
    assert result["p"].shape == (4,)
    # the whole batch's commit was a no-op: exactly the cut's state
    final = s.store.snapshot()
    assert [c[0] for c in final["customers"]] == ["k"]
    assert final["customers"][0][2] == 1


def test_duplicate_key_recency_is_batch_boundary_invariant():
    """Regression: a key appearing twice in one batch must take its LAST
    occurrence's recency — dict insertion order would keep the FIRST, so
    the same record stream replayed with different batch boundaries
    would evict a different survivor set under a binding cap."""
    def survivors(batches):
        st = HistoryStore(length=2, num_features=1, max_customers=2,
                          stripes=4)
        for keys in batches:
            st.commit(st.prepare(
                keys, np.ones((len(keys), 1), np.float32))[1])
        return sorted(str(c[0]) for c in st.snapshot()["customers"])

    # same stream A,B,A,C under three different batchings
    one = survivors([["A", "B", "A", "C"]])
    two = survivors([["A", "B", "A"], ["C"]])
    three = survivors([["A", "B"], ["A"], ["C"]])
    assert one == two == three == ["A", "C"]  # B is the LRU victim


def test_late_commit_from_abandoned_batch_cannot_clobber_newer_state():
    """Regression: a watchdog-abandoned dispatch's commit can land AFTER
    the worker's next batch (same partition keys) prepared and committed.
    The per-key optimistic check must skip the contended key — the newer
    state survives, the skip is counted, and the routed stream (which
    contains both batches' records) rebuilds the full history at the
    next crash-restore replay."""
    st = HistoryStore(length=4, num_features=1, stripes=2)
    st.commit(st.prepare(["c"], np.ones((1, 1), np.float32))[1])
    # both batches prepare from the same base state (B1's dispatch hung;
    # the router abandoned it and moved on to B2)
    _, t1 = st.prepare(["c"], np.full((1, 1), 2.0, np.float32))
    _, t2 = st.prepare(["c"], np.full((1, 1), 3.0, np.float32))
    assert st.commit(t2) is True          # the live batch publishes
    assert st.commit(t1) is True          # the late commit is per-key
    assert st.contended_skips == 1        # ... skipped, not clobbering
    (key, buf, filled), = st.snapshot()["customers"]
    assert key == "c" and filled == 2
    assert np.asarray(buf)[-1, 0] == 3.0  # B2's append survived


# -- the in-flight window across calls (PR 27) ------------------------------

def _records(ids):
    """What the pipelined router hands ``score_with_ids``: the decoded
    records, marked as a caller's that takes the scores deferred."""
    from ccfd_tpu.router.router import DeferrableRecords

    return DeferrableRecords(
        [{} if i is None else {"id": i, "customer_id": i} for i in ids])


def _stream(case: str, batches: int = 6):
    """(x, ids) per router batch; ring length 4 wraps under all of them."""
    rng = np.random.default_rng(sum(map(ord, case)))
    out = []
    for k in range(batches):
        if case == "repeated_inside_a_batch":
            ids = [f"c{k}", "hot", f"c{k}", "hot", "hot", f"d{k}", "hot"]
        elif case == "across_two_batches":
            ids = [f"p{k // 2}", f"q{k}", f"p{k // 2}"]
        elif case == "across_three_batches":
            ids = ["every", f"t{k // 3}", "every", f"u{k}"]
        elif case == "fresh_customers":
            ids = [f"k{k}_{j}" for j in range(9)]
        elif case == "anonymous_rows":
            ids = [None, "a", None, f"n{k}", "a", None]
        elif case == "multichunk":  # 2-3 dispatches a batch, keys shared
            ids = [f"m{j % 7}" for j in range(20 + 7 * (k % 2))]
        else:  # mixed: random keys over a small set, a few anonymous
            n = int(rng.integers(3, 16))
            ids = [None if j == 9 else f"r{j}"
                   for j in rng.integers(0, 10, n)]
        out.append((rng.normal(size=(len(ids), 30)).astype(np.float32), ids))
    return out


OVERLAP_CASES = ["repeated_inside_a_batch", "across_two_batches",
                 "across_three_batches", "fresh_customers", "anonymous_rows",
                 "multichunk", "mixed"]


def _same_snapshot(a, b):
    sa, sb = a.snapshot(), b.snapshot()
    assert [c[0] for c in sa["customers"]] == [c[0] for c in sb["customers"]]
    for (_, ba, fa), (_, bb, fb) in zip(sa["customers"], sb["customers"]):
        assert fa == fb
        np.testing.assert_array_equal(ba, bb)


@pytest.mark.parametrize("force", ["by_the_next_call", "before_the_next_call"])
@pytest.mark.parametrize("case", OVERLAP_CASES)
def test_overlapped_entry_matches_batch_by_batch(case, force):
    """The same stream through ``score`` batch by batch and through the
    deferring entry (batch k left open, resolved and committed inside the
    call for k+1, which is staged on k's uncommitted rows): bit-identical
    probabilities, an identical store, nothing contended, nothing stale.
    ``before_the_next_call`` is light load: each result is forced before
    the next batch arrives, so nothing overlaps and the path is the same."""
    params = seq_mod.init(jax.random.PRNGKey(11))
    reg = Registry()
    kw = dict(length=4, batch_sizes=(4, 16), compute_dtype="float32")
    plain = SeqScorer(params, **kw)
    over = SeqScorer(params, registry=reg, **kw)
    stream = _stream(case)
    want = [plain.score(x, ids) for x, ids in stream]
    got = []
    for x, ids in stream:
        res = over.score_with_ids(_records(ids), x)
        assert res.deferred and res.ready_at is None
        if got and force == "by_the_next_call":
            assert got[-1].ready_at is not None  # this call readied it
        if force == "before_the_next_call":
            np.asarray(res)
            assert not over._open
        got.append(res)
    assert len(over._open) == (force == "by_the_next_call")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert not over._open
    _same_snapshot(plain.store, over.store)
    assert over.store.contended_skips == 0
    assert reg.counter("seq_stale_commits_total").total() == 0
    overlapped = reg.counter("seq_overlapped_batches_total").total()
    assert overlapped == (len(stream) - 1 if force == "by_the_next_call"
                          else 0)
    assert reg.get("seq_dispatch_seconds").count() == len(stream)
    assert reg.get("seq_assembly_seconds").count() == len(stream)
    assert reg.gauge("seq_inflight_dispatches").value() == 0
    # every staging batch came back, and no more than two open batches
    # of at most inflight + 1 each were ever out
    assert 1 <= len(over._staging) <= 2 * (over.inflight + 1)


@pytest.mark.parametrize("blocker", ["aux_tap", "shadow_tap", "canary_gate",
                                     "inflight_0", "unmarked_records"])
def test_callers_that_must_see_the_batch_resolved_get_host_memory(blocker):
    """The deferring entry is taken only where the call's own arguments
    say the caller forces the result and nothing armed on the scorer has
    to see the batch resolved inside the call."""
    params = seq_mod.init(jax.random.PRNGKey(12))
    s = SeqScorer(params, length=4, batch_sizes=(4,),
                  compute_dtype="float32",
                  inflight=0 if blocker == "inflight_0" else 2)

    class Armed:
        armed_version = 1
        active = True

        def offer(self, hist, proba):
            pass

        def apply(self, x, out, rescore):
            return out

    if blocker == "aux_tap":
        s.aux_tap = lambda rows, m, aux: None
    elif blocker == "shadow_tap":
        s.shadow_tap = Armed()
    elif blocker == "canary_gate":
        s.canary_gate = Armed()
    x = np.ones((3, 30), np.float32)
    ids = ["a", "b", "a"]
    records = (_records(ids) if blocker != "unmarked_records"
               else [{"id": i} for i in ids])
    out = s.score_with_ids(records, x)
    assert isinstance(out, np.ndarray) and out.shape == (3,)
    assert not s._open and len(s.store) == 2


def test_a_resolving_call_settles_what_a_deferring_caller_left_open():
    """``score`` after the deferring entry (the REST path, a tool, the
    router's ``step``) reads the open batch's rows from the store: it
    resolves and commits every open batch first, in order."""
    params = seq_mod.init(jax.random.PRNGKey(13))
    kw = dict(length=4, batch_sizes=(4,), compute_dtype="float32")
    plain, over = SeqScorer(params, **kw), SeqScorer(params, **kw)
    (x0, i0), (x1, i1) = _stream("across_two_batches", 2)
    want = [plain.score(x0, i0), plain.score(x1, i1)]
    res = over.score_with_ids(_records(i0), x0)
    got1 = over.score(x1, i1)
    assert res.ready_at is not None and not over._open
    np.testing.assert_array_equal(np.asarray(res), want[0])
    np.testing.assert_array_equal(got1, want[1])
    _same_snapshot(plain.store, over.store)


class _LostResult:
    """A device result that the runtime fails to deliver."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device result lost")


def _recording_apply(scorer, lose: set):
    """Wrap the scorer's program: keep every input it was handed (the
    memory the runtime reads), and lose the results of the dispatches
    numbered in ``lose``."""
    real, seen = scorer._apply, []

    def apply(p, xs):
        seen.append(xs)
        return _LostResult() if len(seen) - 1 in lose else real(p, xs)

    scorer._apply = apply
    return seen


def test_a_batch_that_fails_with_the_next_one_open_is_dropped_alone():
    """Batch k's dispatch fails to resolve while k+1 is open, staged on
    k's rows: k is dropped (its error raised where it is forced), k+1 is
    staged and dispatched again from the store without k's rows, so its
    verdicts and the store are those of a stream in which k never
    arrived; and no input the runtime may still read is filled again."""
    params = seq_mod.init(jax.random.PRNGKey(14))
    reg = Registry()
    kw = dict(length=4, batch_sizes=(4,), compute_dtype="float32")
    plain = SeqScorer(params, **kw)
    over = SeqScorer(params, registry=reg, **kw)
    stream = _stream("across_three_batches", 4)
    seen = _recording_apply(over, lose={1})  # batch 1's one dispatch
    got = [over.score_with_ids(_records(ids), x) for x, ids in stream]
    with pytest.raises(RuntimeError, match="device result lost"):
        np.asarray(got[1])
    with pytest.raises(RuntimeError, match="device result lost"):
        np.asarray(got[1])  # every time it is read
    want = [plain.score(x, ids) for k, (x, ids) in enumerate(stream)
            if k != 1]
    for w, g in zip(want, [got[0], got[2], got[3]]):
        np.testing.assert_array_equal(np.asarray(g), w)
    _same_snapshot(plain.store, over.store)
    assert over.store.contended_skips == 0
    assert reg.counter("seq_stale_commits_total").total() == 0
    # dispatches: 0, 1 (lost), 2 (staged on 1's rows, dropped), 2 again, 3
    assert len(seen) == 5
    for dropped in (seen[1], seen[2]):
        for later in seen[3:]:
            assert not np.shares_memory(dropped, later)
        for free in over._staging:
            assert not np.shares_memory(dropped, free.hist)
    assert not np.array_equal(seen[2], seen[3])  # without batch 1's rows


def test_a_failed_enqueue_leaves_the_open_batch_to_be_forced():
    """The dispatch seam of ``runtime/faults.py`` (``put_fail`` at the
    staging put): batch k+1 fails while it is enqueued, with k open. The
    call raises and k+1 is dropped as a failed call's batch always was; k
    stays open, is forced later, and the store holds k's rows alone."""
    from ccfd_tpu.runtime import faults

    params = seq_mod.init(jax.random.PRNGKey(15))
    kw = dict(length=4, batch_sizes=(4,), compute_dtype="float32")
    plain, over = SeqScorer(params, **kw), SeqScorer(params, **kw)
    (x0, i0), (x1, i1), (x2, i2) = _stream("across_three_batches", 3)
    res0 = over.score_with_ids(_records(i0), x0)
    faults.install_device_faults(faults.DeviceFaultPlan(
        {"put_fail": faults.DeviceFaultSpec(rate=1.0)}))
    try:
        with pytest.raises(faults.InjectedFault):
            over.score_with_ids(_records(i1), x1)
    finally:
        faults.install_device_faults(None)
    assert len(over._open) == 1 and res0.ready_at is None
    res2 = over.score_with_ids(_records(i2), x2)
    np.testing.assert_array_equal(np.asarray(res0), plain.score(x0, i0))
    np.testing.assert_array_equal(np.asarray(res2), plain.score(x2, i2))
    _same_snapshot(plain.store, over.store)


def test_restore_with_batches_open_makes_their_commits_counted_no_ops():
    """A crash restore lands while batch k is open and k+1 is being
    enqueued on k's rows: both commits are stale no-ops, counted, and the
    store is exactly the cut (the rewound bus re-drives both). A batch
    staged after the restore with the doomed k+1 still open reads nothing
    of it and commits onto the restored state."""
    params = seq_mod.init(jax.random.PRNGKey(16))
    reg = Registry()
    kw = dict(length=4, batch_sizes=(4,), compute_dtype="float32")
    s = SeqScorer(params, registry=reg, **kw)
    stream = _stream("across_three_batches", 4)
    s.score(*stream[0])
    snap = s.store.snapshot()
    real, calls = s._apply, []

    def apply(p, xs):
        calls.append(len(s._open))
        if len(calls) == 2:  # k+1's enqueue: k and k+1 are open
            s.store.restore(snap)
        return real(p, xs)

    s._apply = apply
    res1 = s.score_with_ids(_records(stream[1][1]), stream[1][0])
    res2 = s.score_with_ids(_records(stream[2][1]), stream[2][0])
    assert calls == [1, 2]
    assert res1.ready_at is not None and res2.ready_at is None
    assert reg.counter("seq_stale_commits_total").total() == 1
    res3 = s.score_with_ids(_records(stream[3][1]), stream[3][0])
    assert np.asarray(res2).shape == (len(stream[2][0]),)
    assert reg.counter("seq_stale_commits_total").total() == 2
    fresh = SeqScorer(params, **kw)
    fresh.store.restore(snap)
    np.testing.assert_array_equal(np.asarray(res3), fresh.score(*stream[3]))
    _same_snapshot(fresh.store, s.store)
    assert s.store.contended_skips == 0


def test_forcing_threads_race_the_staging_thread_and_nothing_is_lost():
    """One thread stages batch after batch through the deferring entry
    while others force whatever results exist, in any order, under a
    shortened switch interval: every batch is resolved once, in order,
    and probabilities and store are those of the batch-by-batch run."""
    import sys
    import threading

    params = seq_mod.init(jax.random.PRNGKey(17))
    kw = dict(length=4, batch_sizes=(4, 16), compute_dtype="float32")
    plain, over = SeqScorer(params, **kw), SeqScorer(params, **kw)
    stream = _stream("mixed", 40)
    want = [plain.score(x, ids) for x, ids in stream]
    results: list = []
    stop = threading.Event()
    errors: list = []

    def force(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            if results:
                try:
                    np.asarray(results[int(rng.integers(len(results)))])
                except Exception as e:  # noqa: BLE001 - fails the test
                    errors.append(e)
                    return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    forcers = [threading.Thread(target=force, args=(s,)) for s in range(6)]
    try:
        for t in forcers:
            t.start()
        for x, ids in stream:
            results.append(over.score_with_ids(_records(ids), x))
    finally:
        stop.set()
        for t in forcers:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in forcers)
    for w, g in zip(want, results):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert not over._open and over.store.contended_skips == 0
    _same_snapshot(plain.store, over.store)
