"""The pipelined router with a scorer that defers its result
(router/router.py ``_run_pipelined``; serving/history.py ``SeqScorer``).

What must not change when batch k's scores come back before they are
ready and the worker readies them inside its call for k+1: routing and
offset commits strictly in batch order, never more than ``max_inflight``
consumed and unrouted (nothing shed), one terminal disposition per batch
(a failure at force time is the same counted drop as a failed call), a
pause point that leaves nothing open, and every caller that returns host
memory served as before. One case drives the benchmark's own score tap.
"""

import threading
import time

import jax
import numpy as np
import pytest

from ccfd_tpu.bus.broker import Broker
from ccfd_tpu.config import Config
from ccfd_tpu.data.ccfd import FEATURE_NAMES
from ccfd_tpu.metrics.prom import Registry
from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.router.router import Router
from ccfd_tpu.serving.history import SeqScorer

CFG = Config(customer_reply_timeout_s=30.0, fraud_threshold=0.5)
AMOUNT = FEATURE_NAMES.index("Amount")
BATCH = 32
WORKER = "ccfd-router-score"


class LazyScores:
    """The protocol's result side without the history scorer behind it."""

    deferred = True

    def __init__(self, scorer, k):
        self.scorer, self.k = scorer, k
        self.ready_at = None

    def __array__(self, dtype=None, copy=None):
        return self.scorer.force(self.k)


class DeferringScorer:
    """Leaves batch k open and readies it inside the call for k+1, or when
    it is forced: every older batch first. ``fail``: batches whose result
    cannot be had. The log is the ground truth of who did what when."""

    def __init__(self, fail=(), budget=None):
        self.lock = threading.Lock()
        self.fail = set(fail)
        self.budget = budget
        self.results: list[LazyScores] = []
        self.values: list = []
        self.log: list[tuple] = []
        self.peak_unrouted = 0

    def __call__(self, x):
        raise AssertionError("called without records")

    def _ready_upto(self, k):
        for r in self.results[:k + 1]:
            if r.ready_at is None:
                r.ready_at = time.perf_counter()
                self.log.append(("ready", r.k,
                                 threading.current_thread().name))

    def score_with_ids(self, txs, x):
        assert txs.takes_deferred
        with self.lock:
            k = len(self.results)
            if self.budget is not None:
                self.peak_unrouted = max(self.peak_unrouted,
                                         self.budget.inflight)
            self.log.append(("call", k))
            self._ready_upto(k - 1)
            self.results.append(LazyScores(self, k))
            self.values.append((x[:, AMOUNT] > 100.0).astype(np.float32))
            return self.results[k]

    def force(self, k):
        with self.lock:
            self._ready_upto(k)
            if k in self.fail:
                raise RuntimeError(f"batch {k} lost")
            return self.values[k]

    @property
    def open(self):
        return sum(r.ready_at is None for r in self.results)


class OrderEngine:
    """Engine stub: every start in call order, with the ids started."""

    start_batch_nocopy = True

    def __init__(self, log):
        self.log = log
        self.started: list = []

    def definitions(self):
        return ("standard", "fraud")

    def start_process_batch(self, def_id, vars_list, copy_vars=True):
        ids = [v["transaction"]["id"] for v in vars_list]
        self.log.append(("start", def_id, ids))
        self.started.extend(ids)
        return list(range(len(self.started) - len(ids), len(self.started)))

    def start_process(self, def_id, variables):
        return self.start_process_batch(def_id, [variables])[0]

    def signal(self, pid, name, payload=None):
        return True


def _in_batch_order(started):
    """The ids started, each batch's sorted (a batch's starts are grouped
    by the rule that fired): equal to the consumed order when every batch
    was routed whole and in its turn."""
    return [i for at in range(0, len(started), BATCH)
            for i in sorted(started[at:at + BATCH])]


def _rows(n):
    return [{"id": i, "customer_id": i % 11,
             "Amount": 500.0 if i % 7 == 0 else 1.0} for i in range(n)]


def _run_until_routed(router, reg, n, timeout_s=30.0):
    t = router.start(poll_timeout_s=0.01, pipeline=True)
    deadline = time.monotonic() + timeout_s
    done = lambda: (  # noqa: E731
        reg.counter("transaction_outgoing_total").total()
        + reg.counter("router_score_errors_total").total())
    while done() < n and time.monotonic() < deadline:
        time.sleep(0.005)
    router.stop()
    t.join(timeout=30)
    assert not t.is_alive()
    return done()


def _pipeline(scorer, n, **kw):
    broker = Broker(default_partitions=1)
    reg = Registry()
    log = getattr(scorer, "log", [])
    engine = OrderEngine(log)
    router = Router(CFG, broker, scorer, engine, reg, max_batch=BATCH,
                    commit_after_route=True, **kw)
    commits = router._tx_consumer.commit

    def commit(offs=None, epoch=None):
        log.append(("commit", dict(offs)))
        return commits(offs, epoch)

    router._tx_consumer.commit = commit
    broker.produce_batch(CFG.kafka_topic, _rows(n))
    return broker, reg, engine, router, log


def test_deferred_batches_route_and_commit_in_order_within_the_budget():
    n = 10 * BATCH
    scorer = DeferringScorer()
    broker, reg, engine, router, log = _pipeline(scorer, n)
    scorer.budget = router._budget
    assert router.max_inflight == 2 * BATCH
    assert _run_until_routed(router, reg, n) == n
    assert _in_batch_order(engine.started) == list(range(n))
    # per batch, in batch order: its starts, then its offsets
    ends = [e[1][(CFG.kafka_topic, 0)] for e in log if e[0] == "commit"]
    assert ends == [BATCH * (k + 1) for k in range(10)]
    at = 0
    for k in range(10):
        starts = [i for i, e in enumerate(log) if e[0] == "start"
                  and e[2][0] // BATCH == k]
        commit = next(i for i, e in enumerate(log) if e[0] == "commit"
                      and e[1][(CFG.kafka_topic, 0)] == BATCH * (k + 1))
        assert at <= min(starts) and max(starts) < commit
        at = commit
    # the worker readied every batch but the last inside the next call;
    # the loop forced the last one
    ready = {e[1]: e[2] for e in log if e[0] == "ready"}
    assert all(ready[k].startswith(WORKER) for k in range(9))
    assert not ready[9].startswith(WORKER)
    assert scorer.open == 0
    assert scorer.peak_unrouted <= router.max_inflight
    assert router._budget.inflight == 0
    assert reg.counter("router_shed_total").total() == 0
    assert reg.counter("router_score_errors_total").total() == 0
    # submit-to-ready, one observation a batch
    assert reg.get("router_score_seconds").count() == 10
    router.close()


def test_a_failure_at_force_time_is_one_counted_drop_with_its_offsets():
    n = 6 * BATCH
    scorer = DeferringScorer(fail={2})
    broker, reg, engine, router, log = _pipeline(scorer, n)
    assert _run_until_routed(router, reg, n) == n
    lost = set(range(2 * BATCH, 3 * BATCH))
    assert _in_batch_order(engine.started) == [
        i for i in range(n) if i not in lost]
    assert reg.counter("router_score_errors_total").total() == BATCH
    ends = [e[1][(CFG.kafka_topic, 0)] for e in log if e[0] == "commit"]
    assert ends == [BATCH * (k + 1) for k in range(6)]  # the drop commits
    assert broker.committed_offsets("router", CFG.kafka_topic) == [n]
    assert router._budget.inflight == 0
    assert reg.get("router_score_seconds").count() == 5
    router.close()


def _seq_scorer(reg=None):
    s = SeqScorer(seq_mod.init(jax.random.PRNGKey(3)), length=4,
                  batch_sizes=(BATCH,), compute_dtype="float32",
                  registry=reg)
    s.warmup()
    return s


def test_a_pause_point_leaves_nothing_open():
    scorer = _seq_scorer()
    broker, reg, engine, router, _ = _pipeline(scorer, 5 * BATCH)
    t = router.start(poll_timeout_s=0.01, pipeline=True)
    try:
        consumed = reg.counter("transaction_incoming_total")
        deadline = time.monotonic() + 30.0
        while consumed.value() < 2 * BATCH and time.monotonic() < deadline:
            time.sleep(0.001)
        assert router.pause(30.0)
        # the ack's promise: consumed == routed, and the store holds every
        # routed record (nothing staged and uncommitted anywhere)
        assert not scorer._open
        got = consumed.value()
        assert got == len(engine.started) > 0
        held = sum(c[2] for c in scorer.store.snapshot()["customers"])
        assert held == sum(min(4, len(range(c, int(got), 11)))
                           for c in range(11))
        assert router._budget.inflight == 0
        router.resume()
        while consumed.value() < 5 * BATCH and time.monotonic() < deadline:
            time.sleep(0.001)
    finally:
        router.stop()
        t.join(timeout=30)
    assert not t.is_alive() and not scorer._open
    assert _in_batch_order(engine.started) == list(range(5 * BATCH))
    router.close()


class HostMemoryScorer:
    """``score_with_ids`` that ignores the mark; ``kind`` says what it
    returns: an array, or something only ``np.asarray`` turns into one."""

    def __init__(self, kind):
        self.kind = kind
        self.forced_on: list[str] = []

    def __call__(self, x):
        raise AssertionError("called without records")

    def score_with_ids(self, txs, x):
        proba = (x[:, AMOUNT] > 100.0).astype(np.float32)
        if self.kind == "array":
            return proba
        outer = self

        class OnDevice:
            def __array__(self, dtype=None, copy=None):
                outer.forced_on.append(threading.current_thread().name)
                return proba

        return OnDevice()


@pytest.mark.parametrize("kind", ["callable", "array", "device_like",
                                  "ladder", "pool_worker"])
def test_callers_the_change_does_not_concern_behave_as_before(kind):
    """A plain callable, a ``score_with_ids`` that returns host memory or
    a device array (forced on the worker, as ever), the degradation
    ladder (it has to see the scores) and a pool's worker (its scorer is
    shared): all routed in order, one score observation a batch made on
    the worker, nothing left open."""
    n = 4 * BATCH
    kw = {}
    if kind == "callable":
        scorer = lambda x: (x[:, AMOUNT] > 100.0).astype(  # noqa: E731
            np.float32)
    elif kind in ("array", "device_like"):
        scorer = HostMemoryScorer(kind)
    else:
        scorer = _seq_scorer()
        kw = {"degrade": True} if kind == "ladder" else {"worker_id": 0}
    broker, reg, engine, router, _ = _pipeline(scorer, n, **kw)
    seen = []
    observe = router._h_score_s.observe
    router._h_score_s.observe = lambda *a, **k: (
        seen.append(threading.current_thread().name), observe(*a, **k))
    assert _run_until_routed(router, reg, n) == n
    assert _in_batch_order(engine.started) == list(range(n))
    assert len(seen) == 4 and all(t.startswith(WORKER) for t in seen)
    if kind == "device_like":
        assert len(scorer.forced_on) == 4
        assert all(t.startswith(WORKER) for t in scorer.forced_on)
    if isinstance(scorer, SeqScorer):
        assert not scorer._open
        assert len(scorer.store) == 11
    assert reg.counter("router_score_errors_total").total() == 0
    router.close()


@pytest.mark.parametrize("mode", ["step", "unpipelined"])
def test_the_synchronous_loops_stay_synchronous(mode):
    scorer = _seq_scorer()
    broker, reg, engine, router, _ = _pipeline(scorer, 3 * BATCH)
    if mode == "step":
        while router.step() > 0:
            assert not scorer._open
    else:
        t = router.start(poll_timeout_s=0.01, pipeline=False)
        deadline = time.monotonic() + 30.0
        while (len(engine.started) < 3 * BATCH
               and time.monotonic() < deadline):
            assert not scorer._open
            time.sleep(0.001)
        router.stop()
        t.join(timeout=30)
    assert _in_batch_order(engine.started) == list(range(3 * BATCH))
    assert not scorer._open
    router.close()


def test_the_benchmarks_score_tap_streams_finished_scores_in_order():
    """``benchmark/deployments/kafka_history.py::ScoreTap`` stands between
    the router and the scorer in the history cells: it forwards the marked
    records, keeps what came back in call order and reads the scores only
    when the run is over."""
    from benchmark.deployments.kafka_history import ScoreTap

    n = 7 * BATCH + 5
    reg = Registry()
    scorer = _seq_scorer(reg)
    tap = ScoreTap(scorer)
    broker, rreg, engine, router, _ = _pipeline(tap, n)
    assert _run_until_routed(router, rreg, n) == n
    assert _in_batch_order(engine.started) == list(range(n))
    stream = tap.stream()
    assert stream["customer"].tolist() == list(range(n))
    assert stream["proba"].shape == (n,) and stream["proba"].dtype == float
    assert len(tap.calls) == 8
    assert reg.counter("seq_overlapped_batches_total").total() == 7
    # the same calls, batch by batch, on a scorer that resolves in the call
    plain = _seq_scorer()
    want = np.concatenate([plain.score_with_ids(list(txs), x)
                           for txs, x, _ in tap.calls])
    np.testing.assert_array_equal(stream["proba"], want.astype(np.float64))
    assert not scorer._open and scorer.store.contended_skips == 0
    router.close()
