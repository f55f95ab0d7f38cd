"""The history store against a plain model of itself (serving/history.py).

The store keeps per-customer rings in one slab and fills staging batches
that are used again; what it must return is what a dict of lists returns.
``_RefStore`` below is that dict of lists: it shares no code with the
store, holds every history as a Python list of rows, and builds each
(L, F) buffer from zeros. Both are driven with the same random streams
through the same two-phase protocol ``SeqScorer`` uses (chunks, overlay,
one commit a batch), and everything observable is compared exactly:
``hist``, ``filled``, the commit's verdict, ``len``, ``contended_skips``
and the snapshot (keys coldest first, buffers, depths).

Below the model: the scorer's free list of staging batches (a batch is
never handed out while something still reads it; the shadow tap's batch is
its own) and the snapshot format (version 1 as the store before the slab
wrote it)."""

from __future__ import annotations

import json
import threading

import jax
import numpy as np
import pytest

from ccfd_tpu.models import seq as seq_mod
from ccfd_tpu.serving.history import HistoryStore, SeqScorer, StagingBatch


class _RefStore:
    """key -> (list of rows newest last, stamp): the store's contract in
    the plainest form that holds it."""

    def __init__(self, length: int, num_features: int, max_customers: int):
        self.L, self.F, self.cap = length, num_features, max_customers
        self.h: dict = {}
        self.clock = 0
        self.gen = 0
        self.contended_skips = 0

    def __len__(self) -> int:
        return len(self.h)

    def prepare(self, ids, rows, overlay=None):
        n = len(rows)
        hist = np.zeros((n, self.L, self.F), np.float32)
        filled = np.ones((n,), np.int32)
        staged: dict = {}
        for i, key in enumerate(ids):
            if key is None:
                hist[i, -1] = rows[i]
                continue
            if key in staged:
                ctx, base = staged.pop(key)  # recency = last occurrence
            elif overlay and key in overlay:
                ctx, base = overlay[key]
            elif key in self.h:
                ctx, base = self.h[key]
            else:
                ctx, base = [], None
            ctx = (ctx + [np.array(rows[i], np.float32)])[-self.L:]
            hist[i, self.L - len(ctx):] = ctx
            filled[i] = len(ctx)
            staged[key] = (ctx, base)
        return hist, (self.gen, staged, filled)

    def commit(self, token) -> bool:
        gen, staged = token[0], token[1]
        if not staged:
            return True
        if gen != self.gen:
            return False
        stamped = []
        for key, ent in staged.items():
            self.clock += 1
            stamped.append((key, ent, self.clock))
        for key, (ctx, base), stamp in stamped:
            cur = self.h.get(key)
            if cur is not None and (base is None or cur[1] != base):
                self.contended_skips += 1
                continue
            self.h[key] = (ctx, stamp)
        while len(self.h) > self.cap:
            del self.h[min(self.h, key=lambda k: self.h[k][1])]
        return True

    def snapshot(self) -> dict:
        customers = []
        for key, (ctx, _) in sorted(self.h.items(), key=lambda kv: kv[1][1]):
            buf = np.zeros((self.L, self.F), np.float32)
            buf[self.L - len(ctx):] = ctx
            customers.append([key, buf, len(ctx)])
        return {"version": 1, "length": self.L, "num_features": self.F,
                "customers": customers}

    def restore(self, snap) -> None:
        self.h = {}
        for key, buf, filled in (snap["customers"] if snap else []):
            buf = np.asarray(buf, np.float32).reshape(self.L, self.F)
            self.clock += 1
            self.h[key] = (list(buf[self.L - int(filled):]), self.clock)
        self.gen += 1


class _Driver:
    """One random stream of operations, applied to whatever store it is
    given; ``log`` is everything the store let be observed."""

    def __init__(self, store, case: dict, pooled: bool):
        self.store = store
        self.case = case
        self.rng = np.random.default_rng(case["seed"])
        self.log: list = []
        self.row = 0
        # the pool only exists for the real store; the model allocates
        self.pool: list | None = [] if pooled else None
        self.held: list = []

    # -- stream ------------------------------------------------------------
    def _batch(self, n: int, fresh: bool = False):
        c = self.case
        if fresh:
            ids = [f"fresh{self.row + i}" for i in range(n)]
        else:
            hot = self.rng.random(n) < c.get("hot", 0.0)
            ids = [0 if hot[i] else int(k) for i, k in
                   enumerate(self.rng.integers(0, c["keys"], size=n))]
            anon = self.rng.random(n) < c.get("anon", 0.0)
            ids = [None if anon[i] else ids[i] for i in range(n)]
        # every row is its own number in every feature: a misplaced row
        # cannot equal the one that belongs there
        rows = (np.arange(self.row, self.row + n, dtype=np.float32)[:, None]
                + np.linspace(0.25, 0.75, c["F"], dtype=np.float32)[None])
        self.row += n
        return ids, rows

    def _take(self):
        if self.pool is None:
            return None
        c = self.case
        out = self.pool.pop() if self.pool else StagingBatch(
            c["chunk"] + 2, c["L"], c["F"])
        self.held.append(out)
        return out

    def _release(self) -> None:
        if self.pool is not None:
            self.pool.extend(self.held)
        self.held = []

    def _prepare(self, ids, rows):
        """The chunks of one router batch, as ``SeqScorer._score`` stages
        them: overlay carried, a re-staged key moved to the end."""
        merged: dict = {}
        gen = None
        chunk = self.case["chunk"]
        for s in range(0, len(rows), chunk):
            out = self._take()
            kw = {} if out is None else {"out": out}
            hist, (g, staged, filled) = self.store.prepare(
                ids[s:s + chunk], rows[s:s + chunk], overlay=merged, **kw)
            if gen is None:
                gen = g
            for k in staged:
                merged.pop(k, None)
            merged.update(staged)
            self.log.append(("hist", hist.copy(), np.array(filled)))
            if out is not None:
                n = len(hist)
                assert hist.base is out.hist
                assert not out.hist[n:].any()  # padding already there
                assert np.array_equal(out.depth[:n], filled)
        return gen, merged

    def _observe(self) -> None:
        snap = self.store.snapshot()
        self.log.append((
            "state", len(self.store), self.store.contended_skips,
            [c[0] for c in snap["customers"]],
            [np.array(c[1]) for c in snap["customers"]],
            [int(c[2]) for c in snap["customers"]],
        ))

    # -- operations ----------------------------------------------------------
    def score(self, fresh: bool = False) -> None:
        ids, rows = self._batch(int(self.rng.integers(1, self.case["n"] + 1)),
                                fresh)
        self.log.append(("commit", self.store.commit(self._prepare(ids, rows))))
        self._release()

    def drop(self) -> None:
        self._prepare(*self._batch(self.case["n"]))  # dispatch failed
        self._release()

    def late(self) -> None:
        """A watchdog-abandoned batch commits after the next one did."""
        t1 = self._prepare(*self._batch(self.case["n"]))
        t2 = self._prepare(*self._batch(self.case["n"]))
        self.log.append(("commit", self.store.commit(t2)))
        self.log.append(("commit", self.store.commit(t1)))
        self._release()

    def evicted_between(self) -> None:
        """Every key the prepare stood on is evicted before its commit:
        each comes back with its whole staged history."""
        t1 = self._prepare(*self._batch(self.case["n"]))
        ids, rows = self._batch(self.case["cap"] + 1, fresh=True)
        self.log.append(("commit", self.store.commit(self._prepare(ids, rows))))
        self.log.append(("commit", self.store.commit(t1)))
        self._release()

    def restore_between(self) -> None:
        snap = self.store.snapshot()
        token = self._prepare(*self._batch(self.case["n"]))
        self.store.restore(snap)
        self.log.append(("commit", self.store.commit(token)))  # stale
        self._release()

    def roundtrip(self) -> None:
        snap = self.store.snapshot()
        if self.case.get("json"):  # as the coordinator writes it to disk
            snap = json.loads(json.dumps(
                {**snap, "customers": [[k, np.asarray(b).tolist(), f]
                                       for k, b, f in snap["customers"]]}))
        self.store.restore(snap)

    def run(self) -> list:
        ops = self.case["ops"]
        names = sorted(ops)
        p = np.array([ops[k] for k in names], float)
        for _ in range(self.case["steps"]):
            getattr(self, names[self.rng.choice(len(names), p=p / p.sum())])()
            self._observe()
        return self.log


_BASE = {"L": 4, "F": 3, "cap": 64, "stripes": 4, "chunk": 8, "n": 8,
         "keys": 12, "steps": 40, "ops": {"score": 1}}
CASES = {
    "repeats_inside_a_chunk": {"keys": 3, "seed": 1},
    "anonymous_ids": {"anon": 0.6, "seed": 2},
    "all_anonymous": {"anon": 1.0, "steps": 6, "seed": 3},
    "multi_chunk_overlay": {"chunk": 3, "n": 11, "keys": 5, "seed": 4},
    "more_than_L_rows_of_a_key_in_a_chunk": {
        "hot": 0.8, "chunk": 16, "n": 16, "seed": 5},
    "more_than_L_rows_across_the_overlay": {
        "hot": 0.8, "chunk": 3, "n": 16, "seed": 6},
    "ring_wraps_a_row_at_a_time": {
        "L": 3, "keys": 2, "chunk": 1, "n": 1, "steps": 60, "seed": 7},
    "length_one": {"L": 1, "keys": 4, "seed": 8},
    "eviction_at_a_binding_cap_reuses_slots": {
        "cap": 5, "keys": 40, "steps": 60, "seed": 9},
    "dropped_batches_leave_no_trace": {
        "ops": {"score": 2, "drop": 1}, "seed": 10},
    "late_commit_is_skipped_per_key": {
        "ops": {"score": 2, "late": 1}, "keys": 6, "seed": 11},
    "key_evicted_between_prepare_and_commit": {
        "cap": 6, "keys": 6, "n": 6, "ops": {"score": 2,
                                             "evicted_between": 1},
        "seed": 12},
    "restore_between_prepare_and_commit": {
        "ops": {"score": 2, "restore_between": 1}, "seed": 13},
    "snapshot_restores_into_itself": {
        "cap": 7, "keys": 30, "ops": {"score": 3, "roundtrip": 1},
        "seed": 14},
    "snapshot_restores_from_json": {
        "cap": 7, "keys": 30, "json": True,
        "ops": {"score": 3, "roundtrip": 1}, "seed": 15},
    "everything_at_once": {
        "L": 5, "cap": 9, "keys": 14, "chunk": 4, "n": 13, "anon": 0.2,
        "hot": 0.3, "steps": 120,
        "ops": {"score": 6, "drop": 1, "late": 1, "evicted_between": 1,
                "restore_between": 1, "roundtrip": 1}, "seed": 16},
    "one_stripe_wide_rows": {
        "F": 30, "L": 8, "stripes": 1, "keys": 6, "steps": 25, "seed": 17},
}


def _same(a, b) -> bool:
    if isinstance(a, np.ndarray):
        return (isinstance(b, np.ndarray) and a.dtype == b.dtype
                and a.shape == b.shape and np.array_equal(a, b))
    if isinstance(a, (list, tuple)):
        return (type(a) is type(b) and len(a) == len(b)
                and all(_same(x, y) for x, y in zip(a, b)))
    return a == b


@pytest.mark.parametrize("pooled", [False, True],
                         ids=["fresh_batches", "recycled_batches"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_store_equals_the_dict_of_lists(name, pooled):
    case = {**_BASE, **CASES[name]}
    store = HistoryStore(length=case["L"], num_features=case["F"],
                         max_customers=case["cap"], stripes=case["stripes"])
    got = _Driver(store, case, pooled).run()
    want = _Driver(_RefStore(case["L"], case["F"], case["cap"]), case,
                   False).run()
    assert len(got) == len(want)
    for step, (g, w) in enumerate(zip(got, want)):
        assert _same(g, w), (step, g[0], g, w)
    # the stream did what the case is named for
    kinds = {e[0] for e in got}
    assert {"hist", "state"} <= kinds
    if "late" in case["ops"]:
        assert store.contended_skips > 0
    if "restore_between" in case["ops"]:
        assert ("commit", False) in [e[:2] for e in got if e[0] == "commit"]
    if case["cap"] < case["keys"]:
        assert store._next_slot <= case["cap"] + case["n"] + 1  # slots reused


def test_the_store_touches_no_slab_memory_at_construction():
    """131,072 x 512 x 30 x 4 bytes is 8 GB: built, and nothing mapped;
    one customer maps one block."""
    st = HistoryStore(length=512, num_features=30, max_customers=131_072)
    assert st._blocks == []
    st.commit(st.prepare(["a"], np.ones((1, 30), np.float32))[1])
    assert len(st._blocks) == 1
    assert st._blocks[0].nbytes <= 32 << 20


# -- the scorer's staging batches ------------------------------------------


class _Tap:
    """The shadow tap as far as ``SeqScorer`` reads it: armed, and keeping
    what it is offered."""

    armed_version = 1

    def __init__(self) -> None:
        self.offers: list = []

    def offer(self, hist, proba) -> None:
        self.offers.append((hist, hist.copy(), proba.copy()))


def _stream(seed: int, batches: int, n: int, keys: int):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(n, 30)).astype(np.float32),
             [None if k == 0 else int(k)
              for k in rng.integers(0, keys, size=n)])
            for _ in range(batches)]


def _scorer(**kw):
    params = seq_mod.init(jax.random.PRNGKey(25))
    kw = {"length": 8, "batch_sizes": (4, 16), "compute_dtype": "float32",
          "max_customers": 64, **kw}
    return SeqScorer(params, **kw)


def _record_dispatches(s) -> list:
    real, seen = s._apply, []

    def apply(p, xs):
        seen.append(np.array(xs))
        return real(p, xs)

    s._apply = apply
    return seen


@pytest.mark.parametrize("len_buckets", [None, (2, 4)],
                         ids=["full_length", "ladder_armed"])
@pytest.mark.parametrize("n", [3, 16, 23, 40],
                         ids=["short_of_a_bucket", "one_bucket",
                              "bucket_and_rest", "three_chunks"])
def test_recycled_batches_dispatch_what_fresh_ones_do(n, len_buckets):
    """The same stream through a scorer that recycles its staging batches
    and through one that cannot (an armed tap keeps every batch, so each
    chunk allocates): every dispatched (bucket, L', F) array, every
    verdict and the store's state are equal, the L-bucket ladder armed or
    not."""
    pooled = _scorer(len_buckets=len_buckets)
    plain = _scorer(len_buckets=len_buckets)
    plain.shadow_tap = _Tap()
    seen_pooled = _record_dispatches(pooled)
    seen_plain = _record_dispatches(plain)
    for x, ids in _stream(n, 12, n, 9):
        np.testing.assert_array_equal(pooled.score(x, ids),
                                      plain.score(x, ids))
    assert len(seen_pooled) == len(seen_plain) > 0
    for a, b in zip(seen_pooled, seen_plain):
        np.testing.assert_array_equal(a, b)
    assert plain._staging == type(plain._staging)()  # the tap: never pooled
    assert 1 <= len(pooled._staging) <= pooled.inflight + 1
    a, b = pooled.store.snapshot(), plain.store.snapshot()
    assert [c[0] for c in a["customers"]] == [c[0] for c in b["customers"]]
    for ca, cb in zip(a["customers"], b["customers"]):
        np.testing.assert_array_equal(ca[1], cb[1])
        assert ca[2] == cb[2]


def test_a_staging_batch_is_not_handed_out_while_a_dispatch_reads_it():
    """The runtime reads the host batch after ``apply_fn`` returned. Hold
    the first call's dispatch open before anything has read its batch,
    score other customers from a second thread meanwhile, then let it go:
    the first call's verdicts are those of an unpooled run, and the two
    calls held two batches."""
    x1, ids1 = _stream(1, 1, 16, 9)[0]
    x2, ids2 = _stream(2, 1, 16, 9)[0]
    warm_x, warm_ids = _stream(3, 1, 16, 9)[0]

    plain = _scorer()
    plain.shadow_tap = _Tap()
    plain.score(warm_x, warm_ids)
    want1 = plain.score(x1, ids1)

    s = _scorer()
    s.score(warm_x, warm_ids)  # one batch in the free list, full of rows
    assert len(s._staging) == 1
    real = s._apply
    entered, release = threading.Event(), threading.Event()
    handed: list = []

    def apply(p, xs):
        handed.append(xs)
        if len(handed) == 1:
            entered.set()
            assert release.wait(timeout=30)
        return real(p, xs)

    s._apply = apply
    got: dict = {}
    t1 = threading.Thread(target=lambda: got.update(a=s.score(x1, ids1)))
    t1.start()
    assert entered.wait(timeout=30)
    t2 = threading.Thread(target=lambda: got.update(b=s.score(x2, ids2)))
    t2.start()
    t2.join(timeout=60)
    assert "b" in got  # the second call did not wait for the first
    release.set()
    t1.join(timeout=60)
    np.testing.assert_array_equal(got["a"], want1)
    assert not np.shares_memory(handed[0], handed[1])
    assert len(s._staging) == 2


def test_a_failed_dispatch_keeps_its_batch_out_of_the_free_list():
    s = _scorer()
    x, ids = _stream(4, 1, 16, 9)[0]
    s.score(x, ids)
    assert len(s._staging) == 1
    real = s._apply

    def boom(p, xs):
        raise RuntimeError("dispatch failed")

    s._apply = boom
    with pytest.raises(RuntimeError):
        s.score(x, ids)
    assert len(s._staging) == 0  # the runtime may still be reading it
    s._apply = real
    s.score(x, ids)
    assert len(s._staging) == 1


def test_the_taps_batch_is_intact_after_the_next_score():
    s = _scorer()
    (x0, ids0), (x1, ids1), (x2, ids2) = _stream(5, 3, 16, 9)
    s.score(x0, ids0)  # pooled: the free list holds a used batch
    tap = _Tap()
    s.shadow_tap = tap
    s.score(x1, ids1)
    s.shadow_tap = None
    s.score(x2, ids2)
    s.score(x0, ids0)
    (hist, at_offer, _), = tap.offers
    np.testing.assert_array_equal(hist, at_offer)
    assert all(not np.shares_memory(hist, b.hist) for b in s._staging)


def test_gather_phase_counts_bytes_and_recycling():
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.observability.trace import SpanSink, Tracer

    sink = SpanSink(sample=1.0)
    tracer = Tracer(Registry(), sink=sink)
    s = _scorer()
    x = np.ones((3, 30), np.float32)
    stats = []
    for _ in range(2):
        with tracer.span("router.score") as root:
            s.score(x, ids=["a", "a", None])
        gather, = [d for d in sink.trace(root.trace_id)
                   if d["name"] == "seq.gather"]
        stats.append(gather["attrs"])
    # first call: a's second row copies one row of context; the batch new
    assert stats[0]["gathered_bytes"] == 1 * 30 * 4
    assert stats[0]["recycled"] == 0 and stats[0]["repeated_keys"] == 1
    # second call: contexts of 2 and 3 rows; the batch from the free list
    assert stats[1]["gathered_bytes"] == (2 + 3) * 30 * 4
    assert stats[1]["recycled"] == 1


# -- the snapshot format -----------------------------------------------------


def _v1_snapshot(L: int, F: int, histories: dict) -> dict:
    """Format version 1 from plain arrays, as the store wrote it before
    the slab: [key, (L, F) buffer newest last and zero on the left,
    filled], coldest first."""
    customers = []
    for key, rows in histories.items():
        buf = np.zeros((L, F), np.float32)
        buf[L - len(rows):] = rows
        customers.append([key, buf, len(rows)])
    return {"version": 1, "length": L, "num_features": F,
            "customers": customers}


@pytest.mark.parametrize("as_json", [False, True], ids=["arrays", "json"])
def test_a_snapshot_of_the_old_store_restores_and_scores_the_same(as_json):
    rng = np.random.default_rng(6)
    histories = {f"c{i}": rng.normal(size=(d, 30)).astype(np.float32)
                 for i, d in enumerate([1, 3, 8, 8, 5])}
    snap = _v1_snapshot(8, 30, histories)
    if as_json:
        snap = json.loads(json.dumps(
            {**snap, "customers": [[k, b.tolist(), f]
                                   for k, b, f in snap["customers"]]}))
    restored = _scorer()
    restored.store.restore(snap)
    fed = _scorer()
    for key, rows in histories.items():  # the same histories, by appends
        fed.score(rows, ids=[key] * len(rows))
    assert len(restored.store) == len(fed.store) == 5
    x = rng.normal(size=(7, 30)).astype(np.float32)
    ids = ["c0", "c2", "c2", "c4", None, "new", "c3"]
    np.testing.assert_array_equal(restored.score(x, ids), fed.score(x, ids))
    a, b = restored.store.snapshot(), fed.store.snapshot()
    assert [c[0] for c in a["customers"]] == [c[0] for c in b["customers"]]
    for ca, cb in zip(a["customers"], b["customers"]):
        np.testing.assert_array_equal(ca[1], cb[1])
        assert ca[2] == cb[2]


def test_a_restored_store_evicts_in_the_order_the_cut_had():
    st = HistoryStore(length=3, num_features=2, max_customers=4, stripes=3)
    for key in "abcd":
        st.commit(st.prepare([key] * 4, np.ones((4, 2), np.float32))[1])
    st.commit(st.prepare(["a"], np.ones((1, 2), np.float32))[1])  # b coldest
    twin = HistoryStore(length=3, num_features=2, max_customers=4, stripes=5)
    twin.restore(st.snapshot())
    for store in (st, twin):
        store.commit(store.prepare(["e", "f"],
                                   np.ones((2, 2), np.float32))[1])
    keys = [[c[0] for c in s.snapshot()["customers"]] for s in (st, twin)]
    assert keys[0] == keys[1] == ["d", "a", "e", "f"]
