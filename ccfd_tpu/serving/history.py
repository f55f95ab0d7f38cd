"""Per-customer transaction history for the sequence scorer.

The seq model (models/seq.py) scores the NEWEST transaction given the
customer's recent history (B, L, F). Single-row REST scoring is stateless
by design (the Seldon contract); history lives where the stream lives —
in the routing tier, which already sees every transaction in arrival
order. This module is that state, as an overlapped serving dataflow: a
synchronous chunk loop leaves the device idle while the host assembles
and the host idle while the device computes (PERF.md section 5 has what
a batch's time is made of today).

- ``HistoryStore`` — a fixed-depth ring per customer, all rings in one
  slab that grows in blocks, bounded total customers (LRU eviction at the
  cap; a freed slot is reused), INTERNALLY STRIPED by key hash: N stripes
  with per-stripe locks so ParallelRouter workers stop convoying on one
  global lock, a global monotonic touch-stamp keeping LRU eviction exact
  across stripes, and an all-anonymous path that takes no lock at all
  (cold REST scoring). The assembly touches each byte once: an append
  writes the one new row at the ring's cursor, and ``prepare`` copies a
  customer's ``filled`` rows — at most two contiguous slices — straight
  into the (B, L, F) batch, which the caller may hand in already mapped
  (``out=``, a ``StagingBatch`` that is used again). One path serves a
  chunk with and without repeated keys. Mutation is two-phase:
  ``prepare()`` stages views of the batch's rows, ``commit()`` writes
  the new rows — a failed scorer dispatch must not leave transactions
  in history that were never routed. The store is CHECKPOINTABLE
  (snapshot/restore, format version 1): rings are mutable, so
  ``snapshot`` copies each history out linearised and the barrier pays
  for the live history bytes (154 MB at the default store), and the
  recovery coordinator treats the store as pipeline state: after a
  crash rewind, replayed records re-build exactly the histories the cut
  had — without this, at-least-once redelivery would append every
  replayed transaction a second time and silently corrupt every active
  customer's context.
- ``SeqScorer`` — the router-facing scorer, now an overlapped dataflow:
  each (L-bucket, B-bucket) group's device call is ENQUEUED (JAX async
  dispatch) and the next group assembles while it runs; results resolve
  (``np.asarray``) only when the bounded in-flight window (``inflight``)
  fills or the batch ends, and the store commits once, after every
  dispatch resolved — a crash restore racing an in-flight dispatch
  drops the whole batch's commit (stale generation, counted in
  ``seq_stale_commits_total``), and when the PR 6 dispatch watchdog
  abandons a hung batch whose commit later lands CONCURRENTLY with the
  worker's next batch, the store's per-key optimistic check skips the
  contended keys instead of clobbering newer state
  (``HistoryStore.contended_skips``; the skipped appends are in the
  routed stream, so the next crash-restore replay recovers them). For a
  caller that takes its result before it is ready (the pipelined
  router: its records are marked, ``score_with_ids`` answers with
  ``DeferredScores``) the window spans consecutive calls: batch k stays
  open when its call returns, and the call for k+1 gathers, pads and
  enqueues k+1 on k's staged rows BEFORE it blocks on k, commits k and
  marks k ready, so the host's assembly and the transfer in run beside
  the device's work on the batch before. Whoever forces an open batch
  resolves every older one first, under the scorer's lock; a batch that
  fails to resolve is dropped alone, and the open batches staged on its
  rows are staged and dispatched again without them. Every other caller
  (``score``, REST, tools, an ``aux_tap``, the shadow tap, the canary
  gate) has its batch resolved inside the call. Rows bucket by
  HISTORY LENGTH as well as batch size: a mostly-cold row (filled << L)
  dispatches through a short-sequence executable (the ``len_buckets``
  ladder) instead of padding to full L, with per-(L, B)-bucket hit
  counters; shapes stay static per (L, B) pair so XLA never re-traces.
  The device graph is the history family's, found by name in
  ``models/registry.py`` (``HistorySpec``: ``seq``'s exact last-block
  readout, ``seq_q8``'s int8 variant, ``hybrid_moe``'s tokenised
  language-model backbone); a tree that arrives without a name
  (``swap_params`` on a lifecycle promotion) is asked of the registry once,
  which is how a promoted ``seq_q8`` candidate takes over serving. A family
  with a padding mask (``reads_filled``) gets each row's ``filled`` depth
  with the batch; a family that fills the device is not swappable and
  ``swap_params`` refuses it by name (``seq_swap_refused_total``).

TPU-first notes: histories assemble host-side into one contiguous array
per micro-batch (one transfer, one dispatch — never per-customer gathers
on device), in a staging batch the scorer recycles once the batch's
dispatches resolved and its commit landed (the runtime reads the host
buffer on its own thread after the call returned; a batch the shadow tap
or the canary gate keeps is its own and never recycled); every L bucket
is static so XLA sees fixed (bucket, L, F) shapes; the model runs bf16
with f32 accumulation. Where its rows are whole device tiles a batch
crosses as the flat view of the same bytes (``_Program``).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from functools import partial
from typing import Any

import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES
from ccfd_tpu.observability import trace
from ccfd_tpu.observability.trace import phase
from ccfd_tpu.runtime.faults import device_seam

DEFAULT_STRIPES = 8
# short-sequence ladder OFF by default: bucketed windows attend fewer
# zero-pad tokens than the full-L graph (reference_attention has no
# padding mask), so scores for cold rows differ between rungs — arming
# the ladder is an explicit serving choice (seq.len_buckets /
# CCFD_SEQ_LEN_BUCKETS), the same opt-in posture as the CoDel deadline
DEFAULT_LEN_BUCKETS: tuple = ()
DEFAULT_INFLIGHT = 2
# a float32 array lives on the chip in tiles of 8 sublanes x 128 lanes over
# its last two dimensions: a host array whose minor dimension is 128 and
# whose rows are a whole number of tiles is in that order already
_WIRE_LANES = 128
_WIRE_TILE = 8 * _WIRE_LANES


# bytes of slab mapped at a time: a block is one lazily zeroed mapping
# (np.zeros of this size is calloc's fresh mmap), so memory becomes resident
# in the kernel's fault granule as rows are written and not before: 4 kB
# pages on Linux, where a ring two rows deep holds a fifteenth of its slot;
# the whole slot under gVisor (the chip tool's sandbox) or where transparent
# huge pages are always on, as the 61 kB buffer per customer before it did
_BLOCK_BYTES = 32 << 20


class _Stripe:
    __slots__ = ("lock", "h")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        # key -> (slot, filled count, touch stamp, write cursor)
        self.h: OrderedDict[Any, tuple[int, int, int, int]] = OrderedDict()


class StagingBatch:
    """A reusable (rows, L, F) batch for ``HistoryStore.prepare(out=)``.

    ``depth[i]`` is how many rows of ``hist[i]``, counted from the newest,
    may be nonzero: everything left of them is zero. ``prepare`` keeps it
    true, so a batch that is used again is cleared only where its last
    use reached deeper than this one does."""

    __slots__ = ("hist", "depth")

    def __init__(self, rows: int, length: int, num_features: int):
        self.hist = np.zeros((rows, length, num_features), np.float32)
        self.depth = np.zeros((rows,), np.int32)

    def settle(self, n: int, filled: np.ndarray) -> None:
        """The first ``n`` rows were just filled ``filled`` deep: zero what
        the last use left deeper than that, and whatever it left in the
        rows past ``n``."""
        L = self.hist.shape[1]
        depth = self.depth
        for i in np.nonzero(depth[:n] > filled)[0]:
            self.hist[i, L - depth[i]:L - filled[i]] = 0.0
        depth[:n] = filled
        for i in np.nonzero(depth[n:])[0] + n:
            self.hist[i, L - depth[i]:] = 0.0
        depth[n:] = 0


class HistoryStore:
    """Fixed-depth per-customer rings in one slab, bounded total keys.

    A customer is a slot of the (slots, L, F) float32 slab with a write
    cursor and a ``filled`` count; an append writes the one new row at the
    cursor. The linearised, newest-last (L, F) view of a history exists
    only where something asks for it: in the batch ``prepare`` fills, in
    a snapshot. The slab grows in blocks of ``_BLOCK_BYTES`` as slots are
    first used, and nothing of it is touched at construction.

    Memory bound: ``max_customers * length * num_features * 4`` bytes (a
    commit may stand one batch's new keys over the cap until its eviction
    pass) — the default (20k x 64 x 30 x f32) admits ~150 MB resident on
    the serving host; size the cap to the deployment's live-customer
    working set, not its total cardinality (LRU keeps the hot set). A
    ring holds resident the pages its rows are on; a slot that eviction
    frees is reused before the slab grows.

    Concurrency: reads/stages take only the key's stripe lock (and the
    all-anonymous path none); ``commit``/``restore``/``snapshot``
    serialize on one commit lock (commits are per router batch — rare
    next to prepares — and a restore interleaving a half-published
    commit would corrupt the cut). Rows in the slab are MUTABLE: a slot
    is reachable only through its key's entry, is written under that
    key's stripe lock, and is copied out under it — ``prepare`` never
    hands out a reference into the slab. The slot allocator is touched
    only under the commit lock."""

    def __init__(self, length: int = 64, num_features: int = NUM_FEATURES,
                 max_customers: int = 20_000, stripes: int = DEFAULT_STRIPES):
        if length < 1:
            raise ValueError("history length must be >= 1")
        self.length = int(length)
        self.num_features = int(num_features)
        self.max_customers = int(max_customers)
        self.stripes = max(1, int(stripes))
        self._stripes = [_Stripe() for _ in range(self.stripes)]
        self._commit_lock = threading.Lock()
        self._count_lock = threading.Lock()
        self._total = 0
        slot_bytes = self.length * self.num_features * 4
        self._block_slots = max(1, min(self.max_customers,
                                       _BLOCK_BYTES // slot_bytes))
        self._blocks: list[np.ndarray] = []  # each (block_slots, L, F)
        self._free_slots: list[int] = []     # freed by eviction
        self._next_slot = 0
        # global touch stamp: commit order defines recency ACROSS stripes,
        # so LRU eviction at the cap stays exact despite per-stripe LRU
        # order (itertools.count().__next__ is GIL-atomic)
        self._stamp = itertools.count().__next__
        # commits skipped by the per-key optimistic check (see commit());
        # nonzero means concurrent same-key batches raced — e.g. a
        # watchdog-abandoned dispatch's late commit
        self._contended = 0
        # epoch generation: restore() bumps it and commit() drops staged
        # chunks from an older generation — a scorer dispatch that was in
        # flight across a crash restore (the unacked-barrier path) must
        # not land its doomed-epoch rows on the restored state (the
        # engine's equivalent guard is Engine._check_alive)
        self._gen = 0

    def _stripe_of(self, key: Any) -> _Stripe:
        return self._stripes[hash(key) % self.stripes]

    def __len__(self) -> int:
        with self._count_lock:
            return self._total

    # -- the slab (allocator under the commit lock, rows under the key's
    # stripe lock) ----------------------------------------------------------
    def _ring(self, slot: int) -> np.ndarray:
        return self._blocks[slot // self._block_slots][
            slot % self._block_slots]

    def _new_slot(self) -> int:
        if self._free_slots:
            return self._free_slots.pop()
        slot = self._next_slot
        self._next_slot += 1
        if slot >= len(self._blocks) * self._block_slots:
            self._blocks.append(np.zeros(
                (self._block_slots, self.length, self.num_features),
                np.float32))
        return slot

    def _append(self, slot: int, cursor: int, view: np.ndarray,
                m: int) -> int:
        """Write the ``m`` newest rows of a linearised ``view`` into the
        ring at ``cursor`` (at most two slices); returns the new cursor."""
        L = self.length
        ring = self._ring(slot)
        first = min(m, L - cursor)
        ring[cursor:cursor + first] = view[L - m:L - m + first]
        if m > first:
            ring[:m - first] = view[L - m + first:]
        return (cursor + m) % L

    def _linear(self, dst: np.ndarray, slot: int, k: int,
                cursor: int) -> None:
        """Copy the ``k`` newest rows of a ring, oldest first, into
        ``dst`` (k, F): at most two contiguous slices."""
        ring = self._ring(slot)
        lo = cursor - k
        if lo >= 0:
            dst[:] = ring[lo:cursor]
        else:
            dst[:-lo] = ring[lo:]
            dst[-lo:] = ring[:cursor]

    # -- staging ------------------------------------------------------------
    # ccfd-lint: hot-path
    def prepare(
        self, ids: list, rows: np.ndarray, overlay: dict | None = None,
        out: StagingBatch | None = None,
    ) -> tuple[np.ndarray, tuple[int, dict, np.ndarray]]:
        """Stage this chunk: return the (B, L, F) batch of post-append
        histories (newest last) plus a token ``(gen, staged, filled)``,
        WITHOUT mutating the store. ``commit()`` publishes staged state
        only after the scorer dispatch succeeded — a dropped batch
        (transient scorer failure) must leave histories exactly matching
        the routed stream. ``filled`` is the per-row post-append history
        depth — what the scorer's L-bucket ladder partitions on.

        A customer appearing twice in one chunk sees its earlier
        same-chunk rows in the later assembly; ``overlay`` extends that
        visibility across the chunks of ONE router batch (the caller
        accumulates staged dicts and commits once) and across the
        scorer's open batches (staged, not committed yet; each commits in
        its turn). ``None`` ids are
        anonymous: scored against an empty history and NEVER stored — a
        bounded store must not spend its cap (and evict real customers)
        on keys no future record can match. An ALL-anonymous chunk takes
        no lock and stages nothing (the cold-REST fast path).

        ``out``: the batch to fill, in memory that is already mapped. The
        returned batch is then ``out.hist[:B]``, and the rows of ``out``
        past B are zero: padding to a larger bucket is already there.
        Without it a fresh zeroed batch is allocated. Either way each
        staged entry is ``(view, filled, base, new)``: a VIEW of the
        batch's row of the key's last occurrence; ``base``, the store
        entry it derives from, as ``[stamp, rows committed]`` (stamp None
        for a fresh key; one list per lineage: an entry staged on an
        ``overlay`` entry shares its list, and ``commit`` moves it on);
        and how many of its rows are new since the list was made — so
        the batch must stay as it is until the token is committed or
        dropped."""
        rows = np.ascontiguousarray(rows, np.float32)
        n = len(rows)
        L = self.length
        if out is None:
            hist = np.zeros((n, L, self.num_features), np.float32)
        else:
            hist = out.hist[:n]
        gen = self._gen
        if n:
            hist[:, -1] = rows
        # key -> (row of its last occurrence, filled there, base, new);
        # None until the key's first occurrence is assembled
        state: dict[Any, tuple | None] = {}
        by_stripe: dict[int, list[tuple[int, Any]]] = {}
        later: list[tuple[int, Any]] = []  # overlay hits and repeats
        repeats = False
        for i, key in enumerate(ids):
            if key is None:
                continue  # cold context + this row, already assembled
            if key in state:
                repeats = True
                later.append((i, key))
                continue
            state[key] = None
            if overlay and key in overlay:
                later.append((i, key))
            else:
                by_stripe.setdefault(hash(key) % self.stripes, []).append(
                    (i, key))
        filled_out = [1] * n
        # first occurrences: one pass per touched stripe, the copy out of
        # the slab taken under the lock (rings are mutable)
        for si, group in by_stripe.items():
            st = self._stripes[si]
            with st.lock:
                h = st.h
                for i, key in group:
                    ent = h.get(key)
                    if ent is None:
                        state[key] = (i, 1, [None, 0], 1)
                        continue
                    slot, filled, stamp, cursor = ent
                    k = min(filled, L - 1)
                    if k:
                        self._linear(hist[i, L - 1 - k:L - 1], slot, k,
                                     cursor)
                    filled_out[i] = k + 1
                    state[key] = (i, k + 1, [stamp, 0], 1)
        # then, in arrival order, the rows whose context is an earlier
        # chunk's staged view or an earlier row of this batch
        for i, key in later:
            s = state[key]
            if s is None:  # an earlier chunk's staging keeps its base
                src, filled, base, new = overlay[key]
            else:
                src, (_, filled, base, new) = hist[s[0]], s
            k = min(filled, L - 1)
            if k:
                hist[i, L - 1 - k:L - 1] = src[L - k:]
            filled_out[i] = k + 1
            state[key] = (i, k + 1, base, new + 1)
        filled_arr = np.array(filled_out, np.int32)
        if out is not None:
            out.settle(n, filled_arr)
        # recency = LAST occurrence (see score()): commit stamps in the
        # order of ``staged``
        items = (sorted(state.items(), key=lambda kv: kv[1][0])
                 if repeats else state.items())
        staged = {key: (hist[i], filled, base, new)
                  for key, (i, filled, base, new) in items}
        return hist, (gen, staged, filled_arr)

    # -- publication --------------------------------------------------------
    # ccfd-lint: hot-path
    def commit(self, token: tuple) -> bool:
        """Publish a prepared chunk (call only after every dispatch of the
        batch resolved). Evicts the globally-coldest keys past the cap.
        Returns False — and changes nothing — when the store was restored
        since the prepare (stale generation: the rewound bus will
        re-drive those records onto the restored state).

        Per-key optimistic check: each staged entry carries the stamp of
        the store entry it derives from; a key whose live entry moved
        since the prepare (a CONCURRENT batch committed it — e.g. a
        watchdog-abandoned dispatch's late commit racing the worker's
        next batch on the same partition keys) is SKIPPED rather than
        clobbering the newer state, counted in ``contended_skips``. The
        skipped batch's appends are recovered by the next crash-restore
        replay (the records are in the routed stream).

        Where the live entry still stands on the base stamp, only the
        staged entry's rows not committed yet are appended to its ring;
        a key with no live entry (fresh, or evicted since the prepare)
        takes a slot and the whole staged history.

        A batch prepared LATER with this one as its ``overlay`` and not
        committed yet (the scorer's open batches) shares the ``base`` of
        every entry it derives from one of this batch's. This commit
        moves that ``base`` on to the stamp it gave the key and the rows
        it has appended, which rebases the later entry: its own commit
        finds the live entry on its base, appends exactly its own rows,
        and is not taken for a contended one."""
        gen, staged = token[0], token[1]
        if not staged:
            return True
        L = self.length
        with self._commit_lock:
            if gen != self._gen:
                return False
            # stamps follow the batch's ARRIVAL order (staged dicts
            # preserve last-occurrence order), assigned BEFORE the
            # per-stripe insertion pass: stamping inside that pass would
            # make whole stripe-groups "newest" within a batch, and under
            # a binding cap eviction would systematically keep one hash
            # class of each batch (found by the replay drill: disjoint
            # survivor sets before/after a rewind)
            by_stripe: dict[int, list] = {}
            for key, ent in staged.items():
                by_stripe.setdefault(hash(key) % self.stripes, []).append(
                    (key, ent, self._stamp()))
            added = 0
            for si, items in by_stripe.items():
                st = self._stripes[si]
                with st.lock:
                    h = st.h
                    for key, (view, filled, base, new), stamp in items:
                        cur = h.get(key)
                        if cur is None:
                            slot, cursor, m = self._new_slot(), 0, filled
                            added += 1
                        elif cur[2] != base[0]:
                            # live entry moved since this prepare: a
                            # concurrent batch owns the newer state
                            self._contended += 1
                            continue
                        else:
                            slot, cursor = cur[0], cur[3]
                            m = min(new - base[1], L)
                            h.move_to_end(key)
                        h[key] = (slot, filled, stamp,
                                  self._append(slot, cursor, view, m))
                        base[0], base[1] = stamp, new
            if added:
                with self._count_lock:
                    self._total += added
            self._evict_over_cap()
        return True

    def _evict_over_cap(self) -> None:
        """Pop the globally-oldest entry until under the cap. Runs under
        the commit lock (single evictor); takes one stripe lock at a time
        — the scan reads each stripe's LRU head stamp, the pop re-checks
        under the chosen stripe's lock. The freed slot keeps its rows:
        nothing reads a ring past its ``filled``, and the next key to
        take the slot starts at cursor 0."""
        while True:
            with self._count_lock:
                if self._total <= self.max_customers:
                    return
            best_i, best_stamp = -1, None
            for i, st in enumerate(self._stripes):
                with st.lock:
                    if st.h:
                        stamp = next(iter(st.h.values()))[2]
                        if best_stamp is None or stamp < best_stamp:
                            best_i, best_stamp = i, stamp
            if best_i < 0:
                return
            st = self._stripes[best_i]
            with st.lock:
                if st.h:
                    _, ent = st.h.popitem(last=False)
                    self._free_slots.append(ent[0])
                    with self._count_lock:
                        self._total -= 1

    # -- checkpoint surface (pipeline state, like the engine) ---------------
    def snapshot(self) -> dict:
        """State for the recovery coordinator's cut: runs under the
        checkpoint barrier. Format version 1: ``[key, (L, F) buffer
        newest last, filled]`` per customer, each buffer a COPY linearised
        out of its ring under the stripe lock (rings are mutable, so
        nothing can be shared with the live store). The barrier's cost is
        therefore proportional to the live history bytes, not to churn:
        a full default store copies 20,000 x 64 x 30 x 4 = 154 MB. The
        coordinator JSON-normalizes outside the barrier (recovery.py
        _np_jsonable); ``restore`` accepts either form. Entries are
        ordered coldest first (global touch stamps), so a restore
        rebuilds the same eviction order."""
        L = self.length
        with self._commit_lock:
            entries: list[tuple[int, Any, np.ndarray, int]] = []
            for st in self._stripes:
                with st.lock:
                    for key, (slot, filled, stamp, cursor) in st.h.items():
                        buf = np.zeros((L, self.num_features), np.float32)
                        self._linear(buf[L - filled:], slot, filled, cursor)
                        entries.append((stamp, key, buf, filled))
            entries.sort(key=lambda e: e[0])
            return {
                "version": 1,
                "length": self.length,
                "num_features": self.num_features,
                "customers": [[key, buf, filled]
                              for _, key, buf, filled in entries],
            }

    def restore(self, snap: dict | None) -> None:
        """Replace the store's content with a snapshot's (crash recovery:
        the rewound bus re-drives post-cut records, re-building exactly
        the histories the cut had). ``None`` resets to empty (genesis
        restore — replay from offset 0 rebuilds everything). The
        generation bumps LAST, so a prepare racing this call either sees
        the old generation (its commit is dropped) or the fully-restored
        state. A ``startup.restore`` phase: a service restarts from its
        checkpoint through here."""
        customers = len(snap["customers"]) if snap is not None else 0
        with trace.startup.phase(
                "startup.restore", customers=customers,
                bytes=customers * self.length * self.num_features * 4):
            self._restore(snap)

    def _restore(self, snap: dict | None) -> None:
        L = self.length
        with self._commit_lock:
            for st in self._stripes:
                with st.lock:
                    st.h.clear()
            # every stripe is empty, so no slot is reachable: the slab
            # starts over
            self._blocks = []
            self._free_slots = []
            self._next_slot = 0
            total = 0
            if snap is not None:
                if snap.get("version") != 1:
                    raise ValueError(
                        f"unknown history snapshot {snap.get('version')!r}")
                if (int(snap["length"]) != self.length
                        or int(snap["num_features"]) != self.num_features):
                    raise ValueError("history snapshot shape mismatch")
                for key, buf, filled in snap["customers"]:
                    buf = np.asarray(buf, np.float32).reshape(
                        L, self.num_features)
                    filled = int(filled)
                    st = self._stripe_of(key)
                    with st.lock:
                        slot = self._new_slot()
                        st.h[key] = (slot, filled, self._stamp(),
                                     self._append(slot, 0, buf, filled))
                    total += 1
            with self._count_lock:
                self._total = total
            self._gen += 1  # in-flight prepares become stale commits

    @property
    def contended_skips(self) -> int:
        return self._contended

    @property
    def generation(self) -> int:
        """Bumped by every ``restore``; what a prepare's token carries."""
        return self._gen

    def snapshot_counts(self) -> dict:
        return {"customers": len(self), "length": self.length,
                "stripes": self.stripes}


class _Batch:
    """One router batch from its staging to its commit: what an open
    batch keeps so that a later call, or whoever forces its scores, can
    resolve it, commit it, or stage it again."""

    __slots__ = ("x", "ids", "seq", "out", "scores", "error", "t_asm",
                 "t_disp", "gen", "merged", "pending", "taken", "kept",
                 "n_anon")

    def __init__(self, x: np.ndarray, ids: list, seq: int = 0):
        self.x = x
        self.ids = ids
        # the scorer's count of batches: every phase of this batch carries
        # it (``seq_batch``), whichever call or thread resolves it
        self.seq = seq
        self.out = np.empty((len(x),), np.float32)
        self.scores: DeferredScores | None = None  # of a deferred batch
        # what an older batch's dispatch raised while a later call waited
        # on it: raised again where the batch's turn to settle comes
        self.error: Exception | None = None
        self.t_asm = self.t_disp = 0.0
        self.reset()

    def reset(self) -> None:
        """Nothing staged (yet, or any more). What the last staging left
        is dropped, not used again: the runtime may still read a staging
        batch whose dispatch was never resolved."""
        self.gen: int | None = None
        self.merged: dict = {}
        # (device result, rows inside the batch, real rows, tokens)
        self.pending: deque = deque()
        self.taken: list[StagingBatch] = []
        self.kept: list[tuple[np.ndarray, int, int]] = []  # tap / gate
        self.n_anon = 0


class DeferredScores:
    """A router batch's probabilities, handed out while its dispatches may
    still be in flight (``SeqScorer.score_with_ids`` for a caller that
    takes them so). ``np.asarray`` of it is the (B,) float32 array: ready
    once the scorer's next call has resolved and committed the batch, and
    forced before that by whoever asks (every older open batch first, in
    order, on the asking thread), or the exception the batch was dropped
    for. ``ready_at`` is ``time.perf_counter()`` at that instant."""

    __slots__ = ("_scorer", "_batch", "_value", "ready_at")
    deferred = True

    def __init__(self, scorer: "SeqScorer", batch: _Batch):
        self._scorer = scorer
        self._batch: _Batch | None = batch
        self._value: np.ndarray | Exception | None = None
        self.ready_at: float | None = None

    def ready(self, value: "np.ndarray | Exception") -> None:
        self._value = value
        self._batch = None  # with its records, staging and device results
        self.ready_at = time.perf_counter()

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        batch = self._batch
        if batch is not None:
            self._scorer._force(batch)
        if isinstance(self._value, Exception):
            raise self._value
        return (self._value if dtype is None
                else self._value.astype(dtype, copy=False))


class _Program:
    """A family's device program as ``SeqScorer._make_apply`` built it,
    the form in which a dispatch's history batch crosses to the device,
    and what the program's own trace says of each (L bucket, B bucket)
    executable.

    **The wire.** ``fn(params, hist, ...)`` takes (B, L, F) float32. Sent
    in that shape the runtime transposes every batch on a host thread
    before the copy: F = 30 is no multiple of the chip's 128 lanes, so the
    device keeps such a parameter features-outermost (PERF.md section 5:
    6.5 ms of a 1,024-row batch's 14.9). A window of ``L * F`` values that
    is a whole number of (8, 128) tiles is sent as the
    (B, L * F / 128, 128) view of the same memory instead, whose tiles
    are 4 kB of consecutive host bytes each, and ``flat``, ONE jitted
    program around ``fn``, restores (B, L, F) on the device as its first
    operation: the same values in the same order, so the same
    probabilities bit for bit. Decided per executable from the window's
    shape (``flat_wire``); any other shape, a batch that is on the device
    already, and every batch of a scorer with a mesh (``flat`` is None:
    ``_put_hist`` places the rows itself) go as (B, L, F).

    **The trace.** Which kernel families the executable holds
    (``ops/kernels.py::held``), asked at the shape and in the wire form
    that is dispatched. The program's jaxpr is read the first time it is
    asked (a look-up in the jit's trace cache once the executable has
    run), the memo afterwards; a swap to another variant builds another
    program, so the memo never outlives what it describes."""

    __slots__ = ("fn", "flat", "reads_filled", "num_features", "_held")

    def __init__(self, fn: Any, reads_filled: bool, num_features: int,
                 flat: bool = False):
        self.fn = fn
        self.flat = _behind_flat_wire(fn, num_features) if flat else None
        self.reads_filled = reads_filled
        self.num_features = num_features
        self._held: dict = {}

    def flat_wire(self, lb: int) -> bool:
        """Whether a host batch of ``lb``-record windows crosses flat."""
        return (self.flat is not None
                and lb * self.num_features % _WIRE_TILE == 0)

    def __call__(self, params: Any, hist: Any, *extra: Any):
        if isinstance(hist, np.ndarray) and self.flat_wire(hist.shape[1]):
            # a view where the batch is contiguous (a staging batch's rows,
            # a ladder window's own copy): no byte moves on the host
            return self.flat(params, hist.reshape(
                len(hist), -1, _WIRE_LANES), *extra)
        return self.fn(params, hist, *extra)

    def kernels_held(self, params: Any, lb: int, b: int) -> dict:
        """Each kernel family's key -> 0 / 1 for the (lb, b) executable."""
        got = self._held.get((lb, b))
        if got is None:
            import jax

            from ccfd_tpu.observability.profile import billed
            from ccfd_tpu.ops import kernels

            shape = jax.ShapeDtypeStruct
            extra = (shape((b,), np.int32),) if self.reads_filled else ()
            fn, hist = ((self.flat, (b, lb * self.num_features
                                     // _WIRE_LANES, _WIRE_LANES))
                        if self.flat_wire(lb)
                        else (self.fn, (b, lb, self.num_features)))
            with billed("startup.inventory", l_bucket=int(lb),
                        b_bucket=int(b)) as ph:
                got = self._held[(lb, b)] = kernels.held(
                    fn, params, shape(hist, np.float32), *extra)
                ph.set(**got)
        return got


def _behind_flat_wire(fn: Any, num_features: int):
    """``fn`` as one jitted program that takes its history batch in the
    flat wire form and restores (B, L, F) first (``_Program``). The
    reshape is inside the program: a dispatch stays one executable."""
    import jax

    def flat_wire(params, wire, *extra):
        return fn(params, wire.reshape(len(wire), -1, num_features), *extra)

    return jax.jit(flat_wire)


def _kernels_held(apply_fn: Any, params: Any, lb: int, b: int) -> dict:
    """``_Program.kernels_held``; a stand-in for the program (a test's or
    a drill's gate around it) has no trace to read and holds none."""
    held = getattr(apply_fn, "kernels_held", None)
    if held is not None:
        return held(params, lb, b)
    from ccfd_tpu.ops import kernels

    return dict.fromkeys((f.key for f in kernels.FAMILIES), 0)


def _takes_flat_wire(apply_fn: Any, lb: int) -> bool:
    """``_Program.flat_wire``; a stand-in for the program says nothing of
    the wire behind it."""
    flat_wire = getattr(apply_fn, "flat_wire", None)
    return flat_wire is not None and flat_wire(lb)


class SeqScorer:
    """History-aware scorer with the row scorer's serving discipline —
    bucketed static shapes — run as an overlapped dataflow: per-(L, B)
    bucket dispatches enqueue asynchronously while the next group
    assembles, bounded by ``inflight``; ONE commit per router batch after
    every dispatch resolved (see module docstring). For a caller that
    takes a deferred result the window of ``inflight`` open dispatches
    spans consecutive calls: batch k+1 is gathered, enqueued and on its
    way to the device while the device computes batch k. What crosses to
    the device a dispatch is the padded (bucket, L, F) float32 batch, as
    it is or, where ``L * F`` fills whole (8, 128) tiles and there is no
    mesh, as the flat view of the same memory (``_Program``), and where
    the family masks its padding the rows' ``filled`` depths."""

    def __init__(
        self,
        params: Any,
        length: int = 64,
        batch_sizes: tuple = (16, 128, 1024, 4096),
        compute_dtype: str = "bfloat16",
        max_customers: int = 20_000,
        registry: Any = None,
        mesh: Any = None,
        stripes: int = DEFAULT_STRIPES,
        inflight: int = DEFAULT_INFLIGHT,
        len_buckets: tuple | None = None,
        telemetry: Any = None,
        partitioner: Any = None,
        seq_parallel: str = "none",
        family: str | None = None,
        family_config: Any = None,
    ):
        """``family``: the history family's name in ``models/registry``
        (``seq``, ``seq_q8``, ``hybrid_moe``); left out, the registered
        family that owns ``params``. ``family_config``: what the family's
        program is built from where its tree does not say it (the
        ``hybrid_moe`` settings).

        ``mesh``: serve the seq dispatch over a device mesh — history
        batches split over the partitioned axes, params replicated (the
        same SPMD layout the row Scorer's data-axis path uses; history
        ASSEMBLY stays host-side either way). Bucket sizes round up to
        axis-size multiples so every shard gets identical static shapes.
        ``partitioner`` (parallel/partition.py): the first-class form of
        the same — supplies the mesh, the PARAM layout (the regex rule
        table under ``param_partition: rules``, replicated under data
        parallel; an uncovered tree such as the int8 seq_q8 variant
        replicates with a warning) and the publish path.

        ``seq_parallel``: ``none`` | ``ring`` | ``ulysses`` — shard the
        attention's L dim over the mesh's ``tp`` (or legacy ``model``)
        axis (ops/ring_attention.py / ops/ulysses.py). The previously
        dormant flag, now operator-selectable (CR ``mesh.seq_parallel``).
        Blocks whose static shapes can't shard (the readout block's
        single-query attention; an L bucket not divisible by the axis)
        fall back per-executable to what one chip's program attends with
        (ops/seq_attention.py) — shapes are static at trace time, so the
        choice costs nothing at runtime.

        ``inflight``: async dispatches in flight before the loop blocks
        on the oldest (0 = resolve immediately, the synchronous path),
        counted over every open batch: for a caller that takes a deferred
        result the window spans consecutive calls.
        ``len_buckets``: the short-sequence ladder; the full ``length``
        is always appended. A row dispatches at the smallest bucket
        covering its post-append history depth."""
        import jax
        import jax.numpy as jnp

        with trace.startup.phase(
                "startup.store",
                bytes=max_customers * length * NUM_FEATURES * 4):
            self.store = HistoryStore(length=length,
                                      max_customers=max_customers,
                                      stripes=stripes)
        # recycled (largest bucket, L, F) staging batches: a batch takes
        # at most inflight + 1 and puts them back once it is committed, so
        # the list is bounded by what is in flight (deque.pop / extend are
        # GIL-atomic: safe under the ParallelRouter's workers)
        self._staging: deque = deque()
        # batches a deferring caller left open, oldest first: staged and
        # enqueued, not yet resolved or committed. The lock is held by
        # whoever stages, settles or forces one, for as long as it does
        self._open: deque = deque()
        self._lock = threading.Lock()
        # a batch's ordinal on its phases (``seq_batch``); ``next`` of a
        # count is GIL-atomic, as the workers of a pool need it
        self._batch_ids = itertools.count(1)
        # device telemetry plane (observability/device.py): the seq
        # dispatch ships (B, L, F) history batches whose transfer happens
        # INSIDE the jitted call, so only the bytes are separately
        # countable here (ccfd_h2d_bytes_total); the row scorer's explicit
        # staging carries the timed samples
        if telemetry is None:
            from ccfd_tpu.observability import device as _device

            telemetry = _device.get_default()
        self.telemetry = telemetry
        self._dtype = (jnp.bfloat16 if compute_dtype == "bfloat16"
                       else jnp.float32)
        self.inflight = max(0, int(inflight))
        if len_buckets is None:
            len_buckets = DEFAULT_LEN_BUCKETS
        self.len_buckets = tuple(sorted(
            {int(b) for b in len_buckets if 0 < int(b) < length}
            | {int(length)}))
        self.partitioner = partitioner
        if partitioner is not None:
            mesh = partitioner.mesh
        self.mesh = mesh
        self.seq_parallel = str(seq_parallel or "none").lower()
        if self.seq_parallel not in ("none", "ring", "ulysses"):
            raise ValueError(
                f"seq_parallel={seq_parallel!r}: expected none|ring|ulysses")
        self._batch_sharding = None
        self._part_axes = None
        self._sp_axis = None
        # trace-time seq-parallel engagement tally (_mesh_attention): did
        # the configured mode ever actually shard an attention block?
        self._sp_engaged = 0
        self._sp_fallback = 0
        self._sp_warned = False
        if self.seq_parallel != "none" and mesh is None:
            raise ValueError("seq_parallel needs a mesh")
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            if self.seq_parallel != "none":
                # L shards over the tensor-parallel axis (named mesh
                # "tp"; legacy 2-D mesh "model") — the batch must NOT
                # also split over it
                for a in ("tp", "model"):
                    if mesh.shape.get(a, 1) > 1:
                        self._sp_axis = a
                        break
                if self._sp_axis is None:
                    raise ValueError(
                        f"seq_parallel={self.seq_parallel!r} needs a "
                        f"tp/model mesh axis of size > 1; mesh axes are "
                        f"{dict(mesh.shape)}")
            # split the batch over EVERY non-sp axis the mesh has: the
            # data axis alone would idle the other devices on a
            # replicated-param elementwise path, and naming an axis the
            # mesh lacks (e.g. a data-only mesh) would raise
            part_axes = tuple(
                a for a in ("data", "fsdp", "tp", "model")
                if mesh.shape.get(a, 1) > 1 and a != self._sp_axis) \
                or tuple(a for a in mesh.axis_names
                         if a != self._sp_axis)[:1]
            dsize = 1
            for a in part_axes:
                dsize *= mesh.shape[a]
            batch_sizes = tuple(
                max(1, -(-b // dsize)) * dsize for b in batch_sizes
            )
            self._part_axes = part_axes
            self._batch_sharding = NamedSharding(
                mesh, PartitionSpec(part_axes, None, None))
        with trace.startup.phase("startup.weights") as ph:
            if mesh is not None:
                # param layout: the partitioner's (rule table under
                # `rules`, replicated under dp); legacy bare-mesh callers
                # replicate
                params = jax.device_put(params, self._param_layout(params))
            # whoever drew or loaded the tree may still be writing it: the
            # wait is the weights', not the first executable's first run
            leaves = jax.block_until_ready(jax.tree.leaves(params))
            ph.set(leaves=len(leaves),
                   bytes=sum(getattr(x, "nbytes", 0) for x in leaves))
        self.params = params
        self.batch_sizes = tuple(sorted(set(batch_sizes)))
        self._jax = jax
        from ccfd_tpu.models import registry as model_registry

        self._family_config = family_config
        self._family = (model_registry.get_history(family) if family
                        else model_registry.history_family_of(params))
        self._apply = self._make_apply(self._family)
        # per resolved dispatch: the family's counters from the program's
        # ``aux``, and the stats ``seq.wait`` carries
        self._observe = None
        if registry is not None and self._family.make_observer is not None:
            self._observe = self._family.make_observer(registry)
        # the deployment's tap on what the program returns beside the
        # probabilities: ``aux_tap(rows, m, aux)`` with ``rows`` the
        # dispatch's row numbers inside the router batch, per dispatch
        self.aux_tap: Any = None
        self._params_lock = threading.Lock()
        # challenger slot (lifecycle/): a second params tree + jit scored
        # off the hot path by the shadow tap's worker — how the seq_q8
        # variant earns its AUC/PSI verdict before it may serve
        self._challenger: tuple[int, Any, Any] | None = None
        # shadow tap + canary gate (lifecycle/): the router calls
        # score_with_ids on this OBJECT, so there is no score_fn lane to
        # wrap — when armed, each resolved chunk offers its (hist, proba)
        # pair to the tap, and an active canary gate re-scores its
        # deterministic challenger slice against the same assembled
        # contexts (the seq analog of tap-inside/gate-outside)
        self.shadow_tap: Any = None
        self.canary_gate: Any = None
        self._swap_gate: Any = None  # partitioner publish gate (set_swap_gate)
        self._g_customers = None
        self._h_assembly = self._h_dispatch = None
        self._c_bucket = self._c_bucket_rows = self._c_flat_wire = None
        self._c_kernels: dict = {}  # a kernel family's key -> its counter
        self._g_inflight = self._c_anon = self._c_stale = None
        self._c_overlapped = None
        self._c_swap_refused = None
        if registry is not None:
            self._c_swap_refused = registry.counter(
                "seq_swap_refused_total",
                "swap_params calls refused: the served family holds one "
                "tree at a time, or the tree is another family's program",
            )
            self._g_customers = registry.gauge(
                "seq_history_customers", "customers with live history"
            )
            self._h_assembly = registry.histogram(
                "seq_assembly_seconds",
                "host-side history assembly time per router batch "
                "(prepare + L/B bucketing + padding)",
            )
            self._h_dispatch = registry.histogram(
                "seq_dispatch_seconds",
                "device dispatch time per router batch: enqueue plus the "
                "blocking waits the overlap could not hide",
            )
            self._c_bucket = registry.counter(
                "seq_bucket_dispatch_total",
                "seq dispatches by (L bucket, B bucket) executable",
            )
            from ccfd_tpu.ops import kernels

            self._c_kernels = {
                family.key: registry.counter(family.counter, family.help)
                for family in kernels.FAMILIES}
            self._c_flat_wire = registry.counter(
                "seq_flat_wire_dispatch_total",
                "seq dispatches whose history batch crossed to the device "
                "in the device's own tile order, (B, L * F / 128, 128) "
                "(beside seq_bucket_dispatch_total: the rest crossed as "
                "(B, L, F) and were transposed by the runtime on the host)",
            )
            self._c_bucket_rows = registry.counter(
                "seq_bucket_rows_total",
                "rows scored per L bucket (short buckets = the cold-row "
                "fast lane actually firing)",
            )
            self._c_overlapped = registry.counter(
                "seq_overlapped_batches_total",
                "router batches staged and enqueued while an earlier "
                "batch's dispatch was unresolved (the in-flight window "
                "spanning two score calls, actually engaged)",
            )
            self._g_inflight = registry.gauge(
                "seq_inflight_dispatches",
                "async seq dispatches currently in flight, over every "
                "open batch",
            )
            self._c_anon = registry.counter(
                "seq_anonymous_rows_total",
                "anonymous rows scored cold (lock-free prepare fast path; "
                "never stored)",
            )
            self._c_stale = registry.counter(
                "seq_stale_commits_total",
                "commits dropped for stale generation (dispatch in flight "
                "across a crash restore — the no-op that keeps replay "
                "from double-appending)",
            )

    # -- variant dispatch ---------------------------------------------------
    def _mesh_attention(self):
        """What a mesh executable attends with. By default one chip's
        program (``models/seq.py::serving_attention``) on each device's
        rows: under ``shard_map`` over the batch axes, because a kernel is
        not partitioned for us, so each device decides from the shapes it
        holds. Where the operator selected a sequence-parallel attention
        (ring / ulysses over the sp axis) that wins wherever the static
        shapes shard: the readout block's single-query attention and any
        L bucket the axis doesn't divide (ulysses additionally: a head
        count it doesn't divide) take the default for that executable —
        decided at trace time, free at runtime. Engagement is TRACKED at
        trace time (``_sp_engaged``/``_sp_fallback``) so the executable
        inventory reports whether the configured mode ever actually
        sharded an attention block, and an all-fallback config warns
        loudly instead of silently serving unsharded under a
        ``seq_parallel`` label."""
        from jax import shard_map
        from jax.sharding import PartitionSpec

        from ccfd_tpu.models.seq import serving_attention

        mesh, axis = self.mesh, self._sp_axis
        rows = PartitionSpec(self._part_axes, None, None, None)
        # unchecked: rows are independent and nothing inside communicates;
        # the kernel's interpreter (off the TPU) does not pass the check
        local = shard_map(serving_attention, mesh=mesh, check_vma=False,
                          in_specs=(rows, rows, rows), out_specs=rows)
        if axis is None:
            return local
        n = int(mesh.shape[axis])
        if self.seq_parallel == "ring":
            from ccfd_tpu.ops.ring_attention import ring_attention as sp_fn
        else:
            from ccfd_tpu.ops.ulysses import ulysses_attention as sp_fn
        needs_heads = self.seq_parallel == "ulysses"

        def attn(q, k, v):
            shardable = (
                q.shape[2] == k.shape[2]      # not the readout query
                and q.shape[2] % n == 0       # L divides the axis
                and (not needs_heads or q.shape[1] % n == 0)
            )
            if not shardable:
                # trace-time accounting: this executable's block falls
                # back (the readout query always does — only warn when a
                # FULL-attention block can't shard, which means the
                # configured mode never engages for that shape)
                self._sp_fallback += 1
                if q.shape[2] == k.shape[2] and not self._sp_warned:
                    self._sp_warned = True
                    import logging

                    logging.getLogger(__name__).warning(
                        "seq_parallel=%s cannot shard a (heads=%d, L=%d)"
                        " attention over the %d-way %r axis; that "
                        "executable serves unsharded attention",
                        self.seq_parallel, q.shape[1], q.shape[2], n,
                        axis)
                return local(q, k, v)
            self._sp_engaged += 1
            return sp_fn(q, k, v, mesh, axis)

        return attn

    def _make_apply(self, family: Any):
        """The family's device program, from its registered spec:
        ``fn(params, hist)`` or, where the family reads the padding,
        ``fn(params, hist, filled)``; on one device also the same program
        behind the flat wire (``_Program``)."""
        import jax

        dtype = self._dtype
        # positional encodings anchor at the store's FULL length: a short
        # L-bucket window's tokens keep the positions the full-L path
        # gives them, so a customer's score doesn't jump at ladder
        # crossovers (models/seq.py logits_readout pos_length)
        plen = self.store.length
        program = partial(_Program, reads_filled=family.reads_filled,
                          num_features=self.store.num_features)
        if self.mesh is None:
            return program(family.make_apply(dtype, plen,
                                             self._family_config), flat=True)
        if family.mesh_logits is None:
            raise ValueError(
                f"history family {family.name!r} is not served over a mesh")
        from jax.sharding import NamedSharding, PartitionSpec

        fn = family.mesh_logits
        attn = self._mesh_attention()
        return program(jax.jit(
            lambda p, xs: jax.nn.sigmoid(
                fn(p, xs, dtype, attention_fn=attn, pos_length=plen)),
            out_shardings=NamedSharding(self.mesh,
                                        PartitionSpec(self._part_axes)),
        ))

    def _put_hist(self, hist: np.ndarray):
        """H2D with placement: on a mesh each device gets its row shard.
        Shares the staging fault seam with the row scorer's _put_batch
        (runtime/faults.py put_fail): an injected staging failure rides
        the same exception path a real transfer failure would."""
        try:
            device_seam("put")
        except Exception:
            if self.telemetry is not None:
                self.telemetry.record_h2d_failure()
            raise
        if self._batch_sharding is None:
            return hist
        return self._jax.device_put(hist, self._batch_sharding)

    def set_swap_gate(self, gate: Any) -> None:
        """Arm the partitioner's publish gate (parallel/partition.py):
        every ``swap_params`` then pauses the router pool at a batch
        boundary first — same contract as the row Scorer's."""
        self._swap_gate = gate

    def _param_layout(self, params: Any) -> Any:
        """Sharding pytree for the seq params on the mesh: the
        partitioner's layout when one is armed (the rule table under
        ``param_partition: rules``, replicated under data parallel);
        a tree the rule table does not cover — the promoted int8
        ``seq_q8`` variant has its own leaf names — replicates with a
        LOUD warning rather than crashing the promotion swap (the int8
        tree is 4x smaller, so replication is the sane fallback)."""
        from ccfd_tpu.parallel.sharding import replicated

        if self.partitioner is None:
            return replicated(self.mesh)
        try:
            return self.partitioner.param_sharding(params)
        except ValueError as e:
            import logging

            logging.getLogger(__name__).warning(
                "seq param layout: rule table does not cover this tree "
                "(%s); replicating instead", e)
            return replicated(self.mesh)

    def swap_params(self, params: Any) -> None:
        """Hot-swap model weights (the lifecycle promotion surface; the
        row scorer exposes the same). A variant change — bf16 champion
        replaced by a promoted int8 ``seq_q8`` tree, or back — re-binds
        the jitted apply; same-variant swaps reuse the jit cache (same
        treedef, same executable). All staging (mesh re-layout, variant
        grid precompile) happens BEFORE the publish gate: with a gate
        armed the router pool quiesces only for the reference flip."""
        staged, family, new_apply = self._stage_swap(params)
        gate = getattr(self, "_swap_gate", None)
        if gate is None:
            self._commit_swap(staged, family, new_apply)
            return
        with gate:
            self._commit_swap(staged, family, new_apply)

    def _stage_swap(self, params: Any) -> tuple:
        from ccfd_tpu.models.registry import history_family_of

        family = history_family_of(params)
        if not (self._family.swappable and family.swappable):
            # a family that fills the device holds one tree: staging a
            # second beside it would run the device out of memory
            if self._c_swap_refused is not None:
                self._c_swap_refused.inc()
            held = family if self._family.swappable else self._family
            raise ValueError(
                f"swap_params refused: history family {held.name!r} holds "
                "one parameter tree at a time (restart to change it)")
        if self.mesh is not None:
            params = self._jax.device_put(params,
                                          self._param_layout(params))
        new_apply = None
        if family is not self._family:
            # variant change (e.g. a promoted seq_q8): compile the whole
            # (B, L) executable grid BEFORE publishing — scoring keeps the
            # old graph meanwhile, so the hot path never pays an XLA
            # compile (which could outlive the dispatch watchdog deadline
            # and roll back the candidate that was just promoted)
            from ccfd_tpu.observability.profile import compile_stage

            new_apply = self._make_apply(family)
            with compile_stage("seq.swap"):
                self._run_grid(new_apply, params, family)
        return params, family, new_apply

    def _commit_swap(self, params: Any, family: Any, new_apply: Any) -> None:
        with self._params_lock:
            self.params = params
            if new_apply is not None:
                self._family = family
                self._apply = new_apply

    def _run_grid(self, apply_fn: Any, params: Any, family: Any) -> None:
        """Every (B bucket, L bucket) executable once, on zeros: a
        ``startup.executable`` phase each, which carries at close what
        JAX traced, lowered, compiled or loaded for it
        (``observability/profile.py::billed``); what is left of the phase
        is the first run and its wait."""
        from ccfd_tpu.observability.profile import billed

        for b in self.batch_sizes:
            for lb in self.len_buckets:
                with billed("startup.executable", l_bucket=int(lb),
                            b_bucket=int(b),
                            flat_wire=int(_takes_flat_wire(apply_fn, lb)),
                            **self._scan_chunk(lb)):
                    xs = np.zeros((b, lb, self.store.num_features),
                                  np.float32)
                    extra = ((np.zeros((b,), np.int32),)
                             if family.reads_filled else ())
                    self._jax.block_until_ready(
                        apply_fn(params, self._put_hist(xs), *extra))

    def warmup(self) -> None:
        """Compile every (B bucket, L bucket) executable the ladder can
        dispatch — the re-trace-stable static shape set."""
        from ccfd_tpu.observability.profile import compile_stage

        with compile_stage("seq.warmup"):
            self._run_grid(self._apply, self.params, self._family)

    def executable_grid(self) -> dict:
        """The (L, B) executable grid with per-executable dispatch counts,
        which kernel families the executable holds (a key each:
        ``ops/kernels.py``), the chunk of the state-space scan where the
        model has one, and whether its history batch crosses flat — the
        seq family's entry in the device telemetry inventory."""
        with self._params_lock:
            params, apply_fn = self.params, self._apply
        grid = []
        for lb in self.len_buckets:
            for b in self.batch_sizes:
                held = _kernels_held(apply_fn, params, lb, b)
                entry: dict = {
                    "l_bucket": int(lb), "b_bucket": int(b),
                    **{key: bool(on) for key, on in held.items()},
                    "flat_wire": _takes_flat_wire(apply_fn, lb),
                    **self._scan_chunk(lb)}
                if self._c_bucket is not None:
                    entry["dispatches"] = int(self._c_bucket.value(
                        {"l_bucket": str(lb), "b_bucket": str(b)}))
                grid.append(entry)
        out = {
            "model": self._family.name,
            "length": int(self.store.length),
            "grid": grid,
        }
        if self._family.describe is not None:
            out.update(self._family.describe(self._family_config))
        if self.mesh is not None:
            out["mesh_devices"] = int(self.mesh.size)
            out["seq_parallel"] = self.seq_parallel
            if self.seq_parallel != "none":
                # truthful telemetry: configured is not engaged — an
                # operator debugging a missing sp speedup reads whether
                # any traced executable actually sharded its attention
                out["seq_parallel_engaged"] = self._sp_engaged > 0
        return out

    def _scan_chunk(self, lb: int) -> dict:
        """``{"scan_chunk": tokens a step}`` of the executables of
        ``lb``-record windows, where the served model has a state-space
        scan (``HistorySpec.scan_chunk``); else nothing."""
        of = self._family.scan_chunk
        chunk = None if of is None else of(
            self._family_config, lb * self.store.num_features)
        return {} if chunk is None else {"scan_chunk": int(chunk)}

    def _bucket(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    def _len_bucket_index(self, filled: np.ndarray) -> np.ndarray:
        """Per-row ladder index: smallest L bucket covering the row's
        post-append history depth."""
        return np.searchsorted(np.asarray(self.len_buckets), filled,
                               side="left")

    def _take_staging(self) -> tuple[StagingBatch, int]:
        """A staging batch and whether it came from the free list."""
        try:
            return self._staging.pop(), 1
        except IndexError:
            return StagingBatch(self.batch_sizes[-1], self.store.length,
                                self.store.num_features), 0

    # -- the overlapped scoring loop ---------------------------------------
    def score(self, x: np.ndarray, ids: list | None = None) -> np.ndarray:
        """Router-compatible scorer: (B, F) rows -> (B,) probabilities,
        each conditioned on that customer's history. Rows with no id
        (``ids`` absent or None entries) score against an empty history
        and are not tracked.

        ONE commit for the whole router batch, after EVERY dispatch
        resolved: a mid-batch failure drops the batch at the router (or
        the PR 6 dispatch watchdog kills it), and a half-committed
        history would diverge from the routed stream. The overlay keeps
        same-customer visibility across chunks; the generation token
        makes a commit that raced a crash restore a no-op (the rewind
        re-drives those records).

        Resolved inside the call: whatever ``score_with_ids`` left open
        for a deferring caller is resolved and committed first, in order,
        so this batch reads their rows from the store.

        Every stretch of the call is a :class:`phase` (``seq.gather``,
        ``seq.pad``, ``seq.enqueue``, ``seq.wait``, ``seq.commit`` inside
        ``seq.score``; inside ``seq.wait`` of a family that hands back
        ``aux``, after the program's end, ``seq.fetch`` around the copy of
        its leaves to the host and ``seq.tap`` around the observer and
        ``aux_tap``), so a device capture shows what the host did beside
        what the device did; ``seq.score`` and what resolves a batch
        (``seq.wait``, ``seq.fetch``, ``seq.tap``, ``seq.commit``) carry
        the batch's ordinal ``seq_batch``, so a wait inside the next
        batch's call says whose it is; ``seq_assembly_seconds`` is the
        batch's gather + pad, ``seq_dispatch_seconds`` its enqueue + wait,
        from the same clock reads."""
        n = len(x)
        if n == 0:
            return np.zeros((0,), np.float32)
        seq = next(self._batch_ids)
        with phase("seq.score", rows=n, open_batches=0, overlapped=0,
                   seq_batch=seq):
            return self._score(x, ids, seq)

    def _score(self, x: np.ndarray, ids: list | None,
               seq: int) -> np.ndarray:
        if self._open:
            with self._lock:
                while self._open:
                    self._settle_oldest()
        # shadow/canary lane: when a challenger is armed (tap) or a
        # canary slice is live (gate), keep each chunk's assembled
        # (full-L) history batch so the challenger scores the SAME
        # contexts the champion just did (one flag read when idle)
        tap, gate = self._armed()
        batch = _Batch(x, [None] * len(x) if ids is None else ids, seq)
        self._stage(batch, (), keep_hist=tap is not None or gate is not None)
        self._settle(batch)
        out = batch.out
        if tap is not None:
            # the tap pairs PURE champion scores (offered before any
            # canary override, like the row lane's tap-inside/gate-outside
            # composition)
            for hist, s0, s1 in batch.kept:
                tap.offer(hist, out[s0:s1])
        if gate is not None and batch.kept:
            # canary slice: the challenger arm re-scores against the SAME
            # assembled contexts (bounded by the gate's weight; a
            # challenger failure keeps champion scores and counts)
            def rescore(mask: np.ndarray) -> np.ndarray:
                parts = [h[mask[s0:s1]] for h, s0, s1 in batch.kept]
                sel = parts[0] if len(parts) == 1 else np.concatenate(parts)
                return self.challenger_score(sel)

            out = gate.apply(np.ascontiguousarray(x, np.float32), out,
                             rescore=rescore)
        return out

    def _armed(self) -> tuple:
        """The shadow tap and the canary gate, each None unless live."""
        tap = self.shadow_tap
        if tap is not None and tap.armed_version is None:
            tap = None
        gate = self.canary_gate
        if gate is not None and not gate.active:
            gate = None
        return tap, gate

    def _score_deferred(self, x: np.ndarray, ids: list) -> "DeferredScores":
        """``score`` for a caller that takes the result before it is
        ready: this batch is gathered, padded and enqueued FIRST, beside
        whatever the device still computes of the batch before it; then
        that batch is resolved and committed and its scores are marked
        ready; this one stays open, for the next call or for whoever
        forces its scores (``_force``). One caller at a time: the lock is
        held for the whole call."""
        seq = next(self._batch_ids)
        with phase("seq.score", rows=len(x), seq_batch=seq) as ph, \
                self._lock:
            older = tuple(self._open)
            overlapped = any(b.pending for b in older)
            ph.set(open_batches=len(older), overlapped=int(overlapped))
            batch = _Batch(x, ids, seq)
            batch.scores = DeferredScores(self, batch)
            self._open.append(batch)
            try:
                self._stage(batch, older)
            except BaseException:
                self._open.remove(batch)
                raise
            if overlapped and self._c_overlapped is not None:
                self._c_overlapped.inc()
            while self._open and self._open[0] is not batch:
                self._settle_oldest()
            return batch.scores

    def _force(self, batch: "_Batch") -> None:
        """Make an open batch's scores ready now, on the calling thread:
        every older open batch first, in order."""
        with self._lock:
            while batch.scores.ready_at is None:
                self._settle_oldest()

    def _settle_oldest(self) -> None:
        """Resolve and commit the oldest open batch and mark its scores
        ready (under the lock). If it cannot be resolved it is dropped,
        as a failed call's batch always was, and every later open batch,
        staged on rows that will now never be committed, is staged and
        dispatched again from the store and the open batches that are
        left: its verdicts are what they would have been had the dropped
        batch never arrived."""
        batch = self._open[0]
        try:
            if batch.error is not None:
                raise batch.error
            self._settle(batch)
        # ccfd-lint: disable=counted-drops -- not swallowed: raised to whoever forces the batch's scores (the router counts it in router_score_errors_total)
        except Exception as e:  # noqa: BLE001
            self._open.popleft()
            batch.scores.ready(e)
            later = tuple(self._open)
            self._open.clear()
            for b in later:
                b.reset()
                older = tuple(self._open)
                self._open.append(b)
                try:
                    self._stage(b, older)
                # ccfd-lint: disable=counted-drops -- as above
                except Exception as e2:  # noqa: BLE001
                    self._open.pop()
                    b.scores.ready(e2)
        else:
            self._open.popleft()
            batch.scores.ready(batch.out)

    def _stage(self, batch: "_Batch", older: tuple,
               keep_hist: bool = False) -> None:
        """Gather, pad and enqueue every dispatch of ``batch``. ``older``:
        the open batches before it, oldest first; their staged rows are
        this batch's context beside the store's, and their dispatches
        count toward the in-flight window."""
        x, ids = batch.x, batch.ids
        n = len(x)
        largest = self.batch_sizes[-1]
        L = self.store.length
        ladder = self.len_buckets
        merged = batch.merged
        taken = batch.taken
        pending = batch.pending
        context: dict = {}  # the older open batches' staged entries
        if older:
            # a batch staged before a restore is doomed (its commit will
            # be the stale no-op): its rows are no context for this one,
            # and this one's commit stands on the generation read here
            batch.gen = self.store.generation
            for b in older:
                if b.gen == batch.gen and b.error is None:
                    context.update(b.merged)
        start = 0
        while start < n:
            stop = min(start + largest, n)
            with phase("seq.gather", rows=stop - start) as ph:
                chunk_ids = ids[start:stop]
                # a batch the tap or the gate keeps past this call is the
                # chunk's own (prepare allocates it); so is one past what
                # can be in flight
                stage, recycled = None, 0
                if not keep_hist and len(taken) <= self.inflight:
                    stage, recycled = self._take_staging()
                    taken.append(stage)
                hist, (chunk_gen, staged, filled) = self.store.prepare(
                    chunk_ids, x[start:stop], out=stage,
                    overlay=({**context, **merged} if context and merged
                             else context or merged)
                )
                # the FIRST chunk's generation stamps the whole batch: a
                # restore landing between chunk prepares bumps the store's
                # generation, and committing with a later chunk's (fresh)
                # gen would publish the earlier chunks' pre-restore staging
                # onto the restored state — the first gen is stale then, so
                # the commit is the no-op replay correctness requires
                if batch.gen is None:
                    batch.gen = chunk_gen
                # recency = LAST occurrence: a key re-staged by a later
                # chunk moves to the end of merged, so commit stamps (and
                # therefore LRU eviction under a binding cap) follow stream
                # order, not first-touch order — replay with different
                # batch boundaries must rebuild the same survivor set
                for k in staged:
                    if k in merged:
                        del merged[k]
                merged.update(staged)
                anon = chunk_ids.count(None)
                batch.n_anon += anon
                li = self._len_bucket_index(filled)
                if keep_hist:
                    batch.kept.append((hist, start, stop))
                # a row at depth 1 is anonymous or its customer's first;
                # rows beyond one per staged key repeat a key of the chunk;
                # every row's context is its depth less the row itself
                ph.set(new_customers=int(np.count_nonzero(filled == 1))
                       - anon,
                       repeated_keys=stop - start - anon - len(staged),
                       gathered_bytes=(int(filled.sum()) - (stop - start))
                       * hist.shape[2] * hist.itemsize,
                       recycled=recycled)
            batch.t_asm += ph.seconds
            for bi in np.unique(li):
                lb = ladder[bi]
                idx = np.nonzero(li == bi)[0]
                # greedy B decomposition: a group between bucket sizes
                # dispatches as exact-fit sub-batches (1229 -> 1024 + 128
                # + 128-padded-77) instead of one bucket padded to 3x the
                # rows — padding is wasted device compute, and with async
                # dispatch the extra launches pipeline instead of queuing
                pos = 0
                m_total = len(idx)
                # every row of the chunk at full length (always, with the
                # ladder off): the group is the chunk in order, and its
                # sub-batches are views of it, not copies
                whole = lb == L and m_total == len(hist)
                while pos < m_total:
                    with phase("seq.pad", l_bucket=lb) as ph:
                        rem = m_total - pos
                        bucket = None
                        for b in reversed(self.batch_sizes):
                            if b <= rem:
                                bucket = b
                                break
                        if bucket is None:
                            bucket = self.batch_sizes[0]
                        m = min(rem, bucket)
                        sub_idx = idx[pos:pos + m]
                        if not whole:  # right-aligned window
                            sub = hist[sub_idx, L - lb:, :]
                        elif stage is None:
                            sub = hist[pos:pos + m]
                        else:
                            # a staging batch is zero past the chunk's
                            # rows: its padding is already there
                            sub = stage.hist[pos:pos + bucket]
                        pos += m
                        if len(sub) < bucket:
                            sub = np.concatenate(
                                [sub, np.zeros((bucket - len(sub),
                                                *sub.shape[1:]), np.float32)]
                            )
                        # real records per row of the dispatch (a window
                        # shorter than L holds at most its own length; a
                        # padding row holds none): goes to the device with
                        # the batch where the family masks its padding
                        sub_filled = np.zeros((bucket,), np.int32)
                        np.minimum(filled[sub_idx], lb, out=sub_filled[:m])
                        tokens = int(sub_filled.sum()) * sub.shape[2]
                        with self._params_lock:
                            params, apply_fn = self.params, self._apply
                            extra = ((sub_filled,)
                                     if self._family.reads_filled else ())
                        ph.set(rows=m, b_bucket=bucket,
                               padded_rows=bucket - m)
                    batch.t_asm += ph.seconds
                    held = _kernels_held(apply_fn, params, lb, bucket)
                    flat_wire = _takes_flat_wire(apply_fn, lb)
                    with phase("seq.enqueue", bytes=sub.nbytes,
                               b_bucket=bucket, l_bucket=lb,
                               tokens=tokens, **held,
                               flat_wire=int(flat_wire),
                               **self._scan_chunk(lb)) as ph:
                        # device-fault dispatch seam (runtime/faults.py):
                        # device_hang / compile_stall drill the heal ladder
                        # through the seq path's own dispatch loop
                        device_seam("dispatch")
                        # JAX async dispatch: the call ENQUEUES the
                        # executable and returns; the next group assembles
                        # while it runs.
                        dev = apply_fn(params, self._put_hist(sub), *extra)
                    batch.t_disp += ph.seconds
                    if self.telemetry is not None:
                        self.telemetry.record_h2d(sub.nbytes)
                    pending.append((dev, sub_idx + start, m, tokens))
                    if self._c_bucket is not None:
                        self._c_bucket.inc(labels={
                            "l_bucket": str(lb), "b_bucket": str(bucket)})
                        for key, counter in self._c_kernels.items():
                            if held[key]:
                                counter.inc()
                        if flat_wire:
                            self._c_flat_wire.inc()
                        self._c_bucket_rows.inc(
                            m, labels={"l_bucket": str(lb)})
                    self._bound_window(batch, older)
            start = stop

    def _bound_window(self, batch: "_Batch", older: tuple) -> None:
        """Block on the oldest dispatches until at most ``inflight`` are
        unresolved: the older open batches' first, then this batch's own.
        An older batch that fails here is settled, and dropped, where its
        turn comes (``_settle_oldest``); this batch's own failure is the
        call's."""
        queues = [b for b in (*older, batch) if b.pending]
        over = sum(len(b.pending) for b in queues) - self.inflight
        for b in queues:
            while over > 0 and b.pending:
                over -= 1
                try:
                    self._resolve(b)
                except Exception as e:  # noqa: BLE001 - see above
                    if b is batch:
                        raise
                    over -= len(b.pending)
                    b.pending.clear()
                    b.error = e
        if self._g_inflight is not None:
            self._g_inflight.set(float(
                sum(len(b.pending) for b in queues)))

    def _settle(self, batch: "_Batch") -> None:
        """Every dispatch of ``batch`` resolved, then its one commit
        (which moves the open batches staged on its rows on to what it
        wrote), then its staging batches back on the free list."""
        while batch.pending:
            self._resolve(batch)
        if self._g_inflight is not None:
            self._g_inflight.set(float(
                sum(len(b.pending) for b in self._open)))
        if batch.gen is not None:
            with phase("seq.commit", customers=len(batch.merged),
                       seq_batch=batch.seq) as ph:
                committed = self.store.commit((batch.gen, batch.merged))
                ph.set(stale=int(not committed))
            if not committed and self._c_stale is not None:
                self._c_stale.inc()
        # every dispatch resolved and the staged views are spent: only now
        # may another call fill these batches (the runtime reads a host
        # buffer on its own thread after apply_fn returned; a batch that
        # failed keeps its own out of the list for that reason)
        self._staging.extend(batch.taken)
        if self._g_customers is not None:
            self._g_customers.set(float(len(self.store)))
        if self._h_assembly is not None:
            self._h_assembly.observe(batch.t_asm)
            self._h_dispatch.observe(batch.t_disp)
        if batch.n_anon and self._c_anon is not None:
            self._c_anon.inc(batch.n_anon)

    def _resolve(self, batch: "_Batch") -> None:
        """Block on the batch's oldest in-flight dispatch and scatter its
        rows; the blocking wait (the dispatch time overlap failed to
        hide) goes to the batch's dispatch time."""
        dev, idx, m, tokens = batch.pending.popleft()
        seq = batch.seq
        with phase("seq.wait", rows=m, tokens=tokens, seq_batch=seq) as ph:
            if isinstance(dev, tuple):  # (proba, aux): the family's counts
                # the program's end as the host sees it: what no child
                # phase covers of seq.wait is the wait for the program
                proba = np.asarray(dev[0])
                with phase("seq.fetch", leaves=len(dev[1]),
                           seq_batch=seq) as fetch:
                    aux = {k: np.asarray(v) for k, v in dev[1].items()}
                    fetch.set(bytes=sum(v.nbytes for v in aux.values()))
                with phase("seq.tap", rows=m, seq_batch=seq):
                    if self._observe is not None:
                        ph.set(**self._observe(aux))
                    if self.aux_tap is not None:
                        self.aux_tap(idx, m, aux)
            else:
                proba = np.asarray(dev)
        batch.out[idx] = proba[:m]
        batch.t_disp += ph.seconds

    # Router contract: passing the SeqScorer OBJECT as the router's
    # score_fn makes it callable for the plain (x,) path, and the router
    # detects score_with_ids and feeds decoded records alongside x
    def __call__(self, x: np.ndarray) -> np.ndarray:
        return self.score(x)

    def score_with_ids(self, txs: list, x: np.ndarray):
        """Batch entry for the router: ids come from each record's
        ``customer_id``/``id`` field; records with neither are anonymous
        (scored cold, not tracked). When the shadow tap is armed,
        ``score`` offers each chunk's assembled history batch alongside
        the champion's probabilities — the challenger shadow-scores the
        SAME contexts.

        Records that say their caller takes a result before it is ready
        (``takes_deferred``, the pipelined router's mark on the list it
        hands over) get :class:`DeferredScores` and leave the batch open
        across the call, unless something has to see the batch resolved
        inside it: an ``aux_tap``, an armed shadow tap, a live canary
        gate, or a window of no open dispatch (``inflight`` 0). Everyone
        else gets host memory."""
        ids: list = []
        for t in txs:
            key = None
            if isinstance(t, dict):
                key = t.get("customer_id")
                if key is None:
                    key = t.get("id")
            ids.append(key)
        if (ids and getattr(txs, "takes_deferred", False)
                and self.inflight > 0 and self.aux_tap is None
                and self._armed() == (None, None)):
            return self._score_deferred(x, ids)
        return self.score(x, ids)

    # -- challenger slot (model lifecycle: shadow scoring of seq_q8) --------
    def install_challenger(self, version: int, params: Any) -> None:
        """Stage a challenger (typically the int8 ``seq_q8`` tree) beside
        the champion. Challenger forwards run on the shadow tap's worker
        thread against cold contexts or tapped batches — sample-bounded
        by the tap's token bucket, so the hot path never waits on it."""
        fn = self._make_challenger_apply(params)
        with self._params_lock:
            self._challenger = (int(version), params, fn)

    def _make_challenger_apply(self, params: Any):
        from ccfd_tpu.models.registry import history_family_of

        family = history_family_of(params)
        if family.reads_filled != self._family.reads_filled:
            raise ValueError(
                f"challenger of family {family.name!r} beside a "
                f"{self._family.name!r} champion: the two read different "
                "batches")
        return family.make_apply(self._dtype, self.store.length,
                                 self._family_config)

    def clear_challenger(self, version: int | None = None) -> None:
        with self._params_lock:
            if (self._challenger is not None
                    and (version is None
                         or self._challenger[0] == int(version))):
                self._challenger = None

    @property
    def challenger_version(self) -> int | None:
        ch = self._challenger
        return None if ch is None else ch[0]

    def challenger_score(self, x: np.ndarray) -> np.ndarray:
        """(n, F) rows (scored against a COLD context — the evaluator's
        label joins carry no history) or (n, L', F) histories (tapped
        batches) -> (n,) proba on the challenger params."""
        ch = self._challenger
        if ch is None:
            raise RuntimeError("no challenger installed")
        _, params, fn = ch
        return self._score_direct(np.asarray(x, np.float32), params, fn,
                                  put=lambda h: h)

    def host_score(self, x: np.ndarray) -> np.ndarray:
        """Champion cold-context scoring for (n, F) rows — the paired
        half of the evaluator's label join (same rows, same cold
        context, champion vs challenger)."""
        with self._params_lock:
            params, fn = self.params, self._apply
        return self._score_direct(np.asarray(x, np.float32), params, fn,
                                  put=self._put_hist)

    def _score_direct(self, x: np.ndarray, params: Any, fn, put) -> np.ndarray:
        if x.ndim == 2:
            lb = self.len_buckets[0]
            h = np.zeros((len(x), lb, self.store.num_features), np.float32)
            h[:, -1] = x
            x = h
        n = len(x)
        out = np.empty((n,), np.float32)
        largest = self.batch_sizes[-1]
        start = 0
        while start < n:
            stop = min(start + largest, n)
            m = stop - start
            sub = x[start:stop]
            bucket = self._bucket(m)
            if m < bucket:
                sub = np.concatenate(
                    [sub, np.zeros((bucket - m, *sub.shape[1:]), np.float32)]
                )
            extra = ()
            if self._family.reads_filled:
                # a right-aligned window's depth: from its first record
                # that is not all zeros
                live = np.abs(sub).sum(-1) > 0
                extra = (np.where(live.any(1), sub.shape[1] - live.argmax(1),
                                  0).astype(np.int32),)
            res = fn(params, put(np.ascontiguousarray(sub)), *extra)
            proba = np.asarray(res[0] if isinstance(res, tuple) else res)
            out[start:stop] = proba[:m]
            start = stop
        return out
