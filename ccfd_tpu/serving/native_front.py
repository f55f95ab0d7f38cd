"""Python half of the native HTTP serving front (native/httpfront.cpp).

The C++ side owns sockets, HTTP parsing, auth, canonical-payload decode,
and response formatting; this module runs the only parts that need
Python — scoring and the rare non-canonical routes:

- N scorer threads: ``ccfd_front_take`` hands over MANY requests as ONE
  concatenated float32 row block (the C++ queue IS the dynamic batcher);
  one ``scorer.score`` per block; ``ccfd_front_respond`` fans results
  back out per request. N > 1 overlaps device round trips exactly like
  DynamicBatcher's workers.
- one misc thread: GET /prometheus, health, and payloads the native
  decoder bailed on (names remapping, ragged rows, bad JSON) flow
  through the SAME ``PredictionServer._http_handler`` routing as the
  pure-Python server — identical contract, different fast path.

Metrics parity with serving/server.py: per-request latency lands in the
seldon histogram using the C++ enqueue timestamp (CLOCK_MONOTONIC, the
same clock as time.monotonic), request counters by code, and the
ModelPrediction gauges from the last scored row. C++-side 401s are
reconciled into the counter at scrape time.
"""

from __future__ import annotations

import ctypes
import json
import logging
import threading
import time

import numpy as np

from ccfd_tpu.native import _load
from ccfd_tpu.serving.dispatch import ScorerTimeout


def extract_dense_model(spec_name: str, params) -> tuple | None:
    """Flatten a scorer's host params into the C++ front's dense layout.

    Returns ``(dims, weights, biases, mean, inv_std)`` — weights per layer
    TRANSPOSED to (out x in) row-major and concatenated, so each output
    neuron's weights are contiguous for the C++ inner loop — or None when
    the model has no dense form (e.g. trees), in which case the front
    keeps routing predict requests to the Python takers.
    """
    try:
        if spec_name == "mlp":
            layers = params["layers"]
            dims = [int(np.asarray(layers[0]["w"]).shape[0])] + [
                int(np.asarray(layer["w"]).shape[1]) for layer in layers
            ]
            weights = np.concatenate(
                [np.asarray(layer["w"], np.float32).T.ravel() for layer in layers]
            )
            biases = np.concatenate(
                [np.asarray(layer["b"], np.float32).ravel() for layer in layers]
            )
            mean = np.asarray(params["norm"]["mu"], np.float32)
            sigma = np.asarray(params["norm"]["sigma"], np.float32)
            inv_std = np.where(sigma == 0.0, 1.0, 1.0 / sigma).astype(np.float32)
            return dims, weights, biases, mean, inv_std
        if spec_name in ("logreg", "modelfull"):
            w = np.asarray(params["w"], np.float32).reshape(-1)
            b = np.asarray(params["b"], np.float32).reshape(-1)[:1]
            # standardizer already folded into (w, b) by from_sklearn/fit
            return [int(w.shape[0]), 1], w.copy(), b.copy(), None, None
    except (KeyError, TypeError, IndexError, ValueError):
        return None
    return None


def extract_q8_model(params) -> tuple | None:
    """Flatten int8-quantized MLP params (ops/quant.py layout) into the
    C++ front's q8 layout: weights are the int8 VALUES widened to f32
    (the front's f32 SIMD dot of <=2^24-magnitude integers IS the int32
    accumulate), transposed (out x in) row-major and concatenated;
    scales/biases per-output concatenated; mu/sigma RAW (the front
    divides by sigma for bit parity with apply_numpy)."""
    try:
        layers = params["layers"]
        if "wq" not in layers[0]:
            return None
        dims = [int(np.asarray(layers[0]["wq"]).shape[0])] + [
            int(np.asarray(layer["wq"]).shape[1]) for layer in layers
        ]
        weights = np.concatenate(
            [np.asarray(layer["wq"], np.float32).T.ravel() for layer in layers]
        )
        scales = np.concatenate(
            [np.asarray(layer["scale"], np.float32).ravel() for layer in layers]
        )
        biases = np.concatenate(
            [np.asarray(layer["b"], np.float32).ravel() for layer in layers]
        )
        mean = np.asarray(params["norm"]["mu"], np.float32)
        sigma = np.asarray(params["norm"]["sigma"], np.float32)
        return dims, weights, scales, biases, mean, sigma
    except (KeyError, TypeError, IndexError, ValueError):
        return None


def extract_tree_model(params) -> tuple | None:
    """Flatten a tree-ensemble param tree (models/trees.py dense embedding)
    into the C++ front's layout: ``(n_trees, depth, feat, thr, leaf, base)``
    with feat/thr/leaf as flat contiguous arrays in heap order."""
    from ccfd_tpu.models import trees

    try:
        feat = np.ascontiguousarray(params["feature"], np.int32)
        thr = np.ascontiguousarray(params["threshold"], np.float32)
        leaf = np.ascontiguousarray(params["leaf"], np.float32)
        n_trees = int(leaf.shape[0])
        depth = trees.depth_of(params)
        if feat.shape != (n_trees, trees.num_internal(depth)) or \
                thr.shape != feat.shape:
            return None
        return n_trees, depth, feat, thr, leaf, float(params["base"])
    except (KeyError, TypeError, IndexError, ValueError):
        return None


class NativeFront:
    # In-IO-thread scoring cap, SEPARATE from the scorer's host-tier
    # threshold: the epoll thread serializes all connections, so an inline
    # score must stay well under a millisecond (~512 rows at ~1.4 us/row)
    # or one big request head-of-line blocks every other client. Requests
    # between this cap and host_tier_rows still avoid the device — they
    # flow to the Python takers where scorer.score applies the numpy host
    # tier on a worker thread.
    INLINE_MAX_ROWS = 512

    def __init__(
        self,
        server,  # PredictionServer (duck-typed: scorer, cfg, registry, ...)
        max_batch_rows: int = 16384,
        max_reqs_per_take: int = 1024,
    ):
        self._server = server
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native toolchain unavailable")
        self._handle = None
        self._threads: list[threading.Thread] = []
        self._stopping = threading.Event()
        self._max_rows = max_batch_rows
        self._max_reqs = max_reqs_per_take
        self._auth_fail_synced = 0
        self.server_address = ("0.0.0.0", 0)
        # host-model scrape-fold state (see _sync_native_counters)
        self._n_buckets = 0
        self._host_synced_counts: np.ndarray | None = None
        self._host_synced_sums = np.zeros(2, np.float64)
        self._host_synced_n = 0
        self._gauge_synced_ms = 0.0
        self._swap_listener = None
        # serializes host-model pushes (swap_params listener thread) against
        # stop(): a push in flight must complete before the handle is torn
        # down, or ctypes hands C++ a null/freed Front*
        self._push_lock = threading.Lock()
        self.host_model_active = False
        # computed once at install (re-parsing the env per swap-push would
        # spam the malformed-value warning at swap frequency)
        self._inline_cap_cached: int | None = None

    # -- lifecycle ---------------------------------------------------------
    def start(self, port: int = 0, host: str = "0.0.0.0") -> int:
        srv = self._server
        port_out = ctypes.c_int(0)
        handle = self._lib.ccfd_front_create(
            (host or "0.0.0.0").encode(),
            int(port),
            srv.scorer.num_features,
            (srv.cfg.seldon_token or "").encode(),
            ctypes.byref(port_out),
        )
        if not handle:
            raise OSError(f"native front failed to bind {host}:{port}")
        self._handle = handle
        self.server_address = (host or "0.0.0.0", int(port_out.value))
        workers = max(1, getattr(srv.cfg, "batch_workers", 2))
        for i in range(workers):
            t = threading.Thread(
                target=self._score_loop, daemon=True, name=f"ccfd-front-score-{i}"
            )
            t.start()
            self._threads.append(t)
        t = threading.Thread(
            target=self._misc_loop, daemon=True, name="ccfd-front-misc"
        )
        t.start()
        self._threads.append(t)
        self._install_host_model()
        return int(port_out.value)

    # -- in-front host-tier model ------------------------------------------
    def _inline_rows_cap(self) -> int:
        """Row cap for in-IO-thread scoring. An explicit host tier
        (``host_tier_rows`` > 0) governs where it is armed. Where it is
        off — the default — an accelerator gets no inline scoring (every
        request reaches the device), while on the CPU backend, where the
        "device" is the same silicon, the C++ SIMD forward beats a jax
        dispatch for small requests and the front keeps a default 256-row
        cap. CCFD_INLINE_ROWS overrides; 0 disables."""
        import os

        if self._inline_cap_cached is not None:
            return self._inline_cap_cached
        env = os.environ.get("CCFD_INLINE_ROWS", "").strip()
        if env:
            try:
                self._inline_cap_cached = min(int(env), self.INLINE_MAX_ROWS)
                return self._inline_cap_cached  # explicit wins
            except ValueError:
                import sys

                print(
                    f"[native-front] ignoring non-integer "
                    f"CCFD_INLINE_ROWS={env!r}",
                    file=sys.stderr,
                )
        htr = int(self._server.scorer.host_tier_rows)
        if htr > 0:
            cap = htr
        else:
            import jax

            # tier off: in-front scoring on the cpu backend only; on an
            # accelerator nothing on the host stands in for the device
            cap = 256 if jax.default_backend() == "cpu" else 0
        self._inline_cap_cached = min(cap, self.INLINE_MAX_ROWS)
        return self._inline_cap_cached

    def _install_host_model(self) -> None:
        """Push the scorer's host params into the C++ front so small
        canonical requests score in the IO thread with ZERO Python handoffs
        (the decisive path on a small serving host: the queue round trip
        costs more in context switches than the forward itself). Re-pushed
        on every ``swap_params`` so online retrain reaches the front."""
        srv = self._server
        if self._inline_rows_cap() <= 0:
            return
        host_params = getattr(srv.scorer, "_host_params", None)
        if host_params is None:
            return
        h = srv._h_latency
        ubs = (ctypes.c_double * len(h.buckets))(*h.buckets)
        self._n_buckets = len(h.buckets)
        self._lib.ccfd_front_set_latency_buckets(
            self._handle, ubs, len(h.buckets)
        )
        self._host_synced_counts = np.zeros((2, self._n_buckets), np.int64)
        self._host_synced_sums = np.zeros(2, np.float64)
        if self._push_host_model(host_params):
            self._swap_listener = self._push_host_model
            srv.scorer.add_swap_listener(self._swap_listener)

    def _push_host_model(self, host_params) -> bool:
        spec_name = self._server.scorer.spec.name
        if spec_name == "gbt":
            extracted = extract_tree_model(host_params)
            pusher = self._push_host_trees_locked
        elif spec_name == "mlp_q8":
            extracted = extract_q8_model(host_params)
            pusher = self._push_host_q8_locked
        else:
            extracted = extract_dense_model(spec_name, host_params)
            pusher = self._push_host_model_locked
        if extracted is None:
            return False
        # one guarded call for every model family: the stop()-vs-push
        # interlock (handle/stopping re-check under the lock) must not be
        # duplicated per branch
        with self._push_lock:
            if self._handle is None or self._stopping.is_set():
                return False
            return pusher(extracted)

    def _gauge_cols(self):
        from ccfd_tpu.serving.server import _AMOUNT_COL, _V10_COL, _V17_COL

        return (ctypes.c_int * 3)(_AMOUNT_COL, _V17_COL, _V10_COL)

    def _push_host_trees_locked(self, trees) -> bool:
        n_trees, depth, feat, thr, leaf, base = trees
        fp = ctypes.POINTER(ctypes.c_float)
        self._lib.ccfd_front_set_host_trees(
            self._handle,
            n_trees,
            depth,
            feat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            thr.ctypes.data_as(fp),
            leaf.ctypes.data_as(fp),
            base,
            self._inline_rows_cap(),
            self._server.scorer.spec.name.encode(),
            self._gauge_cols(),
        )
        self.host_model_active = True
        return True

    def _push_host_model_locked(self, extracted) -> bool:
        dims, weights, biases, mean, inv_std = extracted

        dims_c = (ctypes.c_int * len(dims))(*dims)
        gcols = self._gauge_cols()
        # locals keep the arrays alive across the ctypes call
        w = np.ascontiguousarray(weights, np.float32)
        b = np.ascontiguousarray(biases, np.float32)
        m = None if mean is None else np.ascontiguousarray(mean, np.float32)
        s = None if inv_std is None else np.ascontiguousarray(inv_std, np.float32)
        fp = ctypes.POINTER(ctypes.c_float)
        self._lib.ccfd_front_set_host_model(
            self._handle,
            len(dims) - 1,
            dims_c,
            w.ctypes.data_as(fp),
            b.ctypes.data_as(fp),
            None if m is None else m.ctypes.data_as(fp),
            None if s is None else s.ctypes.data_as(fp),
            self._inline_rows_cap(),
            self._server.scorer.spec.name.encode(),
            gcols,
        )
        self.host_model_active = True
        return True

    def _push_host_q8_locked(self, extracted) -> bool:
        if not hasattr(self._lib, "ccfd_front_set_host_q8_model"):
            return False  # pre-q8 shipped .so: requests flow to Python takers
        dims, weights, scales, biases, mean, sigma = extracted
        dims_c = (ctypes.c_int * len(dims))(*dims)
        gcols = self._gauge_cols()
        # locals keep the arrays alive across the ctypes call
        w = np.ascontiguousarray(weights, np.float32)
        sc = np.ascontiguousarray(scales, np.float32)
        b = np.ascontiguousarray(biases, np.float32)
        m = np.ascontiguousarray(mean, np.float32)
        sg = np.ascontiguousarray(sigma, np.float32)
        fp = ctypes.POINTER(ctypes.c_float)
        self._lib.ccfd_front_set_host_q8_model(
            self._handle,
            len(dims) - 1,
            dims_c,
            w.ctypes.data_as(fp),
            sc.ctypes.data_as(fp),
            b.ctypes.data_as(fp),
            m.ctypes.data_as(fp),
            sg.ctypes.data_as(fp),
            self._inline_rows_cap(),
            self._server.scorer.spec.name.encode(),
            gcols,
        )
        self.host_model_active = True
        return True

    def stop(self) -> None:
        if self._handle is None:
            return
        if self._swap_listener is not None:
            self._server.scorer.remove_swap_listener(self._swap_listener)
            self._swap_listener = None
        self._stopping.set()
        # barrier: a swap-listener push snapshotted before the removal
        # above may still be inside the ctypes call — wait it out before
        # tearing the handle down (it re-checks _stopping under this lock)
        with self._push_lock:
            pass
        # stop: wakes takers (-1) + joins the C++ IO thread; the handle
        # stays VALID until every Python worker that may be inside
        # take()/take_misc() has joined — only then destroy frees it
        self._lib.ccfd_front_stop(self._handle)
        for t in self._threads:
            t.join(timeout=10.0)
        still_alive = [t for t in self._threads if t.is_alive()]
        self._threads = []
        if not still_alive:
            self._lib.ccfd_front_destroy(self._handle)
        # else: a worker is wedged inside a device dispatch that never
        # returned and may still touch the handle — LEAK the Front rather
        # than free memory a live thread will poke
        self._handle = None

    # -- predict hot path --------------------------------------------------
    def _score_loop(self) -> None:
        srv = self._server
        nf = srv.scorer.num_features
        rows_buf = np.empty((self._max_rows, nf), np.float32)
        rows_ptr = rows_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        meta = (ctypes.c_int * (3 * self._max_reqs))()
        enq = (ctypes.c_double * self._max_reqs)()
        model = srv.scorer.spec.name.encode()
        while not self._stopping.is_set():
            handle = self._handle
            if handle is None:
                return
            n_reqs = self._lib.ccfd_front_take(
                handle, rows_ptr, self._max_rows, meta, enq, self._max_reqs, 200
            )
            if n_reqs <= 0:
                if n_reqs < 0:
                    return  # stopping
                continue
            ids = (ctypes.c_int * n_reqs)()
            counts = (ctypes.c_int * n_reqs)()
            tags = [0] * n_reqs
            total = 0
            for i in range(n_reqs):
                ids[i] = meta[3 * i]
                counts[i] = meta[3 * i + 1]
                tags[i] = meta[3 * i + 2]
                total += meta[3 * i + 1]
            # overload admission (runtime/overload.py): the C++ queue does
            # not forward headers, so native-path requests admit at NORMAL
            # priority, request-atomically from the front of the block;
            # the refused tail gets an explicit 429 + retry-after hint in
            # the body. The reserve is released after the respond below.
            gate = getattr(srv, "admission", None)
            admitted_rows = total
            if gate is not None:
                n_admit = 0
                admitted_rows = 0
                for i in range(n_reqs):
                    if not gate.try_admit(counts[i]):
                        break
                    admitted_rows += counts[i]
                    n_admit += 1
                if n_admit < n_reqs:
                    rej = json.dumps({
                        "error": "overloaded",
                        "retry_after_s": round(gate.retry_after_s, 3),
                    }).encode()
                    for i in range(n_admit, n_reqs):
                        self._lib.ccfd_front_respond_misc(
                            handle, ids[i], 429, b"application/json",
                            rej, len(rej),
                        )
                        srv._c_requests.inc(labels={"code": "429"})
                    n_reqs = n_admit
                    total = admitted_rows
                    if n_reqs == 0:
                        continue
            x = rows_buf[:total]
            t_sc = time.monotonic()
            try:
                proba = np.ascontiguousarray(
                    np.asarray(srv.scorer.score(x)), np.float32
                )
            except ScorerTimeout as e:
                # wedged device, no host fallback: bounded 503 (server-side
                # SELDON_TIMEOUT) instead of a taker thread stuck forever
                err = json.dumps({"error": f"scoring unavailable: {e}"}).encode()
                for i in range(n_reqs):
                    self._lib.ccfd_front_respond_misc(
                        handle, ids[i], 503, b"application/json", err, len(err)
                    )
                    srv._c_requests.inc(labels={"code": "503"})
                if gate is not None:
                    gate.release(admitted_rows)
                continue
            except Exception:  # noqa: BLE001 - fail the requests, not the loop
                err = b'{"error": "scoring failed"}'
                for i in range(n_reqs):
                    self._lib.ccfd_front_respond_misc(
                        handle, ids[i], 500, b"application/json", err, len(err)
                    )
                    srv._c_requests.inc(labels={"code": "500"})
                if gate is not None:
                    gate.release(admitted_rows)
                continue
            if gate is not None:
                gate.release(admitted_rows)
                gate.observe(time.monotonic() - t_sc)
            self._lib.ccfd_front_respond(
                handle, ids, counts, n_reqs,
                proba.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), model,
            )
            # metrics parity with the Python server path (same endpoint
            # labels the Python transport records)
            now_ms = time.monotonic() * 1e3
            for i in range(n_reqs):
                srv._h_latency.observe(
                    max(0.0, (now_ms - enq[i]) / 1e3),
                    labels={"endpoint": "/predict" if tags[i]
                            else "/api/v0.1/predictions"},
                )
            srv._c_requests.inc(n_reqs, labels={"code": "200"})
            if total:
                srv._g_proba.set(float(proba[total - 1]))
                from ccfd_tpu.serving.server import _AMOUNT_COL, _V10_COL, _V17_COL

                srv._g_amount.set(float(x[total - 1, _AMOUNT_COL]))
                srv._g_v17.set(float(x[total - 1, _V17_COL]))
                srv._g_v10.set(float(x[total - 1, _V10_COL]))
                srv._gauges_set_ms = time.monotonic() * 1e3

    # -- everything else ---------------------------------------------------
    def _misc_loop(self) -> None:
        srv = self._server
        method_buf = ctypes.create_string_buffer(16)
        path_buf = ctypes.create_string_buffer(512)
        body_ptr = ctypes.c_void_p()
        body_len = ctypes.c_int(0)
        # C++ validated the bearer token before queueing, but it does not
        # forward headers; re-synthesize the authorization the Python
        # routing re-checks so valid requests don't double-401
        auth_hdr = {}
        if srv.cfg.seldon_token:
            auth_hdr = {b"authorization": f"Bearer {srv.cfg.seldon_token}".encode()}
        while not self._stopping.is_set():
            handle = self._handle
            if handle is None:
                return
            req_id = self._lib.ccfd_front_take_misc(
                handle, method_buf, 16, path_buf, 512,
                ctypes.byref(body_ptr), ctypes.byref(body_len), 200,
            )
            if req_id < 0:
                return
            if req_id == 0:
                continue
            body = ctypes.string_at(body_ptr, body_len.value)
            self._lib.ccfd_front_free(body_ptr)
            method = method_buf.value.decode("latin-1")
            path = path_buf.value.decode("latin-1")
            if path in ("/prometheus", "/metrics"):
                self._sync_native_counters(handle)
            try:
                res = srv._http_handler(method, path, auth_hdr, body)
                # 3-tuple, or 4-tuple with extra response headers (429
                # Retry-After); the C++ responder has no header channel,
                # so the extra headers ride only in the JSON body here
                status, ctype, resp = res[0], res[1], res[2]
            except Exception:  # noqa: BLE001 - fail the request, not the loop
                logging.getLogger("ccfd_tpu.native_front").warning(
                    "misc handler raised for %s %s; answered 500",
                    method, path, exc_info=True)
                status, ctype, resp = 500, "text/plain", b"internal error"
            self._lib.ccfd_front_respond_misc(
                handle, req_id, status, ctype.encode(), resp, len(resp)
            )

    def _sync_native_counters(self, handle) -> None:
        """Fold C++-side counts into the registry before a scrape: 401s,
        plus everything the in-front host model scored without touching
        Python — request counts, the seldon latency histogram (bucket
        layout pushed at install matches 1:1), and the ModelPrediction
        gauges from the last host-scored row."""
        srv = self._server
        stats = (ctypes.c_long * 4)()
        self._lib.ccfd_front_stats(handle, stats)
        delta = int(stats[3]) - self._auth_fail_synced
        if delta > 0:
            srv._c_requests.inc(delta, labels={"code": "401"})
            self._auth_fail_synced += delta

        if self._host_synced_counts is None:
            return
        nb = self._n_buckets
        counts = (ctypes.c_long * (2 * nb))()
        sums = (ctypes.c_double * 2)()
        gauges = (ctypes.c_float * 4)()
        gauge_ms = ctypes.c_double(0.0)
        n_host = int(
            self._lib.ccfd_front_host_stats(
                handle, counts, sums, gauges, ctypes.byref(gauge_ms)
            )
        )
        d_n = n_host - self._host_synced_n
        if d_n > 0:
            srv._c_requests.inc(d_n, labels={"code": "200"})
            self._host_synced_n = n_host
        # as_array derives the dtype from the ctypes type: c_long is 8 bytes
        # on LP64 but 4 on other ABIs, so a hardcoded int64 would misparse
        cur = np.ctypeslib.as_array(counts).astype(np.int64).reshape(2, nb)
        cur_sums = np.ctypeslib.as_array(sums).astype(np.float64)
        endpoints = ("/api/v0.1/predictions", "/predict")
        for tag in (0, 1):
            d_counts = cur[tag] - self._host_synced_counts[tag]
            d_sum = cur_sums[tag] - self._host_synced_sums[tag]
            if d_counts.any() or d_sum:
                srv._h_latency.merge_counts(
                    d_counts.tolist(), float(d_sum),
                    labels={"endpoint": endpoints[tag]},
                )
        self._host_synced_counts = cur
        self._host_synced_sums = cur_sums
        # the "last scored" gauges must reflect whichever path scored most
        # recently: fold the C++ values only when they are BOTH new since
        # the last fold AND newer than the Python path's last write (same
        # CLOCK_MONOTONIC as time.monotonic, ms)
        host_ms = float(gauge_ms.value)
        if host_ms > self._gauge_synced_ms and host_ms > getattr(
            srv, "_gauges_set_ms", 0.0
        ):
            self._gauge_synced_ms = host_ms
            srv._g_proba.set(float(gauges[0]))
            srv._g_amount.set(float(gauges[1]))
            srv._g_v17.set(float(gauges[2]))
            srv._g_v10.set(float(gauges[3]))
