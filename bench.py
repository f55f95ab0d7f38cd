"""Benchmark: transaction-scoring throughput + latency, end to end.

Seven timed surfaces, matching the hops the reference instruments on its
SeldonCore/Router dashboards (SURVEY.md §3 stack A, §6):

1. **Scorer hop** — host feature matrix -> bucketed jit dispatch
   (ccfd_tpu/serving/scorer.py) -> probabilities on host. Full H2D +
   XLA executable + D2H round trip, the number ``metric``/``value`` report.
2. **Fused vs XLA A/B** — the same hop through the Pallas fused kernel and
   through plain XLA, so the kernel's win (or loss) is a recorded number
   (VERDICT r1 next-steps #2).
3. **REST hop** — concurrent HTTP clients -> PredictionServer ->
   DynamicBatcher -> scorer; p50/p99 per request plus aggregate req/s and
   rows/s. This is the hop the reference's Seldon engine histograms
   measure (reference deploy/grafana/SeldonCore.json:499-531).
4. **Pipeline loop** — producer -> bus -> router (micro-batch + rules) ->
   engine (batched process starts) sustained tx/s with the real fraud
   process at a realistic fired mix.
5. **Mesh scoring** — batch sharded over the data axis of a device mesh
   (runs when >1 device is visible; SURVEY.md §7 stage 6).
6. **Online retrain** — SGD steps/s and labels/s for the loop the engine's
   label topic feeds (BASELINE.json configs[4]); sharded when >1 device.
7. **Sequence scoring** — the per-customer history transformer
   (long-context family; ring attention over the mesh when >1 device).

Prints ONE JSON line; primary fields:
  {"metric": ..., "value": tx/s, "unit": "tx/s", "vs_baseline": ratio,
   "p99_ms": ..., "platform": ...}
plus sections ``rest`` / ``pipeline`` / ``fused_ab`` / ``mesh`` /
``retrain`` / ``seq`` / ``zoo`` (logreg + GBT scorer hop) /
``quant_int8`` (int8 vs the bf16 headline on the same hop; TPU-gated,
force with CCFD_BENCH_QUANT=1) / ``replay`` (bulk re-score rate of a
recorded window through the live path at bulk priority, with the live
lane's fast-window SLO breach count — held zero — alongside).

``vs_baseline`` is the ratio against the 50,000 tx/s north-star target
(BASELINE.json; the reference publishes no numbers of its own). ``p99_ms``
covers the p99 < 10 ms target on the REST surface when measured, else the
scorer hop.

Backend: the one rule of ccfd_tpu/utils/backend.py — a TPU, or
``JAX_PLATFORMS=cpu`` said in the environment; with no chip and no such
request the bench fails at start-up instead of measuring the CPU. Every
timed number is a device number only when ``platform`` says ``tpu``. A
section that fails raises and the run exits non-zero: there are no error
rows.

Env knobs: CCFD_BENCH_BATCH (default 131072), CCFD_BENCH_SECONDS (default 3),
CCFD_BENCH_PIPELINE (in-flight dispatch depth, default 2),
CCFD_BENCH_LATENCY_BATCH (default 4096), CCFD_BENCH_REST_CLIENTS (default
4), CCFD_BENCH_REST_ROWS (rows per request, default 128),
CCFD_BENCH_SKIP=rest,pipeline,ab,mesh,retrain,seq,zoo,quant,replay to
skip sections, CCFD_BENCH_MAX_S (whole-bench watchdog: a device wait that
never returns would otherwise hang the bench forever; on expiry every
section that COMPLETED is printed, clearly labeled partial, and the
process exits 3).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

NORTH_STAR_TX_S = 50_000.0  # BASELINE.json north_star: >=50k tx/s on v5e-1
NORTH_STAR_P99_MS = 10.0  # BASELINE.json north_star: p99 e2e predict <10ms

# Sections append here as they complete so a mid-run hang (watchdog fire)
# still reports every number that was actually measured, clearly labeled,
# instead of discarding the whole run.
_PARTIAL: dict = {}


class _DeviceMeter:
    """Per-section device telemetry for bench rows (ISSUE 10 satellite):
    installs a DeviceTelemetry plane as the process default — every
    scorer the sections build stages through it — and hands out per-
    section H2D byte deltas + the running peak device memory."""

    def __init__(self, attach_rows: bool):
        from ccfd_tpu.observability.device import (
            DeviceTelemetry,
            set_default,
        )

        self.attach_rows = attach_rows
        self.tele = DeviceTelemetry()
        set_default(self.tele)
        self._last_bytes = 0

    def section(self, row) -> None:
        """Attach {h2d_bytes, peak_device_memory_bytes} to a completed
        section row (on-device runs; a CPU run exercises the same
        counters but its rows stay unchanged)."""
        if self.tele is None:
            return
        total = self.tele.h2d_bytes()
        delta, self._last_bytes = total - self._last_bytes, total
        if not (self.attach_rows and isinstance(row, dict)):
            return
        row["device"] = {
            "h2d_bytes": int(delta),
            "peak_device_memory_bytes": self.tele.peak_memory_bytes(),
        }


def _bench_scorer(scorer, X, batch, lat_batch, seconds, depth):
    import numpy as np

    x = X[:batch]
    n_rows = 0
    t0 = time.perf_counter()
    while True:
        proba = scorer.score_pipelined(x, depth=depth)
        n_rows += x.shape[0]
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    assert proba.shape == (batch,)
    tx_per_s = n_rows / elapsed

    xl = X[:lat_batch]
    lat = []
    t_end = time.perf_counter() + max(1.0, seconds / 2)
    while time.perf_counter() < t_end:
        t1 = time.perf_counter()
        scorer.score(xl)
        lat.append((time.perf_counter() - t1) * 1e3)
    lat_a = np.asarray(lat)
    return tx_per_s, float(np.percentile(lat_a, 50)), float(np.percentile(lat_a, 99))


# The REST client lives in ccfd_tpu/utils/loadgen.py (_CLIENT): ONE copy
# shared with `ccfd_tpu loadgen`, so operator-side numbers against a
# deployed scorer compare directly with the bench's rest section.


def _loadgen_client() -> str:
    from ccfd_tpu.utils.loadgen import _CLIENT

    return _CLIENT


def _hop_buckets(top: int) -> tuple[int, ...]:
    """ONE bucket ladder for every per-model bench section. The sections
    had drifted apart — rest compiled (16, 128, 1024, top), zoo/quant a
    single (top,) bucket — so the 'same' hop ran different executable
    sets and padding regimes on CPU vs TPU and the captures were not
    comparable (ROADMAP item 1 / SNIPPETS PR-5 header). Every section now
    compiles the canonical serving ladder clipped at its top size."""
    return tuple(b for b in (16, 128, 1024, 4096) if b < top) + (int(top),)


def _section_scorer(model, params, top, use_fused=None, host_tier_rows=0,
                    partitioner=None):
    """The shared Scorer construction for the rest/zoo/quant/mesh sections:
    same bucket ladder (:func:`_hop_buckets`), same bfloat16 compute
    dtype, differing ONLY in what the section is isolating (fused path
    on/off; host tier 0 for raw device-hop rates, None = the serving
    default for the REST section — which is also 0;
    ``partitioner`` shards the same construction over a device mesh — the
    devices=N scaling row and tools/multichip_scaling.py both build
    through here so their numbers stay comparable)."""
    from ccfd_tpu.serving.scorer import Scorer

    kw = {} if use_fused is None else {"use_fused": use_fused}
    s = Scorer(
        model_name=model, params=params, batch_sizes=_hop_buckets(top),
        compute_dtype="bfloat16", host_tier_rows=host_tier_rows,
        partitioner=partitioner, **kw,
    )
    s.warmup()
    return s


def _bench_rest(scorer_params, lat_batch, seconds, n_clients, rows_per_req,
                native=True):
    """HTTP clients -> PredictionServer -> DynamicBatcher -> scorer: the full
    REST round trip. Clients run in SUBPROCESSES — in-process client threads
    would share the GIL with the server handlers and pollute the p99 with
    client-side scheduling, which is not the hop under test. ``native``
    selects the C++ front vs the Python transport (the A/B records the
    native front's win as a number)."""
    import numpy as np

    from ccfd_tpu.config import Config
    from ccfd_tpu.serving.server import PredictionServer

    scorer = _section_scorer("mlp", scorer_params, lat_batch,
                             host_tier_rows=None)
    srv = PredictionServer(scorer, Config(dynamic_batching=True,
                                          native_front=native))
    port = srv.start(host="127.0.0.1", port=0)
    transport = type(srv._httpd).__name__  # read before stop() nulls it
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _loadgen_client(),
             "127.0.0.1", str(port), "/api/v0.1/predictions",
             str(rows_per_req), str(seconds)],
            stdout=subprocess.PIPE,
        )
        for _ in range(n_clients)
    ]
    lat: list[float] = []
    rate = 0.0
    ok = 0
    errors = 0
    try:
        for p in procs:
            # throughput aggregates per-client measured windows: the
            # parent's wall clock would also count interpreter startup,
            # which is not the hop under test
            try:
                out, _ = p.communicate(timeout=seconds + 120)
            except subprocess.TimeoutExpired:
                p.kill()
                continue
            if p.returncode == 0:
                try:
                    r = json.loads(out)
                except ValueError:
                    continue
                lat.extend(r["lat"])
                rate += len(r["lat"]) / max(r["loop_s"], 1e-9)
                errors += int(r.get("errors", 0))
                ok += 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.stop()
    if not lat:
        raise RuntimeError("bench section rest: every REST client failed")
    lat_a = np.asarray(lat)
    return {
        "clients": ok,
        "rows_per_request": rows_per_req,
        "requests_s": round(rate, 1),
        "tx_s": round(rate * rows_per_req, 1),
        "p50_ms": round(float(np.percentile(lat_a, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_a, 99)), 3),
        # rows at or under this score on the host (numpy), not the
        # device; the serving default is 0
        "host_tier_rows": scorer.host_tier_rows,
        "transport": transport,
        # non-200s during the run (the shared client counts, never dies)
        "errors": errors,
    }


def _bench_pipeline(scorer_params, seconds):
    """producer -> bus -> router -> engine sustained loop, realistic mix.

    Records ride the wire as raw CSV rows — the reference's producer
    streams creditcard.csv lines to the topic (reference
    deploy/kafka/ProducerDeployment.yaml:90-95), and the router decodes
    that format through the native C++ path (decode.cpp); dict-format
    records remain covered by tests/test_pipeline.py."""
    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.router.router import Router
    from ccfd_tpu.serving.scorer import Scorer

    cfg = Config()
    broker = Broker()
    reg = Registry()
    engine = build_engine(cfg, broker, reg, None)
    scorer = Scorer(model_name="mlp", params=scorer_params)
    scorer.warmup()
    router = Router(cfg, broker, scorer.score, engine, reg, max_batch=4096)

    ds = synthetic_dataset(n=8192, fraud_rate=0.01, seed=1)
    recs = [
        ",".join(f"{v:.6g}" for v in ds.X[i]).encode()
        for i in range(len(ds.X))
    ]
    keys = list(range(len(recs)))

    # one saturated-phase harness for BOTH router shapes: a feeder thread
    # keeps the topic ahead of the consumer under one backpressure policy,
    # so the workers=N row is ratioed against a baseline measured under
    # identical feed conditions
    import threading

    def saturated_run(broker_x, c_in, router_obj) -> float:
        stop_x = threading.Event()

        def feed() -> None:
            while not stop_x.is_set():
                backlog = sum(broker_x.end_offsets(cfg.kafka_topic))
                if backlog - c_in.value() > 50_000:
                    time.sleep(0.002)
                    continue
                broker_x.produce_batch(cfg.kafka_topic, recs, keys)

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        t0 = time.perf_counter()
        th = router_obj.start(poll_timeout_s=0.05, pipeline=True)
        time.sleep(seconds)
        router_obj.stop()
        th.join(timeout=60)
        elapsed = time.perf_counter() - t0
        stop_x.set()
        feeder.join(timeout=5)
        return elapsed

    elapsed = saturated_run(broker, router._c_in, router)
    total = router._c_in.value()
    out = reg.counter("transaction_outgoing_total")
    result = {
        "tx_s": round(total / elapsed, 1),
        "standard_starts": out.value(labels={"type": "standard"}),
        "fraud_starts": out.value(labels={"type": "fraud"}),
    }

    # Phase 1a — shadow-scoring overhead (lifecycle/shadow.py): the SAME
    # saturated harness with a challenger armed in the scorer's slot and
    # the router's score lane tap-wrapped. The lifecycle's hot-path
    # contract is that shadow evaluation rides a bounded queue serviced
    # off-thread (host numpy forward), so tx_s must sit within noise of
    # the baseline — overhead_pct is the acceptance number, with the
    # dropped-batch count showing where backpressure went instead.
    from ccfd_tpu.lifecycle.shadow import ShadowTap

    broker_s = Broker()
    reg_s = Registry()
    engine_s = build_engine(cfg, broker_s, reg_s, None)
    tap = ShadowTap(scorer, broker_s, cfg.shadow_topic, reg_s)
    scorer.install_challenger(1, scorer_params)
    tap.arm(1)
    router_s = Router(cfg, broker_s, tap.wrap(scorer.score), engine_s,
                      reg_s, max_batch=4096)
    shadow_thread = threading.Thread(
        target=lambda: tap.run(interval_s=0.01), daemon=True)
    shadow_thread.start()
    c_in_s = reg_s.counter("transaction_incoming_total")
    elapsed_s = saturated_run(broker_s, c_in_s, router_s)
    tap.stop()
    shadow_thread.join(timeout=5)
    tap.disarm()
    scorer.clear_challenger()
    tx_s_shadow = c_in_s.value() / elapsed_s
    result["shadow"] = {
        "tx_s": round(tx_s_shadow, 1),
        "overhead_pct": round(
            100.0 * (1.0 - tx_s_shadow / max(result["tx_s"], 1e-9)), 1),
        "rows_shadow_scored": int(reg_s.counter(
            "ccfd_lifecycle_shadow_rows_total").value()),
        "rows_dropped": int(reg_s.counter(
            "ccfd_lifecycle_shadow_dropped_total").value()),
    }

    # Phase 1b — worker-count axis (router/parallel.py ParallelRouter):
    # the SAME max_batch budget, N partition-parallel worker loops
    # sharing one coalescing batcher. Reports scaling efficiency against
    # the single-router phase above and the coalesced-dispatch fan-in
    # (dispatches < worker batches == concurrent sub-batches merged into
    # one device launch). ``cpus`` rides along because thread fan-out is
    # hardware-bounded: on a 2-core CPU host the GIL thread and the XLA
    # pool already saturate the box at workers=1, so the scaling ceiling
    # is ~1x there; the row exists to prove the machinery and to measure
    # real scaling where the cores exist. Dispatches coalesce toward an
    # 8192 bucket (2 worker polls): big enough to show fan-in, small
    # enough that the pool's finishes don't convoy behind one
    # device-batch the size of every worker's poll combined.
    import os as _os

    from ccfd_tpu.router.parallel import ParallelRouter

    result["workers"] = {"1": {"tx_s": result["tx_s"]}}
    result["workers_cpus"] = _os.cpu_count()
    scorer_w = Scorer(model_name="mlp", params=scorer_params,
                      batch_sizes=(128, 1024, 4096, 8192))
    scorer_w.warmup()
    for n_workers in (4,):
        broker_w = Broker(default_partitions=2 * n_workers)
        reg_w = Registry()
        engine_w = build_engine(cfg, broker_w, reg_w, None)
        pr = ParallelRouter(cfg, broker_w, scorer_w.score, engine_w, reg_w,
                            workers=n_workers, max_batch=4096,
                            coalesce_max_batch=8192)
        c_in_w = reg_w.counter("transaction_incoming_total")
        elapsed_w = saturated_run(broker_w, c_in_w, pr)
        shed_w = reg_w.counter("router_shed_total").value()
        # routed-only throughput: transaction_incoming_total counts shed
        # (consumed-but-dropped) rows too, and the scaling ratio must not
        # be inflatable by drops (shed stays 0 with the default budget;
        # the row reports it so a nonzero value is visible)
        total_w = c_in_w.value() - shed_w
        tx_s_w = total_w / elapsed_w
        worker_batches = reg_w.counter(
            "router_worker_batches_total").total()
        dispatches = reg_w.counter(
            "router_coalesced_dispatches_total").value()
        pr.close()
        result["workers"][str(n_workers)] = {
            "tx_s": round(tx_s_w, 1),
            "scaling_x": round(tx_s_w / max(result["tx_s"], 1e-9), 2),
            "scaling_efficiency": round(
                tx_s_w / max(result["tx_s"], 1e-9) / n_workers, 3),
            "worker_batches": int(worker_batches),
            "coalesced_dispatches": int(dispatches),
            "shed": int(shed_w),
        }

    # Phase 2 — decision latency at a PACED rate (the business SLO the
    # reference tracks as SeldonCore board quantiles): under the
    # saturated phase above, latency is just backlog depth; the SLO
    # question is producer -> process-start at a sustainable arrival
    # rate. Fresh registry/router so the histogram holds only this phase,
    # and the consumer group skips phase 1's unconsumed backlog — its
    # seconds-old timestamps would otherwise dominate the quantiles.
    broker.reset_offsets("router", cfg.kafka_topic,
                         broker.end_offsets(cfg.kafka_topic))
    reg2 = Registry()
    engine2 = build_engine(cfg, broker, reg2, None)
    router2 = Router(cfg, broker, scorer.score, engine2, reg2,
                     max_batch=4096)
    # pace AT the north-star rate when the saturated phase shows headroom
    # (capped at half of saturation so an overloaded host still measures
    # a sustainable rate, not its own backlog)
    rate = max(5_000.0, min(NORTH_STAR_TX_S, result["tx_s"] * 0.5))
    th2 = router2.start(poll_timeout_s=0.01, pipeline=True)
    t_end = time.perf_counter() + max(3.0, seconds / 2)
    # 5 ms production tick: the tick is a floor under every record's
    # queueing delay (a record waits out the rest of its burst), so a
    # coarse tick would measure the generator, not the pipeline
    tick = 0.005
    chunk = max(1, int(rate * tick))
    i = 0
    while time.perf_counter() < t_end:
        broker.produce_batch(
            cfg.kafka_topic, recs[i % 4096:i % 4096 + chunk],
            keys[i % 4096:i % 4096 + chunk],
        )
        i += chunk
        time.sleep(tick)
    # drain, then read the quantiles
    deadline = time.perf_counter() + 10
    while (router2._c_in.value() < i
           and time.perf_counter() < deadline):
        time.sleep(0.05)
    router2.stop()
    th2.join(timeout=30)
    dec = reg2.histogram("router_decision_seconds")
    result["paced_rate_tx_s"] = round(rate, 0)
    result["p50_ms"] = round(dec.quantile(0.5) * 1e3, 3)
    result["p99_ms"] = round(dec.quantile(0.99) * 1e3, 3)
    return result


def _bench_mesh(params, batch, seconds, depth):
    """devices=N scaling row (ROADMAP item 2, mirroring the PR 3
    worker-scaling row): the SAME work through the SAME
    :func:`_section_scorer` / :func:`_hop_buckets` construction at mesh
    1x1 and on the full local mesh (data-parallel partitioner,
    parallel/partition.py — the live platform's serving construction), so
    the scaling ratio isolates what sharding adds. Records per-device
    dispatch counts off the PR 10 executable inventory: on a mesh each
    dispatch is ONE SPMD launch spanning every device, so the grid's
    tallies ARE the per-device counts. Runs when >1 device is visible (or
    a virtual CPU mesh is forced — the row then stamps
    ``virtual_devices: true`` and reports ``sharding_overhead_x`` INSTEAD
    of scaling_x/efficiency: all N virtual devices share the same host
    cores, so a speedup claim there would be a scheduler artifact;
    tools/multichip_scaling.py documents the confound)."""
    import jax

    from ccfd_tpu.parallel.mesh import make_named_mesh
    from ccfd_tpu.parallel.partition import DataParallelPartitioner

    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    from ccfd_tpu.data.ccfd import synthetic_dataset

    # feed depth x batch rows per call: with a top (batch,) bucket each
    # call then splits into `depth` chunks whose dispatches actually
    # overlap — one bucket-sized call would drain before returning and
    # the pipelining knob would be inert
    x = synthetic_dataset(n=depth * batch, fraud_rate=0.01, seed=2).X

    def rate(scorer):
        n_rows = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            scorer.score_pipelined(x, depth=depth)
            n_rows += depth * batch
        return n_rows / (time.perf_counter() - t0)

    tx_single = rate(_section_scorer("mlp", params, batch))
    part = DataParallelPartitioner(make_named_mesh(jax.devices()))
    sharded = _section_scorer("mlp", params, batch, partitioner=part)
    tx_mesh = rate(sharded)
    grid = sharded.executable_grid()
    scaling = tx_mesh / max(tx_single, 1e-9)
    # virtual devices (forced CPU mesh) all share the same host cores, so
    # a "speedup" column would claim parallel scaling that physically
    # cannot exist — at fixed cores the honest number is the sharding
    # overhead ratio (tools/multichip_scaling.py measures it at fixed
    # global work); scaling_x/efficiency are emitted only on real chips
    virtual = jax.default_backend() == "cpu"
    row = {
        "devices": n_dev,
        "mesh_axes": grid.get("mesh_axes"),
        "tx_s": round(tx_mesh, 1),
        "single_tx_s": round(tx_single, 1),
        "virtual_devices": virtual,
        "per_device_dispatches": grid["dispatches"],
    }
    if virtual:
        row["sharding_overhead_x"] = round(
            tx_single / max(tx_mesh, 1e-9), 2)
    else:
        row["scaling_x"] = round(scaling, 2)
        row["efficiency"] = round(scaling / n_dev, 3)
    return row


def _bench_retrain(seconds):
    """Online-retrain throughput (BASELINE.json configs[4]): labels -> one
    SGD step per batch, the loop the engine's label topic feeds — sharded
    over a data mesh when more than one device is visible, single-device
    otherwise (the ``devices`` field records which)."""
    import jax
    import numpy as np

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import mlp
    from ccfd_tpu.parallel.train import TrainConfig, init_state, make_train_step

    n_dev = len(jax.devices())
    partitioner = None
    if n_dev > 1:
        # the live platform's retrain construction (parallel/partition.py):
        # donated sharded state over the named data-parallel mesh
        from ccfd_tpu.parallel.mesh import make_named_mesh
        from ccfd_tpu.parallel.partition import DataParallelPartitioner

        partitioner = DataParallelPartitioner(make_named_mesh())
    ds = synthetic_dataset(n=4096, fraud_rate=0.2, seed=3)
    tc = TrainConfig(compute_dtype="bfloat16")
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    state = init_state(params, tc)
    step = make_train_step(tc, partitioner=partitioner)
    x = ds.X[:1024]
    y = ds.y[:1024].astype(np.float32)
    state, loss = step(state, x, y)  # compile
    jax.block_until_ready(loss)
    steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        state, loss = step(state, x, y)
        steps += 1
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0
    return {
        "steps_s": round(steps / elapsed, 1),
        "labels_s": round(steps * 1024 / elapsed, 1),
        "batch": 1024,
        "devices": n_dev,
        "final_loss": round(float(loss), 4),
    }


def _scorer_hop_rate(name, params, x, seconds, use_fused=False):
    """Time the REAL scorer hop for one model: numpy in, probabilities on
    host out, full H2D + dispatch + D2H per call through the Scorer (host
    tier forced off so the number is the device path) — the same surface
    the headline MLP metric measures, so the zoo ranks comparably.
    Built through :func:`_section_scorer`, so zoo/quant compile the SAME
    bucket ladder the rest section serves."""
    s = _section_scorer(name, params, x.shape[0], use_fused=use_fused)
    if use_fused and not s.fused:
        # recording the XLA rate under a fused label would corrupt the
        # A/B this exists to settle
        raise RuntimeError(f"bench: {name} params did not fold for the "
                           "fused kernel")
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        s.score(x)
        n += x.shape[0]
    return round(n / (time.perf_counter() - t0), 1)


def _bench_zoo(seconds, batch=16384):
    """Scorer-hop throughput for the rest of the model zoo (the headline
    number is the flagship MLP): logreg (reference modelfull parity family)
    and the tensorized GBT ensemble, each through the same Scorer hop as
    the headline."""
    import jax
    import numpy as np

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import logreg, trees

    ds = synthetic_dataset(n=batch, fraud_rate=0.01, seed=4)
    rng = np.random.default_rng(0)

    def random_tree_params(n_trees, depth):
        # randomized splits so gathers hit varied nodes (an all-inf
        # threshold ensemble would descend one hot path and flatter the
        # number)
        skel = trees.init_empty(n_trees=n_trees, depth=depth)
        return {
            "feature": jax.numpy.asarray(
                rng.integers(0, 30, skel["feature"].shape), "int32"
            ),
            "threshold": jax.numpy.asarray(
                rng.normal(size=skel["threshold"].shape), "float32"
            ),
            "leaf": jax.numpy.asarray(
                rng.normal(scale=0.05, size=skel["leaf"].shape), "float32"
            ),
            "base": skel["base"],
        }

    gbt_params = random_tree_params(100, 4)
    # the servable-HGB shape (HGB_SERVABLE_r04.json best: 44 trees x
    # depth 8): the quality champion's serving cost
    hgb_like = random_tree_params(44, 8)
    out = {}
    for name, model, params in (
        ("logreg", "logreg", logreg.fit_numpy(ds.X[:2048], ds.y[:2048])),
        ("gbt", "gbt", gbt_params),          # lockstep-descent gathers
        ("gbt_mxu", "gbt_mxu", gbt_params),  # gather-free one-hot matmul
        ("gbt_hgb_shape", "gbt", hgb_like),  # 44 trees x depth 8
    ):
        out[name] = {"tx_s": _scorer_hop_rate(model, params, ds.X, seconds),
                     "batch": batch}
    return out


def _median_time(fn, k=5):
    """Median wall time of k calls — the timing primitive the roofline
    split and the seq-pipeline split share."""
    ts = []
    for _ in range(k):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _bench_roofline(scorer, params, X, lat_batch, headline_tx_s,
                    rest, quant):
    """Roofline accounting (VERDICT r4 items 4/5): turn "wire-bound" from
    an assertion into numbers.  Records the model's FLOP/row, each measured
    section's achieved FLOP/s and wire bytes/s against the relevant peaks
    (MXU bf16/int8, HBM, and a *measured* H2D link bandwidth), plus a
    host-prep / H2D / device-compute time split for one serving batch — the
    denominators the batch-size and wire-format decisions (f32 vs bf16 vs
    int8 rows) have been made without.

    Peaks are the published per-chip numbers keyed by ``device_kind``; a
    TPU whose kind is not in the table is an error, not a default. On a
    ``JAX_PLATFORMS=cpu`` run the peaks are null and the H2D figure is
    host memcpy — labeled, and not a device number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    flop_per_row = int(sum(
        2 * int(np.asarray(l["w"]).shape[0]) * int(np.asarray(l["w"]).shape[1])
        for l in params["layers"]
    ) + 2 * int(np.asarray(params["norm"]["mu"]).shape[0]))

    backend = jax.default_backend()
    kind = getattr(jax.devices()[0], "device_kind", backend)
    # published per-chip peaks (dense): bf16 GFLOP/s, int8 GOP/s, HBM GB/s
    peak_table = {
        "v5e": (197_000.0, 394_000.0, 819.0),
        "v5 lite": (197_000.0, 394_000.0, 819.0),
        "v5p": (459_000.0, 918_000.0, 2765.0),
        "v4": (275_000.0, 275_000.0, 1228.0),
        "v3": (123_000.0, 123_000.0, 900.0),
    }
    peaks = None
    if backend == "tpu":
        for tag, (bf16, int8, hbm) in peak_table.items():
            if tag in str(kind).lower():
                peaks = {"mxu_bf16_gflop_s": bf16, "mxu_int8_gop_s": int8,
                         "hbm_gb_s": hbm}
                break
        if peaks is None:
            raise RuntimeError(
                f"bench section roofline: no published peaks for "
                f"device_kind {kind!r}; add it to the table with its source")

    # measured H2D link: one bulk transfer for bandwidth, one small for
    # the fixed per-transfer cost
    def _h2d_s(nbytes):
        arr = np.zeros(nbytes // 4, np.float32)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(arr))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    bulk_bytes = 32 * 1024 * 1024
    small_bytes = 256 * 1024
    h2d_bulk_s = _h2d_s(bulk_bytes)
    h2d = {
        "mb_s_measured": round(bulk_bytes / h2d_bulk_s / 1e6, 1),
        "dispatch_ms_small": round(_h2d_s(small_bytes) * 1e3, 3),
        "bulk_mib": 32,
    }

    # host-prep / H2D / device-compute split for one latency batch through
    # the live scorer's own wire dtype and apply fn
    use_fused = bool(scorer.fused and scorer._fused_params is not None)
    wire_dtype = np.dtype(scorer._fused_in_dtype) if use_fused \
        else np.dtype(np.float32)
    n_feat = int(np.asarray(params["norm"]["mu"]).shape[0])
    bytes_per_row = n_feat * wire_dtype.itemsize
    chunk = np.ascontiguousarray(X[:lat_batch], np.float32)

    prep = lambda: chunk.astype(wire_dtype)  # noqa: E731
    wired = chunk.astype(wire_dtype)
    put = lambda: jax.block_until_ready(jax.device_put(wired))  # noqa: E731
    xdev = jax.device_put(wired)
    jax.block_until_ready(xdev)
    apply_fn = scorer._fused_apply if use_fused else scorer._apply
    wparams = scorer._fused_params if use_fused else scorer._params
    jax.block_until_ready(apply_fn(wparams, xdev))  # compile outside timing
    compute = lambda: jax.block_until_ready(apply_fn(wparams, xdev))  # noqa: E731
    split = {
        "batch": lat_batch,
        "host_prep_ms": round(_median_time(prep, k=7) * 1e3, 3),
        "h2d_ms": round(_median_time(put, k=7) * 1e3, 3),
        "device_compute_ms": round(_median_time(compute, k=7) * 1e3, 3),
    }

    def section(tx_s, row_bytes, int8_math=False):
        if tx_s is None:
            return None
        out = {
            "tx_s": round(tx_s, 1),
            "bytes_per_row": row_bytes,
            "achieved_gflop_s": round(tx_s * flop_per_row / 1e9, 2),
            "wire_mb_s": round(tx_s * row_bytes / 1e6, 2),
        }
        out["h2d_link_util_pct"] = round(
            100.0 * out["wire_mb_s"] / max(h2d["mb_s_measured"], 1e-9), 2)
        if peaks:
            peak = peaks["mxu_int8_gop_s"] if int8_math \
                else peaks["mxu_bf16_gflop_s"]
            out["mfu_pct"] = round(100.0 * out["achieved_gflop_s"] / peak, 4)
            out["hbm_util_pct"] = round(
                100.0 * out["wire_mb_s"] / 1e3 / peaks["hbm_gb_s"], 4)
        return out

    sections = {}
    if headline_tx_s:
        sections["scorer_hop"] = section(headline_tx_s, bytes_per_row)
    if isinstance(rest, dict) and "tx_s" in rest:
        # REST rows land as JSON text host-side; the H2D wire is still the
        # scorer's dtype — host decode cost shows in the split, not here
        sections["rest"] = section(rest["tx_s"], bytes_per_row)
    if isinstance(quant, dict):
        q_tx = quant.get("preq_tx_s") or quant.get("tx_s")
        if q_tx:
            # int8 wire: n_feat int8 + one f32 scale per row
            sections["quant_int8_wire"] = section(
                q_tx, n_feat + 4, int8_math=True)

    head = sections.get("scorer_hop") or next(
        (s for s in sections.values() if s), None)
    if head is None:
        bound = "unmeasured"
        head = {}
    else:
        utils = {"h2d_wire": head["h2d_link_util_pct"]}
        if peaks:
            utils["mxu"] = head.get("mfu_pct", 0.0)
            utils["hbm"] = head.get("hbm_util_pct", 0.0)
        # the bound is whichever resource the headline hop uses the
        # largest fraction of; "host" when nothing device-side is >1%
        # busy — the time goes to host prep/dispatch, which the split
        # quantifies
        bound = max(utils, key=lambda k: utils[k])
        if utils[bound] < 1.0:
            bound = "host"
    return {
        "flop_per_row": flop_per_row,
        "device_kind": str(kind),
        "peaks": peaks,
        "h2d": h2d,
        "split_ms": split,
        "wire_dtype": wire_dtype.name,
        "sections": sections,
        "bound": bound,
        # headline copies for the compact summary line
        "wire_mb_s": head.get("wire_mb_s"),
        "mfu_pct": head.get("mfu_pct"),
        "h2d_mb_s_measured": h2d["mb_s_measured"],
    }


def _bench_quant(params, x, seconds):
    """Int8 vs the bf16 headline on the SAME Scorer hop: per-channel int8
    weights + per-row dynamic activations ride the MXU at twice the bf16
    rate and halve the wire bytes (ops/quant.py); measuring through the
    full H2D/D2H round trip is what lets the wire half show."""
    import jax

    from ccfd_tpu.ops import quant as quantlib

    qp = quantlib.quantize_mlp(params)
    out = {
        "tx_s": _scorer_hop_rate("mlp_q8", qp, x, seconds),
        "batch": int(x.shape[0]),
        "dtype": "int8",
    }
    if jax.default_backend() == "tpu":
        # Three-way ablation, each isolating ONE effect:
        #   tx_s       — XLA q8 graph, f32 wire
        #   fused_tx_s — Pallas kernel, f32 wire (kernel effect alone;
        #                CCFD_Q8_WIRE=f32 pins the wire because the int8
        #                wire is the scorer's default now)
        #   preq_tx_s  — Pallas kernel + int8 wire (the serving default)
        # TPU-only: the CPU interpreter would record noise.
        prev = os.environ.get("CCFD_Q8_WIRE")
        os.environ["CCFD_Q8_WIRE"] = "f32"
        try:
            fused_rate = _scorer_hop_rate(
                "mlp_q8", qp, x, seconds, use_fused=True
            )
        finally:
            if prev is None:
                os.environ.pop("CCFD_Q8_WIRE", None)
            else:
                os.environ["CCFD_Q8_WIRE"] = prev
        out["fused_tx_s"] = fused_rate
        out["preq_tx_s"] = _preq_hop_rate(qp, x, seconds)
    return out


def _preq_hop_rate(qp, x, seconds):
    """int8-at-the-edge wire variant: host normalize+rowquant, int8 rows
    over the wire (34 B/row vs 120 f32), kernel starts at the first MXU
    matmul. Same numpy-in/probas-out surface as _scorer_hop_rate so the
    three quant numbers rank comparably."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ccfd_tpu.ops import fused_mlp_q8 as fq

    folded = fq.fold_for_kernel(qp)
    kp = jax.device_put(folded)
    # host copies of the SAME folded normalizer the kernel uses
    # (raw sigma; zero-sigma sanitization lives in set_normalizer)
    host_norm = {k: np.asarray(folded[k]) for k in ("mu", "sigma")}
    x = np.asarray(x, np.float32)
    tile = fq.fit_tile(x.shape[0])  # shared tiling policy

    def hop(xb):
        q, s = fq.prequantize_rows_numpy(host_norm, xb)
        return np.asarray(
            fq.fused_mlp_q8_score_preq(
                kp, jnp.asarray(q), jnp.asarray(s), tile=tile
            )
        )

    hop(x)  # compile
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        hop(x)
        n += x.shape[0]
    return round(n / (time.perf_counter() - t0), 1)


def _bench_fused_decision(params, X, seconds, batch):
    """Staged vs fused decision on the SAME rows and the SAME Scorer.

    Staged = the pre-PR-19 serving shape: score_pipelined to host probas,
    then ``RuleSet.evaluate`` walks the rule base in numpy between score
    and route. Fused = ops/fused_decision.py: score + FRAUD_THRESHOLD
    compare + first-match rule argmax inside ONE executable, one packed
    (B, 2) transfer back. ``host_syncs_per_batch`` comes from the
    structural counters on each path (scorer.host_syncs / fds.host_syncs)
    so the "the transfer is the only sync left" claim is a recorded
    number; ``parity_bit_exact`` is measured on this box, not assumed.

    The two paths run in ALTERNATING short windows and the row records
    per-path MEDIANS: the deltas under test (host rule walk vs in-
    executable eval) are a few percent of the forward, and a sequential
    A-then-B layout folds machine drift into the ratio.

    Two shapes, because the win lives in different places:
    - ``latency``: the serving micro-batch (one bucket). What fusion
      displaces here is the per-decision FIXED cost — the extra host
      materialization plus the Python/numpy rule walk between score and
      route — which is why this is the headline ``speedup``.
    - ``throughput``: a multi-chunk batch where the fused rule work
      rides inside the depth-2 pending window. On CPU device == host so
      this is near parity by construction; on TPU the removed sync is
      the point, and the row records it either way."""
    import statistics

    import numpy as np

    from ccfd_tpu.config import Config
    from ccfd_tpu.router.rules import Condition, Rule, RuleSet
    from ccfd_tpu.serving.fused import FusedDecisionScorer

    b = int(min(batch, 65536))
    x = np.asarray(X[:b], np.float32)
    # top bucket BELOW b: the A/B wants the multi-chunk serving shape
    top = max(s for s in _hop_buckets(max(b // 4, 16)))
    scorer = _section_scorer("mlp", params, top)
    # a serving-shaped rule base (threshold route + amount band + feature
    # guards), not the 2-rule default: the staged cost being displaced is
    # the per-batch host walk over exactly this kind of table
    thr = Config().fraud_threshold
    rules = RuleSet([
        Rule("fraud_hi", process="fraud", salience=20,
             when=(Condition("proba", ">=", thr),
                   Condition("Amount", ">", 0.0))),
        Rule("fraud", process="fraud", salience=15,
             when=(Condition("proba", ">=", thr),)),
        Rule("review_band", process="standard", salience=10,
             when=(Condition("proba", "between", [thr / 2, thr]),)),
        Rule("v1_guard", process="standard", salience=5,
             when=(Condition("V1", ">", 0.0),
                   Condition("V2", "<=", 0.0))),
        Rule("standard", process="standard"),
    ])
    fds = FusedDecisionScorer(scorer, rules)
    if not fds.enabled:
        raise RuntimeError(
            "bench section fused_decision: the plane declined to arm")
    fds.warmup()

    def staged_hop(xb):
        proba = scorer.score_pipelined(xb)
        rules.evaluate(xb, proba)

    calls = {"staged": 0, "fused": 0}

    def ab(rows, staged, fused, rounds=4):
        """Alternating windows, per-path median rows/s."""
        def window(label, hop):
            n = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds / (2 * rounds):
                hop(rows)
                n += rows.shape[0]
                calls[label] += 1
            return n / (time.perf_counter() - t0)

        staged(rows)
        fused(rows)
        rates: dict[str, list[float]] = {"staged": [], "fused": []}
        for _ in range(rounds):
            rates["staged"].append(window("staged", staged))
            rates["fused"].append(window("fused", fused))
        return (statistics.median(rates["staged"]),
                statistics.median(rates["fused"]))

    # latency shape: one serving micro-batch through the SAME seam the
    # router runs — np.asarray(score(x)) then the host rule walk
    def staged_lat(xb):
        rules.evaluate(xb, np.asarray(scorer.score(xb)))

    lat_b = 128
    s_lat, f_lat = ab(x[:lat_b], staged_lat, fds.decide)

    calls["staged"] = calls["fused"] = 0  # syncs/batch counts thr only
    s0_staged, s0_fused = scorer.host_syncs, fds.host_syncs
    s_thr, f_thr = ab(x, staged_hop, fds.decide)
    staged_syncs = round((scorer.host_syncs - s0_staged)
                         / max(calls["staged"], 1), 2)
    fused_syncs = round((fds.host_syncs - s0_fused)
                        / max(calls["fused"], 1), 2)

    p_s = scorer.score(x)
    p_f, f_f = fds.decide(x)
    parity = bool(
        f_f is not None
        and np.array_equal(p_f, p_s)
        and np.array_equal(f_f, rules.evaluate(x, p_s))
    )
    grid = fds.executable_grid()
    return {
        "batch": b,
        "latency_batch": lat_b,
        "rules": len(rules.rules),
        "staged_decide_us": round(lat_b / s_lat * 1e6, 1),
        "fused_decide_us": round(lat_b / f_lat * 1e6, 1),
        "speedup": round(f_lat / max(s_lat, 1e-9), 3),
        "staged_tx_s": round(s_thr, 1),
        "fused_tx_s": round(f_thr, 1),
        "throughput_speedup": round(f_thr / max(s_thr, 1e-9), 3),
        "staged_host_syncs_per_batch": staged_syncs,
        "fused_host_syncs_per_batch": fused_syncs,
        "parity_bit_exact": parity,
        "staged_fallbacks": grid["staged_fallbacks"],
        "forward": grid["forward"],
    }


def _arm_watchdog() -> None:
    """A device wait that never returns blocks inside XLA, unkillable from
    Python. If the bench doesn't finish inside CCFD_BENCH_MAX_S, print the
    sections that completed (clearly labeled partial) and hard-exit 3, so
    the run records what it measured instead of a stall."""
    import threading

    explicit = os.environ.get("CCFD_BENCH_MAX_S", "")
    if explicit:
        budget = float(explicit)
    else:
        # scale with the knob that stretches a healthy run: ~20 timed
        # windows of `seconds` each, plus cold-compile and client-join
        # slack — a long configured run must not be killed as a hang
        seconds = float(os.environ.get("CCFD_BENCH_SECONDS", "3"))
        budget = 20 * max(seconds, 3.0) + 1440

    def fire() -> None:
        # os._exit(3) must run NO MATTER WHAT: an exception here (e.g. the
        # snapshot racing a concurrent _PARTIAL.update) would disarm the
        # watchdog and leave the hung bench hanging forever
        try:
            snap = dict(_PARTIAL)
            label = (f"partial (bench watchdog fired after {budget:.0f}s; "
                     f"{len(snap)} completed keys below)")
            out = {
                "metric": "end_to_end_scoring_throughput_mlp_bf16",
                "value": float(snap.get("value", 0.0)),
                "unit": "tx/s",
                "vs_baseline": round(
                    float(snap.get("value", 0.0)) / NORTH_STAR_TX_S, 3
                ),
                "platform": label,
            }
            out.update({k: v for k, v in snap.items() if k != "value"})
            print(json.dumps(out), flush=True)
        finally:
            os._exit(3)

    t = threading.Timer(budget, fire)
    t.daemon = True
    t.start()


def _bench_seq_pipeline(seconds):
    """The seq/history PRODUCT path end-to-end (VERDICT r4 item 6):
    producer -> bus -> router -> HistoryStore assembly -> (L, B)-bucketed
    overlapped seq dispatch — not the raw model rate (that is the ``seq``
    section).

    Round 11 reworked the path (ROADMAP item 5) and this section with it:
    traffic models the production mix the ISSUE names — most rows are
    mostly-cold (anonymous REST-style scoring, filled << L) with a warm
    repeating-customer core riding the stream — so the L-bucket ladder,
    the anonymous lock-free fast path and the async double-buffering all
    carry load. Alongside the headline tx/s it records: the
    assembly-vs-dispatch split on a warm full-L bucket (the BENCH_r05
    1412-vs-13 ms number, through the striped store), overlap efficiency
    (sync wall / overlapped wall on the same mixed batch, same
    executables), per-L-bucket row occupancy, the measured rate of the
    OLD path (full-L, synchronous) on the same box and mix — the honest
    speedup denominator — and the quantized ``seq_q8`` variant's row.
    The scorer builds on the shared ``_hop_buckets`` B ladder, so CPU
    and TPU captures stay comparable with the rest/zoo/quant sections."""
    import threading

    import jax
    import numpy as np

    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.models import seq as seq_mod
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.router.router import Router
    from ccfd_tpu.serving.history import SeqScorer

    cfg = Config()
    broker = Broker()
    reg = Registry()
    engine = build_engine(cfg, broker, reg, None)
    L = 32
    bucket = 4096
    # L=1 serves the pure-cold (anonymous) row alone — its whole context;
    # 8 catches short histories; full L the warm core
    len_buckets = (1, 8)
    hot_customers = 2048
    cold_fraction = 0.7  # anonymous one-shot rows (the mostly-cold mix)
    params = seq_mod.init(jax.random.PRNGKey(0))
    scorer = SeqScorer(params, length=L, batch_sizes=_hop_buckets(bucket),
                       max_customers=8192, len_buckets=len_buckets,
                       inflight=2, registry=reg)
    scorer.warmup()
    # the SeqScorer OBJECT is the score_fn: the router detects
    # score_with_ids and feeds decoded records so histories key by
    # customer id (serving/history.py router contract)
    router = Router(cfg, broker, scorer, engine, reg, max_batch=bucket)

    ds = synthetic_dataset(n=8192, fraud_rate=0.01, seed=1)
    recs = [
        ",".join(f"{v:.6g}" for v in ds.X[i]).encode()
        for i in range(len(ds.X))
    ]
    rng = np.random.default_rng(0)
    cold_mask = rng.random(len(recs)) < cold_fraction
    # CSV records key histories by the bus key; a None key decodes to an
    # anonymous row (scored cold, never stored)
    keys = [None if cold_mask[i] else i % hot_customers
            for i in range(len(recs))]

    stop = threading.Event()

    def feed():
        i = 0
        while not stop.is_set():
            backlog = sum(broker.end_offsets(cfg.kafka_topic))
            if backlog - router._c_in.value() > 50_000:
                time.sleep(0.002)
                continue
            j = i % 4096
            broker.produce_batch(cfg.kafka_topic, recs[j:j + 2048],
                                 keys[j:j + 2048])
            i += 2048

    th_feed = threading.Thread(target=feed, daemon=True)
    th_feed.start()
    th = router.start(poll_timeout_s=0.01)
    budget = max(3.0, seconds)
    time.sleep(budget)
    tx = router._c_in.value()
    stop.set()
    router.stop()
    th.join(timeout=30)

    # per-L-bucket row occupancy, sampled NOW — the counters describe the
    # pipeline run's production-shaped mix; the measurement sections
    # below drive the same registry-wired scorer and would pollute them
    c_rows = reg.counter("seq_bucket_rows_total", "")
    l_bucket_rows = {
        str(lb): int(c_rows.value(labels={"l_bucket": str(lb)}))
        for lb in scorer.len_buckets
    }

    # assembly-vs-dispatch split on one warm full-L bucket through the
    # SAME (now warm) striped store: prepare() is the host-side history
    # gather, the jitted full-L apply is the device dispatch — the
    # BENCH_r05 comparison point (1412 ms dispatch / 13 ms assembly)
    ids_warm = [i % hot_customers for i in range(bucket)]
    x = np.ascontiguousarray(ds.X[:bucket], np.float32)
    assembly_s = _median_time(lambda: scorer.store.prepare(ids_warm, x))
    hist, _tok = scorer.store.prepare(ids_warm, x)
    jax.block_until_ready(scorer._apply(scorer.params, hist))  # compiled
    dispatch_s = _median_time(
        lambda: jax.block_until_ready(scorer._apply(scorer.params, hist))
    )

    # overlap efficiency on one representative MIXED batch: identical
    # executables and store, inflight toggled — sync wall / async wall
    ids_mix = [None if cold_mask[i] else i % hot_customers
               for i in range(bucket)]
    scorer.inflight = 0
    sync_s = _median_time(lambda: scorer.score(x, ids_mix))
    scorer.inflight = 2
    wall_s = _median_time(lambda: scorer.score(x, ids_mix))
    mixed_tx_s = bucket / wall_s

    # the OLD path on the same box, same mix: full-L only, synchronous —
    # the denominator that makes the rework's speedup a measured number
    full = SeqScorer(params, length=L, batch_sizes=_hop_buckets(bucket),
                     max_customers=8192, len_buckets=(), inflight=0)
    jax.block_until_ready(full._apply(full.params, hist))  # compile full L
    full.score(x, ids_mix)  # warm its store like the live one
    full_s = _median_time(lambda: full.score(x, ids_mix))

    # the r05-EQUIVALENT path: full `seq.apply` graph (no readout
    # optimization), bf16, synchronous, every row padded to full L — the
    # serving loop BENCH_r05 measured at 5,461 tx/s, reproduced on this
    # box and mix so the acceptance's >=4x is denominated honestly
    # (full_l_sync above isolates bucketing+overlap; this adds back the
    # graph-level readout win)
    import jax.numpy as jnp

    old = SeqScorer(params, length=L, batch_sizes=_hop_buckets(bucket),
                    max_customers=8192, len_buckets=(), inflight=0)
    old._apply = lambda p, xs: seq_mod.apply(p, xs, jnp.bfloat16)
    old.score(x, ids_mix)  # warm + compile the old executable set
    old_s = _median_time(lambda: old.score(x, ids_mix))

    # quantized variant (ops/seq_quant.py): same mixed batch through the
    # int8 graph — rate plus prob delta vs the champion on identical
    # cold contexts (its serving admission is the lifecycle shadow gate,
    # tests/test_seq_lifecycle.py; CPU captures carry accuracy, TPU speed)
    from ccfd_tpu.ops.seq_quant import quantize_seq

    q8 = SeqScorer(quantize_seq(params), length=L,
                   batch_sizes=_hop_buckets(bucket), max_customers=8192,
                   len_buckets=len_buckets, inflight=2)
    q8.score(x, ids_mix)  # warm + compile
    q8_s = _median_time(lambda: q8.score(x, ids_mix), k=3)
    p_champ = scorer.host_score(x[:1024])
    p_q8 = q8.host_score(x[:1024])
    return {
        "tx_s": round(tx / budget, 1),
        "seq_len": L,
        "bucket": bucket,
        "len_buckets": list(scorer.len_buckets),
        "cold_fraction": cold_fraction,
        "customers": len(scorer.store),
        "assembly_ms": round(assembly_s * 1e3, 3),
        "dispatch_ms": round(dispatch_s * 1e3, 3),
        "dispatch_over_assembly": (round(dispatch_s / assembly_s, 1)
                                   if assembly_s else None),
        # the overlapped-batch numbers the acceptance reads
        "wall_ms": round(wall_s * 1e3, 3),
        "sync_wall_ms": round(sync_s * 1e3, 3),
        "overlap_efficiency": round(sync_s / wall_s, 3) if wall_s else None,
        "assembly_fraction": (round(assembly_s / wall_s, 3)
                              if wall_s else None),
        "mixed_batch_tx_s": round(mixed_tx_s, 1),
        "full_l_sync_tx_s": round(bucket / full_s, 1),
        "speedup_vs_full_l": round(full_s / wall_s, 2) if wall_s else None,
        "r05_path_tx_s": round(bucket / old_s, 1),
        "speedup_vs_r05_path": (round(old_s / wall_s, 2)
                                if wall_s else None),
        "l_bucket_rows": l_bucket_rows,
        "quantized": {
            "tx_s": round(bucket / q8_s, 1),
            "max_prob_delta": round(
                float(np.abs(p_champ - p_q8).max()), 4),
        },
    }


def _bench_seq(seconds):
    """Long-context member of the model zoo: the per-customer history
    transformer (models/seq.py). Scores (B, L, 30) histories; when >1
    device is visible the histories shard over the mesh and BOTH
    sequence-parallel strategies run — ring attention (ppermute rotation,
    ops/ring_attention.py) and ulysses (all-to-all head/sequence reshard,
    ops/ulysses.py) — so their tradeoff is a recorded number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ccfd_tpu.models import seq

    n_dev = len(jax.devices())
    B, L = 256, 64
    params = seq.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, L, 30)), jnp.float32)

    def measure(attn, budget_s):
        @jax.jit
        def step(p, xx):
            return jax.nn.sigmoid(
                seq.logits(p, xx, jnp.bfloat16, attention_fn=attn)
            )

        out = step(params, x)
        jax.block_until_ready(out)
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < budget_s:
            out = step(params, x)
            n += B
        jax.block_until_ready(out)
        return round(n / (time.perf_counter() - t0), 1)

    result = {"batch": B, "seq_len": L, "devices": n_dev}
    strategies: list = [("single_device", None)]
    if n_dev > 1 and n_dev % 2 == 0:
        from ccfd_tpu.ops.ring_attention import ring_attention
        from ccfd_tpu.ops.ulysses import ulysses_attention
        from ccfd_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(model_parallel=2)
        strategies = [
            ("ring", lambda q, k, v: ring_attention(q, k, v, mesh, "model")),
            ("ulysses",
             lambda q, k, v: ulysses_attention(q, k, v, mesh, "model")),
        ]
    budget = max(0.5, seconds / len(strategies))
    for name, attn in strategies:
        result[f"histories_s_{name}"] = measure(attn, budget)
    # headline number: the best strategy measured
    result["histories_s"] = max(
        v for k, v in result.items() if k.startswith("histories_s_")
    )
    return result


def _bench_replay(seconds):
    """Bulk replay & backtest plane (ROADMAP round 17): re-score a
    recorded window through the LIVE bus -> router -> scorer path at
    ``bulk`` priority while live traffic keeps flowing, with the
    burn-rate engine armed. The row is the sustained re-score rate over
    repeated window passes — never a single warmup-shaped pass — next to
    the live lane's fast-window breach count, which must stay zero (the
    overload plane's bulk ceiling is the mechanism under test) and the
    parity tally (every pass must re-produce the recorded verdicts
    byte-stable; a bench that scores fast but diverges measures a bug)."""
    import tempfile
    import threading

    import jax
    import numpy as np

    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.observability.audit import AuditLog
    from ccfd_tpu.observability.slo import SLOEngine
    from ccfd_tpu.parallel.partition import params_fingerprint
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.replay.service import ReplayService, ReplayVerdictTap
    from ccfd_tpu.router.router import Router
    from ccfd_tpu.runtime.overload import OverloadControl
    from ccfd_tpu.serving.scorer import Scorer

    state = tempfile.mkdtemp(prefix="ccfd_bench_replay_")
    # short burn windows so the fast-window verdict lands inside the
    # bench budget; targets carry the replay_smoke CI-box margin — the
    # row gates on "zero breaches WHILE replay saturates bulk", not on
    # this box hitting the production latency objective
    cfg = Config(confidence_threshold=1.0, slo_windows="2,4,12",
                 slo_e2e_target_ms=250.0, slo_rest_target_ms=250.0)
    regs = {n: Registry() for n in ("router", "kie", "slo", "replay")}
    slo_engine = SLOEngine.from_config(cfg, regs, regs["slo"])

    broker = Broker(default_partitions=2)
    kie = build_engine(cfg, broker, regs["kie"], None)
    scorer = Scorer(model_name="mlp", batch_sizes=(128, 1024, 4096),
                    host_tier_rows=0)
    scorer.warmup()
    fp = params_fingerprint(jax.tree.map(np.asarray, scorer.params))
    overload = OverloadControl.from_config(cfg, regs["router"],
                                           max_batch=1024, workers=1)
    audit = AuditLog(dir=os.path.join(state, "audit"),
                     registry=regs["router"])
    audit.lineage_fn = lambda: ("bench", fp)
    tap = ReplayVerdictTap(inner=audit, registry=regs["replay"])
    router = Router(cfg, broker, scorer.score, kie, regs["router"],
                    max_batch=1024, overload=overload, audit=tap)
    svc = ReplayService(cfg, broker, audit, tap=tap,
                        registry=regs["replay"],
                        state_dir=os.path.join(state, "replay"),
                        overload=overload,
                        lineage_fn=lambda: ("bench", fp))

    # record the window through the live stack (capture armed by svc)
    n_rows = 2048
    ds = synthetic_dataset(n=n_rows, fraud_rate=0.01, seed=17)
    rows = [",".join(f"{v:.6g}" for v in ds.X[i]).encode()
            for i in range(n_rows)]
    broker.produce_batch(cfg.kafka_topic, rows,
                         [f"tx-{i:05d}" for i in range(n_rows)])
    while router.step() > 0:
        pass
    audit.flush()
    recs = audit.scan_window()
    if len(recs) != n_rows:
        raise RuntimeError(
            f"bench section replay: recorded {len(recs)}/{n_rows} rows")
    since, until = int(recs[0]["seq"]), int(recs[-1]["seq"])

    # live lane keeps flowing for the whole re-drive; burn engine ticks
    stop = threading.Event()
    live_rows = [0]

    def drive():
        i, next_tick = 0, 0.0
        while not stop.is_set():
            broker.produce_batch(cfg.kafka_topic, rows[:16],
                                 [f"live-{i}-{j}" for j in range(16)])
            live_rows[0] += 16
            i += 1
            router.step()
            now = time.monotonic()
            if now >= next_tick:
                slo_engine.tick()
                next_tick = now + 0.3
            time.sleep(0.005)

    driver = threading.Thread(target=drive, daemon=True,
                              name="bench-replay-drive")
    driver.start()

    budget = max(2.0, seconds)
    replayed = match = divergence = passes = 0
    parity = True
    t0 = time.perf_counter()
    while passes == 0 or time.perf_counter() - t0 < budget:
        rep = svc.run_window(since, until,
                             window_id=f"bench-{passes}", resume=False)
        passes += 1
        replayed += rep["replayed"]
        match += rep["match"]
        divergence += rep["divergence"]
        parity = parity and rep["parity"]
    elapsed = time.perf_counter() - t0
    # cross the fast burn window before reading the breach verdict
    time.sleep(max(1.0, 1.5 * slo_engine.windows[0][0]))
    status = slo_engine.tick()
    stop.set()
    driver.join(timeout=10)
    svc.stop()
    router.close()
    broker.close()

    breaches = sum(int(s.get("breaches", 0))
                   for s in status["slos"].values())
    return {
        "tx_s": round(replayed / elapsed, 1),
        "window_rows": n_rows,
        "passes": passes,
        "replayed": replayed,
        "match": match,
        "divergence": divergence,
        "parity": parity,
        "bulk_ceiling": cfg.replay_bulk_ceiling,
        "bulk_ceiling_restored": overload.bulk_ceiling == 1.0,
        "live_rows": live_rows[0],
        "live_fast_breaches": breaches,
        "live_slo_green": not any(
            s.get("breaching") or s.get("breaches")
            for s in status["slos"].values()),
    }


def main() -> None:
    _arm_watchdog()
    import jax
    import numpy as np

    from ccfd_tpu.utils.backend import require_backend
    from ccfd_tpu.utils.compile_cache import enable as _enable_compile_cache

    on_tpu = require_backend() == "tpu"  # no chip and no CPU request: raises
    _enable_compile_cache()  # repeat runs reuse the compiled executables

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import mlp
    from ccfd_tpu.serving.scorer import Scorer

    batch = int(os.environ.get("CCFD_BENCH_BATCH", "131072"))
    seconds = float(os.environ.get("CCFD_BENCH_SECONDS", "3"))
    depth = int(os.environ.get("CCFD_BENCH_PIPELINE", "2"))
    lat_batch = int(os.environ.get("CCFD_BENCH_LATENCY_BATCH", "4096"))
    skip = set(os.environ.get("CCFD_BENCH_SKIP", "").split(","))
    # device telemetry (observability/device.py): every scorer below
    # stages through the process-default plane; sections get h2d/peak-
    # memory rows on device (CCFD_BENCH_DEVICE=1 forces rows on cpu)
    meter = _DeviceMeter(
        attach_rows=on_tpu or os.environ.get("CCFD_BENCH_DEVICE") == "1")

    ds = synthetic_dataset(n=max(batch, lat_batch, 4096), fraud_rate=0.01, seed=0)
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    # push probabilities to a trained-model-like range so the pipeline
    # section's fired mix is realistic (~1% fraud), not the untrained ~50%
    import jax.numpy as jnp

    pipe_params = dict(params)
    pipe_params["layers"] = [dict(l) for l in params["layers"]]
    pipe_params["layers"][-1] = dict(pipe_params["layers"][-1])
    pipe_params["layers"][-1]["b"] = jnp.asarray([-4.0], jnp.float32)

    scorer = Scorer(
        model_name="mlp",
        params=params,
        batch_sizes=(16, 128, 1024, lat_batch, batch),
        compute_dtype="bfloat16",
    )
    scorer.warmup()
    # services tune gc AFTER warmup (cli.py) so compiled executables/params
    # land in the frozen permanent generation; the bench mirrors that
    from ccfd_tpu.utils.gctune import tune_for_service

    tune_for_service()
    tx_per_s, p50, p99 = _bench_scorer(scorer, ds.X, batch, lat_batch, seconds, depth)
    _PARTIAL.update({
        "value": round(tx_per_s, 1), "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3), "fused_active": scorer.fused,
        "platform_measured": jax.default_backend(),
    })
    meter.section(None)  # reset the per-section H2D baseline past warmup

    fused_ab = None
    if "ab" not in skip and (on_tpu or os.environ.get("CCFD_BENCH_AB")):
        # A/B the two scorer paths on identical work so the Pallas kernel's
        # effect is a recorded number, not a docstring claim
        ab = {}
        for label, use_fused in (("fused", True), ("xla", False)):
            s = Scorer(
                model_name="mlp", params=params,
                batch_sizes=(16, 128, 1024, lat_batch, batch),
                compute_dtype="bfloat16", use_fused=use_fused,
            )
            s.warmup()
            if s.fused != use_fused:
                raise RuntimeError(f"bench section ab: {label} scorer came "
                                   f"up with fused={s.fused}")
            r_tx, r_p50, r_p99 = _bench_scorer(
                s, ds.X, batch, lat_batch, max(1.0, seconds / 2), depth
            )
            ab[label] = {"tx_s": round(r_tx, 1), "p50_ms": round(r_p50, 3),
                         "p99_ms": round(r_p99, 3)}
        fused_ab = ab
        _PARTIAL["fused_ab"] = fused_ab
        meter.section(None)

    rest = None
    rest_python = None
    if "rest" not in skip:
        # one read for BOTH transports: drifting defaults between the two
        # call sites would shape the native-vs-python A/B differently
        rest_clients = int(os.environ.get("CCFD_BENCH_REST_CLIENTS", "4"))
        rest_rows = int(os.environ.get("CCFD_BENCH_REST_ROWS", "128"))
        rest = _bench_rest(
            params, lat_batch, max(2.0, seconds), rest_clients, rest_rows,
        )
        _PARTIAL["rest"] = rest
        meter.section(rest)
        if rest.get("transport") == "NativeFront":
            # transport A/B: the same load through the Python server, so
            # the native front's effect is a recorded number
            rest_python = _bench_rest(
                params, lat_batch, max(2.0, seconds / 2),
                rest_clients, rest_rows, native=False,
            )
            _PARTIAL["rest_python_transport"] = rest_python
        # request-latency FLOOR: one client, one row per request — the
        # online-decision RTT a single transaction pays with zero queueing,
        # the other end of the SLO from the throughput-shaped point above
        floor = _bench_rest(params, lat_batch, max(2.0, seconds / 2),
                            n_clients=1, rows_per_req=1)
        _PARTIAL["rest_latency_floor"] = {
            k: floor[k] for k in ("p50_ms", "p99_ms", "requests_s",
                                  "transport", "errors", "host_tier_rows")
        }

    pipeline = None
    if "pipeline" not in skip:
        # fresh H2D baseline: the transport-A/B and latency-floor REST
        # benches above are unmetered and must not bill this section
        meter.section(None)
        pipeline = _bench_pipeline(pipe_params, max(2.0, seconds))
        _PARTIAL["pipeline"] = pipeline
        meter.section(pipeline)

    mesh_res = None
    if "mesh" not in skip:
        mesh_res = _bench_mesh(
            params, min(batch, 65536), max(1.0, seconds / 2), depth
        )
        if mesh_res is not None:
            _PARTIAL["mesh"] = mesh_res
            meter.section(mesh_res)

    retrain_res = None
    if "retrain" not in skip:
        retrain_res = _bench_retrain(max(1.0, seconds / 2))
        _PARTIAL["retrain"] = retrain_res
        meter.section(retrain_res)

    seq_res = None
    if "seq" not in skip:
        seq_res = _bench_seq(max(1.0, seconds / 2))
        _PARTIAL["seq"] = seq_res
        meter.section(seq_res)

    if "seq_pipeline" not in skip:
        _PARTIAL["seq_pipeline"] = _bench_seq_pipeline(max(3.0, seconds))
        meter.section(_PARTIAL["seq_pipeline"])

    if "replay" not in skip:
        meter.section(None)  # replay builds its own full stack: fresh H2D
        _PARTIAL["replay"] = _bench_replay(max(2.0, seconds / 2))
        meter.section(_PARTIAL["replay"])

    zoo_res = None
    if "zoo" not in skip:
        zoo_res = _bench_zoo(max(1.0, seconds / 3))
        _PARTIAL["zoo"] = zoo_res

    quant_res = None
    if "quant" not in skip and (on_tpu or os.environ.get("CCFD_BENCH_QUANT")):
        meter.section(None)  # zoo traffic is unmetered: reset the baseline
        quant_res = _bench_quant(params, ds.X[:batch], max(1.0, seconds / 2))
        _PARTIAL["quant_int8"] = quant_res
        meter.section(quant_res)

    if "fused_decision" not in skip:
        meter.section(None)  # fresh H2D baseline for the A/B
        _PARTIAL["fused_decision"] = _bench_fused_decision(
            params, ds.X, max(1.0, seconds / 2), batch,
        )
        meter.section(_PARTIAL["fused_decision"])

    if "roofline" not in skip:
        _PARTIAL["roofline"] = _bench_roofline(
            scorer, params, ds.X, lat_batch, tx_per_s, rest, quant_res,
        )

    # the e2e p99 the north star talks about is the REST predict hop when
    # measured; the raw scorer-hop p99 otherwise
    p99_e2e = rest["p99_ms"] if rest else p99
    result = {
        "metric": "end_to_end_scoring_throughput_mlp_bf16",
        "value": round(tx_per_s, 1),
        "unit": "tx/s",
        "vs_baseline": round(tx_per_s / NORTH_STAR_TX_S, 3),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "p99_e2e_ms": round(p99_e2e, 3),
        "p99_vs_target": round(NORTH_STAR_P99_MS / max(p99_e2e, 1e-9), 3),
        "latency_batch": lat_batch,
        "fused_active": scorer.fused,
        "platform": jax.default_backend(),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }
    # section results flow through _PARTIAL (written as each completes for
    # the watchdog); the final result picks them up from ONE place instead
    # of re-enumerating every section
    headline_only = {"value", "p50_ms", "p99_ms", "fused_active",
                     "platform_measured"}
    result.update(
        {k: v for k, v in _PARTIAL.items() if k not in headline_only}
    )

    print(json.dumps(result))
    # LAST line: a compact summary that survives the driver's capture
    # window.  BENCH_r03/r04.json both recorded "parsed": null because the
    # full record above is one multi-KB line and the driver keeps only the
    # final ~2000 chars — the tail held a fragment.  This line is the same
    # headline plus per-section extracts, bounded well under that window,
    # so the round's official artifact always ends with one complete JSON
    # object (VERDICT r4 item 3).
    print(json.dumps(compact_summary(result)), flush=True)


def compact_summary(result: dict) -> dict:
    """Headline + per-section extracts, guaranteed small (≤ ~1.2 KB).

    Keeps the keys the driver contract reads (metric / value / unit /
    vs_baseline / platform / device) and one-level numeric extracts of
    each measured section; drops free-form sub-trees (latency grids,
    per-client detail) whose size is unbounded."""
    s = {k: result.get(k) for k in (
        "metric", "value", "unit", "vs_baseline", "p50_ms", "p99_ms",
        "p99_e2e_ms", "p99_vs_target", "fused_active", "platform", "device",
    ) if k in result}
    s["summary"] = True  # full record precedes this line

    def pick(section: str, *keys: str) -> None:
        sec = result.get(section)
        if not isinstance(sec, dict):
            return
        s[section] = {k: sec[k] for k in keys if k in sec}

    pick("rest", "tx_s", "requests_s", "p50_ms", "p99_ms", "transport",
         "rows_per_request", "host_tier_rows", "errors")
    pick("pipeline", "tx_s", "paced_rate_tx_s", "p50_ms", "p99_ms",
         "workers", "workers_cpus", "shadow")
    pick("mesh", "tx_s", "single_tx_s", "devices", "scaling_x",
         "efficiency", "virtual_devices", "sharding_overhead_x")
    pick("retrain", "steps_s", "labels_s", "final_loss")
    pick("seq", "histories_s", "batch", "seq_len")
    pick("seq_pipeline", "tx_s", "assembly_ms", "dispatch_ms",
         "assembly_fraction", "wall_ms", "overlap_efficiency",
         "speedup_vs_full_l", "full_l_sync_tx_s", "r05_path_tx_s",
         "speedup_vs_r05_path", "cold_fraction")
    pick("quant_int8", "tx_s", "fused_tx_s", "preq_tx_s", "batch")
    pick("fused_decision", "speedup", "throughput_speedup",
         "staged_decide_us", "fused_decide_us", "staged_tx_s",
         "fused_tx_s", "parity_bit_exact", "staged_fallbacks",
         "staged_host_syncs_per_batch", "fused_host_syncs_per_batch")
    pick("replay", "tx_s", "passes", "parity", "divergence",
         "live_fast_breaches", "live_slo_green", "bulk_ceiling")
    pick("roofline", "wire_mb_s", "h2d_mb_s_measured", "mfu_pct", "bound")
    zoo = result.get("zoo")
    if isinstance(zoo, dict):
        s["zoo"] = {
            name: fam.get("tx_s") for name, fam in zoo.items()
            if isinstance(fam, dict)
        }
    return s


if __name__ == "__main__":
    main()
