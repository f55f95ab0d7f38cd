#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start, and serve from the
device, on one TPU chip?

    python3 chip_smoke.py                    # on the chip: must exit 0
    JAX_PLATFORMS=cpu python3 chip_smoke.py  # same phases, kernels in
                                             # interpret mode, to debug;
                                             # exits non-zero: not a chip

One process (a chip belongs to one process at a time), no network, nothing
read that git would not commit. It drives the system through the entry
points a user calls, at the full width of the flagship — what
``python -m ccfd_tpu serve`` serves with no options: ``mlp``
30 -> 256 -> 256 -> 1, bf16, the committed ``checkpoints/step_1200``, the
default bucket ladder, the Pallas fused kernel:

1. **REST** — ``cli.start_server`` (Scorer -> warmup -> PredictionServer),
   then Seldon ``ndarray`` requests whose sizes land in every bucket, each
   answer compared with the float32 host forward (``mlp.apply_numpy``).
2. **Pipeline** — ``cli.run_demo`` (producer -> bus -> router -> scorer ->
   engine, the online trainer publishing params) for 100,000
   transactions, conserved.
3. **Heal** — the device supervisor's canary/telemetry ticks over the
   served scorer: the safety code must see a healthy device.
4. **Zoo** — every other served family (``mlp_q8`` fused int8 wire /
   fused f32 wire / XLA, ``gbt``, ``gbt_mxu``, ``seq`` and ``seq_q8`` at
   L = 64, the fused-decision grid): warm-up plus one scored batch at the
   smallest and the largest bucket, against its host or XLA reference.

Nothing on the host may stand in for the device: it fails unless every
bucket's dispatch count rose by exactly the requests sent to it with
``fused`` true, and the host tier, host fallback, dispatch timeouts, the
native front's inline model, the router's degraded tiers and serving-stage
compiles after warm-up are all zero. A phase that raises is not caught.

Output: progress and one ``CHIP_SMOKE {...}`` report line (also written to
``chiprun_out/chip_smoke.json``); then, only when everything held on a
TPU, the LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Times printed here are set-up and wall times for the record, not metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import threading
import time
import urllib.request

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.abspath(__file__))

# probability bands, as the parity tests assert them: the bf16 kernel
# against the float32 forward (tests/test_serving.py, test_ops.py), and an
# int8 graph against the host forward of the same int8 tree
TOL_BF16 = 2e-2
TOL = 1e-2
REQUEST_ROWS = (1, 16, 100, 1000, 4096, 8192)  # 8192 = the front's row cap
# ~0.7% of the demo's traffic routes to the fraud process and the online
# trainer takes its first step at 256 labels (Config.retrain_min_labels)
PIPELINE_TX = 100_000
ZOO_BUCKETS = (16, 16384)
SEQ_BUCKETS = (16, 4096)  # SeqScorer's default B ladder, both ends
BUDGET_S = 1100.0  # the contract allows 1200 s, compilation included


class Checks:
    """Every assertion the smoke makes, printed as it is made; failures
    collect so one chip run reports all of them."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: object = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}" +
              (f": {detail}" if detail != "" else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def close(self, name: str, got, want, tol: float) -> float:
        import numpy as np

        got, want = np.asarray(got), np.asarray(want)
        diff = float(np.abs(got - want).max()) if got.shape == want.shape \
            else float("inf")
        self(name, bool(np.isfinite(got).all()) and diff <= tol,
             f"shape {got.shape}, max |diff| {diff:.2e} (tol {tol:g})")
        return diff


def _serving_compiles(prof) -> int:
    from ccfd_tpu.runtime.heal import NON_SERVING_COMPILE_STAGES

    return sum(n for stage, n in prof.compile_counts().items()
               if stage not in NON_SERVING_COMPILE_STAGES)


@functools.cache
def _rows():
    """The smoke's one pool of seeded rows (largest bucket's worth), shaped
    like what the checkpoint was trained on, fraud rows first so every
    slice of it — even the 1-row request — spans the probability range."""
    import numpy as np

    from ccfd_tpu.data.surrogate import kaggle_surrogate

    n = max(ZOO_BUCKETS)
    ds = kaggle_surrogate(n=8 * n, seed=21)
    return np.ascontiguousarray(
        ds.X[np.argsort(-ds.y, kind="stable")][:n], np.float32)


def phase_rest(check: Checks, report: dict, platform: str, prof) -> object:
    """The REST server the way ``serve`` builds it; returns the scorer."""
    import jax
    import numpy as np

    from ccfd_tpu.cli import _restore_mlp_checkpoint, start_server
    from ccfd_tpu.config import Config
    from ccfd_tpu.models import mlp

    print("== rest: cli.start_server, flagship mlp bf16 ==", flush=True)
    cfg = Config.from_env()
    params = _restore_mlp_checkpoint(os.path.join(ROOT, "checkpoints"))
    check("committed checkpoint restored", params is not None)
    # auto-selection is the chip's; the CPU debug run opts the (interpreted)
    # kernel in so the same dispatch path runs, and switches the front's
    # CPU-only inline scoring off so requests reach the jit there too
    kw = {} if platform == "tpu" else {"use_fused": True}
    if platform != "tpu":
        os.environ["CCFD_INLINE_ROWS"] = "0"
    srv, port = start_server(cfg, params, "127.0.0.1", 0, **kw)
    scorer = srv.scorer
    setup_s = time.perf_counter() - T0
    transport = type(srv._httpd).__name__
    before = dict(scorer.executable_grid()["dispatches"])
    compiles_warm = _serving_compiles(prof)
    host = jax.tree.map(np.asarray, params)
    x_all = _rows()
    expect: dict[str, int] = {}
    worst = 0.0
    try:
        for n in REQUEST_ROWS:
            x = x_all[:n]
            body = json.dumps({"data": {"ndarray": x.tolist()}}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v0.1/predictions", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                doc = json.loads(resp.read())
            got = np.asarray(doc["data"]["ndarray"], np.float64)[:, 1]
            worst = max(worst, check.close(
                f"POST {n} rows -> bucket {scorer.bucket(n)} vs f32 host "
                "forward", got, mlp.apply_numpy(host, x), TOL_BF16))
            b = str(scorer.bucket(n))
            expect[b] = expect.get(b, 0) + 1
        front = srv._httpd
        inline = bool(getattr(front, "host_model_active", False))
    finally:
        srv.stop()
    grid = scorer.executable_grid()
    rose = {b: grid["dispatches"].get(b, 0) - before.get(b, 0)
            for b in map(str, grid["batch_sizes"])}
    check("every bucket's device dispatches rose by the requests sent to it",
          rose == expect and all(rose.values()), f"{rose} (sent {expect})")
    check("scorer.fused", grid["fused"] is True)
    check("host_tier_rows == 0", grid["host_tier_rows"] == 0,
          grid["host_tier_rows"])
    check("host_fallback_scores == 0", scorer.host_fallback_scores == 0,
          scorer.host_fallback_scores)
    check("dispatch_timeouts == 0", scorer.dispatch_timeouts == 0,
          scorer.dispatch_timeouts)
    check("native front scored nothing inline", not inline, transport)
    after = _serving_compiles(prof)
    check("zero serving-stage compiles after warm-up",
          after == compiles_warm, prof.compile_counts())
    from ccfd_tpu import native

    report["rest"] = {
        "transport": transport, "fused": grid["fused"],
        # the library the front loaded: named by the digest of the sources
        # it was built from, on this host
        "native_library": os.path.basename(native._build() or ""),
        "batch_sizes": grid["batch_sizes"], "dispatches": rose,
        "host_tier_rows": grid["host_tier_rows"],
        "host_fallback_scores": scorer.host_fallback_scores,
        "dispatch_timeouts": scorer.dispatch_timeouts,
        "dispatch_deadline_s": scorer.dispatch_deadline_s,
        "serving_compiles_after_warmup": after - compiles_warm,
        "max_abs_diff_vs_f32_host": worst,
        "setup_s": round(setup_s, 1),
    }
    return scorer


def phase_pipeline(check: Checks, report: dict, platform: str) -> None:
    from ccfd_tpu.cli import run_demo

    print(f"== pipeline: cli.run_demo, {PIPELINE_TX} transactions ==",
          flush=True)
    s = run_demo(argparse.Namespace(
        transactions=PIPELINE_TX, rate=None, train_steps=200,
        reply_timeout=2.0, drain_s=120.0, wire_format="dict", seed=0))
    routed = s["standard_routed"] + s["fraud_routed"]
    check(f"incoming >= {PIPELINE_TX} and == standard + fraud",
          s["transactions"] >= PIPELINE_TX and s["transactions"] == routed,
          f"{s['transactions']} in, {s['standard_routed']} standard + "
          f"{s['fraud_routed']} fraud")
    check("online trainer published params", s["retrain_swaps"] >= 1,
          s["retrain_swaps"])
    check("router_degraded_total == 0", s["router_degraded"] == 0,
          s["router_degraded"])
    sc = s["scorer"]
    check("pipeline rows reached the device",
          sum(sc["dispatches"].values()) > 0 and sc["host_tier_rows"] == 0
          and sc["host_fallback_scores"] == 0
          and sc["dispatch_timeouts"] == 0, sc)
    if platform == "tpu":  # the demo takes the auto selection as it comes
        check("pipeline scorer.fused", sc["fused"] is True)
    report["pipeline"] = s


def phase_heal(check: Checks, report: dict, scorer, prof) -> None:
    """The operator's device supervisor over the served scorer: canary
    dispatches under its deadline, allocator pressure, compile-storm rate.
    On a healthy chip it must never leave ``healthy``."""
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.observability.device import DeviceTelemetry
    from ccfd_tpu.runtime.heal import DeviceSupervisor

    print("== heal: DeviceSupervisor ticks ==", flush=True)
    reg = Registry()
    sup = DeviceSupervisor(scorer, registry=reg, telemetry=DeviceTelemetry(),
                           profiler=prof)
    states = []
    for _ in range(5):
        states.append(sup.tick())
        time.sleep(0.2)
    gauge = reg.gauge("ccfd_device_health")
    healthy = gauge.value({"device": sup.device, "state": "healthy"})
    check("ccfd_device_health never left healthy",
          set(states) == {"healthy"} and healthy == 1.0,
          f"{states} on {sup.device}; reasons {sup.status()['reasons']}")
    report["heal"] = {"device": sup.device, "states": states}


def phase_zoo(check: Checks, report: dict, platform: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ccfd_tpu.cli import _restore_mlp_checkpoint
    from ccfd_tpu.config import Config
    from ccfd_tpu.models import seq as seq_mod
    from ccfd_tpu.models import trees
    from ccfd_tpu.ops import quant, seq_quant
    from ccfd_tpu.router.rules import PROBA_FIELD, Condition, Rule, RuleSet
    from ccfd_tpu.serving.fused import FusedDecisionScorer
    from ccfd_tpu.serving.history import SeqScorer
    from ccfd_tpu.serving.scorer import Scorer

    print("== zoo: every other served family, smallest + largest bucket ==",
          flush=True)
    zoo: dict[str, dict] = {}
    x = _rows()
    params = _restore_mlp_checkpoint(os.path.join(ROOT, "checkpoints"))

    def row_family(label: str, model: str, p, ref_fn, tol: float,
                   use_fused: bool, want_wire: bool | None = None) -> Scorer:
        s = Scorer(model_name=model, params=p, batch_sizes=ZOO_BUCKETS,
                   use_fused=use_fused, host_tier_rows=0)
        s.warmup()
        diffs = [check.close(f"{label} bucket {b}", s.score(x[:b]),
                             ref_fn(x[:b]), tol) for b in ZOO_BUCKETS]
        grid = s.executable_grid()
        check(f"{label} served as built",
              grid["fused"] is use_fused
              and (want_wire is None or grid["int8_wire"] is want_wire)
              and set(grid["dispatches"]) == set(map(str, ZOO_BUCKETS))
              and s.host_fallback_scores == 0 and s.dispatch_timeouts == 0,
              {k: grid[k] for k in ("fused", "int8_wire", "dispatches")})
        zoo[label] = {"max_abs_diff": max(diffs), "fused": grid["fused"],
                      "int8_wire": grid["int8_wire"]}
        return s

    # mlp_q8: the host forward of the SAME int8 tree is the reference
    qp = quant.quantize_mlp(params)
    q_host = jax.tree.map(np.asarray, qp)
    q_ref = lambda xb: quant.apply_numpy(q_host, xb)  # noqa: E731
    row_family("mlp_q8 fused int8 wire", "mlp_q8", qp, q_ref, TOL, True, True)
    os.environ["CCFD_Q8_WIRE"] = "f32"  # read at construction
    try:
        row_family("mlp_q8 fused f32 wire", "mlp_q8", qp, q_ref, TOL, True,
                   False)
    finally:
        del os.environ["CCFD_Q8_WIRE"]
    row_family("mlp_q8 xla", "mlp_q8", qp, q_ref, TOL, False)

    # trees: seeded random splits (an all-inf ensemble would descend one
    # path); both evaluators against the numpy lockstep descent
    rng = np.random.default_rng(21)
    skel = trees.init_empty(n_trees=100, depth=4)
    tp = {
        "feature": jnp.asarray(
            rng.integers(0, 30, skel["feature"].shape), jnp.int32),
        "threshold": jnp.asarray(
            rng.normal(size=skel["threshold"].shape), jnp.float32),
        "leaf": jnp.asarray(
            rng.normal(scale=0.05, size=skel["leaf"].shape), jnp.float32),
        "base": skel["base"],
    }
    t_host = jax.tree.map(np.asarray, tp)
    t_ref = lambda xb: trees.apply_numpy(t_host, xb)  # noqa: E731
    row_family("gbt", "gbt", tp, t_ref, 1e-4, False)
    row_family("gbt_mxu", "gbt_mxu", tp, t_ref, 1e-4, False)

    # seq / seq_q8: no host forward exists; the reference is the model's
    # own full XLA graph at float32 over the SAME assembled histories
    # 128 records: the shortest window whose full-attention block is the
    # Pallas kernel (ops/seq_attention.py), so Mosaic compiles it and the
    # device serves it here at both ends of the B ladder
    sp = seq_mod.init(jax.random.PRNGKey(21))
    length = 128
    for label, p, full in (
        ("seq", sp, seq_mod.apply),
        ("seq_q8", seq_quant.quantize_seq(sp), seq_quant.apply),
    ):
        s = SeqScorer(p, length=length, batch_sizes=SEQ_BUCKETS,
                      max_customers=2 * max(SEQ_BUCKETS))
        s.warmup()
        diffs = []
        next_id = 0
        for b in SEQ_BUCKETS:
            rows = x[:b]
            ids = list(range(next_id, next_id + b))  # fresh: history == [row]
            next_id += b
            hist = np.zeros((b, length, rows.shape[1]), np.float32)
            hist[:, -1] = rows
            ref = np.asarray(full(p, jnp.asarray(hist), jnp.float32))
            diffs.append(check.close(f"{label} B={b} L={length}",
                                     s.score(rows, ids), ref, 0.03))
        grid = s.executable_grid()["grid"]
        check(f"{label} executables hold the attention kernel",
              all(g["attn_kernel"] for g in grid))
        # which wire this proof crossed (serving/history.py::_Program): 128
        # records are 3.75 tiles a row, so these cross as (B, L, F)
        for g in grid:
            print(f"  {label} B={g['b_bucket']} L={g['l_bucket']}: history "
                  "batch crosses as " + ("(B, L*F/128, 128), flat"
                                         if g["flat_wire"] else "(B, L, F)"),
                  flush=True)
        zoo[label] = {"max_abs_diff": max(diffs), "grid": grid}

    # hybrid_moe (KDA + MLA + sparse experts over a tokenised window): the
    # tests' small preset and seeded weights, found by name through the
    # registry. What is held here is the seam (ids, windows, ``filled``,
    # buckets): the served scores against the family's own program called
    # directly on the same windows. How close bfloat16 serving comes to the
    # plain float32 reference is the benchmark's to say, at real widths (at
    # width 64 a token near a routing tie moves a score by 0.03)
    from benchmark.reference import hybrid_moe_f32
    from ccfd_tpu.models import hybrid_moe

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "ling3_small_config.json")) as f:
        small = json.load(f)
    h_cfg = hybrid_moe.HybridConfig.from_dict(small)
    hp = hybrid_moe_f32.make_params(small)
    s = SeqScorer(hp, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=h_cfg, max_customers=64)
    s.warmup()
    rows = x[:16]
    hist = np.zeros((16, 8, rows.shape[1]), np.float32)
    hist[:, -1] = rows
    direct = np.asarray(hybrid_moe.apply_serving(
        hp, hist, np.ones(16, np.int32), h_cfg, jnp.bfloat16)[0])
    zoo["hybrid_moe"] = {
        "max_abs_diff": check.close("hybrid_moe B=16 L=8", s.score(
            rows, list(range(10_000, 10_016))), direct, 1e-6),
        "grid": s.executable_grid()}
    # the family's second model (CCA, the carried router with its skip,
    # scaled residuals, tied head; layers stacked and scanned): the same seam
    from benchmark.reference import cca_moe_f32

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "zaya1_small_config.json")) as f:
        small = json.load(f)
    z_cfg = hybrid_moe.HybridConfig.from_dict(small)
    zp = cca_moe_f32.make_params(small)
    s = SeqScorer(zp, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=z_cfg, max_customers=64)
    s.warmup()
    direct = np.asarray(hybrid_moe.apply_serving(
        zp, hist, np.ones(16, np.int32), z_cfg, jnp.bfloat16)[0])
    zoo["hybrid_moe.zaya"] = {
        "max_abs_diff": check.close("hybrid_moe (zaya) B=16 L=8", s.score(
            rows, list(range(10_000, 10_016))), direct, 1e-6),
        "grid": s.executable_grid()}

    # the family's third model (MLA in every layer with low-rank queries,
    # interleaved YaRN rotary, softmax top 4 over 32 experts of which 8 are
    # held, a shared expert, untied head): the same seam, and every chosen
    # pair served here or counted as another chip's
    from benchmark.reference import mla_moe_f32

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "mistral4_small_config.json")) as f:
        small = json.load(f)
    m_cfg = hybrid_moe.HybridConfig.from_dict(small)
    mp = mla_moe_f32.make_params(small)
    s = SeqScorer(mp, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=m_cfg, max_customers=64)
    s.warmup()
    direct, aux = hybrid_moe.apply_serving(
        mp, hist, np.ones(16, np.int32), m_cfg, jnp.bfloat16)
    check("hybrid_moe (mistral4) served + absent pairs = 4 a token and "
          "layer", int(aux["pairs_served"]) + int(aux["pairs_absent"])
          == 4 * int(aux["routed_tokens"]) * m_cfg.moe_layers)
    zoo["hybrid_moe.mistral4"] = {
        "max_abs_diff": check.close("hybrid_moe (mistral4) B=16 L=8", s.score(
            rows, list(range(10_000, 10_016))), np.asarray(direct), 1e-6),
        "grid": s.executable_grid()}

    # the family's fourth model (four residual streams mixed by
    # Sinkhorn-normalised maps around every sublayer, a leading dense layer
    # listed before the scanned expert layers, sigmoid top 4 with a bias
    # over 16 experts, all held): the same seam, no pair absent, and the
    # stream-to-stream maps doubly stochastic to the steps' residue
    from benchmark.reference import mhc_moe_f32

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "xing4_small_config.json")) as f:
        small = json.load(f)
    x_cfg = hybrid_moe.HybridConfig.from_dict(small)
    xp = mhc_moe_f32.make_params(small)
    s = SeqScorer(xp, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=x_cfg, max_customers=64)
    s.warmup()
    direct, aux = hybrid_moe.apply_serving(
        xp, hist, np.ones(16, np.int32), x_cfg, jnp.bfloat16)
    check("hybrid_moe (xing4_0) four pairs a token and expert layer, none "
          "absent, defect under 0.2", int(aux["pairs_served"])
          == 4 * int(aux["routed_tokens"]) * x_cfg.moe_layers
          and int(aux["pairs_absent"]) == 0
          and 0 < float(aux["hc_defect"]) < 0.2)
    zoo["hybrid_moe.xing4_0"] = {
        "max_abs_diff": check.close("hybrid_moe (xing4_0) B=16 L=8", s.score(
            rows, list(range(10_000, 10_016))), np.asarray(direct), 1e-6),
        "grid": s.executable_grid()}

    # the family's fifth model (a Mamba-2 state-space mixer in four layers
    # of five, two stacks scanned around the one layer of grouped-query
    # attention without positions, top 4 of 12 experts by logit of which 6
    # are held, a shared expert, the four constant multipliers, tied head):
    # the same seam, every chosen pair served here or the other chip's, the
    # scan's chunk in the grid and its running log-decay handed back
    from benchmark.reference import ssm_moe_f32

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "granite4h_small_config.json")) as f:
        small = json.load(f)
    g_cfg = hybrid_moe.HybridConfig.from_dict(small)
    gp = ssm_moe_f32.make_params(small)
    s = SeqScorer(gp, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=g_cfg, max_customers=64)
    s.warmup()
    direct, aux = hybrid_moe.apply_serving(
        gp, hist, np.ones(16, np.int32), g_cfg, jnp.bfloat16)
    check("hybrid_moe (granitemoehybrid) served + absent pairs = 4 a token "
          "and layer, a chunk in every executable, decays below 0",
          int(aux["pairs_served"]) + int(aux["pairs_absent"])
          == 4 * int(aux["routed_tokens"]) * g_cfg.moe_layers
          and all(g["scan_chunk"] == 32 for g in s.executable_grid()["grid"])
          and float(aux["ssm_log_decay_min"]) < 0)
    zoo["hybrid_moe.granitemoehybrid"] = {
        "max_abs_diff": check.close(
            "hybrid_moe (granitemoehybrid) B=16 L=8", s.score(
                rows, list(range(10_000, 10_016))), np.asarray(direct), 1e-6),
        "grid": s.executable_grid()}

    # the Mamba-2 mixer at widths that fill lane tiles, which that preset's
    # 16-wide heads do not (hidden 256, 8 heads of 64, a state of 128, 768
    # tokens, one row padded on the left past the first chunk): Mosaic
    # compiles the scan's kernel (ops/ssd_scan.py) at chunks of 128 and 384
    # and before it the convolution's (ops/short_conv.py: 768 columns from
    # column 512, 24 strips of 32 tokens), and the device's answer is the
    # convolution and the recurrence a token at a time of the plain
    # reference, both in float32 on the device
    from ccfd_tpu.ops import kernels, short_conv, ssd_scan

    wide = dict(small, hidden_size=256, mamba_n_heads=8, mamba_d_head=64,
                mamba_d_state=128, mamba_n_groups=1, layers_kept=[0],
                layer_stack="listed")
    wp = jax.jit(lambda: ssm_moe_f32.make_params(wide)["layers"][0][
        "mixer"])()
    rng = np.random.default_rng(44)
    z = jnp.asarray(rng.normal(size=(2, 768, 256)), jnp.float32)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [200]]))
    keep = np.asarray(real)[..., None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ssm_moe_f32.mamba(wp, z, real, wide))
        for chunk in (128, 384):
            w_cfg = hybrid_moe.HybridConfig.from_dict(
                dict(wide, scan_chunk=chunk))
            mixer = jax.jit(lambda p, z: hybrid_moe.mamba2(
                p, z, real, w_cfg, jnp.float32))
            check(f"hybrid_moe mamba2 at lane-wide heads, chunk {chunk}, "
                  "convolves and scans through the kernels",
                  kernels.kernels_of(mixer, wp, z) == {
                      short_conv.KERNEL, ssd_scan.KERNEL})
            got, low = mixer(wp, z)
            zoo[f"hybrid_moe.mamba2.chunk{chunk}"] = {
                "max_abs_diff": check.close(
                    f"hybrid_moe mamba2 at lane-wide heads, chunk {chunk}: "
                    "chunked scan vs recurrence", np.asarray(got) * keep,
                    want * keep, 2e-2),
                "log_decay_min": float(low)}

    # the family's sixth model (layers of one sublayer each by the pattern
    # MEMEM*E: a Mamba-2 mixer of two groups of B and C with the gated norm
    # inside each of two groups, grouped-query attention 16 wide where
    # hidden / heads is 8, top 3 of 8 experts by sigmoid score and choice
    # bias of which 4 are held, experts of two matrices with relu squared
    # stored 32 wide where 24 are published, an untied head): the same
    # seam, three expert layers of seven, every chosen pair served here or
    # the other chip's, the body and the norm's groups in the grid
    from benchmark.reference import ssm_relu2_moe_f32

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "nemotron3n_small_config.json")) as f:
        small = json.load(f)
    n_cfg = hybrid_moe.HybridConfig.from_dict(small)
    n_params = ssm_relu2_moe_f32.make_params(small)
    s = SeqScorer(n_params, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=n_cfg, max_customers=64)
    s.warmup()
    direct, aux = hybrid_moe.apply_serving(
        n_params, hist, np.ones(16, np.int32), n_cfg, jnp.bfloat16)
    grid = s.executable_grid()
    check("hybrid_moe (nemotron_h) served + absent pairs = 3 a token and "
          "expert layer over 3 of 7 layers, relu2 and 2 norm groups in the "
          "grid, decays below 0",
          int(aux["pairs_served"]) + int(aux["pairs_absent"])
          == 3 * int(aux["routed_tokens"]) * n_cfg.moe_layers
          and n_cfg.moe_layers == 3 and grid["expert_body"] == "relu2"
          and grid["kinds"]["mamba2"]["norm_groups"] == 2
          and float(aux["ssm_log_decay_min"]) < 0)
    zoo["hybrid_moe.nemotron_h"] = {
        "max_abs_diff": check.close(
            "hybrid_moe (nemotron_h) B=16 L=8", s.score(
                rows, list(range(10_000, 10_016))), np.asarray(direct), 1e-6),
        "grid": grid}

    # its Mamba-2 mixer at widths that fill lane tiles (hidden 256, 16
    # heads of 64 in 2 groups of B and C, a state of 128, the gated norm
    # inside each of 2 groups of 512; 768 tokens, one row padded on the
    # left past the first chunk): Mosaic compiles the scan's kernel for
    # more than one group and the convolution's at B and C 256 wide, and the
    # device's answer is the recurrence's a token at a time of the plain
    # reference, both in float32
    wide = dict(small, hidden_size=256, mamba_num_heads=16, mamba_head_dim=64,
                ssm_state_size=128, n_groups=2, layers_kept=[0],
                scan_chunk=384)
    w_cfg = hybrid_moe.HybridConfig.from_dict(wide)
    wp = jax.jit(lambda: ssm_relu2_moe_f32.make_params(wide)["layers"][0][
        "mixer"])()
    rng = np.random.default_rng(49)
    z = jnp.asarray(rng.normal(size=(2, 768, 256)), jnp.float32)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [200]]))
    keep = np.asarray(real)[..., None]
    mixer = jax.jit(lambda p, z: hybrid_moe.mamba2(p, z, real, w_cfg,
                                                   jnp.float32))
    check("hybrid_moe mamba2 in 2 groups of B and C convolves and scans "
          "through the kernels", kernels.kernels_of(mixer, wp, z) == {
              short_conv.KERNEL, ssd_scan.KERNEL})
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ssm_relu2_moe_f32.mamba(wp, z, real, wide))
        got, low = mixer(wp, z)
    zoo["hybrid_moe.mamba2.grouped"] = {
        "max_abs_diff": check.close(
            "hybrid_moe mamba2 in 2 groups, norm inside each: chunked scan "
            "vs recurrence", np.asarray(got) * keep, want * keep, 2e-2),
        "log_decay_min": float(low)}

    # the family's seventh model (three Gated DeltaNet layers, 2 key heads
    # on 4 value heads of 16 with one scalar decay a value head, and a gated
    # attention layer 4 : 2 at head width 16 with normed heads and a quarter
    # rotary; softmax top 4 of 16 experts of which 4 are held, a shared
    # expert behind a sigmoid gate, norms that multiply by 1 + w, an untied
    # head): the same seam, every chosen pair served here or another
    # chip's, the mixers' settings in the grid
    from benchmark.reference import gdn_moe_f32
    from ccfd_tpu.ops import causal_attention

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "qwen3next_small_config.json")) as f:
        small = json.load(f)
    q_cfg = hybrid_moe.HybridConfig.from_dict(small)
    q_params = gdn_moe_f32.make_params(small)
    s = SeqScorer(q_params, length=8, batch_sizes=(16,), family="hybrid_moe",
                  family_config=q_cfg, max_customers=64)
    s.warmup()
    direct, aux = hybrid_moe.apply_serving(
        q_params, hist, np.ones(16, np.int32), q_cfg, jnp.bfloat16)
    grid = s.executable_grid()
    check("hybrid_moe (qwen3_next) served + absent pairs = 4 a token and "
          "layer over 4 layers, the gdn chunk and the gated gqa in the grid, "
          "decays below 0",
          int(aux["pairs_served"]) + int(aux["pairs_absent"])
          == 4 * int(aux["routed_tokens"]) * q_cfg.moe_layers
          and q_cfg.moe_layers == 4 and grid["kinds"]["gdn"]["chunk"] == 32
          and grid["kinds"]["gqa"]["gated"] and q_cfg.norm_offset == 1.0
          and float(aux["gdn_log_decay_min"]) < 0)
    zoo["hybrid_moe.qwen3_next"] = {
        "max_abs_diff": check.close(
            "hybrid_moe (qwen3_next) B=16 L=8", s.score(
                rows, list(range(11_000, 11_016))), np.asarray(direct), 1e-6),
        "grid": grid}

    # its Gated DeltaNet mixer at the served heads (hidden 256, 2 key heads
    # on 4 value heads of 128; 768 tokens = six spans, one row padded on the
    # left past the first chunks): Mosaic compiles the convolution's kernel
    # over q, k and v at offset 0 of the wide projection and the
    # scalar-decay delta rule's (ops/gdn_scan.py: the L2 norms inside) at
    # the served chunk and a longer one, and the device's answer is the
    # loop's over ``_gdn_chunk`` through XLA and the delta rule's a token at
    # a time of the plain reference
    from unittest import mock

    from ccfd_tpu.ops import gdn_scan

    wide = dict(small, hidden_size=256, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=128,
                linear_value_head_dim=128, layers_kept=[0])
    wp = jax.jit(lambda: gdn_moe_f32.make_params(wide)["layers"][0][
        "mixer"])()
    rng = np.random.default_rng(52)
    z = jnp.asarray(rng.normal(size=(2, 768, 256)), jnp.float32)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [200]]))
    position = jnp.maximum(jnp.arange(768)[None, :]
                           - jnp.array([[0], [200]]), 0)
    keep = np.asarray(real)[..., None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gdn_moe_f32.gdn(wp, z, real, wide)) * keep
    for chunk in (16, 64):
        w_cfg = hybrid_moe.HybridConfig.from_dict(dict(wide, gdn_chunk=chunk))

        def gdn_mixer():
            return jax.jit(lambda p, z: hybrid_moe.gdn(p, z, real, w_cfg,
                                                       jnp.bfloat16))

        check(f"hybrid_moe gdn at heads of 128, chunk {chunk}, convolves "
              "and scans through the kernels",
              kernels.kernels_of(gdn_mixer(), wp, z)
              == {short_conv.KERNEL, gdn_scan.KERNEL})
        got, low = gdn_mixer()(wp, z)
        got = np.asarray(got) * keep
        with mock.patch.object(gdn_scan, "kernel_fits", return_value=False):
            through_xla = np.asarray(gdn_mixer()(wp, z)[0]) * keep
        zoo[f"hybrid_moe.gdn.chunk{chunk}"] = {
            "max_abs_diff_xla": check.close(
                f"hybrid_moe gdn at heads of 128, chunk {chunk}: kernel vs "
                "the loop through XLA", got, through_xla, 2e-2),
            "max_abs_diff": check.close(
                f"hybrid_moe gdn at heads of 128, chunk {chunk}: kernel vs "
                "recurrence", got, want, 5e-2),
            "xla_vs_recurrence": float(np.abs(through_xla - want).max()),
            "log_decay_min": float(low)}

    # its gated attention at the served heads (hidden 256, 16 query heads
    # on 2 key-value heads of 256, the first 64 dims turned): Mosaic
    # compiles the causal-attention kernel at a query-key and value width
    # of 256 and 8 query heads a key-value head, and the device's answer is
    # the plain reference's full masked softmax with its norms, turn and
    # gate
    wide = dict(small, hidden_size=256, num_attention_heads=16,
                num_key_value_heads=2, head_dim=256, layers_kept=[3])
    a_cfg = hybrid_moe.HybridConfig.from_dict(wide)
    ap = jax.jit(lambda: gdn_moe_f32.make_params(wide)["layers"][0][
        "mixer"])()
    attend = jax.jit(lambda p, z: hybrid_moe.gqa(p, z, real, a_cfg,
                                                 jnp.bfloat16, position))
    check("hybrid_moe gated gqa at heads of 256 attends through the kernel",
          kernels.kernels_of(attend, ap, z) == {causal_attention.KERNEL})
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gdn_moe_f32.attention(ap, z, real, position, wide))
    zoo["hybrid_moe.gqa.gated"] = {"max_abs_diff": check.close(
        "hybrid_moe gated gqa at heads of 256: kernel vs full softmax",
        np.asarray(attend(ap, z), np.float32) * keep, want * keep, 5e-2)}

    # the KDA mixer at heads a lane tile wide, which the first preset's
    # 16-wide heads are not (2 heads of 128, 768 tokens = six spans of the
    # kernel, one row padded on the left past the first chunks): Mosaic
    # compiles the delta rule's kernel (ops/kda_scan.py), and the device's
    # answer is the loop's over ``_kda_chunk`` through XLA and the
    # recurrence's a token at a time of the plain reference
    from ccfd_tpu.ops import kda_scan

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "ling3_small_config.json")) as f:
        wide = dict(json.load(f), num_attention_heads=2, head_dim=128,
                    v_head_dim=128, layers_kept=[0])
    k_cfg = hybrid_moe.HybridConfig.from_dict(wide)
    kp = jax.jit(lambda: hybrid_moe_f32.make_params(wide)["layers"][0][
        "mixer"])()
    rng = np.random.default_rng(47)
    z = jnp.asarray(rng.normal(size=(2, 768, wide["hidden_size"])),
                    jnp.float32)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [200]]))
    keep = np.asarray(real)[..., None]

    def kda_mixer():
        return jax.jit(lambda p, z: hybrid_moe.kda(p, z, real, k_cfg,
                                                   jnp.bfloat16))

    check("hybrid_moe kda at lane-wide heads scans through the kernel",
          kernels.held_by(kda_mixer(), kp, z,
                                names=(kda_scan.KERNEL,)))
    got = np.asarray(kda_mixer()(kp, z)) * keep
    with mock.patch.object(kda_scan, "kernel_fits", return_value=False):
        through_xla = np.asarray(kda_mixer()(kp, z)) * keep
    with jax.default_matmul_precision("highest"):
        want = np.asarray(hybrid_moe_f32.kda(kp, z, real, wide)) * keep
    zoo["hybrid_moe.kda.lane_wide"] = {
        "max_abs_diff_xla": check.close(
            "hybrid_moe kda at lane-wide heads: kernel vs the loop through "
            "XLA", got, through_xla, 2e-2),
        "max_abs_diff": check.close(
            "hybrid_moe kda at lane-wide heads: kernel vs recurrence", got,
            want, 5e-2),
        "xla_vs_recurrence": float(np.abs(through_xla - want).max())}

    # the CCA mixer at heads a lane tile wide, which the second preset's
    # 16-wide heads are not (4 : 2 heads of 128, 768 tokens = two tiles of
    # 384, one row padded on the left past the first strips): Mosaic
    # compiles the convolved latent's kernel (ops/cca_conv.py), the mixer
    # holds it and the attention's and nothing between them, and the
    # device's q, k and v are the plain chain's through XLA at every token
    from benchmark.reference import cca_moe_f32
    from ccfd_tpu.ops import cca_conv

    with open(os.path.join(ROOT, "tests", "benchmark",
                           "zaya1_small_config.json")) as f:
        wide = dict(json.load(f), head_dim=128, num_attention_heads=4,
                    num_key_value_heads=2, hidden_size=256, layers_kept=[0])
    c_cfg = hybrid_moe.HybridConfig.from_dict(wide)
    cp = jax.jit(lambda: cca_moe_f32.layer_of(
        cca_moe_f32.make_params(wide), 0)["mixer"])()
    rng = np.random.default_rng(51)
    z = jnp.asarray(rng.normal(size=(2, 768, 256)), jnp.float32)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [200]]))
    position = jnp.maximum(jnp.arange(768)[None, :]
                           - jnp.array([[0], [200]]), 0)
    check("hybrid_moe cca at lane-wide heads holds the latent's kernel and "
          "the attention's", kernels.kernels_of(
              lambda p, z: hybrid_moe.cca(p, z, real, position, c_cfg,
                                          jnp.bfloat16), cp, z)
          == {cca_conv.KERNEL, causal_attention.KERNEL})
    latent = [jax.jit(lambda p, z, path=path: path(
        p, z, real, position, c_cfg.mixer("cca"), jnp.bfloat16))(cp, z)
        for path in (hybrid_moe._cca_latent_kernel, hybrid_moe._cca_latent)]
    zoo["hybrid_moe.cca.lane_wide"] = {
        "max_abs_diff_" + name: check.close(
            f"hybrid_moe cca: convolved latent through the kernel vs the "
            f"plain chain, {name}", np.asarray(got, np.float32),
            np.asarray(want, np.float32), 0.05)
        for name, got, want in zip("qkv", *latent)}

    # the family's causal attention at head widths the small presets lack
    # (128 wide, and MLA's 128 + 64 = 192 in q and k against values of
    # 128; 768 tokens = two blocks of 384): Mosaic compiles the kernel
    # (ops/causal_attention.py) for plain heads, for grouped queries and
    # for a block that ends in half a lane tile, and the device's answer
    # is the plain path's at every real position, one row padded on the
    # left past the first block
    rng = np.random.default_rng(37)
    real = jnp.asarray(np.arange(768)[None, :] >= np.array([[0], [400]]))
    for label, q_shape in (("plain heads", (2, 768, 2, 128)),
                           ("grouped queries", (2, 768, 2, 2, 128)),
                           ("half-tile heads", (2, 768, 2, 192))):
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for shape in (q_shape, (2, 768, 2, q_shape[-1]),
                                 (2, 768, 2, 128)))

        def attend(q, k, v, real):
            return hybrid_moe._causal_attention(q, k, v, real, 0.09,
                                                jnp.bfloat16)

        check(f"hybrid_moe causal attention, {label}: the program holds "
              "the kernel", kernels.held_by(
                  attend, q, k, v, real, names=(causal_attention.KERNEL,)))
        keep = np.asarray(real).reshape((2, 768) + (1,) * (len(q_shape) - 2))
        zoo[f"causal_attention.{label.split()[0]}"] = {
            "max_abs_diff": check.close(
                f"hybrid_moe causal attention, {label}: kernel vs plain path",
                np.asarray(jax.jit(attend)(q, k, v, real), np.float32) * keep,
                np.asarray(hybrid_moe._plain_causal_attention(
                    q, k, v, real, 0.09, jnp.bfloat16), np.float32) * keep,
                0.04)}

    # the family's held experts at widths the small presets lack (hidden
    # 256, experts 384 wide; three pairs in four another chip's): Mosaic
    # compiles the grouped kernels (ops/grouped_experts.py), and the
    # device's answer is the plain tile loop's with the same counts
    from ccfd_tpu.ops import grouped_experts

    e_cfg = dataclasses.replace(m_cfg, held_first=8, held_count=8, routed=32,
                                per_token=4)
    ex = {name: jnp.asarray(rng.normal(size=shape) / shape[1] ** 0.5,
                            jnp.bfloat16)
          for name, shape in (("gate", (8, 256, 384)), ("up", (8, 256, 384)),
                              ("down", (8, 384, 256)))}
    tokens = jnp.asarray(rng.normal(size=(3000, 256)), jnp.float32)
    chosen = jnp.asarray(np.stack([rng.permutation(32)[:4]
                                   for _ in range(3000)]), jnp.int32)
    weight = jnp.asarray(rng.uniform(0.1, 1.0, size=(3000, 4)), jnp.float32)

    def experts(ex, tokens, chosen, weight):
        return hybrid_moe.held_experts(ex, tokens, chosen, weight, e_cfg,
                                       jnp.bfloat16)

    check("hybrid_moe held experts: the program holds the kernels",
          kernels.kernels_of(experts, ex, tokens, chosen, weight)
          == frozenset(grouped_experts.KERNELS))
    y, pairs, served = jax.jit(experts)(ex, tokens, chosen, weight)
    fits, grouped_experts.kernel_fits = (grouped_experts.kernel_fits,
                                         lambda *_: False)
    try:
        want, want_pairs, want_served = jax.jit(experts)(
            ex, tokens, chosen, weight)
    finally:
        grouped_experts.kernel_fits = fits
    check("hybrid_moe held experts: pairs and served as the loop counts "
          "them", np.array_equal(pairs, want_pairs)
          and int(served) == int(want_served) == int(pairs.sum()))
    zoo["grouped_experts"] = {"max_abs_diff": check.close(
        "hybrid_moe held experts: kernels vs tile loop", np.asarray(y),
        np.asarray(want), 0.04)}

    # the same layer with the other expert body (relu squared between two
    # matrices, no gate), the stack stored 384 wide where 320 are
    # published: the kernels over the stored stack against the tile loop
    # over the published one
    r_cfg = dataclasses.replace(e_cfg, expert_body="relu2")
    published = {"up": ex["up"][..., :320], "down": ex["down"][:, :320]}
    stored = {"up": jnp.pad(published["up"], ((0, 0), (0, 0), (0, 64))),
              "down": jnp.pad(published["down"], ((0, 0), (0, 64), (0, 0)))}

    def relu2_experts(ex, tokens, chosen, weight):
        return hybrid_moe.held_experts(ex, tokens, chosen, weight, r_cfg,
                                       jnp.bfloat16)

    check("hybrid_moe relu2 experts: the stored stack holds the kernels, "
          "the published one the loop",
          kernels.kernels_of(relu2_experts, stored, tokens, chosen, weight)
          == frozenset(grouped_experts.KERNELS)
          and not kernels.kernels_of(relu2_experts, published, tokens,
                                     chosen, weight))
    y, pairs, served = jax.jit(relu2_experts)(stored, tokens, chosen, weight)
    want, want_pairs, want_served = jax.jit(relu2_experts)(
        published, tokens, chosen, weight)
    check("hybrid_moe relu2 experts: pairs and served as the loop counts "
          "them", np.array_equal(pairs, want_pairs)
          and int(served) == int(want_served) == int(pairs.sum()))
    zoo["grouped_experts.relu2"] = {"max_abs_diff": check.close(
        "hybrid_moe relu2 experts: kernels over the padded stack vs tile "
        "loop over the published", np.asarray(y), np.asarray(want), 0.04)}

    # the fused-decision grid over the flagship: score + threshold + rules
    # in one executable per bucket, against the staged seam
    thr = Config().fraud_threshold
    rules = RuleSet([
        Rule("fraud", process="fraud", salience=10,
             when=(Condition(PROBA_FIELD, ">=", thr),)),
        Rule("v1_guard", process="standard", salience=5,
             when=(Condition("V1", ">", 0.0),)),
        Rule("standard", process="standard"),
    ])
    base = Scorer(model_name="mlp", params=params, batch_sizes=ZOO_BUCKETS,
                  use_fused=True, host_tier_rows=0)
    base.warmup()
    fds = FusedDecisionScorer(base, rules, strict=True)
    fds.warmup()
    diffs = []
    for b in ZOO_BUCKETS:
        proba, fired = fds.decide(x[:b])
        diffs.append(check.close(f"fused decision bucket {b} proba vs staged",
                                 proba, base.score(x[:b]), TOL_BF16))
        check(f"fused decision bucket {b} fired == rules on its proba",
              fired is not None
              and np.array_equal(fired, rules.evaluate(x[:b], proba)))
    g = fds.executable_grid()
    check("fused decision grid enabled, zero staged fallbacks",
          g["enabled"] and g["staged_fallbacks"] == 0
          and set(g["dispatches"]) == set(map(str, ZOO_BUCKETS)),
          {k: g[k] for k in ("forward", "enabled", "staged_fallbacks",
                             "dispatches")})
    zoo["fused_decision"] = {"max_abs_diff": max(diffs),
                             "forward": g["forward"]}
    report["zoo"] = zoo


def main() -> int:
    def out_of_time() -> None:
        print(f"chip_smoke: no result after {BUDGET_S:.0f}s",
              file=sys.stderr, flush=True)
        os._exit(2)

    # the 1200 s contract, and a hang would cost more than a failure
    watchdog = threading.Timer(BUDGET_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()

    import jax

    from ccfd_tpu.observability.profile import StageProfiler
    from ccfd_tpu.utils.backend import require_backend
    from ccfd_tpu.utils.compile_cache import enable as enable_compile_cache

    platform = require_backend()  # no chip and no CPU request: raises here
    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    prof = StageProfiler()
    prof.arm_compile_listener()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: jax {jax.__version__} on {device}; compile cache "
          f"{cache_dir}", flush=True)

    check = Checks()
    report: dict = {"device": device, "jax": jax.__version__,
                    "compile_cache_dir": cache_dir}
    scorer = phase_rest(check, report, platform, prof)
    phase_pipeline(check, report, platform)
    phase_heal(check, report, scorer, prof)
    phase_zoo(check, report, platform)
    check("platform is tpu", device["platform"] == "tpu", device)

    report["compile_cache"] = cache
    report["compiles_by_stage"] = prof.compile_counts()
    report["wall_s"] = round(time.perf_counter() - T0, 1)
    report["failed"] = check.failed
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print("CHIP_SMOKE " + json.dumps(report, sort_keys=True), flush=True)
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: the result is printed, and a lingering service thread or
    # a slow runtime teardown must not turn it into a hang
    os._exit(rc)
