"""Command-line entry points: the reference run-book as one binary.

The reference's "entry point" is a 600-line oc-apply run-book whose step
order is a dependency sort (SURVEY.md §3 D). Here the same topology boots
in-process:

  python -m ccfd_tpu demo    # full pipeline: produce -> route -> score ->
                             # process -> notify -> retrain, prints metrics
  python -m ccfd_tpu serve   # REST scorer (Seldon contract) on a port
  python -m ccfd_tpu train   # offline-train the flagship MLP + checkpoint
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any

from ccfd_tpu.config import Config


def run_demo(args: argparse.Namespace) -> dict:
    """The ``demo`` pipeline, start to drained stop; returns the summary
    ``cmd_demo`` prints (chip_smoke.py drives the same function and reads
    the counters)."""
    import jax

    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import load_dataset
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.notify.service import NotificationService
    from ccfd_tpu.parallel.online import OnlineTrainer
    from ccfd_tpu.parallel.train import TrainConfig, fit_mlp
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.process.prediction import ScorerPredictionService
    from ccfd_tpu.producer.producer import Producer
    from ccfd_tpu.router.router import Router
    from ccfd_tpu.serving.scorer import Scorer

    import dataclasses

    cfg = dataclasses.replace(
        Config.from_env(), customer_reply_timeout_s=args.reply_timeout
    )
    ds = load_dataset(n_synthetic=max(args.transactions, 4000))
    print(f"[demo] dataset: {ds.n} rows; training flagship MLP...", file=sys.stderr)
    params = fit_mlp(
        ds.X, ds.y, steps=args.train_steps, tc=TrainConfig(compute_dtype="float32")
    )

    broker = Broker(log_dir=cfg.bus_log_dir or None, fsync=cfg.bus_fsync,
                    retention_records=cfg.bus_retention_records or None,
                    retention_overrides=cfg.parsed_retention_overrides())
    reg_router, reg_kie, reg_notify, reg_retrain = (
        Registry(), Registry(), Registry(), Registry(),
    )
    scorer = Scorer(model_name="mlp", params=params, compute_dtype=cfg.compute_dtype,
                    dispatch_deadline_ms=cfg.scorer_dispatch_deadline_ms())
    scorer.warmup()
    engine = build_engine(
        cfg, broker, reg_kie,
        prediction_service=ScorerPredictionService(scorer.score),
    )
    router = Router(cfg, broker, scorer.score, engine, reg_router)
    notify = NotificationService(cfg, broker, reg_notify, seed=args.seed)
    trainer = OnlineTrainer(cfg, broker, scorer, params, registry=reg_retrain)

    _tune_gc()  # before the hot loops start: freeze races live churn
    router.start(poll_timeout_s=0.02)
    notify.start(poll_timeout_s=0.02)
    trainer.start(interval_s=0.5)

    t0 = time.perf_counter()
    Producer(cfg, broker, ds).run(
        limit=args.transactions,
        rate_per_s=args.rate,
        wire_format=args.wire_format,
    )
    # drain: wait until the router consumed everything + timers fired
    deadline = time.monotonic() + args.drain_s
    while time.monotonic() < deadline:
        if reg_router.counter("transaction_incoming_total").value() >= args.transactions:
            break
        time.sleep(0.1)
    time.sleep(args.reply_timeout + 1.0)
    elapsed = time.perf_counter() - t0
    router.stop(); notify.stop(); trainer.stop()

    out = reg_router.counter("transaction_outgoing_total")
    summary = {
        "transactions": int(reg_router.counter("transaction_incoming_total").value()),
        "fraud_routed": int(out.value({"type": "fraud"})),
        "standard_routed": int(out.value({"type": "standard"})),
        "notifications": int(reg_router.counter("notifications_outgoing_total").value()),
        "approved_amount_n": reg_kie.histogram("fraud_approved_amount").count(),
        "rejected_amount_n": reg_kie.histogram("fraud_rejected_amount").count(),
        "low_amount_auto_n": reg_kie.histogram("fraud_approved_low_amount").count(),
        "investigations_n": reg_kie.histogram("fraud_investigation_amount").count(),
        "open_tasks": len(engine.tasks()),
        "retrain_swaps": int(reg_retrain.counter("retrain_param_swaps_total").value()),
        # batches the router served below the device tier (host forward or
        # rules only), and what the scorer itself fell back on
        "router_degraded": int(reg_router.counter("router_degraded_total").total()),
        "scorer": dict(scorer.executable_grid(),
                       dispatch_timeouts=scorer.dispatch_timeouts,
                       host_fallback_scores=scorer.host_fallback_scores),
        "wall_s": round(elapsed, 2),
        "backend": jax.default_backend(),
    }
    return summary


def cmd_demo(args: argparse.Namespace) -> int:
    print(json.dumps(run_demo(args)))
    return 0


def start_server(cfg: Config, params: Any, host: str, port: int,
                 **scorer_kw: Any):
    """Scorer -> warmup -> PredictionServer.start: what ``serve`` runs,
    as one function so chip_smoke.py drives exactly this. Returns the
    started server and the port it bound."""
    from ccfd_tpu.serving.scorer import Scorer
    from ccfd_tpu.serving.server import PredictionServer

    scorer = Scorer(
        model_name=cfg.model_name, params=params, compute_dtype=cfg.compute_dtype,
        batch_sizes=cfg.batch_sizes,
        host_tier_rows=None if cfg.host_tier_rows < 0 else cfg.host_tier_rows,
        dispatch_deadline_ms=cfg.scorer_dispatch_deadline_ms(),
        **scorer_kw,
    )
    scorer.warmup()
    _tune_gc()
    srv = PredictionServer(scorer, cfg)
    return srv, srv.start(host, port)


def cmd_serve(args: argparse.Namespace) -> int:
    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import load_dataset
    from ccfd_tpu.parallel.train import TrainConfig, fit_mlp

    cfg = Config.from_env()
    if cfg.graph_cr:
        # Serve a whole SeldonDeployment-shaped inference graph (ensemble /
        # router / transformer tree) compiled to one jitted callable.
        from ccfd_tpu.serving.graph import load_graph_cr

        if args.train:
            print(
                "[serve] --train trains the MLP; a CCFD_GRAPH_CR graph has "
                "graph-shaped params — unset --train or unset CCFD_GRAPH_CR",
                file=sys.stderr,
            )
            return 2
        spec = load_graph_cr(cfg.graph_cr)
        cfg = dataclasses.replace(cfg, model_name=spec.name)
    params = None
    if args.train:
        if cfg.model_name != "mlp":
            print(
                f"[serve] --train trains the MLP; CCFD_MODEL={cfg.model_name!r} "
                "params would not match — unset --train or set CCFD_MODEL=mlp",
                file=sys.stderr,
            )
            return 2
        ds = load_dataset()
        params = fit_mlp(ds.X, ds.y, steps=args.train_steps,
                         tc=TrainConfig(compute_dtype="float32"))
    elif cfg.model_name == "mlp":
        # serve the newest `train` checkpoint when one exists: training and
        # serving compose through the checkpoint dir, so `ccfd_tpu train`
        # followed by `ccfd_tpu serve` serves the trained (AUC-recorded)
        # params instead of random init
        params = _restore_mlp_checkpoint(getattr(args, "checkpoint_dir", ""))
    elif cfg.model_name == "mlp_q8":
        # int8 lifecycle: `train` -> `quantize` -> CCFD_MODEL=mlp_q8 serve
        params = _restore_q8_checkpoint(getattr(args, "quantized_dir", ""))
    elif cfg.model_name == "gbt":
        # tree lifecycle: `train --family hgb` -> CCFD_MODEL=gbt serve
        params = _restore_gbt_params(getattr(args, "gbt_dir", ""))
    srv, port = start_server(cfg, params, args.host, args.port)
    print(f"[serve] model={cfg.model_name} listening on {args.host}:{port}",
          file=sys.stderr)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


def _training_dataset():
    """The dataset model-lifecycle commands (train/quantize) run on: the
    real Kaggle table when CCFD_CSV points at it, else the committed
    deterministic Kaggle-shaped surrogate (data/surrogate.py) — never the
    small test synthetic, so shipped checkpoints always carry full-scale
    quality evidence."""
    from ccfd_tpu.data.ccfd import load_dataset

    if os.environ.get("CCFD_CSV"):
        return load_dataset(), os.environ["CCFD_CSV"]
    from ccfd_tpu.data.surrogate import SURROGATE_VERSION, kaggle_surrogate

    # CCFD_SURROGATE_ROWS shrinks the dataset for fast CI/unit runs; the
    # default (full 284,807 rows) is what shipped artifacts train on
    rows = int(os.environ.get("CCFD_SURROGATE_ROWS", "0") or 0)
    if rows > 0:
        return kaggle_surrogate(n=rows), f"surrogate:{SURROGATE_VERSION}:n={rows}"
    return kaggle_surrogate(), f"surrogate:{SURROGATE_VERSION}"


def cmd_train(args: argparse.Namespace) -> int:
    """Offline training with the reference's data path: the CSV comes from
    the object store (reference README.md:303-343 uploads creditcard.csv to
    S3 and every consumer reads it from there) via ``--from-store``, from a
    local file via CCFD_CSV, else the synthetic surrogate. Records held-out
    AUC for the trained MLP AND the sklearn LogReg baseline (the reference's
    modelfull is a sklearn classifier) so every checkpoint ships with its
    quality evidence; the checkpoint it writes is what ``serve`` loads by
    default."""
    import numpy as np

    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import load_csv_bytes
    from ccfd_tpu.models import mlp as mlp_mod
    from ccfd_tpu.parallel.checkpoint import CheckpointManager
    from ccfd_tpu.parallel.train import TrainConfig, fit_mlp
    from ccfd_tpu.utils.metrics_math import roc_auc

    cfg = Config.from_env()
    source = "synthetic"
    if args.from_store:
        from ccfd_tpu.store.client import S3Client
        from ccfd_tpu.store.objectstore import Credentials

        client = S3Client(
            args.store_url or cfg.s3_endpoint or "http://127.0.0.1:9000",
            Credentials(cfg.access_key_id or "ccfd-access",
                        cfg.secret_access_key or "ccfd-secret"),
        )
        ds = load_csv_bytes(client.get(cfg.s3_bucket, cfg.filename))
        source = f"store:{cfg.s3_bucket}/{cfg.filename}"
    else:
        ds, source = _training_dataset()

    # held-out split for honest AUC (stratification unnecessary at 284k rows;
    # the tail is sorted by Time in the real CSV, so shuffle first)
    rng = np.random.default_rng(0)
    order = rng.permutation(ds.n)
    n_test = max(1, int(ds.n * args.test_frac))
    test, train = order[:n_test], order[n_test:]
    Xtr, ytr, Xte, yte = ds.X[train], ds.y[train], ds.X[test], ds.y[test]

    if getattr(args, "family", "mlp") == "hgb":
        # the strongest reference-family model, made servable: sklearn
        # HistGradientBoosting (bounded depth) -> the served dense-tree
        # params (models/trees.py from_sklearn_hgb; HGB_SERVABLE_r04.json
        # has the depth sweep). CCFD_MODEL=gbt serve restores the result.
        import jax.numpy as jnp

        from ccfd_tpu.models import trees as trees_mod

        try:
            from sklearn.ensemble import HistGradientBoostingClassifier
        except ImportError:
            print("[train] --family hgb needs scikit-learn", file=sys.stderr)
            return 2
        if args.hgb_depth > 10:
            # fail BEFORE the minutes-long fit: the dense embedding is
            # 2^depth nodes/tree and the converter refuses deeper trees
            print(f"[train] --hgb-depth {args.hgb_depth} > 10: the dense "
                  "embedding is 2^depth nodes/tree (see "
                  "trees.from_sklearn_hgb)", file=sys.stderr)
            return 2
        clf = HistGradientBoostingClassifier(
            max_depth=args.hgb_depth, class_weight="balanced",
            random_state=0,
        ).fit(Xtr, ytr)
        gbt_params = trees_mod.from_sklearn_hgb(clf)
        served = np.asarray(trees_mod.apply(gbt_params, jnp.asarray(Xte)))
        conv_delta = float(
            np.abs(served - clf.predict_proba(Xte)[:, 1]).max()
        )
        path = _save_gbt_params(args.gbt_dir, gbt_params)
        print(json.dumps({
            "checkpoint": path, "rows": int(ds.n), "family": "hgb",
            "max_depth": args.hgb_depth, "source": source,
            "test_rows": int(n_test),
            "auc_hgb_served": round(roc_auc(yte, served), 5),
            "conversion_max_prob_delta": conv_delta,
        }))
        return 0

    params = fit_mlp(Xtr, ytr, steps=args.steps,
                     tc=TrainConfig(compute_dtype="float32"))
    proba = np.asarray(mlp_mod.apply(params, Xte))
    auc_mlp = roc_auc(yte, proba)

    auc_ref = None
    try:
        from sklearn.linear_model import LogisticRegression
        from sklearn.preprocessing import StandardScaler

        sc = StandardScaler().fit(Xtr)
        clf = LogisticRegression(max_iter=1000).fit(sc.transform(Xtr), ytr)
        auc_ref = roc_auc(yte, clf.predict_proba(sc.transform(Xte))[:, 1])
    except ImportError:
        pass  # baseline AUC simply absent without sklearn

    path = CheckpointManager(args.checkpoint_dir).save(args.steps, params)
    print(json.dumps({
        "checkpoint": path, "rows": int(ds.n), "steps": args.steps,
        "source": source, "test_rows": int(n_test),
        "auc_mlp": round(auc_mlp, 5),
        "auc_sklearn_logreg": round(auc_ref, 5) if auc_ref is not None else None,
    }))
    return 0


def _restore_checkpoint(checkpoint_dir: str, like):
    """Latest checkpoint structured like ``like``, or None."""
    if not checkpoint_dir:
        return None
    from ccfd_tpu.parallel.checkpoint import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir)
    if mgr.latest_step() is None:
        return None
    restored = mgr.restore(like)
    if restored is None:
        return None
    params, step = restored
    print(f"[checkpoint] restored step={step} from {checkpoint_dir}",
          file=sys.stderr)
    return params


_Q8_DIR = "./checkpoints_q8"  # quantize writes here; serve/score read it
_GBT_DIR = "./checkpoints_gbt"  # train --family hgb writes here


def _save_gbt_params(gbt_dir: str, params) -> str:
    """Dense-tree params (models/trees.py layout) -> one npz. The tree
    family's artifact is four arrays, not an optimizer-bearing pytree, so
    a plain npz beats an orbax checkpoint here (humanly inspectable,
    loadable without the model's init shapes)."""
    import io

    import numpy as np

    from ccfd_tpu.runtime.durability import write_artifact

    d = gbt_dir or _GBT_DIR
    path = os.path.join(d, "params.npz")
    # checksummed atomic swap (runtime/durability.py — the hand-rolled
    # tmp+rename here skipped the fsync, so a power loss could lose BOTH
    # copies): a crash mid-save or a reader racing a refresh never sees a
    # half-written artifact, and a corrupt file falls back to the
    # retained last-good generation on read
    buf = io.BytesIO()
    np.savez(
        buf,
        feature=np.asarray(params["feature"]),
        threshold=np.asarray(params["threshold"]),
        leaf=np.asarray(params["leaf"]),
        base=np.asarray(params["base"]),
    )
    write_artifact(path, buf.getvalue(), artifact="gbt_params",
                   best_effort=False)
    return path


def _restore_gbt_params(gbt_dir: str):
    """The `train --family hgb` artifact as served gbt params, or None."""
    import io
    import zipfile

    import jax.numpy as jnp
    import numpy as np

    from ccfd_tpu.runtime.durability import (
        CorruptArtifactError,
        read_artifact,
    )

    path = os.path.join(gbt_dir or _GBT_DIR, "params.npz")
    try:
        # verified read: a corrupt file quarantines and the last-good
        # retained generation serves; legacy unframed files still load
        raw = read_artifact(path, artifact="gbt_params")
        with np.load(io.BytesIO(raw)) as z:
            params = {k: jnp.asarray(z[k])
                      for k in ("feature", "threshold", "leaf", "base")}
    except FileNotFoundError:
        return None
    # BadZipFile subclasses Exception directly — a truncated npz raises it
    except (OSError, ValueError, KeyError, zipfile.BadZipFile,
            CorruptArtifactError) as e:
        print(f"[checkpoint] unreadable gbt params at {path} ({e!r}); "
              "serving fresh init", file=sys.stderr)
        return None
    print(f"[checkpoint] restored gbt params from {path}", file=sys.stderr)
    return params


def _restore_mlp_checkpoint(checkpoint_dir: str):
    """Latest `train` checkpoint as MLP params, or None. The checkpoint
    format is the MLP's pytree, so callers must only apply this when the
    configured model is the MLP (serve and score share this guard)."""
    import jax

    from ccfd_tpu.models import mlp as mlp_mod

    return _restore_checkpoint(
        checkpoint_dir, mlp_mod.init(jax.random.PRNGKey(0))
    )


def _restore_q8_checkpoint(quantized_dir: str):
    """Latest `quantize` checkpoint as mlp_q8 params, or None."""
    from ccfd_tpu.models.registry import get_model

    return _restore_checkpoint(quantized_dir or _Q8_DIR,
                               get_model("mlp_q8").init())


def cmd_quantize(args: argparse.Namespace) -> int:
    """Model-lifecycle step between `train` and `serve`: load the newest
    f32 MLP checkpoint, emit int8 params (ops/quant.py) plus evidence
    that quantization kept the model's quality. The evidence is the
    f32-to-int8 DELTA (AUC and probability) on a sampled evaluation set —
    both models score identical rows, so the delta is valid even if this
    run's dataset/sample differs from the train run's held-out split;
    absolute held-out AUC is `train`'s claim, recorded at training time."""
    import jax
    import numpy as np

    from ccfd_tpu.models import mlp as mlp_mod
    from ccfd_tpu.ops import quant
    from ccfd_tpu.parallel.checkpoint import CheckpointManager
    from ccfd_tpu.utils.metrics_math import roc_auc

    mgr = CheckpointManager(args.checkpoint_dir)
    step = mgr.latest_step()
    if step is None:
        print(
            f"[quantize] no checkpoint in {args.checkpoint_dir!r}; "
            "run `ccfd_tpu train` first",
            file=sys.stderr,
        )
        return 2
    params, step = mgr.restore(mlp_mod.init(jax.random.PRNGKey(0)))
    qp = quant.quantize_mlp(params)

    ds, _source = _training_dataset()
    rng = np.random.default_rng(0)
    te = rng.permutation(ds.n)[: max(1, int(ds.n * args.test_frac))]
    p32 = np.asarray(mlp_mod.apply(params, ds.X[te]))
    p8 = quant.apply_numpy(jax.tree.map(np.asarray, qp), ds.X[te])
    path = CheckpointManager(args.out_dir).save(step, qp)
    print(json.dumps({
        "source_step": step,
        "eval_rows": int(len(te)),
        "auc_f32": round(roc_auc(ds.y[te], p32), 6),
        "auc_int8": round(roc_auc(ds.y[te], p8), 6),
        "max_prob_delta": round(float(np.abs(p8 - p32).max()), 6),
        # the claim: f32 vs int8 on IDENTICAL rows (quantization delta);
        # absolute held-out AUC lives in the train command's record
        "evidence": "f32-to-int8 delta on a sampled evaluation set",
        "checkpoint": path,
        "serve_with": "CCFD_MODEL=mlp_q8 ccfd_tpu serve",
    }))
    return 0


def _audit_fetch_json(url: str):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read().decode())
    except (urllib.error.URLError, OSError, ValueError):
        return None


def cmd_audit_reconstruct(args: argparse.Namespace, cfg) -> int:
    """``ccfd_tpu audit <tx_id>``: the regulator question, answered from
    one command — the DecisionRecord stamped at the route seam, joined
    to the lifecycle lineage (version + checkpoint hash, with a parity
    verdict), the incident bundle open when the decision was made, and
    the kept trace when the tail sampler sampled it. Reads the live
    exporter with ``--url``; otherwise reconstructs OFFLINE from the
    on-disk artifacts — which is exactly what a crash-restore drill
    exercises (tools/audit_smoke.py)."""
    doc: dict = {"tx_id": args.tx_id}
    record = None
    base = args.url.rstrip("/") if args.url else ""
    if base:
        record = _audit_fetch_json(f"{base}/decisions/{args.tx_id}")
    if record is None:
        audit_dir = args.dir or cfg.audit_dir
        if audit_dir:
            from ccfd_tpu.observability.audit import AuditLog

            # readonly: an inspection command must never truncate the
            # live log out from under a running platform. The ring is
            # sized from config so recovery rebuilds as deep a view as
            # the configured platform would (CCFD_AUDIT_RING).
            log = AuditLog(dir=audit_dir, readonly=True,
                           max_records=cfg.audit_ring)
            record = log.get(args.tx_id)
    if record is None:
        print(f"[audit] no decision record for {args.tx_id!r} (checked "
              + (f"{base}/decisions and " if base else "")
              + f"dir={args.dir or cfg.audit_dir or '<unset>'})",
              file=sys.stderr)
        return 2
    doc["record"] = record

    # -- lineage join: the version that scored it, hash parity ------------
    lc_dir = args.lifecycle_dir or cfg.lifecycle_dir
    if lc_dir and record.get("version") is not None:
        from ccfd_tpu.lifecycle.versions import VersionStore

        path = os.path.join(lc_dir, "versions.json")
        try:
            store = VersionStore(path, recover=False)
            v = store.get(int(record["version"]))
            doc["lineage"] = {
                "version": v.to_dict(),
                "events": store.audit_trail(v.version),
                # the compliance check: the hash stamped on the decision
                # equals the hash the lineage records for that version
                "hash_parity": (record.get("hash") is not None
                                and v.checkpoint_hash == record.get("hash")),
            }
        except (OSError, ValueError, KeyError, TypeError) as e:
            doc["lineage"] = {"error": repr(e)}

    # -- incident join: what was burning while this decision was made -----
    inc_id = record.get("incident")
    if inc_id:
        bundle = None
        if base:
            bundle = _audit_fetch_json(f"{base}/incidents/{inc_id}")
        if bundle is None:
            inc_dir = args.incident_dir or cfg.incident_dir
            if inc_dir:
                try:
                    with open(os.path.join(inc_dir, f"{inc_id}.json")) as f:
                        bundle = json.load(f)
                except (OSError, ValueError):
                    bundle = None
        if bundle is not None:
            doc["incident"] = {
                "id": bundle.get("id"),
                "trigger": bundle.get("trigger"),
                "generated_unix": bundle.get("generated_unix"),
                "found": True,
            }
        else:
            doc["incident"] = {"id": inc_id, "found": False}

    # -- trace join: only the live sink holds kept traces -----------------
    trace_id = record.get("trace")
    if trace_id and base:
        tr = _audit_fetch_json(f"{base}/traces/{trace_id}")
        doc["trace"] = ({"trace_id": trace_id,
                         "spans": len(tr.get("spans", [])), "kept": True}
                        if tr is not None
                        else {"trace_id": trace_id, "kept": False})
    elif trace_id:
        doc["trace"] = {"trace_id": trace_id, "kept": None}

    if args.json:
        print(json.dumps(doc, indent=1, default=str))
        return 0
    r = record
    print(f"decision tx={r.get('tx')} uid={r.get('uid')} seq={r.get('seq')}")
    print(f"  score: proba={r.get('proba')} threshold={r.get('threshold')} "
          f"-> rule={r.get('rule')} branch={r.get('branch')} "
          f"pid={r.get('pid')}")
    tier = r.get("tier", "?")
    cause = f" ({r['cause']})" if r.get("cause") else ""
    print(f"  served by: {tier} tier{cause}  priority={r.get('priority')}"
          + (f"  events={r['events']}" if r.get("events") else ""))
    print(f"  model: version={r.get('version')} hash={r.get('hash')}")
    lin = doc.get("lineage")
    if lin and "version" in lin:
        parity = "OK" if lin["hash_parity"] else "MISMATCH"
        v = lin["version"]
        print(f"  lineage: v{v['version']} stage={v['stage']} "
              f"ckpt={v['checkpoint_step']} hash parity: {parity} "
              f"({len(lin['events'])} audit events)")
    inc = doc.get("incident")
    if inc:
        mark = "" if inc.get("found") else " (bundle not found)"
        print(f"  incident: {inc['id']}{mark}"
              + (f" trigger={inc['trigger']}" if inc.get("trigger") else ""))
    trc = doc.get("trace")
    if trc:
        kept = {True: "kept", False: "not retained",
                None: "offline (query --url for spans)"}[trc.get("kept")]
        print(f"  trace: {trc['trace_id']} [{kept}]")
    return 0


def cmd_audit(args: argparse.Namespace) -> int:
    """With a tx id: reconstruct that decision end-to-end (provenance
    plane, observability/audit.py). Without one: tail the engine's audit
    stream (CCFD_AUDIT_TOPIC) — one JSON event per line, the operator
    view of jBPM's process-instance history. ``--follow`` keeps
    consuming; otherwise drains what's there and exits."""
    from ccfd_tpu.config import Config

    cfg = Config.from_env()
    if args.tx_id:
        return cmd_audit_reconstruct(args, cfg)
    topic = args.topic or cfg.audit_topic
    if not topic:
        # surface the misconfiguration instead of an empty-but-successful
        # tail: without CCFD_AUDIT_TOPIC the engine emits nothing
        print(
            "[audit] CCFD_AUDIT_TOPIC is unset (the engine's audit stream "
            "is OFF); tailing the default topic 'ccd-audit'",
            file=sys.stderr,
        )
        topic = "ccd-audit"
    broker = _broker_for(cfg)
    consumer = broker.consumer(args.group, (topic,))
    printed = 0
    try:
        while True:
            # cap the fetch at the remaining limit: poll auto-commits what
            # it returns, and over-fetching would silently skip events the
            # group never printed
            want = min(1024, args.limit - printed) if args.limit else 1024
            recs = consumer.poll(want, 0.5 if args.follow else 0.0)
            for rec in recs:
                print(json.dumps(rec.value))
                printed += 1
                if args.limit and printed >= args.limit:
                    return 0
            if not recs and not args.follow:
                return 0
    except KeyboardInterrupt:
        return 0
    finally:
        consumer.close()


def cmd_replay(args: argparse.Namespace) -> int:
    """``ccfd_tpu replay``: the bulk replay & backtest console (replay/).

    Offline (default): scan the recorded window out of the audit
    segments read-only and summarize it; with ``--what-if-threshold``
    run the host-side backtest diff (which recorded decisions flip under
    the new threshold) — no platform, no bus. With ``--live``: bring the
    platform up, re-produce the window through the real
    producer→bus→router→scorer path under ``bulk`` admission, and print
    the verdict-parity report (divergences classified by cause)."""
    from ccfd_tpu.config import Config

    cfg = Config.from_env()
    audit_dir = args.dir or cfg.audit_dir
    if not audit_dir:
        print("[replay] no audit dir: pass --dir or set CCFD_AUDIT_DIR "
              "(windows are reconstructed from the audit segments)",
              file=sys.stderr)
        return 2
    since, until = args.since_seq, args.until_seq
    if args.from_incident:
        from ccfd_tpu.replay.service import bundle_window

        with open(args.from_incident) as f:
            rng = bundle_window(json.load(f))
        if rng is None:
            print(f"[replay] {args.from_incident} embeds no decision "
                  "summaries; nothing to re-drive", file=sys.stderr)
            return 2
        since, until = rng

    if args.live:
        from ccfd_tpu.platform.operator import Platform, PlatformSpec

        if args.cr:
            spec = PlatformSpec.from_yaml(args.cr, cfg=cfg)
        else:
            # minimal replay platform: bus + scorer + engine + router +
            # the audit/replay planes over the recorded segments
            spec = PlatformSpec.from_cr({"spec": {
                "audit": {"dir": audit_dir},
                "replay": {"enabled": True,
                           "dir": args.state_dir or cfg.replay_dir},
                "monitoring": {"enabled": False},
                "health": {"enabled": False},
                "analytics": {"enabled": False},
                "retrain": {"enabled": False},
                "notify": {"enabled": False},
            }}, cfg=cfg)
        p = Platform(spec).up()
        try:
            if p.replay is None:
                print("[replay] the platform came up without the replay "
                      "component (CR replay.enabled / audit plane off?)",
                      file=sys.stderr)
                return 2
            report = p.replay.run_window(
                since, until,
                window_id=(args.window_id or None),
                resume=not args.no_resume)
        finally:
            p.down()
        print(json.dumps(report if args.json else {
            k: report[k] for k in ("window_id", "total", "replayed",
                                   "match", "divergence", "drop", "ghost",
                                   "causes", "parity", "rows_per_s")}))
        return 0 if report.get("parity") else 1

    from ccfd_tpu.observability.audit import AuditLog
    from ccfd_tpu.replay.service import ReplayService

    audit = AuditLog(dir=audit_dir, readonly=True,
                     max_records=cfg.audit_ring)
    if args.what_if_threshold is not None:
        svc = ReplayService(cfg, None, audit,
                            state_dir=(args.state_dir or None))
        report = svc.run_window(since, until, mode="whatif",
                                threshold=args.what_if_threshold,
                                window_id=(args.window_id or None))
        print(json.dumps(report if args.json else {
            k: report[k] for k in ("window_id", "total", "threshold",
                                   "flips", "flip_rate",
                                   "mean_abs_delta")}))
        return 0
    recs = audit.scan_window(since, until)
    tiers: dict[str, int] = {}
    for r in recs:
        t = str(r.get("tier", "device"))
        tiers[t] = tiers.get(t, 0) + 1
    doc = {
        "records": len(recs),
        "rescorable": sum(1 for r in recs if r.get("row") is not None),
        "seq": ([int(recs[0].get("seq", -1)),
                 int(recs[-1].get("seq", -1))] if recs else None),
        "tiers": tiers,
    }
    print(json.dumps(doc))
    return 0


def cmd_lifecycle(args: argparse.Namespace) -> int:
    """Model-lifecycle console: the versioned lineage + transition audit
    trail the controller persists (lifecycle/versions.py). Reads the
    store the platform's ``lifecycle.state_dir`` (or CCFD_LIFECYCLE_DIR)
    points at — the compliance question "which model served when, trained
    on which labels, and why was it promoted/rolled back" answered from
    one JSON file, no running platform needed."""
    from ccfd_tpu.lifecycle.versions import VersionStore

    cfg = Config.from_env()
    state_dir = args.dir or cfg.lifecycle_dir
    if not state_dir:
        print("[lifecycle] no state dir: pass --dir or set "
              "CCFD_LIFECYCLE_DIR (the CR's lifecycle.state_dir)",
              file=sys.stderr)
        return 2
    path = os.path.join(state_dir, "versions.json")
    if not os.path.exists(path):
        print(f"[lifecycle] no lineage at {path}", file=sys.stderr)
        return 2
    try:
        # recover=False: an INSPECTION command must never quarantine the
        # live lineage file out from under a running platform — report
        # the corruption and let the controller's own recovery handle it
        store = VersionStore(path, recover=False)
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"[lifecycle] lineage at {path} is unreadable ({e!r}); the "
              "controller quarantines and re-bootstraps it at next "
              "bring-up", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({
            "versions": [v.to_dict() for v in store.versions()],
            "audit": store.audit_trail(args.version or None),
        }, indent=1))
        return 0
    champ = store.champion()
    print(f"champion: v{champ.version}" if champ else "champion: none")
    for v in store.versions():
        mark = "*" if champ and v.version == champ.version else " "
        print(f"{mark} v{v.version:<4} stage={v.stage:<12} "
              f"parent={v.parent if v.parent is not None else '-':<4} "
              f"labels@{v.label_watermark:<8} "
              f"ckpt={v.checkpoint_step if v.checkpoint_step is not None else '-'}")
    if args.audit:
        for e in store.audit_trail(args.version or None):
            detail = json.dumps(e["detail"], sort_keys=True)
            print(f"  {e['ts']:.3f} v{e['version']} {e['event']}: {detail}")
    return 0


def cmd_score(args: argparse.Namespace) -> int:
    """Offline bulk scoring: CSV in -> probabilities out, through the same
    pipelined bucketed dispatch the serving path uses. The batch analog of
    the REST hop for notebook/backfill workflows (the reference would loop
    single Seldon requests; here one command rides score_pipelined).
    Honors CCFD_GRAPH_CR and CCFD_MODEL exactly like `serve`, so a backfill
    scores with the SAME model the REST endpoint serves."""
    import numpy as np

    from ccfd_tpu.config import Config
    from ccfd_tpu.data.ccfd import load_dataset
    from ccfd_tpu.serving.scorer import Scorer

    cfg = Config.from_env()
    if cfg.graph_cr:
        from ccfd_tpu.serving.graph import load_graph_cr

        spec = load_graph_cr(cfg.graph_cr)
        cfg = dataclasses.replace(cfg, model_name=spec.name)
    ds = load_dataset(path=args.input or None)
    # checkpoints hold a model-specific pytree: restore only into the
    # matching model (same guard as `serve`), so backfills score with the
    # SAME params the REST endpoint serves
    if cfg.model_name == "mlp":
        params = _restore_mlp_checkpoint(args.checkpoint_dir)
    elif cfg.model_name == "mlp_q8":
        params = _restore_q8_checkpoint(getattr(args, "quantized_dir", ""))
    elif cfg.model_name == "gbt":
        params = _restore_gbt_params(getattr(args, "gbt_dir", ""))
    else:
        params = None
    scorer = Scorer(
        model_name=cfg.model_name, params=params,
        compute_dtype=cfg.compute_dtype, batch_sizes=cfg.batch_sizes,
    )
    scorer.warmup()
    t0 = time.perf_counter()
    proba = scorer.score_pipelined(ds.X, depth=args.depth)
    elapsed = time.perf_counter() - t0
    if args.output:
        # ccfd-lint: disable=durability-seam -- user-requested CSV export to the path THEY named; not a platform artifact
        with open(args.output, "w") as f:
            f.write("proba_1\n")
            f.write("\n".join(repr(float(p)) for p in proba) + "\n")
    frauds = int((proba >= cfg.fraud_threshold).sum())
    print(json.dumps({
        "rows": int(ds.n),
        "seconds": round(elapsed, 3),
        "tx_s": round(ds.n / max(elapsed, 1e-9), 1),
        "flagged_fraud": frauds,
        "fraud_threshold": cfg.fraud_threshold,
        # 0-row input (e.g. a filtered-to-header CSV): mean of nothing is
        # NaN, which json.dumps would emit as invalid JSON
        "mean_proba": round(float(np.mean(proba)), 6) if ds.n else None,
        "output": args.output or None,
        "checkpoint": bool(params is not None),
    }))
    return 0


def cmd_analyze(args: argparse.Namespace) -> int:
    """Batch analytics report: the notebook workflow the reference runs on
    JupyterHub+Spark (frauddetection_cr.yaml:7-53), as one CLI command."""
    import numpy as np

    from ccfd_tpu.analytics.engine import AnalyticsEngine
    from ccfd_tpu.data.ccfd import FEATURE_NAMES, load_dataset

    ds = load_dataset()
    engine = AnalyticsEngine(nbins=args.nbins)
    report = engine.summarize(ds.X, ds.y)
    out = report.to_dict()
    out["workers"] = engine.mesh.size
    # strongest off-diagonal correlations — what the exploration notebook eyeballs
    corr = report.corr.copy()
    idx = np.triu_indices_from(corr, k=1)
    order = np.argsort(-np.abs(corr[idx]))[: args.top_corr]
    out["top_correlations"] = [
        {
            "a": FEATURE_NAMES[idx[0][k]],
            "b": FEATURE_NAMES[idx[1][k]],
            "corr": float(corr[idx][k]),
        }
        for k in order
    ]
    if args.drift_split:
        half = ds.n // 2
        scores = engine.drift(engine.summarize(ds.X[:half]), ds.X[half:])
        worst = int(np.argmax(scores))
        out["drift_self_check"] = {
            "max_psi": float(scores[worst]),
            "worst_feature": FEATURE_NAMES[worst],
        }
    print(json.dumps(out))
    return 0


def cmd_store(args: argparse.Namespace) -> int:
    """Object-store ops: the reference run-book's Ceph/S3 steps
    (README.md:136-343 — serve the store, upload the CSV, `aws s3 ls`)."""
    from ccfd_tpu.config import Config
    from ccfd_tpu.store.client import S3Client
    from ccfd_tpu.store.objectstore import Credentials, ObjectStore
    from ccfd_tpu.store.server import StoreServer

    cfg = Config.from_env()
    creds = Credentials(
        cfg.access_key_id or "ccfd-access", cfg.secret_access_key or "ccfd-secret"
    )
    if args.action == "serve":
        store = ObjectStore(root=args.root)
        store.add_credentials(creds)
        store.create_bucket(cfg.s3_bucket)
        srv = StoreServer(store, host=args.host, port=args.port).start()
        print(json.dumps({"endpoint": srv.endpoint, "bucket": cfg.s3_bucket}))
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            srv.stop()
        return 0

    # explicit --endpoint beats the s3endpoint env var
    client = S3Client(
        args.endpoint or cfg.s3_endpoint or "http://127.0.0.1:9000", creds
    )
    if args.action == "put":
        if args.file:
            with open(args.file, "rb") as f:
                data = f.read()
        else:  # upload the (synthetic or CCFD_CSV) dataset as creditcard.csv
            from ccfd_tpu.data.ccfd import load_dataset, to_csv_bytes

            data = to_csv_bytes(load_dataset())
        client.create_bucket(cfg.s3_bucket)
        client.put(cfg.s3_bucket, cfg.filename, data)
        print(json.dumps({"bucket": cfg.s3_bucket, "key": cfg.filename,
                          "bytes": len(data)}))
    elif args.action == "ls":
        print(json.dumps({"bucket": cfg.s3_bucket,
                          "keys": client.list(cfg.s3_bucket)}))
    return 0


def cmd_manifests(args: argparse.Namespace) -> int:
    """Emit per-service k8s manifests from the platform CR (the reference's
    deploy/*.yaml topology, generated so it can't drift from the spec)."""
    from ccfd_tpu.platform.k8s import write_manifests
    from ccfd_tpu.platform.operator import PlatformSpec

    spec = PlatformSpec.from_yaml(args.file)
    written = write_manifests(spec, args.out)
    print(json.dumps({"written": written}))
    return 0


def cmd_up(args: argparse.Namespace) -> int:
    """Operator entry: CR file -> running platform (the reference run-book
    README.md:44-537 as one command)."""
    from ccfd_tpu.platform.operator import Platform, PlatformSpec

    spec = PlatformSpec.from_yaml(args.file)
    platform = Platform(spec).up()
    print(json.dumps(platform.status(), indent=2), file=sys.stderr)
    try:
        if args.exit_after_producer and not spec.component("producer").enabled:
            print("[up] --exit-after-producer given but producer is disabled "
                  "in the CR", file=sys.stderr)
            platform.down()
            return 2
        _tune_gc()
        if args.exit_after_producer:
            platform.wait_producer(timeout_s=args.drain_s)
            time.sleep(2.0)  # let timers/signals drain
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        for name, reg in platform.registries.items():
            print(f"--- {name} ---", file=sys.stderr)
            print(reg.render(), file=sys.stderr)
        platform.down()
    return 0


def cmd_fleet_member(args: argparse.Namespace) -> int:
    """One fleet member: a full operator Platform from a CR-shaped JSON
    spec file (written by fleet/supervisor.py), sharing the networked bus
    named in its ``bus.url``. Runs until SIGTERM/SIGINT — or SIGKILL,
    which is the point: the fleet drill proves the FLEET survives that."""
    from ccfd_tpu.platform.operator import Platform, PlatformSpec

    with open(args.spec) as f:
        cr = json.load(f)
    platform = Platform(PlatformSpec.from_cr(cr)).up()
    fleet = platform.fleet
    print(json.dumps({
        "member": (fleet.member if fleet is not None else None),
        "heartbeat": (fleet.endpoint if fleet is not None else None),
        "status": platform.status(),
    }, indent=2), file=sys.stderr)
    _tune_gc()
    rc = _serve_forever()
    platform.down()
    return rc


def cmd_fleet_up(args: argparse.Namespace) -> int:
    """Bring up an N-member fleet on this box: one shared bus server
    (embedded unless --bus names one) + N member processes, babysat until
    interrupted. The drill form of this command lives in
    tools/fleet_drill.py (kill/respawn + invariant assertions)."""
    from ccfd_tpu.fleet.supervisor import (
        FleetSupervisor,
        _free_port,
        build_member_cr,
    )

    bus_url = args.bus
    bus_srv = None
    if not bus_url:
        from ccfd_tpu.bus.broker import Broker
        from ccfd_tpu.bus.server import BrokerServer

        broker = Broker(default_partitions=args.partitions)
        bus_srv = BrokerServer(broker)
        port = bus_srv.start("127.0.0.1", 0)
        bus_url = f"http://127.0.0.1:{port}"
        print(f"[fleet] embedded bus on {bus_url}", file=sys.stderr)
    names = [f"m{i:02d}" for i in range(args.members)]
    ports = {n: _free_port() for n in names}
    endpoints = {n: f"http://127.0.0.1:{p}" for n, p in ports.items()}
    sup = FleetSupervisor(bus_url, args.state_dir)
    for n in names:
        sup.add_member(n, build_member_cr(
            n, bus_url, ports[n],
            [endpoints[o] for o in names if o != n],
            args.state_dir,
            ttl_s=args.ttl_s,
            global_max_inflight=args.global_max_inflight,
        ))
        sup.spawn(n)
    try:
        sup.wait_ready(timeout_s=120.0)
        print(json.dumps(sup.status(), indent=2), file=sys.stderr)
        rc = _serve_forever()
    finally:
        sup.stop_all()
        if bus_srv is not None:
            bus_srv.stop()
    return rc


def cmd_fleet_status(args: argparse.Namespace) -> int:
    """Fleet health by heartbeat endpoint: membership, partition
    ownership (with disjointness verdict) and champion parity."""
    from urllib.error import URLError
    from urllib.request import urlopen

    from ccfd_tpu.fleet.member import HEALTH_PATH
    from ccfd_tpu.fleet.protocol import (
        check_disjoint_ownership,
        check_fingerprint_parity,
    )

    health: dict[str, Any] = {}
    for peer in [p.strip() for p in args.peers.split(",") if p.strip()]:
        try:
            with urlopen(peer.rstrip("/") + HEALTH_PATH, timeout=2.0) as r:
                health[peer] = json.loads(r.read().decode())
        except (URLError, OSError, ValueError):
            health[peer] = None
    up = {p: h for p, h in health.items() if h is not None}
    owners = {h["member"]: h.get("partitions", []) for h in up.values()}
    n_partitions = (max((max(ps) for ps in owners.values() if ps),
                        default=-1) + 1)
    doc = {
        "members": health,
        "ownership_violations": check_disjoint_ownership(
            owners, n_partitions),
        "parity": check_fingerprint_parity(
            {h["member"]: h.get("fingerprint") for h in up.values()}),
    }
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        for peer, h in health.items():
            if h is None:
                print(f"{peer}: DOWN")
            else:
                print(f"{peer}: {h['member']} partitions={h.get('partitions')} "
                      f"epoch={h.get('epoch')} "
                      f"quarantined={h.get('quarantined')}")
        print(f"ownership: "
              f"{doc['ownership_violations'] or 'disjoint, all owned'}")
        print(f"parity: {doc['parity']}")
    return 0 if not doc["ownership_violations"] else 1


def _tracing_for(cfg, registry, component):
    """(tracer, sink) for a standalone service role, or (None, None) when
    CCFD_TRACE_SAMPLE=0 turns tracing off. The tracer lands spans in the
    role's SCRAPED registry; the sink's own sampler metrics live in a
    'tracing' registry the caller may also export."""
    if cfg.trace_sample <= 0:
        return None, None
    from ccfd_tpu.metrics.prom import Registry
    from ccfd_tpu.observability.trace import SpanSink, Tracer

    sink = SpanSink(sample=cfg.trace_sample,
                    slow_s=cfg.trace_slow_ms / 1e3, registry=Registry())
    return Tracer(registry, component=component, sink=sink), sink


def _broker_for(cfg, registry=None):
    """BROKER_URL decides the transport: http:// -> RemoteBroker against a
    `bus serve` process; kafka:// -> real-cluster adapter (health counters
    into ``registry`` when given); anything else -> in-process Broker
    (durable when CCFD_BUS_DIR is set)."""
    from ccfd_tpu.bus.client import broker_from_url

    kwargs = (
        {"registry": registry}
        if registry is not None and cfg.broker_url.startswith("kafka://")
        else {}
    )
    remote = broker_from_url(cfg.broker_url, **kwargs)
    if remote is not None:
        return remote
    from ccfd_tpu.bus.broker import Broker

    return Broker(log_dir=cfg.bus_log_dir or None, fsync=cfg.bus_fsync,
                    retention_records=cfg.bus_retention_records or None,
                    retention_overrides=cfg.parsed_retention_overrides())


def _install_sigterm_as_interrupt() -> None:
    """k8s stops pods with SIGTERM (the generated manifests run these
    commands as containers); Python's default handler would kill the
    process without running any of the KeyboardInterrupt cleanup paths
    below (server stop, engine state save). Map SIGTERM to the same
    graceful path SIGINT takes."""
    import signal

    def raise_interrupt(signum, frame):  # noqa: ARG001
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, raise_interrupt)
    except (ValueError, OSError):  # pragma: no cover - non-main thread
        pass


def _serve_forever() -> int:
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        return 0


def cmd_bus(args: argparse.Namespace) -> int:
    """Standalone networked broker — the Kafka-cluster role (reference
    deploy/frauddetection_cr.yaml:73-77), durable when --dir is given."""
    from ccfd_tpu.bus.broker import Broker
    from ccfd_tpu.bus.server import BrokerServer

    cfg = Config.from_env()
    log_dir = args.dir or (cfg.bus_log_dir or None)
    broker = Broker(log_dir=log_dir, fsync=cfg.bus_fsync,
                    retention_records=cfg.bus_retention_records or None,
                    retention_overrides=cfg.parsed_retention_overrides())
    from ccfd_tpu.metrics.prom import Registry

    bus_registry = Registry()
    tracer, _sink = _tracing_for(cfg, bus_registry, "bus")
    srv = BrokerServer(broker, registry=bus_registry, tracer=tracer)
    port = srv.start(args.host, args.port)
    print(f"[bus] listening on {args.host}:{port}"
          + (f" (durable: {log_dir})" if log_dir else " (memory)"), file=sys.stderr)
    _tune_gc()
    rc = _serve_forever()
    srv.stop()
    return rc


def cmd_engine(args: argparse.Namespace) -> int:
    """Standalone KIE-shaped engine server (reference ccd-service on :8090)."""
    from ccfd_tpu.process.fraud import build_engine
    from ccfd_tpu.process.server import EngineServer

    cfg = Config.from_env()
    broker = _broker_for(cfg)
    engine = build_engine(cfg, broker)
    if args.state_file:
        import os as _os

        if _os.path.exists(args.state_file):
            engine.load(args.state_file)
    tracer, _sink = _tracing_for(cfg, engine.registry, "kie")
    srv = EngineServer(engine, tracer=tracer)
    port = srv.start(args.host, args.port)
    print(f"[engine] KIE REST on {args.host}:{port} "
          f"definitions={list(engine.definitions())}", file=sys.stderr)
    _tune_gc()
    try:
        while True:
            time.sleep(args.save_interval_s if args.state_file else 3600)
            if args.state_file:
                engine.save(args.state_file)
    except KeyboardInterrupt:
        if args.state_file:
            engine.save(args.state_file)
    srv.stop()
    return 0


def cmd_router(args: argparse.Namespace) -> int:
    """Standalone decision router (reference ccd-fuse): remote bus, remote
    or local scorer (SELDON_URL), remote engine (KIE_SERVER_URL)."""
    from ccfd_tpu.router.router import Router

    cfg = Config.from_env()
    # fail the cheap misconfiguration first: building + warming the local
    # scorer can cost minutes of XLA compilation
    if not cfg.kie_server_url.startswith("http"):
        print("[router] standalone mode needs KIE_SERVER_URL=http://... "
              "(run `python -m ccfd_tpu engine`)", file=sys.stderr)
        return 2
    from ccfd_tpu.metrics.prom import Registry

    router_registry = Registry()
    # the adapter's produce/send-error counters land in the router's
    # scraped registry (the KafkaCluster board's adapter panels)
    broker = _broker_for(cfg, registry=router_registry)
    tracer, trace_sink = _tracing_for(cfg, router_registry, "router")
    # standing fault plan from CCFD_FAULTS (runtime/faults.py): degraded
    # edges are injectable on the standalone role exactly like under the
    # platform operator
    fault_plan = None
    if cfg.faults_spec:
        from ccfd_tpu.runtime.faults import FaultPlan

        fault_plan = FaultPlan.from_string(cfg.faults_spec)
    scorer_faults = (fault_plan.injector("scorer", router_registry)
                     if fault_plan else None)
    host_score_fn = None
    if cfg.seldon_url.startswith("http"):
        from ccfd_tpu.serving.client import SeldonClient

        score_fn = SeldonClient(cfg, faults=scorer_faults,
                                tracer=tracer).score
    else:
        from ccfd_tpu.serving.scorer import Scorer

        scorer = Scorer(model_name=cfg.model_name, compute_dtype=cfg.compute_dtype,
                        batch_sizes=cfg.batch_sizes,
                        dispatch_deadline_ms=cfg.scorer_dispatch_deadline_ms())
        scorer.warmup()
        score_fn = scorer.score
        if scorer_faults is not None:
            score_fn = scorer_faults.wrap_fn(score_fn)
        if scorer.has_host_forward:
            host_score_fn = scorer.host_score
    from ccfd_tpu.process.client import EngineRestClient

    engine = EngineRestClient(cfg.kie_server_url,
                              timeout_s=cfg.seldon_timeout_ms / 1000.0,
                              retries=cfg.client_retries,
                              tracer=tracer)
    if fault_plan is not None:
        inj = fault_plan.injector("engine", router_registry)
        if inj is not None:
            engine = inj.wrap(engine, methods=("start_process",
                                               "start_process_batch",
                                               "signal"))
    # production role: the degradation ladder is on (same default as the
    # platform operator) — a sick scorer edge degrades, never stalls.
    # --workers (or CCFD_ROUTER_WORKERS) fans the loop out partition-
    # parallel with shared coalesced dispatch (router/parallel.py).
    workers = (args.workers if args.workers is not None
               else cfg.router_workers)
    # overload control (runtime/overload.py): same default-on wiring as
    # the platform operator — adaptive AIMD in-flight budget, priority-
    # aware shedding, dispatch watchdog (CCFD_OVERLOAD_* env knobs)
    overload = None
    if cfg.overload_enabled:
        from ccfd_tpu.runtime.overload import OverloadControl

        n_eff = workers if workers > 0 else max(
            1, len(broker.end_offsets(cfg.kafka_topic)))
        overload = OverloadControl.from_config(
            cfg, router_registry, max_batch=4096, workers=n_eff)
    if workers == 1:
        router = Router(cfg, broker, score_fn, engine,
                        registry=router_registry,
                        host_score_fn=host_score_fn, degrade=True,
                        tracer=tracer, overload=overload)
    else:
        from ccfd_tpu.router.parallel import ParallelRouter

        router = ParallelRouter(cfg, broker, score_fn, engine,
                                registry=router_registry, workers=workers,
                                host_score_fn=host_score_fn, degrade=True,
                                tracer=tracer, coalesce=cfg.router_coalesce,
                                overload=overload)
    # the reference scrapes the router on :8091/prometheus
    # (reference README.md:503-507); the standalone role must expose the
    # same surface the generated k8s Service/annotations point at
    from ccfd_tpu.metrics.exporter import MetricsExporter

    regs = {"router": router.registry}
    if trace_sink is not None:
        regs["tracing"] = trace_sink.registry
    exporter = MetricsExporter(
        regs, host="0.0.0.0", port=args.metrics_port, sink=trace_sink,
    ).start()
    print(f"[router] consuming {cfg.kafka_topic!r} from {cfg.broker_url}; "
          f"metrics on :{args.metrics_port}/prometheus", file=sys.stderr)
    _tune_gc()
    try:
        router.run(poll_timeout_s=0.05)
    except KeyboardInterrupt:
        router.close()
    exporter.stop()
    return 0


def cmd_notify(args: argparse.Namespace) -> int:
    """Standalone notification service (reference notification-service)."""
    from ccfd_tpu.notify.service import NotificationService

    cfg = Config.from_env()
    broker = _broker_for(cfg)
    from ccfd_tpu.metrics.prom import Registry

    notify_registry = Registry()
    tracer, trace_sink = _tracing_for(cfg, notify_registry, "notify")
    svc = NotificationService(cfg, broker, notify_registry,
                              reply_prob=args.reply_prob,
                              approve_prob=args.approve_prob, seed=args.seed,
                              tracer=tracer)
    from ccfd_tpu.metrics.exporter import MetricsExporter

    regs = {"notify": svc.registry}
    if trace_sink is not None:
        regs["tracing"] = trace_sink.registry
    exporter = MetricsExporter(
        regs, host="0.0.0.0", port=args.metrics_port, sink=trace_sink,
    ).start()
    print(f"[notify] consuming {cfg.customer_notification_topic!r} from "
          f"{cfg.broker_url}; metrics on :{args.metrics_port}/prometheus",
          file=sys.stderr)
    _tune_gc()
    try:
        svc.run(poll_timeout_s=0.05)
    except KeyboardInterrupt:
        svc.stop()
    exporter.stop()
    return 0


def cmd_investigate(args: argparse.Namespace) -> int:
    """Investigator simulation working the engine's task queue over the
    KIE-shaped REST contract (the demo's Business Central humans,
    reference README.md:547-581) — seeded verdicts, rate-limited, trusts
    confident console pre-fills; the decisions train the user-task
    model."""
    from ccfd_tpu.process.client import EngineRestClient
    from ccfd_tpu.process.investigator import InvestigatorService

    cfg = Config.from_env()
    engine = EngineRestClient(
        args.engine_url or cfg.kie_server_url,
        timeout_s=cfg.seldon_timeout_ms / 1000.0,
        retries=cfg.client_retries,
    )
    svc = InvestigatorService(
        engine, rate_per_s=args.rate, trust_threshold=args.trust,
        base_fraud_rate=args.fraud_rate, seed=args.seed,
    )
    from ccfd_tpu.metrics.exporter import MetricsExporter

    exporter = MetricsExporter(
        {"investigator": svc.registry}, host="0.0.0.0",
        port=args.metrics_port,
    ).start()
    print(f"[investigate] working {args.engine_url or cfg.kie_server_url} "
          f"at <= {args.rate}/s; metrics on :{args.metrics_port}/prometheus",
          file=sys.stderr)
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.stop()
    exporter.stop()
    return 0


def cmd_producer(args: argparse.Namespace) -> int:
    """Standalone transaction producer (reference ProducerDeployment)."""
    from ccfd_tpu.producer.producer import Producer

    cfg = Config.from_env()
    broker = _broker_for(cfg)
    from ccfd_tpu.metrics.prom import Registry

    producer_registry = Registry()
    tracer, _sink = _tracing_for(cfg, producer_registry, "producer")
    producer = Producer(cfg, broker, registry=producer_registry,
                        tracer=tracer)
    n = producer.run(limit=args.limit, rate_per_s=args.rate,
                     wire_format=args.wire_format)
    print(f"[producer] streamed {n} rows to {cfg.producer_topic!r}",
          file=sys.stderr)
    return 0


def cmd_tasks(args: argparse.Namespace) -> int:
    """The investigator's CLI: list and complete user tasks on the engine
    (reference: KIE console user-task workflow, README.md:571-605 /
    docs/images/events-3 — the investigation branch's human decisions).
    Completing with --outcome approved/rejected is exactly the decision
    the user-task prediction model learns from (process/usertask_model)."""
    from ccfd_tpu.process.client import EngineRestClient

    cfg = Config.from_env()
    url = args.engine_url or cfg.kie_server_url
    if not url.startswith("http"):
        print(
            f"[tasks] KIE_SERVER_URL={url!r} is not an http engine endpoint; "
            "start one with `ccfd_tpu engine` and point --engine-url at it",
            file=sys.stderr,
        )
        return 2
    client = EngineRestClient(
        url,
        timeout_s=cfg.seldon_timeout_ms / 1000.0,
        retries=cfg.client_retries,
    )
    if args.complete is not None:
        # the engine's completion payload is the boolean is_fraud verdict
        # (fraud.py task_outcome gateway: truthy => cancel the transaction);
        # the CLI speaks the investigator's words and maps them explicitly —
        # passing the raw string through would make "approved" truthy and
        # CANCEL the transaction
        verdicts = {"approved": False, "rejected": True,
                    "false": False, "true": True}
        if args.outcome is None or args.outcome.lower() not in verdicts:
            print(
                "[tasks] --complete requires --outcome approved|rejected "
                "(approved = legitimate transaction, rejected = confirmed "
                "fraud)",
                file=sys.stderr,
            )
            return 2
        is_fraud = verdicts[args.outcome.lower()]
        try:
            client.complete_task(args.complete, is_fraud)
        except (RuntimeError, OSError) as e:
            print(f"[tasks] engine error: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"completed": args.complete,
                          "outcome": args.outcome.lower(),
                          "is_fraud": is_fraud}))
        return 0
    try:
        views = client.tasks(args.status)
    except (RuntimeError, OSError) as e:
        print(f"[tasks] engine error: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"status": args.status, "count": len(views),
                      "tasks": views}))
    return 0


def cmd_loadgen(args: argparse.Namespace) -> int:
    """Drive a RUNNING scorer endpoint (local or remote) with the lean
    client of ``utils/loadgen.py`` and print its report. Exits non-zero
    when any request errored — usable as a smoke gate in deploy pipelines."""
    from ccfd_tpu.utils.loadgen import run_loadgen

    cfg = Config.from_env()
    report = run_loadgen(
        args.url, clients=args.clients, rows_per_request=args.rows,
        seconds=args.seconds, path=args.path, token=cfg.seldon_token,
    )
    print(json.dumps(report))
    return 0 if report["errors"] == 0 and report["failed_clients"] == 0 else 3


def cmd_doctor(args: argparse.Namespace) -> int:
    """One-shot operational health report: one JSON object on stdout, exit
    code 0 only when the accelerator answered.

    ``doctor`` itself never initialises a JAX backend; the accelerator
    section runs in a child process with a timeout, which is what makes
    that child legitimate (a chip belongs to one process at a time, and
    the parent never holds it). For the same reason the probe FAILS while
    a server, benchmark or smoke on this host holds the chip — run it before
    starting one, or read the server's own ``/debug/device``.

    Sections: accelerator (platform, device count, measured dispatch RTT),
    native toolchain, bus/store reachability for the configured URLs,
    checkpoint presence, and the env-contract values in effect.
    """
    import subprocess

    cfg = Config.from_env()
    report: dict[str, Any] = {"ok": True}

    # --- accelerator (subprocess probe + tiny-dispatch RTT) ---------------
    probe_code = (
        "import json, time, jax\n"
        "d = jax.devices()\n"
        "import jax.numpy as jnp\n"
        "x = jnp.zeros((16, 30), jnp.float32)\n"
        "(x @ x.T).block_until_ready()  # compile\n"
        "t0 = time.perf_counter()\n"
        "for _ in range(5): (x @ x.T).block_until_ready()\n"
        "rtt_ms = (time.perf_counter() - t0) / 5 * 1e3\n"
        "print(json.dumps({'platform': jax.default_backend(),"
        " 'devices': len(d), 'dispatch_rtt_ms': round(rtt_ms, 3)}))\n"
    )
    t0 = time.perf_counter()
    try:
        r = subprocess.run(
            [sys.executable, "-c", probe_code],
            timeout=args.probe_s, capture_output=True, text=True,
        )
        if r.returncode == 0 and r.stdout.strip():
            report["accelerator"] = json.loads(r.stdout.strip().splitlines()[-1])
            report["accelerator"]["probe_s"] = round(
                time.perf_counter() - t0, 2
            )
        else:
            report["accelerator"] = {
                "error": (r.stderr or "probe failed").strip()[-300:],
            }
            report["ok"] = False
    except subprocess.TimeoutExpired:
        report["accelerator"] = {
            "error": f"no answer within {args.probe_s:.0f}s (jax.devices() "
            "did not return: the chip is held by another process or the "
            "runtime is stuck)",
        }
        report["ok"] = False

    # --- native toolchain -------------------------------------------------
    try:
        from ccfd_tpu.native import native_available

        report["native_toolchain"] = bool(native_available())
    except Exception as e:  # noqa: BLE001 - report, don't crash the doctor
        report["native_toolchain"] = f"error: {e}"

    # --- bus / store reachability (only for networked URLs) ---------------
    def _tcp_check(url: str) -> str:
        import socket
        from urllib.parse import urlparse

        if not url.startswith(("http://", "https://", "kafka://")):
            return "in-process (nothing to dial)"
        p = urlparse(url)
        # scheme-correct default ports: 9092 is Kafka's, not HTTP's
        port = p.port or {
            "kafka": 9092, "https": 443
        }.get(p.scheme, 80)
        try:
            with socket.create_connection((p.hostname, port), timeout=3):
                return "reachable"
        except OSError as e:
            return f"unreachable: {e}"

    report["bus"] = {"url": cfg.broker_url, "status": _tcp_check(cfg.broker_url)}
    if cfg.s3_endpoint:
        report["store"] = {
            "url": cfg.s3_endpoint, "status": _tcp_check(cfg.s3_endpoint),
        }

    # --- model artifacts --------------------------------------------------
    from ccfd_tpu.parallel.checkpoint import CheckpointManager

    for label, d in (("checkpoint", args.checkpoint_dir),
                     ("quantized", args.quantized_dir)):
        try:
            step = CheckpointManager(d).latest_step()
        except Exception:  # noqa: BLE001 - unreadable dir reads as absent
            step = None
        report[label] = {"dir": d, "latest_step": step}
    report["gbt"] = {
        "dir": _GBT_DIR,
        "present": os.path.exists(os.path.join(_GBT_DIR, "params.npz")),
    }

    # --- config in effect -------------------------------------------------
    report["config"] = {
        "model": cfg.model_name,
        "compute_dtype": cfg.compute_dtype,
        "fraud_threshold": cfg.fraud_threshold,
        "seldon_timeout_ms": cfg.seldon_timeout_ms,
        "dispatch_deadline_ms": cfg.dispatch_deadline_ms,
        # the resolved value serving would arm (-1 above = auto). Computed
        # from the SUBPROCESS probe's platform — Config's own helper calls
        # jax.default_backend(), which would initialize a backend in THIS
        # process, and doctor stays off JAX
        "dispatch_deadline_ms_effective": (
            cfg.dispatch_deadline_ms
            if cfg.dispatch_deadline_ms >= 0
            else (
                f"unknown (probe failed; accelerator backends arm "
                f"{cfg.seldon_timeout_ms})"
                if "platform" not in report["accelerator"]
                else (
                    0.0
                    if report["accelerator"]["platform"] == "cpu"
                    else float(cfg.seldon_timeout_ms)
                )
            )
        ),
        "host_tier_rows": cfg.host_tier_rows,
        "batch_sizes": list(cfg.batch_sizes),
    }
    print(json.dumps(report))
    return 0 if report["ok"] else 3


def cmd_lint(args: argparse.Namespace) -> int:
    """``ccfd_tpu lint``: the repo's review-finding invariants as a
    machine-checked gate (analysis/ — AST rules + suppression pragmas +
    baseline). Exit 0 only when every finding is fixed, suppressed with
    an inline justification, or grandfathered in the baseline. Stays
    jax-free: the gate must run before (and regardless of) any
    accelerator bring-up."""
    from ccfd_tpu.analysis import core as lint_core

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    baseline_path = args.baseline
    if baseline_path is None:
        baseline_path = os.path.join(root, "tools", "lint_baseline.json")
    if args.write_baseline and args.rules:
        # a subset run sees only that subset's findings; writing them out
        # would silently DROP every other rule's grandfathered entries
        print("[lint] --write-baseline regenerates the FULL baseline; "
              "combining it with --rules would drop the other rules' "
              "entries", file=sys.stderr)
        return 2
    try:
        report = lint_core.run_lint(
            root,
            paths=args.paths or None,
            # --write-baseline must see EVERY finding, including ones the
            # current baseline already grandfathers — filtering first
            # would empty the baseline on the second consecutive run
            baseline_path=(None if (args.no_baseline or args.write_baseline)
                           else baseline_path),
            rule_names=args.rules.split(",") if args.rules else None,
        )
    except ValueError as e:  # unknown rule, bad target, malformed baseline
        print(f"[lint] {e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        lint_core.write_baseline(baseline_path, report.findings)
        print(f"[lint] wrote {len(report.findings)} finding(s) to "
              f"{baseline_path}", file=sys.stderr)
        return 0
    if args.json:
        print(json.dumps(report.to_json(), indent=1, sort_keys=True))
    else:
        for line in report.human_lines():
            print(line)
    return report.exit_code


def _tune_gc() -> None:
    """Service processes amortize gc over large gen-0 batches: jax's gc
    callback runs XLA garbage collection on EVERY Python collection, and
    the hot loops' record churn fires gen-0 hundreds of times per second
    at the default threshold (utils/gctune.py; CCFD_GC_THRESHOLD=0 opts
    out)."""
    from ccfd_tpu.utils.gctune import tune_for_service

    tune_for_service()


# commands whose code path imports jax; the others (bus, notify, producer,
# store, engine) stay jax-free and must not pay the import at startup
_JAX_CMDS = {"demo", "serve", "train", "analyze", "router", "up", "score",
             "quantize"}


def _is_jax_command(argv: list[str]) -> bool:
    """Does this invocation run JAX in THIS process? ``fleet member`` and
    ``replay --live`` bring a platform up; ``fleet up`` / ``fleet status``
    only start and poll member processes and must stay off JAX — a chip
    belongs to one process, and a supervisor that held it would starve
    the member it spawns."""
    if not argv:
        return False
    return (argv[0] in _JAX_CMDS
            or argv[:2] == ["fleet", "member"]
            or (argv[0] == "replay" and "--live" in argv))


_SERVICE_CMDS = {"serve", "bus", "engine", "router", "notify", "store", "up",
                 "fleet", "replay"}


def main(argv: list[str] | None = None) -> int:
    args_list = list(sys.argv[1:] if argv is None else argv)
    if _is_jax_command(args_list):
        from ccfd_tpu.utils.backend import require_backend
        from ccfd_tpu.utils.compile_cache import enable as _enable_cache

        # one backend rule, no fallback: JAX_PLATFORMS=cpu means CPU,
        # anything else must come up on a TPU or the command stops here
        require_backend()
        # persistent XLA compilation cache: a restart reuses the bucket
        # ladder's executables instead of compiling them again
        _enable_cache()
    if args_list and args_list[0] in _SERVICE_CMDS:
        _install_sigterm_as_interrupt()
    p = argparse.ArgumentParser(prog="ccfd_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    d = sub.add_parser("demo", help="run the full pipeline in-process")
    d.add_argument("--transactions", type=int, default=2000)
    d.add_argument("--rate", type=float, default=None)
    d.add_argument("--train-steps", type=int, default=200)
    d.add_argument("--reply-timeout", type=float, default=2.0)
    d.add_argument("--drain-s", type=float, default=30.0)
    d.add_argument("--wire-format", choices=("dict", "csv"), default="dict")
    d.add_argument("--seed", type=int, default=0)
    d.set_defaults(fn=cmd_demo)

    s = sub.add_parser(
        "serve", help="REST prediction server (Seldon contract)",
        description="Model selection is CCFD_MODEL (config.py). Decided "
        "defaults (measured, ENSEMBLE_r04.json): `mlp` for THROUGHPUT "
        "(the MXU path), `logreg`/modelfull for RANKING QUALITY (held-out "
        "AUC 0.9638 vs 0.9484 — and the validation-selected ensemble "
        "blend weight is w_mlp=0.0, i.e. blending the MLP into the "
        "linear model does not improve ranking on the canonical table; "
        "the graph CR remains the multi-node serving surface, not a "
        "quality upgrade).",
    )
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--train", action="store_true", help="train before serving")
    s.add_argument("--train-steps", type=int, default=300)
    s.add_argument("--checkpoint-dir", default="./checkpoints",
                   help="serve the newest `train` checkpoint when present")
    s.add_argument("--quantized-dir", default=_Q8_DIR,
                   help="int8 checkpoint dir used when CCFD_MODEL=mlp_q8")
    s.add_argument("--gbt-dir", default=_GBT_DIR,
                   help="tree params dir used when CCFD_MODEL=gbt "
                        "(written by `train --family hgb`)")
    s.set_defaults(fn=cmd_serve)

    t = sub.add_parser(
        "train",
        help="offline-train the flagship MLP (or --family hgb for the "
             "servable HistGradientBoosting tree ensemble)",
    )
    t.add_argument("--steps", type=int, default=500)
    t.add_argument("--checkpoint-dir", default="./checkpoints")
    t.add_argument("--family", choices=("mlp", "hgb"), default="mlp",
                   help="hgb: sklearn HistGradientBoosting (bounded depth) "
                        "-> served gbt params; quality-tied with logreg at "
                        "0.9641 held-out (HGB_SERVABLE_r04.json)")
    t.add_argument("--hgb-depth", type=int, default=8,
                   help="max tree depth for --family hgb (the dense "
                        "embedding is 2^depth nodes/tree)")
    t.add_argument("--gbt-dir", default=_GBT_DIR,
                   help="output dir for --family hgb params")
    t.add_argument("--from-store", action="store_true",
                   help="fetch creditcard.csv from the object store "
                        "(the reference's S3 data path)")
    t.add_argument("--store-url", default="",
                   help="store endpoint (default: s3endpoint env)")
    t.add_argument("--test-frac", type=float, default=0.2)
    t.set_defaults(fn=cmd_train)

    q = sub.add_parser(
        "quantize", help="int8-quantize the newest train checkpoint (mlp_q8)"
    )
    q.add_argument("--checkpoint-dir", default="./checkpoints")
    q.add_argument("--out-dir", default=_Q8_DIR)
    q.add_argument("--test-frac", type=float, default=0.2)
    q.set_defaults(fn=cmd_quantize)

    au = sub.add_parser(
        "audit",
        help="reconstruct one decision by tx id (decision provenance "
             "plane), or tail the engine's audit event stream",
    )
    au.add_argument("tx_id", nargs="?", default=None,
                    help="transaction id (or partition:offset uid) to "
                    "reconstruct; omit to tail the engine audit stream")
    au.add_argument("--dir", default="",
                    help="audit log dir (default: CCFD_AUDIT_DIR)")
    au.add_argument("--lifecycle-dir", default="",
                    help="lifecycle state dir for the lineage join "
                    "(default: CCFD_LIFECYCLE_DIR)")
    au.add_argument("--incident-dir", default="",
                    help="incident bundle dir for the incident join "
                    "(default: CCFD_INCIDENT_DIR)")
    au.add_argument("--url", default="",
                    help="live exporter endpoint: fetch the record, "
                    "incident bundle and kept trace over HTTP instead "
                    "of (or in addition to) the on-disk artifacts")
    au.add_argument("--json", action="store_true",
                    help="emit the full reconstruction document as JSON")
    au.add_argument("--topic", default="", help="default: CCFD_AUDIT_TOPIC")
    au.add_argument("--group", default="audit-tail",
                    help="consumer group (offsets persist per group)")
    au.add_argument("--follow", action="store_true", help="keep consuming")
    au.add_argument("--limit", type=int, default=0, help="stop after N events")
    au.set_defaults(fn=cmd_audit)

    rp = sub.add_parser(
        "replay",
        help="bulk replay & backtest: re-score a recorded audit window "
             "with verdict-parity conservation (replay plane)",
    )
    rp.add_argument("--dir", default="",
                    help="audit log dir holding the recorded window "
                    "(default: CCFD_AUDIT_DIR)")
    rp.add_argument("--since-seq", type=int, default=None,
                    help="window start (DecisionRecord seq, inclusive)")
    rp.add_argument("--until-seq", type=int, default=None,
                    help="window end (DecisionRecord seq, inclusive)")
    rp.add_argument("--from-incident", default="",
                    help="incident bundle JSON: re-drive the decisions "
                    "in flight across the breach window")
    rp.add_argument("--what-if-threshold", type=float, default=None,
                    help="host-side backtest: which recorded decisions "
                    "flip under this FRAUD_THRESHOLD (never touches the "
                    "live path)")
    rp.add_argument("--live", action="store_true",
                    help="bring the platform up and re-produce the window "
                    "through the live serving path under bulk admission")
    rp.add_argument("--cr", default="",
                    help="CR file for --live (default: a minimal replay "
                    "platform over --dir)")
    rp.add_argument("--state-dir", default="",
                    help="durable replay-cursor dir (default: "
                    "CCFD_REPLAY_DIR)")
    rp.add_argument("--window-id", default="",
                    help="explicit window id (cursor key; default: the "
                    "seq range)")
    rp.add_argument("--no-resume", action="store_true",
                    help="ignore an existing cursor and restart the "
                    "window from its first row")
    rp.add_argument("--json", action="store_true",
                    help="emit the full report (bounded findings "
                    "included) as JSON")
    rp.set_defaults(fn=cmd_replay)

    lc = sub.add_parser(
        "lifecycle",
        help="model-lifecycle lineage + audit trail (versions console)",
    )
    lc.add_argument("--dir", default="",
                    help="lifecycle state dir (default: CCFD_LIFECYCLE_DIR)")
    lc.add_argument("--audit", action="store_true",
                    help="print the transition audit trail too")
    lc.add_argument("--version", type=int, default=0,
                    help="restrict the audit trail to one version id")
    lc.add_argument("--json", action="store_true",
                    help="emit the full lineage+audit as JSON")
    lc.set_defaults(fn=cmd_lifecycle)

    sc = sub.add_parser("score", help="offline bulk scoring: CSV -> probabilities")
    sc.add_argument("--input", default="", help="creditcard.csv path (default: CCFD_CSV/synthetic)")
    sc.add_argument("--output", default="", help="write proba_1 CSV here")
    sc.add_argument("--depth", type=int, default=2, help="pipelined dispatch depth")
    sc.add_argument("--checkpoint-dir", default="./checkpoints")
    sc.add_argument("--quantized-dir", default=_Q8_DIR,
                    help="int8 checkpoint dir used when CCFD_MODEL=mlp_q8")
    sc.add_argument("--gbt-dir", default=_GBT_DIR,
                    help="tree params dir used when CCFD_MODEL=gbt")
    sc.set_defaults(fn=cmd_score)

    an = sub.add_parser("analyze", help="dataset analytics report (Spark/notebook analog)")
    an.add_argument("--nbins", type=int, default=32)
    an.add_argument("--top-corr", type=int, default=8)
    an.add_argument("--drift-split", action="store_true",
                    help="also run a first-half vs second-half drift self-check")
    an.set_defaults(fn=cmd_analyze)

    st = sub.add_parser("store", help="S3-shaped object store (serve/put/ls)")
    st.add_argument("action", choices=("serve", "put", "ls"))
    st.add_argument("--root", default=None, help="persistence dir (serve)")
    st.add_argument("--host", default="127.0.0.1")
    st.add_argument("--port", type=int, default=9000)
    st.add_argument("--endpoint", default=None,
                    help="store endpoint (overrides s3endpoint env)")
    st.add_argument("--file", default=None, help="local file to upload (put)")
    st.set_defaults(fn=cmd_store)

    bus = sub.add_parser("bus", help="networked broker (Kafka-cluster role)")
    bus.add_argument("--host", default="0.0.0.0")
    bus.add_argument("--port", type=int, default=9092)
    bus.add_argument("--dir", default=None, help="durable segment-log dir")
    bus.set_defaults(fn=cmd_bus)

    en = sub.add_parser("engine", help="KIE-shaped process engine server")
    en.add_argument("--host", default="0.0.0.0")
    en.add_argument("--port", type=int, default=8090)
    en.add_argument("--state-file", default=None)
    en.add_argument("--save-interval-s", type=float, default=5.0)
    en.set_defaults(fn=cmd_engine)

    ro = sub.add_parser("router", help="standalone decision router")
    ro.add_argument("--metrics-port", type=int, default=8091)  # README.md:503-507
    ro.add_argument("--workers", type=int, default=None,
                    help="partition-parallel worker loops sharing one "
                    "coalesced scorer dispatch (default: "
                    "CCFD_ROUTER_WORKERS; 1 = single router, 0 = one "
                    "worker per bus partition)")
    ro.set_defaults(fn=cmd_router)

    no = sub.add_parser("notify", help="standalone notification service")
    no.add_argument("--reply-prob", type=float, default=0.8)
    no.add_argument("--approve-prob", type=float, default=0.7)
    no.add_argument("--seed", type=int, default=0)
    no.add_argument("--metrics-port", type=int, default=8080)
    no.set_defaults(fn=cmd_notify)

    inv = sub.add_parser(
        "investigate",
        help="investigator simulation over the KIE REST contract",
    )
    inv.add_argument("--engine-url", default="",
                     help="engine REST base (default: KIE_SERVER_URL)")
    inv.add_argument("--rate", type=float, default=50.0,
                     help="max task completions per second")
    inv.add_argument("--trust", type=float, default=0.9,
                     help="follow the console pre-fill at/above this "
                          "prediction confidence")
    inv.add_argument("--fraud-rate", type=float, default=0.05,
                     help="independent-verdict fraud probability")
    inv.add_argument("--seed", type=int, default=0)
    inv.add_argument("--metrics-port", type=int, default=8082)
    inv.set_defaults(fn=cmd_investigate)

    pr = sub.add_parser("producer", help="standalone transaction producer")
    pr.add_argument("--limit", type=int, default=None)
    pr.add_argument("--rate", type=float, default=None)
    pr.add_argument("--wire-format", choices=("dict", "csv"), default="csv")
    pr.set_defaults(fn=cmd_producer)

    mf = sub.add_parser("manifests", help="emit k8s manifests from the CR")
    mf.add_argument("-f", "--file", default="deploy/platform_cr.yaml")
    mf.add_argument("-o", "--out", default="deploy/k8s")
    mf.set_defaults(fn=cmd_manifests)

    u = sub.add_parser("up", help="bring up the platform from a CR file")
    u.add_argument("-f", "--file", default="deploy/platform_cr.yaml")
    u.add_argument("--exit-after-producer", action="store_true")
    u.add_argument("--drain-s", type=float, default=120.0)
    u.set_defaults(fn=cmd_up)

    fl = sub.add_parser(
        "fleet",
        help="multi-host fleet: N operator processes over one shared bus "
             "(membership, admission shares, champion parity; fleet/). "
             "Each member is a JAX process and a chip belongs to one "
             "process: on a one-chip host only one member can hold it — "
             "run the others with JAX_PLATFORMS=cpu or on their own chip",
    )
    flsub = fl.add_subparsers(dest="action", required=True)
    flm = flsub.add_parser(
        "member", help="run ONE fleet member from a CR-shaped JSON spec "
                       "(normally exec'd by the fleet supervisor)")
    flm.add_argument("--spec", required=True,
                     help="member spec file (fleet/supervisor.py "
                          "build_member_cr shape)")
    flm.set_defaults(fn=cmd_fleet_member)
    flu = flsub.add_parser(
        "up", help="spawn an N-member fleet (embedded bus unless --bus)")
    flu.add_argument("--members", type=int, default=2)
    flu.add_argument("--bus", default="",
                     help="shared bus URL (default: start an embedded "
                          "bus server on a free port)")
    flu.add_argument("--state-dir", default="./fleet-state")
    flu.add_argument("--partitions", type=int, default=4,
                     help="tx-topic partitions for the embedded bus")
    flu.add_argument("--ttl-s", type=float, default=3.0,
                     help="membership lease")
    flu.add_argument("--global-max-inflight", type=int, default=0,
                     help="fleet-wide admission ceiling (0 = per-member "
                          "budgets stand alone)")
    flu.set_defaults(fn=cmd_fleet_up)
    fls = flsub.add_parser(
        "status", help="fleet health by peer heartbeat endpoints")
    fls.add_argument("--peers", required=True,
                     help="comma-separated heartbeat endpoints")
    fls.add_argument("--json", action="store_true")
    fls.set_defaults(fn=cmd_fleet_status)

    tk = sub.add_parser(
        "tasks", help="investigator workflow: list/complete engine user tasks"
    )
    tk.add_argument("--engine-url", default="",
                    help="engine REST base (default: KIE_SERVER_URL)")
    tk.add_argument("--status", default="open")
    tk.add_argument("--complete", type=int, default=None, metavar="TASK_ID")
    tk.add_argument("--outcome", default=None,
                    help="approved | rejected (with --complete)")
    tk.set_defaults(fn=cmd_tasks)

    lg = sub.add_parser(
        "loadgen", help="drive a deployed scorer's REST endpoint (JSON report)"
    )
    lg.add_argument("--url", default="http://127.0.0.1:8000")
    lg.add_argument("--clients", type=int, default=8)
    lg.add_argument("--rows", type=int, default=16)
    lg.add_argument("--seconds", type=float, default=10.0)
    lg.add_argument("--path", default=None,
                    help="request path (default: the URL's own path, else "
                         "/api/v0.1/predictions)")
    lg.set_defaults(fn=cmd_loadgen)

    li = sub.add_parser(
        "lint",
        help="AST invariant checker over ccfd_tpu/ (review findings as "
             "machine-checked rules; see analysis/)",
    )
    li.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: ccfd_tpu/)")
    li.add_argument("--root", default="",
                    help="repo root (default: the installed package's "
                         "parent)")
    li.add_argument("--json", action="store_true",
                    help="strict-JSON report instead of human lines")
    li.add_argument("--rules", default="",
                    help="comma-separated rule subset (default: all)")
    li.add_argument("--baseline", default=None,
                    help="baseline file (default: tools/lint_baseline.json)")
    li.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report everything)")
    li.add_argument("--write-baseline", action="store_true",
                    help="grandfather the current findings into the "
                         "baseline file")
    li.set_defaults(fn=cmd_lint)

    dr = sub.add_parser(
        "doctor",
        help="environment/accelerator health report (JSON); probes the "
             "chip from a child process, so it fails while a server on "
             "this host holds the chip",
    )
    dr.add_argument("--probe-s", type=float, default=30.0,
                    help="accelerator probe timeout (subprocess)")
    dr.add_argument("--checkpoint-dir", default="./checkpoints")
    dr.add_argument("--quantized-dir", default=_Q8_DIR)
    dr.set_defaults(fn=cmd_doctor)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
