"""Trace report: drive the pipeline, print the per-stage critical path.

Answers "where did this transaction's 40 ms go?" with evidence: runs the
in-process pipeline (producer → bus → router → scorer → engine, plus the
notify leg) with tracing at a configurable sample rate, collects the
retained end-to-end traces from the tail-sampling sink, and prints a
p50/p99 critical-path decomposition per stage — queueing on the bus,
decode, scorer dispatch, rule-eval + engine starts — the per-stage
visibility InferLine-style pipeline SLOs need (arXiv:1812.01776; the
"300M predictions/sec" stack's latency budget discipline,
arXiv:2109.09541).

Also verifies the full observability loop the acceptance criteria ask for:
at least one retained trace spans producer→bus→router→scorer→engine with
monotone parent/child spans, an exported latency histogram carries a
trace-id exemplar (OpenMetrics scrape of the live exporter), and that
exemplar's trace id resolves over HTTP via the exporter's /traces/<id>.

    JAX_PLATFORMS=cpu python tools/trace_report.py --transactions 3000

Prints a human table on stderr and one JSON line on stdout; exit 0 only
when an end-to-end trace was retained, spans are monotone, and the
exemplar resolved.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU drill, whatever the host has

import numpy as np  # noqa: E402

from ccfd_tpu.bus.broker import Broker  # noqa: E402
from ccfd_tpu.config import Config  # noqa: E402
from ccfd_tpu.data.ccfd import synthetic_dataset  # noqa: E402
from ccfd_tpu.metrics.exporter import MetricsExporter  # noqa: E402
from ccfd_tpu.metrics.prom import Registry  # noqa: E402
from ccfd_tpu.models import mlp  # noqa: E402
from ccfd_tpu.notify.service import NotificationService  # noqa: E402
from ccfd_tpu.observability.trace import SpanSink, Tracer  # noqa: E402
from ccfd_tpu.process.fraud import build_engine  # noqa: E402
from ccfd_tpu.producer.producer import Producer  # noqa: E402
from ccfd_tpu.router.router import Router  # noqa: E402
from ccfd_tpu.serving.scorer import Scorer  # noqa: E402

# the pipeline stages, in causal order, with how each one's wall time is
# derived from the trace's spans
STAGE_SPANS = ("producer.batch", "router.decode", "router.score",
               "router.route")


def _quantile(values: list[float], q: float) -> float:
    if not values:
        return float("nan")
    return float(np.quantile(np.asarray(values), q))


def stage_breakdown(traces: list[list[dict]]) -> dict[str, dict]:
    """Per-stage wall-time samples across traces -> p50/p99 + share.

    ``bus.queue`` is derived: router.batch start minus producer.batch end —
    the time records waited on the topic before the router polled them
    (micro-batching deadline + backlog), which no single span times."""
    samples: dict[str, list[float]] = {name: [] for name in STAGE_SPANS}
    samples["bus.queue"] = []
    for spans in traces:
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], s)
        for name in STAGE_SPANS:
            s = by_name.get(name)
            if s is not None:
                samples[name].append(s["duration_s"])
        prod, rb = by_name.get("producer.batch"), by_name.get("router.batch")
        if prod is not None and rb is not None:
            samples["bus.queue"].append(max(
                0.0, rb["start"] - (prod["start"] + prod["duration_s"])))
    total_p50 = sum(_quantile(v, 0.5) for v in samples.values() if v)
    out = {}
    for name, vals in samples.items():
        if not vals:
            continue
        p50 = _quantile(vals, 0.5)
        out[name] = {
            "n": len(vals),
            "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(_quantile(vals, 0.99) * 1e3, 3),
            "critical_path_share": round(p50 / total_p50, 4) if total_p50 else 0.0,
        }
    return out


def monotone_ok(spans: list[dict]) -> bool:
    """Every child starts at/after its parent (small clock-read slack)."""
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        parent = by_id.get(s["parent_id"]) if s["parent_id"] else None
        if parent is not None and s["start"] < parent["start"] - 1e-3:
            return False
    return True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--transactions", type=int, default=3000)
    ap.add_argument("--sample", type=float, default=1.0,
                    help="tail-sampler keep rate for boring traces "
                    "(1.0: keep everything this run retains)")
    ap.add_argument("--batch", type=int, default=256,
                    help="producer batch size == trace granularity")
    ap.add_argument("--fraud-rate", type=float, default=0.02)
    ap.add_argument("--workers", type=int, default=2,
                    help="router workers (router/parallel.py): >1 verifies "
                    "the per-stage trace decomposition survives the "
                    "partition-parallel fan-out (worker-labelled "
                    "router.batch spans); 1 = single router")
    ap.add_argument("--json", dest="json_out", default="",
                    help="also write the report as a machine-readable "
                    "artifact (crash-safe tmp+rename) — the trace-derived "
                    "sibling of the StageProfile schema family "
                    "(observability/profile.py), for CI and the "
                    "provisioning planner; exit stays nonzero when no "
                    "end-to-end trace was retained")
    args = ap.parse_args()

    cfg = Config()
    broker = Broker()
    regs = {name: Registry() for name in
            ("producer", "router", "kie", "notify", "tracing")}
    # max_retained sized to the run: at sample=1.0 every trace is kept and
    # the report must not evict the end-to-end ones mid-run
    sink = SpanSink(sample=args.sample, registry=regs["tracing"],
                    max_retained=8192)

    def tracer(name: str) -> Tracer:
        return Tracer(regs[name], component=name, sink=sink)

    engine = build_engine(cfg, broker, regs["kie"], None)
    ds = synthetic_dataset(n=max(args.transactions, 1024),
                           fraud_rate=args.fraud_rate, seed=0)
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    scorer = Scorer(model_name="mlp", params=params,
                    batch_sizes=(128, 1024, 4096))
    scorer.warmup()
    if args.workers > 1:
        from ccfd_tpu.router.parallel import ParallelRouter

        router = ParallelRouter(cfg, broker, scorer.score, engine,
                                regs["router"], workers=args.workers,
                                max_batch=args.batch,
                                tracer=tracer("router"))
    else:
        router = Router(cfg, broker, scorer.score, engine, regs["router"],
                        max_batch=args.batch, tracer=tracer("router"))
    notify = NotificationService(cfg, broker, regs["notify"],
                                 tracer=tracer("notify"))
    producer_tracer = tracer("producer")
    exporter = MetricsExporter(regs, sink=sink).start()

    # chunked produce/route ping-pong: every producer batch is one trace
    produced = 0
    while produced < args.transactions:
        n = min(args.batch, args.transactions - produced)
        lo = produced
        chunk = type(ds)(X=ds.X[lo:lo + n], y=ds.y[lo:lo + n])
        produced += Producer(cfg, broker, chunk,
                             registry=regs["producer"],
                             tracer=producer_tracer).run(limit=n)
        while router.step() > 0:
            pass
        notify.step(max_records=args.batch)

    sink.flush(0.0)
    summaries = sink.traces()
    full = [sink.trace(t["trace_id"]) for t in summaries]
    e2e = [spans for spans in full
           if spans is not None
           and {"producer.batch", "router.batch", "router.score",
                "router.route"} <= {s["name"] for s in spans}]
    breakdown = stage_breakdown(e2e)
    mono = all(monotone_ok(spans) for spans in e2e) and bool(e2e)
    # parallel-router attribution: every router.batch span carries its
    # worker id, and with workers>1 more than one worker must actually
    # have contributed spans (the fan-out genuinely split the stream)
    worker_ids = sorted({
        s["attrs"].get("worker")
        for spans in full if spans is not None
        for s in spans
        if s["name"] == "router.batch" and "worker" in s.get("attrs", {})
    })
    workers_ok = (args.workers <= 1) or len(worker_ids) > 1

    # -- exemplar loop: scrape OpenMetrics, resolve the trace over HTTP ----
    req = urllib.request.Request(
        exporter.endpoint + "/prometheus",
        headers={"Accept": "application/openmetrics-text"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        scrape = resp.read().decode()
    exemplar_ids = re.findall(r'# \{trace_id="([0-9a-f]{32})"\}', scrape)
    resolved = None
    for tid in exemplar_ids:
        try:
            with urllib.request.urlopen(
                f"{exporter.endpoint}/traces/{tid}", timeout=10
            ) as resp:
                if resp.status == 200:
                    resolved = tid
                    break
        except urllib.error.HTTPError:
            continue  # exemplar from a dropped trace: try the next
    exporter.stop()
    broker.close()

    keep_counter = regs["tracing"].counter("ccfd_traces_kept_total")
    report = {
        "transactions": produced,
        "traces_retained": len(summaries),
        "end_to_end_traces": len(e2e),
        "monotone_ok": mono,
        "router_workers": args.workers,
        "worker_span_labels": worker_ids,
        "worker_labels_ok": workers_ok,
        "stages": breakdown,
        "exemplars_in_scrape": len(exemplar_ids),
        "exemplar_trace_resolved": resolved,
        "sampler": {
            "sample": args.sample,
            "kept_fraud": int(keep_counter.value({"reason": "fraud"})),
            "kept_slow": int(keep_counter.value({"reason": "slow"})),
            "kept_sampled": int(keep_counter.value({"reason": "sampled"})),
            "dropped": int(regs["tracing"].counter(
                "ccfd_traces_dropped_total").value()),
        },
    }
    print("\n== per-stage critical path (p50 / p99, ms) ==", file=sys.stderr)
    for name, st in sorted(breakdown.items(),
                           key=lambda kv: -kv[1]["critical_path_share"]):
        print(f"  {name:<16} {st['p50_ms']:>9.3f} / {st['p99_ms']:>9.3f}"
              f"   share={st['critical_path_share']:.1%}  (n={st['n']})",
              file=sys.stderr)
    print(json.dumps(report))
    ok = bool(e2e) and mono and resolved is not None and workers_ok
    if args.json_out:
        # StageProfile-family artifact: trace-derived decomposition under
        # its own schema id, stages shaped like the profile's digests so a
        # planner can consume either. Written even on failure (the "ok"
        # flag and exit code carry the verdict; CI wants the evidence).
        artifact = {
            "schema": "ccfd.stage_profile.trace.v1",
            "generated_unix": time.time(),
            "ok": ok,
            "source": "trace_report",
            "stages": {
                name: {
                    "count": st["n"],
                    "p50_ms": st["p50_ms"],
                    "p99_ms": st["p99_ms"],
                    "critical_path_share": st["critical_path_share"],
                }
                for name, st in breakdown.items()
            },
            "report": report,
        }
        from ccfd_tpu.observability.profile import write_json_crash_safe

        write_json_crash_safe(args.json_out, artifact)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
