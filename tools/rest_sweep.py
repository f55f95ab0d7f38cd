"""Pre-scripted REST north-star sweep: one run, every point recorded.

Sweeps the serving configuration space in one bounded pass (~6-8 min),
records EVERY point, and reports the best configuration that meets the
north star (>=50k tx/s, p99 < 10 ms, BASELINE.md:23-26) plus the
native-vs-python A/B at that configuration.

Grid: transport {native C++ front, python} x clients {4, 8} x
rows-per-request {8, 32, 128}.  GC tuning and the host tier (off) are the
production defaults (cli.py serve), so the sweep measures the deployed
configuration, not a bench special.  Same backend rule as bench.py
(utils/backend.py): a TPU, or JAX_PLATFORMS=cpu said out loud.

Artifact: REST_SWEEP_r04.json (or --out).  Reference acceptance surface:
the Seldon latency/request-rate dashboard
(/root/reference/deploy/grafana/SeldonCore.json:499-531).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "ccfd_bench", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # type: ignore[union-attr]
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "REST_SWEEP_r04.json"))
    ap.add_argument("--seconds", type=float, default=12.0,
                    help="measured window per grid point")
    ap.add_argument("--clients", default="4,8")
    ap.add_argument("--rows", default="8,32,128")
    args = ap.parse_args()

    bench = _load_bench()

    import jax

    from ccfd_tpu.data.ccfd import synthetic_dataset
    from ccfd_tpu.models import mlp
    from ccfd_tpu.utils.backend import require_backend
    from ccfd_tpu.utils.compile_cache import enable as enable_cache
    from ccfd_tpu.utils.gctune import tune_for_service

    platform_label = require_backend()
    enable_cache()
    ds = synthetic_dataset(n=8192, fraud_rate=0.01, seed=0)
    params = mlp.init(jax.random.PRNGKey(0))
    params = mlp.set_normalizer(params, ds.X.mean(0), ds.X.std(0))
    tune_for_service()
    grid = []
    t_start = time.time()

    def flush_partial() -> None:
        """Persist after every point so an interrupted sweep keeps
        everything measured so far."""
        with open(args.out, "w") as f:
            json.dump({"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                       "platform": platform_label, "partial": True,
                       "seconds_per_point": args.seconds,
                       "grid": grid}, f, indent=1)

    for native in (True, False):
        for n_clients in [int(c) for c in args.clients.split(",")]:
            for rows in [int(r) for r in args.rows.split(",")]:
                point = bench._bench_rest(
                    params, lat_batch=4096, seconds=args.seconds,
                    n_clients=n_clients, rows_per_req=rows, native=native,
                )
                point["native"] = native
                point["n_clients_requested"] = n_clients
                grid.append(point)
                print(json.dumps(point), flush=True)
                flush_partial()

    ok_points = [p for p in grid if "error" not in p]
    meets = [p for p in ok_points if p["p99_ms"] < 10.0]
    best = max(meets, key=lambda p: p["tx_s"]) if meets else None
    # A/B at the best configuration: the native win must be a number
    ab = None
    if best is not None:
        twin = [p for p in ok_points
                if p["native"] != best["native"]
                and p["n_clients_requested"] == best["n_clients_requested"]
                and p["rows_per_request"] == best["rows_per_request"]]
        if twin:
            nat = best if best["native"] else twin[0]
            py = twin[0] if best["native"] else best
            ab = {"native_tx_s": nat["tx_s"], "python_tx_s": py["tx_s"],
                  "native_over_python": round(nat["tx_s"] /
                                              max(py["tx_s"], 1e-9), 3)}

    report = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": platform_label,
        "seconds_per_point": args.seconds,
        "sweep_wall_s": round(time.time() - t_start, 1),
        "grid": grid,
        "best": best,
        "native_vs_python_at_best": ab,
        "north_star": {
            "target_tx_s": 50_000, "target_p99_ms": 10.0,
            "met": bool(best and best["tx_s"] >= 50_000),
            "best_tx_s": best["tx_s"] if best else None,
            "best_p99_ms": best["p99_ms"] if best else None,
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"rest_sweep": report["north_star"],
                      "platform": report["platform"]}))
    return 0 if report["north_star"]["met"] else 3


if __name__ == "__main__":
    sys.exit(main())
