"""Servable HistGradientBoosting on the canonical table.

BASELINE.md's AUC table lists sklearn HistGradientBoosting (0.9650) as
the strongest model family — but until round 4 it was not convertible to
the served dense-tree embedding (`from_sklearn_gbt` covers only the
classic GradientBoostingClassifier). This measures what the SERVABLE
bounded-depth variant gives up: train HGB with max_depth bounded (the
dense embedding is 2^depth nodes/tree), convert via
``trees.from_sklearn_hgb``, verify conversion parity, and record the
held-out AUC of the exact params the Scorer serves.

Protocol: cmd_train's split (seed-0 permutation, 20% test), the same as
the BASELINE AUC table and tools/ensemble_eval.py.

Artifact: HGB_SERVABLE_r04.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    from sklearn.ensemble import HistGradientBoostingClassifier

    from ccfd_tpu.cli import _training_dataset
    from ccfd_tpu.models import trees
    from ccfd_tpu.utils.metrics_math import roc_auc

    ds, source = _training_dataset()
    rng = np.random.default_rng(0)  # cmd_train's exact split protocol
    order = rng.permutation(ds.n)
    n_test = max(1, int(ds.n * 0.2))
    test, train = order[:n_test], order[n_test:]
    Xtr, ytr, Xte, yte = ds.X[train], ds.y[train], ds.X[test], ds.y[test]

    by_depth = []
    for max_depth in (6, 8, 10):
        clf = HistGradientBoostingClassifier(
            max_depth=max_depth, class_weight="balanced", random_state=0
        ).fit(Xtr, ytr)
        params = trees.from_sklearn_hgb(clf)
        served = np.asarray(trees.apply(params, jnp.asarray(Xte)))
        sk = clf.predict_proba(Xte)[:, 1]
        by_depth.append({
            "max_depth": max_depth,
            "n_trees": int(np.asarray(params["feature"]).shape[0]),
            "embed_depth": trees.depth_of(params),
            "conversion_max_prob_delta": float(np.abs(served - sk).max()),
            "auc_served_params": float(roc_auc(yte, served)),
        })
    best = max(by_depth, key=lambda r: r["auc_served_params"])
    auc_served = best["auc_served_params"]
    # the unbounded reference row of the BASELINE table, same split
    clf_free = HistGradientBoostingClassifier(
        class_weight="balanced", random_state=0
    ).fit(Xtr, ytr)
    auc_unbounded = float(roc_auc(yte, clf_free.predict_proba(Xte)[:, 1]))

    result = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "dataset": source,
        "rows_train": int(len(train)),
        "rows_test": int(len(test)),
        "servable_by_depth": by_depth,
        "servable_best": best,
        "unbounded_reference": {
            "auc": auc_unbounded,
            "servable_gives_up": round(auc_unbounded - auc_served, 5),
        },
    }
    with open(os.path.join(REPO, "HGB_SERVABLE_r04.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
