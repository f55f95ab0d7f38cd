"""Gradient-boosted tree ensemble re-expressed as tensorized XLA evaluation.

BASELINE.json configs[1]: "XGBoost / GBT fraud classifier re-expressed as JAX
inference". A CPU tree library walks pointers per row; that shape is hostile
to TPU. Here every tree is embedded into a *complete* binary tree of static
depth D stored as three dense arrays

    feature   (T, 2^D - 1) int32   — split feature id per internal node
    threshold (T, 2^D - 1) float32 — split threshold per internal node
    leaf      (T, 2^D)     float32 — leaf values (learning rate folded in)

and a batch descends all T trees in lockstep with D vectorized gather steps
(heap layout: children of node i are 2i+1 / 2i+2). D is recovered from the
leaf-array shape, so the Python loop unrolls statically under ``jit`` — no
data-dependent control flow, no host sync, pure VPU gathers + one reduce.

Sparse/unbalanced source trees (e.g. fitted sklearn estimators) embed by
propagating early leaves to every descendant leaf slot, which preserves exact
semantics while keeping the dense layout.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

Params = Mapping[str, Any]


def num_internal(depth: int) -> int:
    return (1 << depth) - 1


def init_empty(n_trees: int, depth: int, base: float = 0.0) -> Params:
    """All-zero ensemble (every tree returns 0) — useful as a starting point."""
    return {
        "feature": jnp.zeros((n_trees, num_internal(depth)), jnp.int32),
        "threshold": jnp.full((n_trees, num_internal(depth)), jnp.inf, jnp.float32),
        "leaf": jnp.zeros((n_trees, 1 << depth), jnp.float32),
        "base": jnp.asarray(base, jnp.float32),
    }


def depth_of(params: Params) -> int:
    return int(params["leaf"].shape[-1]).bit_length() - 1


def logits(params: Params, x: jax.Array) -> jax.Array:
    """(B, F) -> (B,) raw ensemble scores (base + sum of leaf values)."""
    feat, thr, leaf = params["feature"], params["threshold"], params["leaf"]
    n_trees = leaf.shape[0]
    depth = depth_of(params)
    batch = x.shape[0]
    tree_ids = jnp.arange(n_trees)[None, :]  # (1, T) broadcasts over batch
    idx = jnp.zeros((batch, n_trees), jnp.int32)
    for _ in range(depth):
        node_feat = feat[tree_ids, idx]  # (B, T)
        node_thr = thr[tree_ids, idx]
        xv = jnp.take_along_axis(x[:, None, :], node_feat[:, :, None], axis=2)[..., 0]
        go_right = (xv > node_thr).astype(jnp.int32)
        idx = 2 * idx + 1 + go_right
    leaf_idx = idx - num_internal(depth)
    return params["base"] + leaf[tree_ids, leaf_idx].sum(axis=-1)


@jax.jit
def apply(params: Params, x: jax.Array) -> jax.Array:
    """proba_1 per row: (B, F) -> (B,)."""
    return jax.nn.sigmoid(logits(params, x))


def logits_mxu(params: Params, x: jax.Array) -> jax.Array:
    """Gather-free ensemble evaluation: feature selection as ONE matmul.

    The lockstep descent in :func:`logits` does two gathers per level
    (``feat/thr`` by node index, then ``x`` by feature id) — VPU-bound
    dynamic addressing that leaves the MXU idle. TPU-first alternative:

    1. Pre-gather EVERY node's feature value for every row with one
       matmul against a static one-hot matrix:
       ``xv = x @ onehot(feat)`` — (B, F) x (F, T*nI) rides the MXU.
    2. Compare against all thresholds at once -> (B, T, nI) decisions.
    3. Walk the D levels with ``one_hot(idx) * dec`` sums — dense
       elementwise VPU work, no dynamic indexing anywhere.

    FLOP cost grows (every node evaluates, not just the D on the path),
    but the work is MXU-shaped and gather-free — the same trade the
    dense tree embedding itself makes. Exact same semantics as
    :func:`logits` (parity-tested on the CPU and, by chip_smoke.py, on the
    chip); choose per backend via the ``gbt_mxu`` registry entry.

    Regimes (BASELINE.md "Model variants"): on a CPU the gather path
    wins — extra FLOPs with no systolic array to feed them to. The MXU
    inversion is the HYPOTHESIS this variant exists to test; treat
    ``gbt_mxu`` as experimental until an on-chip A/B records it winning
    (no cell of the benchmark serves it yet: ROADMAP C4).
    """
    feat, thr, leaf = params["feature"], params["threshold"], params["leaf"]
    n_trees = leaf.shape[0]
    depth = depth_of(params)
    n_int = num_internal(depth)
    # Non-finite features would poison the select-by-matmul (inf * 0 = NaN
    # spreads to EVERY node of the row); map them to huge finite values
    # that preserve the gather path's comparison outcomes: NaN compares
    # False against any finite threshold (like -big), +/-inf compare like
    # +/-big. Dead slots (thr=+inf) stay always-left either way.
    big = jnp.asarray(3.0e38, x.dtype)
    x_safe = jnp.nan_to_num(x, nan=-big, posinf=big, neginf=-big)
    # (F, T*nI) one-hot of each node's split feature. Params are traced
    # jit arguments, so this small build (F x T*nI) runs per call — it is
    # a few percent of the matmul it feeds, not a folded constant.
    onehot = jax.nn.one_hot(
        feat.reshape(-1), x.shape[1], dtype=x.dtype
    ).T  # (F, T*nI)
    # HIGHEST: the MXU's default f32 matmul rounds its operands to bf16,
    # and a feature value rounded before it meets its threshold takes the
    # wrong branch (6e-2 in probability against the gather path at 16,384
    # rows on a v5e). The multi-pass form reproduces every selected f32
    # value exactly — the one-hot columns are 0/1.
    xv = jnp.matmul(x_safe, onehot, precision=jax.lax.Precision.HIGHEST)
    xv = xv.reshape(x.shape[0], n_trees, n_int)
    dec = (xv > thr[None]).astype(jnp.int32)  # (B, T, nI)
    idx = jnp.zeros((x.shape[0], n_trees), jnp.int32)
    for _ in range(depth):
        # d = dec[b, t, idx[b, t]] without a gather: one-hot mask + sum
        mask = jax.nn.one_hot(idx, n_int, dtype=dec.dtype)
        d = (dec * mask).sum(axis=-1)
        idx = 2 * idx + 1 + d
    leaf_idx = idx - n_int
    leaf_mask = jax.nn.one_hot(leaf_idx, 1 << depth, dtype=leaf.dtype)
    return params["base"] + (leaf[None] * leaf_mask).sum(axis=(-1, -2))


@jax.jit
def apply_mxu(params: Params, x: jax.Array) -> jax.Array:
    """proba_1 per row via the gather-free MXU evaluation."""
    return jax.nn.sigmoid(logits_mxu(params, x))


def apply_numpy(params: Params, x: np.ndarray) -> np.ndarray:
    """Pure-numpy forward, semantically `apply` without a device.

    Enables the serving host latency tier for the tree family (the
    reference's actual model class — sklearn `modelfull`): same lockstep
    descent as `logits`, with numpy gathers. Params must be host arrays.
    """
    from ccfd_tpu.utils.metrics_math import stable_sigmoid

    # callers holding a uniformly-float32 host copy of the params (e.g. a
    # scorer host tier) would otherwise feed float indices into
    # take_along_axis, which raises; already-integer arrays pass through
    # uncopied (this is the per-request host latency path)
    feat = np.asarray(params["feature"])
    if not np.issubdtype(feat.dtype, np.integer):
        feat = feat.astype(np.int64)
    thr = np.asarray(params["threshold"])
    leaf = np.asarray(params["leaf"])
    x = np.asarray(x, np.float32)
    n_trees = leaf.shape[0]
    depth = depth_of(params)
    tree_ids = np.arange(n_trees)[None, :]
    idx = np.zeros((x.shape[0], n_trees), np.int32)
    for _ in range(depth):
        node_feat = feat[tree_ids, idx]  # (B, T)
        node_thr = thr[tree_ids, idx]
        xv = np.take_along_axis(x, node_feat, axis=1)
        idx = 2 * idx + 1 + (xv > node_thr).astype(np.int32)
    leaf_idx = idx - num_internal(depth)
    z = float(params["base"]) + leaf[tree_ids, leaf_idx].sum(axis=-1)
    return stable_sigmoid(z.astype(np.float32))


def _embed_tree(
    children_left: np.ndarray,
    children_right: np.ndarray,
    feature: np.ndarray,
    threshold: np.ndarray,
    value: np.ndarray,
    depth: int,
    scale: float,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    n_int = num_internal(depth)
    f = np.zeros(n_int, np.int32)
    t = np.full(n_int, np.inf, np.float32)  # inf => always branch left
    leaves = np.zeros(1 << depth, np.float32)

    def rec(node: int, pos: int, level: int) -> None:
        is_leaf = children_left[node] == -1
        if level == depth:
            if not is_leaf:
                raise ValueError(f"source tree deeper than depth={depth}")
            leaves[pos - n_int] = scale * float(value[node])
            return
        if is_leaf:
            # dead internal slot: keep (feature=0, thr=inf); both subtrees get
            # the leaf's value so the taken path is irrelevant.
            rec(node, 2 * pos + 1, level + 1)
            rec(node, 2 * pos + 2, level + 1)
            return
        f[pos] = int(feature[node])
        t[pos] = float(threshold[node])
        rec(int(children_left[node]), 2 * pos + 1, level + 1)
        rec(int(children_right[node]), 2 * pos + 2, level + 1)

    rec(0, 0, 0)
    return f, t, leaves


def from_sklearn_hgb(clf, max_embed_depth: int = 10) -> Params:
    """Convert a fitted sklearn HistGradientBoostingClassifier (binary) —
    the strongest reference-family model on the canonical table
    (BASELINE.md AUC 0.9650) — into the dense complete-tree embedding.

    Parity: raw_score(x) = baseline + sum_t tree_t(x); leaf values already
    carry shrinkage, and "x <= num_threshold goes left" matches the
    evaluator's ``x > thr`` right branch. The missing-value branch
    (``missing_go_to_left``) is intentionally not embedded: this pipeline
    zero-fills bad cells at decode (native/decode.cpp), so NaN never
    reaches the scorer; categorical splits are rejected.

    HGB grows leaf-count-bounded (default 31 leaves), possibly unbalanced,
    so the complete-binary embedding is exponential in the DEEPEST path:
    ``max_embed_depth`` refuses pathological trees (train with
    ``max_depth<=10`` for servable models) instead of silently allocating
    2^depth nodes per tree.
    """
    if getattr(clf, "n_trees_per_iteration_", 1) != 1:
        raise ValueError("from_sklearn_hgb supports binary classifiers "
                         "only (one tree per boosting iteration)")
    predictors = [p[0] for p in clf._predictors]
    adapters = []
    max_depth_seen = 0
    for pred in predictors:
        nodes = pred.nodes
        if np.any(nodes["is_categorical"]):
            raise ValueError("categorical splits are not embeddable")
        is_leaf = nodes["is_leaf"].astype(bool)
        cl = np.where(is_leaf, -1, nodes["left"].astype(np.int64))
        cr = np.where(is_leaf, -1, nodes["right"].astype(np.int64))
        feat = nodes["feature_idx"].astype(np.int64)
        thr = nodes["num_threshold"].astype(np.float64)
        val = nodes["value"].astype(np.float64)

        def depth_of(node=0, cl=cl, cr=cr):
            if cl[node] == -1:
                return 0
            return 1 + max(depth_of(int(cl[node])), depth_of(int(cr[node])))

        d = depth_of()
        max_depth_seen = max(max_depth_seen, d)
        adapters.append((cl, cr, feat, thr, val))
    if max_depth_seen > max_embed_depth:
        raise ValueError(
            f"HGB tree depth {max_depth_seen} > {max_embed_depth}: the "
            "dense embedding is 2^depth nodes/tree — retrain with "
            "max_depth bounded (e.g. 6-8) for a servable model"
        )
    depth = max(max_depth_seen, 1)
    fs, ts, ls = [], [], []
    for cl, cr, feat, thr, val in adapters:
        f, th, lv = _embed_tree(cl, cr, feat, thr, val, depth, scale=1.0)
        fs.append(f)
        ts.append(th)
        ls.append(lv)
    base = float(np.asarray(clf._baseline_prediction).reshape(()))
    return {
        "feature": jnp.asarray(np.stack(fs)),
        "threshold": jnp.asarray(np.stack(ts)),
        "leaf": jnp.asarray(np.stack(ls)),
        "base": jnp.asarray(base, jnp.float32),
    }


def from_sklearn_gbt(clf) -> Params:
    """Convert a fitted sklearn GradientBoostingClassifier (binary).

    Decision-function parity: score(x) = init_prior + lr * sum_t tree_t(x),
    with sklearn's "x <= threshold goes left" matching our ``x > thr`` right
    branch. The learning rate folds into leaf values; the prior into base.
    """
    trees = [e[0].tree_ for e in clf.estimators_]
    depth = max(t.max_depth for t in trees)
    fs, ts, ls = [], [], []
    for t in trees:
        f, th, lv = _embed_tree(
            t.children_left,
            t.children_right,
            t.feature,
            t.threshold,
            t.value.reshape(-1),
            depth,
            scale=float(clf.learning_rate),
        )
        fs.append(f)
        ts.append(th)
        ls.append(lv)
    # Recover the init prior empirically (robust across sklearn versions):
    # decision_function = base + lr * sum_t tree_t, so probe one row.
    probe = np.zeros((1, clf.n_features_in_), dtype=np.float64)
    tree_sum = float(clf.learning_rate) * sum(float(e[0].predict(probe)[0]) for e in clf.estimators_)
    base = float(np.asarray(clf.decision_function(probe)).reshape(())) - tree_sum
    return {
        "feature": jnp.asarray(np.stack(fs)),
        "threshold": jnp.asarray(np.stack(ts)),
        "leaf": jnp.asarray(np.stack(ls)),
        "base": jnp.asarray(base, jnp.float32),
    }
