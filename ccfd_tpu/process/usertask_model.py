"""Learned user-task outcome model — the reference's second Seldon model.

The reference deploys a dedicated Seldon model
(``ruivieira/ccfd-seldon-usertask-model``, reference README.md:347-353)
whose sole job is predicting the outcome of jBPM investigation user tasks:
confidence >= ``CONFIDENCE_THRESHOLD`` auto-closes the task with the
predicted outcome, lower confidence only pre-fills it (README.md:571-581,
docs/images/events-3.final.png). That model is trained on investigators'
past decisions.

TPU-native re-design: ``OnlineUserTaskModel`` is both the prediction
service and its trainer in one object —

- ``predict(task)`` scores a (1, 31) row — the 30 transaction features
  plus the fraud probability the router attached — through a jitted
  logistic regression. Confidence is the margin ``max(p, 1-p)``.
- ``observe(task)`` ingests a HUMAN task completion as a labeled example.
  Auto-completed tasks are never observed: learning from the model's own
  auto-closures would be feedback, not supervision — jBPM likewise trains
  its prediction service on investigator decisions only.
- Every ``fit_every`` observations it runs a few jitted SGD epochs over
  the example buffer and atomically swaps the params it serves.

Until ``min_examples`` human decisions exist, ``predict`` returns zero
confidence, so every task stays open for a human — the cold-start behavior
the reference gets by shipping the user-task model separately.

The engine hook is ``Engine(task_listener=...)``: called once per human
``complete_task`` with the finished task.
"""

from __future__ import annotations

import atexit
import os
import threading
import weakref
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import numpy as np

from ccfd_tpu.data.ccfd import FEATURE_NAMES

if TYPE_CHECKING:  # pragma: no cover
    from ccfd_tpu.process.engine import Task

NUM_TASK_FEATURES = len(FEATURE_NAMES) + 1  # + fraud probability

# Models whose construction-time warmup thread may still be compiling; a
# WeakSet so discarded models are collectable. The single atexit hook stops
# and joins the stragglers (a thread mid-XLA-compile killed at exit aborts
# the process with "exception not rethrown").
_live_warmups: "weakref.WeakSet[OnlineUserTaskModel]" = weakref.WeakSet()
_atexit_registered = False


def _register_warmup(model: "OnlineUserTaskModel") -> None:
    global _atexit_registered
    _live_warmups.add(model)
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_cancel_all_warmups)


def _cancel_all_warmups() -> None:
    for m in list(_live_warmups):
        m._warmup_cancel()


def task_row(task: "Task") -> np.ndarray:
    """(1, 31) float32: transaction features + attached fraud probability.

    Delegates the 30 transaction columns to ``prediction.task_features`` so
    both prediction services extract features identically (including the
    flat-vars fallback when no "transaction" dict is present).
    """
    from ccfd_tpu.process.prediction import task_features

    feats = task_features(task)
    proba = np.asarray([[float(task.vars.get("proba", 0.0))]], np.float32)
    return np.concatenate([feats, proba], axis=1)


@jax.jit
def _predict(params, x):
    xs = (x - params["mean"]) / params["scale"]
    z = jnp.dot(xs, params["w"], preferred_element_type=jnp.float32) + params["b"]
    return jax.nn.sigmoid(z)


@jax.jit
def _sgd_epoch(params, x, y, m, lr):
    """One full-batch logistic-regression step over pre-standardized rows
    (the buffer IS the batch: investigator decisions are rare, so
    full-batch beats minibatching). ``m`` masks padding rows — the batch is
    padded to a power-of-two bucket so XLA compiles one executable instead
    of one per buffer length.
    """

    def loss_fn(p):
        z = jnp.dot(x, p["w"], preferred_element_type=jnp.float32) + p["b"]
        # weighted BCE over real rows only: outcomes can be imbalanced
        n = jnp.maximum(jnp.sum(m), 1.0)
        n_pos = jnp.maximum(jnp.sum(y * m), 1.0)
        n_neg = jnp.maximum(jnp.sum((1.0 - y) * m), 1.0)
        w_pos = n / (2.0 * n_pos)
        w_neg = n / (2.0 * n_neg)
        ll = jax.nn.log_sigmoid(z) * y * w_pos + jax.nn.log_sigmoid(-z) * (1.0 - y) * w_neg
        return -jnp.sum(ll * m) / n

    loss, grads = jax.value_and_grad(loss_fn)(params)
    new = {k: params[k] - lr * grads[k] for k in ("w", "b")}
    return {**params, **new}, loss


class OnlineUserTaskModel:
    """Prediction service + online trainer for investigation outcomes."""

    def __init__(
        self,
        min_examples: int = 32,
        fit_every: int = 8,
        epochs: int = 50,
        learning_rate: float = 0.5,
        buffer_size: int = 4096,
        seed: int = 0,
        warmup: bool = True,
    ):
        self.min_examples = min_examples
        self.fit_every = fit_every
        self.epochs = epochs
        self.learning_rate = learning_rate
        self.buffer_size = buffer_size
        key = jax.random.PRNGKey(seed)
        self._params = {
            "w": jax.random.normal(key, (NUM_TASK_FEATURES,), jnp.float32) * 0.01,
            "b": jnp.zeros((), jnp.float32),
            # feature standardization learned from the buffer at fit time
            # (raw Amounts span orders of magnitude; GD on raw scales
            # diverges) — carried with the params so predict() matches
            "mean": jnp.zeros((NUM_TASK_FEATURES,), jnp.float32),
            "scale": jnp.ones((NUM_TASK_FEATURES,), jnp.float32),
        }
        self._x: list[np.ndarray] = []
        self._y: list[float] = []
        self._seen = 0
        self._trained = False
        self._lock = threading.Lock()
        self.last_loss: float | None = None
        # Pre-compile the jitted predict/fit executables off the request
        # path: the first _fit would otherwise run XLA compilation
        # synchronously inside the investigator's complete_task call (the
        # engine task_listener fires in the REST handler thread), and every
        # new power-of-two buffer bucket would recompile again. Warming on a
        # daemon thread at construction covers every bucket this buffer can
        # ever reach, so human task completions never pay a compile.
        self._warmup_thread: threading.Thread | None = None
        self._warmup_stop = threading.Event()
        if warmup:
            self._warmup_thread = threading.Thread(
                target=self._warmup, name="usertask-model-warmup", daemon=True
            )
            self._warmup_thread.start()
            # a daemon thread killed mid-XLA-compile at interpreter exit
            # aborts the process ("exception not rethrown"); stop between
            # buckets and join instead. One module-level atexit hook over a
            # WeakSet — registering a bound method per instance would pin
            # every model (params + example buffer) until interpreter exit.
            _register_warmup(self)

    def _warmup(self) -> None:
        try:
            params = self._params
            _predict(params, jnp.zeros((1, NUM_TASK_FEATURES), jnp.float32))
            lr = jnp.float32(self.learning_rate)
            bucket = 1
            while bucket < self.min_examples:
                bucket *= 2
            while not self._warmup_stop.is_set():
                x = jnp.zeros((bucket, NUM_TASK_FEATURES), jnp.float32)
                y = jnp.zeros((bucket,), jnp.float32)
                _sgd_epoch(params, x, y, y, lr)
                if bucket >= self.buffer_size:  # pow2 ceiling covered
                    break
                bucket *= 2
        except Exception:  # pragma: no cover - warmup is best-effort
            pass

    def _warmup_cancel(self) -> None:
        self._warmup_stop.set()
        if self._warmup_thread is not None:
            # bounded join: if a compile wedged (a device call that never
            # returns) the thread never sees the stop event — cap the wait so
            # interpreter exit is never blocked forever
            self._warmup_thread.join(timeout=10.0)

    def warmup_join(self, timeout: float | None = None) -> None:
        """Block until the construction-time compile warmup finishes
        (benchmarks and tests that measure fit latency call this first)."""
        if self._warmup_thread is not None:
            self._warmup_thread.join(timeout)

    # -- PredictionService protocol ---------------------------------------
    def predict(self, task: "Task") -> tuple[Any, float]:
        with self._lock:
            trained = self._trained
            params = self._params
        if not trained:
            # cold start: no investigator signal yet -> never auto-close,
            # nothing to pre-fill
            return None, 0.0
        p = float(_predict(params, jnp.asarray(task_row(task)))[0])
        outcome = p >= 0.5
        return outcome, max(p, 1.0 - p)

    # -- engine task_listener ---------------------------------------------
    def observe(self, task: "Task") -> None:
        """Ingest a human-completed task; refit when enough new ones landed."""
        if task.status != "completed":
            return
        with self._lock:
            self._x.append(task_row(task)[0])
            self._y.append(1.0 if task.outcome else 0.0)
            if len(self._x) > self.buffer_size:
                self._x = self._x[-self.buffer_size:]
                self._y = self._y[-self.buffer_size:]
            self._seen += 1
            n = len(self._x)
            due = n >= self.min_examples and (
                not self._trained or self._seen % self.fit_every == 0
            )
            if not due:
                return
            x = np.stack(self._x)
            y = np.asarray(self._y, np.float32)
            params = self._params
        self._fit(params, x, y)

    def _fit(self, params, x: np.ndarray, y: np.ndarray) -> None:
        # train outside the lock: predict() keeps serving the old params
        mu = x.mean(axis=0)
        sigma = x.std(axis=0)
        sigma = np.where(sigma < 1e-6, 1.0, sigma)
        params = {
            **params,
            "mean": jnp.asarray(mu, jnp.float32),
            "scale": jnp.asarray(sigma, jnp.float32),
        }
        # pad to a power-of-two bucket: one compiled executable instead of a
        # recompile per buffer length (each fit would otherwise stall a
        # human complete_task call on a fresh XLA compile)
        n = x.shape[0]
        bucket = 1
        while bucket < n:
            bucket *= 2
        xs = np.zeros((bucket, x.shape[1]), np.float32)
        xs[:n] = (x - mu) / sigma
        ys = np.zeros((bucket,), np.float32)
        ys[:n] = y
        mask = np.zeros((bucket,), np.float32)
        mask[:n] = 1.0
        x_j, y_j, m_j = jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)
        lr = jnp.float32(self.learning_rate)
        loss = None
        for _ in range(self.epochs):
            params, loss = _sgd_epoch(params, x_j, y_j, m_j, lr)
        jax.block_until_ready(loss)
        with self._lock:
            self._params = params
            self._trained = True
            self.last_loss = float(loss)

    @property
    def n_examples(self) -> int:
        with self._lock:
            return len(self._x)

    @property
    def trained(self) -> bool:
        with self._lock:
            return self._trained

    # -- persistence (restarts must not discard investigator supervision) --
    def save(self, path: str) -> None:
        """Checksummed atomic .npz of params + example buffer (tmp +
        fsync + rename with generation retention, runtime/durability.py)."""
        import io

        from ccfd_tpu.runtime.durability import write_artifact

        with self._lock:
            params = {k: np.asarray(v) for k, v in self._params.items()}
            x = np.stack(self._x) if self._x else np.zeros((0, NUM_TASK_FEATURES), np.float32)
            y = np.asarray(self._y, np.float32)
            trained = self._trained
            seen = self._seen
        buf = io.BytesIO()  # file object: savez won't append .npz
        np.savez(buf, x=x, y=y, trained=trained, seen=seen, **params)
        write_artifact(path, buf.getvalue(), artifact="usertask")

    def load(self, path: str) -> None:
        """Verified restore: a corrupt file quarantines and falls back to
        the last-good retained generation."""
        import io

        from ccfd_tpu.runtime.durability import read_artifact

        data = np.load(io.BytesIO(read_artifact(path, artifact="usertask")))
        with self._lock:
            self._params = {
                k: jnp.asarray(data[k]) for k in ("w", "b", "mean", "scale")
            }
            self._x = list(data["x"])
            self._y = [float(v) for v in data["y"]]
            self._trained = bool(data["trained"])
            self._seen = int(data["seen"])
