"""FusedDecisionScorer: the single-dispatch decision plane.

Wraps the row-family :class:`~ccfd_tpu.serving.scorer.Scorer` with the
compiled decision program (ops/fused_decision.py): one jitted XLA
executable per batch bucket takes the staged feature rows and returns
routed verdicts — the model probability, the FRAUD_THRESHOLD comparison
and the first-matching rule index, all evaluated on device and shipped
back as ONE packed (B, 2) float32 transfer. The only host work left on
the path is transport, the batcher, and the route seam's bookkeeping;
``Router._route_inner`` consumes the fired indices without re-deriving
anything (router/router.py ``decision_fn``).

Contracts kept truthful:

- **Parity is bit-exact.** The decision program traces the SAME forward
  the staged path dispatches — the Pallas fused kernel when the base
  scorer serves it (with the identical wire-dtype cast, now inside the
  jit), the XLA graph otherwise — and the rules tensor pre-casts bounds
  exactly like ``Condition.mask``. Pinned by tests/test_fused_decision.py.
- **Non-vectorizable rules refuse fusion loudly.** A rule base carrying a
  custom ``when_fn`` fails :func:`~ccfd_tpu.ops.fused_decision.compile_rules`
  at construction: ONE warning, ``enabled`` False, the whole set serves
  staged. Never a silent per-row fallback.
- **The ladder still rules.** A fused executable that fails while
  serving (a dispatch, or a swap precompile) disables the plane until
  the next successful swap precompile, counted in ``staged_fallbacks``,
  and the call falls back to the STAGED path (``Scorer.score`` + host
  rules); if the device itself is sick that raises through to the
  router's host and rules tiers unchanged. ``warmup()`` is start-up, not
  serving: a grid that cannot compile there raises.
- **Swaps precompile before publishing.** The plane registers a
  prepublish hook on the base scorer: ``swap_params`` runs every bucket
  of the fused grid against the staged artifacts (under the
  ``fused.warm`` compile stage) BEFORE the reference flip, exactly like
  the seq variant swap — a promotion never pays serving-stage compiles.

The per-bucket executable grid reports through ``executable_grid()``
(device-telemetry inventory entry ``fused_decision``) with dispatch
counters, mirroring the PR 8/PR 10 machinery it generalizes.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any

import jax
import numpy as np

from ccfd_tpu.ops.fused_decision import (
    UnvectorizableRuleSet,
    build_decision_fn,
    compile_rules,
)
from ccfd_tpu.router.rules import RuleSet
from ccfd_tpu.runtime.faults import device_seam

log = logging.getLogger(__name__)


class FusedDecisionScorer:
    """One-dispatch scorer+router verdict plane over a row Scorer.

    ``decide(x) -> (proba, fired)``: float32 probabilities bit-identical
    to the staged path, int64 fired-rule indices into ``rules.rules``
    (the router's own ordering), or ``(proba, None)`` when the plane fell
    back to the staged path (the router then evaluates rules on host —
    staged semantics, not a third behavior).
    """

    def __init__(
        self,
        scorer: Any,
        rules: RuleSet,
        *,
        registry: Any = None,
        profiler: Any = None,
        strict: bool = False,
    ):
        self._base = scorer
        self.rules = rules
        self._profiler = profiler
        self._lock = threading.Lock()
        self._dispatch_counts: dict[int, int] = {}
        self._disabled = False
        self.enabled = False
        self.host_syncs = 0  # device->host materializations (the transfer)
        self.staged_fallbacks = 0
        self._plan = None
        self._decide_xla = None
        self._decide_fused = None
        self._decide_preq = None
        c = (registry.counter if registry is not None else None)
        self._c_decide = c and c("fused_decision_dispatches_total",
                                 "fused decision-kernel dispatches")
        self._c_fallback = c and c(
            "fused_decision_fallbacks_total",
            "decide() calls served by the staged path because the fused "
            "executable was unhealthy or never compiled")
        reason = None
        if getattr(scorer, "mesh", None) is not None:
            reason = ("mesh-sharded scorer: the decision program has no "
                      "shard_map composition yet")
        elif getattr(scorer, "_apply", None) is None:
            reason = f"scorer {type(scorer).__name__} has no traceable apply"
        if reason is None:
            try:
                self._plan = compile_rules(rules)
            except UnvectorizableRuleSet as e:
                reason = str(e)
        if reason is not None:
            # ONE loud compile-time decision for the whole rule set /
            # scorer pairing; per-row or per-batch surprises are banned
            if strict:
                raise RuntimeError(f"fused decision refused: {reason}")
            log.warning(
                "fused decision disabled; serving the STAGED path: %s",
                reason)
            return
        self.enabled = True

    # -- decision-program construction --------------------------------------

    def _fn_for(self, fused_params: Any):
        """The jitted decision program matching the base scorer's live
        forward: the Pallas fused kernel when armed (identical wire-dtype
        cast, traced inside the jit), else the XLA apply. Built once per
        kind; jit caches one executable per bucket shape."""
        base = self._base
        if fused_params is not None:
            if self._decide_fused is None:
                mod = base._fused_mod
                wire = base._fused_in_dtype
                interpret = base._fused_interpret

                def forward(fp, x):
                    # the SAME cast the staged wire applies host-side
                    # (round-to-nearest-even either way: bit-identical)
                    xw = x.astype(wire) if x.dtype != wire else x
                    return mod.fused_score(
                        fp, xw, tile=mod.fit_tile(x.shape[0]),
                        interpret=interpret)

                self._decide_fused = build_decision_fn(forward, self._plan)
            return self._decide_fused
        if self._decide_xla is None:
            self._decide_xla = build_decision_fn(base._apply, self._plan)
        return self._decide_xla

    def _fn_preq(self):
        """Decision program for the q8 int8 WIRE: the staged path ships
        host-prequantized (q, s) rows (Scorer._fused_dispatch), and
        bit-exact parity means the fused program must consume the SAME
        wire — the full-kernel device requantization differs in the last
        float32 ulp. Rows ship as a third f32 arg only when the rule plan
        reads feature columns; otherwise the einsum's feature lanes are
        all-zero selectors and a device-side zeros placeholder costs no
        transfer."""
        if self._decide_preq is None:
            import jax.numpy as jnp

            from ccfd_tpu.ops.fused_decision import eval_plan

            base = self._base
            mod = base._fused_mod
            interpret = base._fused_interpret
            plan = self._plan
            n_feat = plan.sel.shape[2] - 1

            @jax.jit
            def decide(fp, q, s, x=None):
                proba = mod.fused_mlp_q8_score_preq(
                    fp, q, s, tile=mod.fit_tile(q.shape[0]),
                    interpret=interpret,
                ).astype(jnp.float32)
                if x is None:
                    x = jnp.zeros((q.shape[0], n_feat), jnp.float32)
                fired = eval_plan(plan, x, proba)
                return jnp.stack([proba, fired.astype(jnp.float32)], axis=1)

            self._decide_preq = decide
        return self._decide_preq

    def _snapshot(self) -> tuple[Any, Any, Any]:
        with self._base._lock:
            return (self._base._params, self._base._fused_params,
                    self._base._preq_norm)

    def _preq_live(self, fused_params: Any, preq_norm: Any) -> bool:
        base = self._base
        return (fused_params is not None and preq_norm is not None
                and getattr(base, "_preq_wire", False)
                and base.mesh is None)

    # -- serving -------------------------------------------------------------

    def decide(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray | None]:
        """(n, F) rows -> (proba, fired) through the fused grid, or the
        staged fallback ``(proba, None)`` when the plane is unhealthy."""
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        if n == 0:
            return np.zeros((0,), np.float32), np.zeros((0,), np.int64)
        if not self.enabled or self._disabled:
            return self._staged(x)
        params, fused_params, preq_norm = self._snapshot()
        preq = self._preq_live(fused_params, preq_norm)
        fn = self._fn_preq() if preq else self._fn_for(fused_params)
        which = params if fused_params is None else fused_params
        base = self._base
        largest = base.batch_sizes[-1]
        t0 = time.perf_counter()
        pending: list[tuple[jax.Array, int]] = []
        chunks: list[np.ndarray] = []
        start = 0
        try:
            while start < n:
                take = min(n - start, largest)
                b = base.bucket(take)
                chunk = x[start:start + take]
                if take < b:
                    chunk = np.concatenate(
                        [chunk, np.zeros((b - take, x.shape[1]), np.float32)]
                    )
                # same fault seam as the staged dispatch: an injected
                # device_hang / compile_stall rides the fused path too
                device_seam("dispatch")
                with self._lock:
                    self._dispatch_counts[b] = (
                        self._dispatch_counts.get(b, 0) + 1)
                out = self._dispatch_one(fn, which, chunk, preq, preq_norm)
                pending.append((out, take))
                if len(pending) >= 2:
                    done, took = pending.pop(0)
                    chunks.append(np.asarray(done)[:took])
                    self.host_syncs += 1
                start += take
            for done, took in pending:
                # the single allowed sync: ONE packed (b, 2) transfer
                # carries score + threshold verdict + fired rule together
                chunks.append(np.asarray(done)[:took])
                self.host_syncs += 1
        # ccfd-lint: disable=counted-drops -- _disable logs the failure and _staged counts it in fused_decision_fallbacks_total
        except Exception as e:  # noqa: BLE001 - unhealthy executable:
            # disable the plane and serve THIS call staged; a sick device
            # raises out of the staged path into the router's host/rules
            # tiers
            self._disable(e)
            return self._staged(x)
        if self._c_decide:
            self._c_decide.inc(n)
        if self._profiler is not None:
            self._profiler.observe(
                "fused.decide", dispatch_s=time.perf_counter() - t0,
                batch=n, rows=n)
        packed = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
        proba = np.ascontiguousarray(packed[:, 0], np.float32)
        # rule indices are small ints: exact in the float32 lane
        fired = packed[:, 1].astype(np.int64)
        return proba, fired

    def _dispatch_one(self, fn: Any, which: Any, chunk: np.ndarray,
                      preq: bool, preq_norm: Any) -> jax.Array:
        """One bucket-padded chunk through the decision program. In preq
        mode the chunk ships on the SAME int8 wire the staged q8 path
        uses (host prequantization, byte-counted puts); rows ride along
        in f32 only when the rule plan reads feature columns."""
        base = self._base
        if not preq:
            return fn(which, base._put_batch(chunk))
        q, s = base._fused_mod.prequantize_rows_numpy(preq_norm, chunk)
        if base.telemetry is None:
            import jax.numpy as jnp

            qd, sd = jnp.asarray(q), jnp.asarray(s)
        else:
            import jax.numpy as jnp

            from ccfd_tpu.observability.device import timed_put

            qd = timed_put(base.telemetry, q.nbytes, lambda: jnp.asarray(q))
            sd = timed_put(base.telemetry, s.nbytes, lambda: jnp.asarray(s))
        if self._plan.needs_features:
            return fn(which, qd, sd, base._put_batch(chunk))
        return fn(which, qd, sd)

    def _staged(self, x: np.ndarray) -> tuple[np.ndarray, None]:
        """Whole-call staged fallback: base scorer + host rules (the
        router evaluates them on the returned ``fired=None``). One
        semantics per batch, never a per-row split."""
        self.staged_fallbacks += 1
        if self._c_fallback:
            self._c_fallback.inc(len(x))
        return np.asarray(self._base.score(x), np.float32), None

    def _disable(self, e: Exception) -> None:
        log.warning(
            "fused decision executable failed (%r); serving the staged "
            "path until the next swap precompile", e)
        self._disabled = True

    # -- warmup / swap precompile -------------------------------------------

    def warmup(self) -> None:
        """Precompile the whole fused decision grid (every batch bucket)
        under the ``fused.warm`` compile stage — serving dispatches then
        run with zero serving-stage compiles. A grid that cannot compile
        raises, like ``Scorer.warmup``."""
        if not self.enabled:
            return
        self._precompile(*self._snapshot())

    def prepublish(self, staged: Any, staged_fused: Any,
                   staged_preq_norm: Any, staged_host: Any) -> None:
        """Scorer prepublish hook: run the staged artifacts through every
        bucket of the decision grid BEFORE ``swap_params`` flips the
        serving reference — the seq variant swap's discipline applied to
        the fused grid. A healthy precompile re-arms a disabled plane; a
        failing one disables it — serving is live here, so the publish
        goes ahead and verdicts come from the staged path."""
        if not self.enabled:
            return
        try:
            self._precompile(staged, staged_fused, staged_preq_norm)
        # ccfd-lint: disable=counted-drops -- _disable logs the failure; later decide() calls count staged service in fused_decision_fallbacks_total
        except Exception as e:  # noqa: BLE001 - must not block a publish
            self._disable(e)

    def _precompile(self, params: Any, fused_params: Any,
                    preq_norm: Any) -> None:
        from ccfd_tpu.observability.profile import billed, compile_stage

        preq = self._preq_live(fused_params, preq_norm)
        fn = self._fn_preq() if preq else self._fn_for(fused_params)
        which = params if fused_params is None else fused_params
        base = self._base
        with compile_stage("fused.warm"):
            for b in base.batch_sizes:
                with billed("startup.executable", b_bucket=int(b)):
                    zeros = np.zeros((b, base.num_features), np.float32)
                    jax.block_until_ready(self._dispatch_one(
                        fn, which, zeros, preq, preq_norm))
        self._disabled = False  # the whole grid compiled and ran: (re-)armed

    # -- observability -------------------------------------------------------

    def executable_grid(self) -> dict:
        """The fused decision grid's executable-inventory entry
        (device-telemetry source ``fused_decision``), mirroring the row
        and seq families: bucket ladder, per-bucket dispatch counts, and
        the plane's health so a scrape shows WHAT is serving verdicts."""
        with self._lock:
            counts = dict(self._dispatch_counts)
        _, fused_params, preq_norm = (self._snapshot() if self.enabled
                                      else (None, None, None))
        forward = "xla"
        if fused_params is not None:
            forward = ("fused_kernel_int8_wire"
                       if self._preq_live(fused_params, preq_norm)
                       else "fused_kernel")
        return {
            "model": getattr(self._base.spec, "name", "?"),
            "batch_sizes": list(self._base.batch_sizes),
            "forward": forward,
            "rules": (self._plan.n_rules if self._plan is not None else 0),
            "needs_features": bool(self._plan is not None
                                   and self._plan.needs_features),
            "enabled": bool(self.enabled and not self._disabled),
            "staged_fallbacks": int(self.staged_fallbacks),
            "host_syncs": int(self.host_syncs),
            "dispatches": {str(b): int(c)
                           for b, c in sorted(counts.items())},
        }


__all__ = ["FusedDecisionScorer"]
