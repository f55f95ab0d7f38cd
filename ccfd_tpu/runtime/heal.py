"""Device self-healing: failure taxonomy, heal ladder, warm re-promotion.

Every resilience layer so far treats the accelerator as infrastructure
that either works or is someone else's problem: PR 1 hardened the RPC
edges around it, PR 6 bounded individual dispatches into it, PR 10
measured it. Nothing OWNS the device as a fallible component — detects
that it wedged / OOM'd / fell into a compile storm, takes it out of
rotation, heals it, and returns traffic safely. It is the layer the
serving literature presupposes: InferLine's planner retunes over hardware
it assumes stays healthy, and the "300M predictions/sec" utilization
story needs chips that stay IN rotation.

:class:`DeviceSupervisor` is that owner — a health state machine per
device::

    HEALTHY ──strike──▶ SUSPECT ──strikes──▶ QUARANTINED
       ▲                   │ (signals clear)        │ heal ladder:
       │                   ▼                        │  1. canary retry
       └──────────────  HEALTHY                     │  2. backend reinit
       ▲                                            │  3. scorer respawn
       │      N canaries + score parity             ▼ (jittered backoff)
       └───────────────  PROBATION  ◀───── canary passes

driven by three signal families, all drillable on CPU CI through the
device-fault plan (``runtime/faults.py``):

- **canary dispatch** — one tiny precompiled executable through the real
  serving dispatch path, bounded by the PR 6 ``bounded_dispatch``
  watchdog (a hung canary is killed and counted, never stalls the
  supervisor);
- **device telemetry** (PR 10) — allocator ``bytes_in_use`` vs
  ``bytes_limit`` for OOM pressure, per-stage compile rates for compile
  storms, H2D staging-put failures;
- **scorer-edge breaker** — an OPEN breaker means live traffic already
  found the device sick.

On QUARANTINE the supervisor pins the router's PR 1 degradation ladder to
the host tier (rules-only stays the last resort below it): the router's
``heal_gate`` check sits ABOVE the breaker, so not even a half-open probe
leaks traffic to the sick device. It then walks the heal ladder with
jittered exponential backoff, and re-promotes only **warm**: the full
executable inventory precompiles under the ``heal.warm`` compile-stage
label (the row bucket ladder and the seq (L, B) grid alike — zero XLA
compiles on the serving hot path after the flip), then N consecutive
canaries plus a host-vs-device score-parity check must pass, with
hysteresis so a flapping device backs off harder each round instead of
thrashing serving. Every transition exports
``ccfd_device_health{device,state}``, and quarantine/re-promotion edges
dump FlightRecorder bundles (``reason=device_quarantine`` /
``device_repromote``) so each incident is post-mortem-able.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Any, Callable

import numpy as np

from ccfd_tpu.runtime.breaker import backoff_s

log = logging.getLogger(__name__)

# state machine values, "bigger is sicker" except PROBATION (recovering)
HEALTHY, SUSPECT, QUARANTINED, PROBATION = 0, 1, 2, 3
STATE_NAMES = {HEALTHY: "healthy", SUSPECT: "suspect",
               QUARANTINED: "quarantined", PROBATION: "probation"}

# heal-ladder rungs, walked in order (the last repeats until it works)
RUNGS = ("canary_retry", "reinit", "respawn")

# compile-stage labels that legitimately compile OUTSIDE the serving hot
# path: warmups, swap precompiles, and the heal ladder's own warm step.
# Everything else counting a compile while serving is a storm signal —
# and after a re-promotion flip it would mean the re-promotion was COLD.
NON_SERVING_COMPILE_STAGES = frozenset({
    "total", "heal.warm", "heal.canary", "scorer.warmup", "seq.warmup",
    "seq.swap", "fused.warm",
})


def default_device_label() -> str:
    """``platform:id`` of the first local device (the gauge label)."""
    try:
        import jax

        d = jax.local_devices()[0]
        return f"{d.platform}:{d.id}"
    except Exception:  # noqa: BLE001 - no backend is itself a device state
        return "device:0"


def mesh_domain_label(mesh: Any) -> str:
    """``mesh:<platform>x<n>`` — the health-domain label for a multi-chip
    SPMD serving mesh.

    **The mesh is ONE health domain.** Every sharded executable spans
    every mesh device (one SPMD program, one launch), so there is no
    per-chip traffic to steer away from a sick chip: a canary kill or an
    OOM signal "on device 3" still fails the whole dispatch, and
    quarantining chip 3 alone would leave executables that *require*
    chip 3 in rotation. The supervisor therefore quarantines the MESH
    TIER — the router ladder pins to the host tier for the whole heal
    cycle — and re-promotes the mesh as a unit after the warm gate
    (documented in ARCHITECTURE "Partitioning & multi-chip serving")."""
    try:
        platform = mesh.devices.flat[0].platform
        return f"mesh:{platform}x{int(mesh.size)}"
    except Exception:  # noqa: BLE001
        return "mesh:unknown"


class DeviceSupervisor:
    """Per-device health state machine + heal ladder; see the module
    docstring. Runs as a supervised service (``run``/``stop``/``reset``)
    under the operator's ``heal:`` component; ``tick()`` is the test and
    drill surface.

    The supervisor IS the router's ``heal_gate``: ``device_allowed()``
    answers False from the moment of quarantine until the warm
    re-promotion flip, which pins the degradation ladder to its host tier
    (rules-only as the last resort) for the whole heal cycle.
    """

    def __init__(
        self,
        scorer: Any,
        registry: Any = None,
        breaker: Any = None,
        telemetry: Any = None,
        profiler: Any = None,
        recorder: Any = None,
        overload: Any = None,
        device: str | None = None,
        canary_rows: int = 16,
        canary_deadline_ms: float = 250.0,
        suspect_strikes: int = 2,
        probation_canaries: int = 3,
        parity_tol: float = 0.05,
        oom_ratio: float = 0.92,
        compile_storm_per_s: float = 2.0,
        backoff_base_s: float = 0.5,
        backoff_cap_s: float = 30.0,
        flap_window_s: float = 60.0,
        reinit_fn: Callable[[], None] | None = None,
        respawn_fn: Callable[[], None] | None = None,
        seed: int = 0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.scorer = scorer
        self.breaker = breaker
        self.telemetry = telemetry
        self.profiler = profiler
        self.recorder = recorder
        self.overload = overload
        # health-domain resolution: a mesh-sharded scorer is supervised as
        # ONE domain (see mesh_domain_label — every SPMD executable spans
        # every mesh device, so quarantine/heal/re-promote act on the
        # mesh tier, never on an individual chip)
        scorer_mesh = getattr(scorer, "mesh", None)
        self.domain = "mesh" if scorer_mesh is not None else "device"
        if device is None:
            device = (mesh_domain_label(scorer_mesh)
                      if scorer_mesh is not None else default_device_label())
        self.device = device
        self.canary_deadline_s = max(1e-3, float(canary_deadline_ms) / 1e3)
        self.suspect_strikes = max(1, int(suspect_strikes))
        self.probation_canaries = max(1, int(probation_canaries))
        self.parity_tol = float(parity_tol)
        self.oom_ratio = float(oom_ratio)
        self.compile_storm_per_s = float(compile_storm_per_s)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.flap_window_s = float(flap_window_s)
        self._reinit_fn = reinit_fn
        self._respawn_fn = respawn_fn
        self._rng = random.Random(seed)
        self._clock = clock
        self._mu = threading.Lock()
        self._stop = threading.Event()

        # canary probe: real (seeded) rows, NOT zeros — the parity check
        # compares device vs host probabilities, and an all-zeros batch
        # collapses to one output value that can't catch a scrambled graph
        nf = int(getattr(scorer, "num_features", 30))
        rng = np.random.default_rng(seed)
        self._probe_x = rng.standard_normal(
            (max(1, int(canary_rows)), nf)).astype(np.float32)

        self._state = HEALTHY
        self._strikes = 0
        self._last_reasons: list[str] = []
        self._rung_idx = 0
        self._heal_attempt = 0       # backoff exponent within a quarantine
        self._next_heal_at = 0.0
        self._probation_passes = 0
        self._flap_streak = 0        # re-quarantines inside flap_window_s
        self._last_promote_at: float | None = None
        self._prev_compile: dict[str, int] = {}
        self._prev_compile_at: float | None = None
        # baseline diffed signals from their LIVE values: the supervisor
        # comes up after serving (operator step 7e), and history that
        # predates it must not read as first-tick strikes
        self._prev_put_failures = (telemetry.h2d_failures()
                                   if telemetry is not None else 0)
        self._prev_breaker_opens = (breaker.opens
                                    if breaker is not None else 0)
        # lifetime counters for drills/tests
        self.quarantines = 0
        self.repromotions = 0
        self.canary_failures = 0

        self._g_health = self._c_transitions = None
        self._c_attempts = self._c_canary = None
        if registry is not None:
            self._g_health = registry.gauge(
                "ccfd_device_health",
                "device health state one-hot: 1 on the current state's "
                "series, 0 elsewhere (healthy/suspect/quarantined/"
                "probation per device)",
            )
            self._c_transitions = registry.counter(
                "ccfd_heal_transitions_total",
                "device health state transitions by target state",
            )
            self._c_attempts = registry.counter(
                "ccfd_heal_attempts_total",
                "heal-ladder attempts by rung (canary_retry -> reinit -> "
                "respawn, jittered backoff between attempts)",
            )
            self._c_canary = registry.counter(
                "ccfd_heal_canary_total",
                "canary dispatch outcomes (pass / fail)",
            )
            self._export_state()

        self._own_dispatcher = None
        if overload is None:
            from ccfd_tpu.serving.dispatch import DeviceDispatcher

            self._own_dispatcher = DeviceDispatcher(
                max_threads=2, name="ccfd-heal-canary")

    # -- state surface ------------------------------------------------------
    @property
    def state(self) -> str:
        return STATE_NAMES[self._state]

    def device_allowed(self) -> bool:
        """The router ladder's gate: may live traffic touch the device?
        False from quarantine entry until the warm re-promotion flip —
        PROBATION still answers False (canaries + parity must pass before
        serving returns; that asymmetry is the hysteresis)."""
        return self._state in (HEALTHY, SUSPECT)

    def _export_state(self) -> None:
        if self._g_health is None:
            return
        for s, name in STATE_NAMES.items():
            self._g_health.set(
                1.0 if s == self._state else 0.0,
                labels={"device": self.device, "state": name})

    def _set_state(self, state: int) -> None:
        if state == self._state:
            return
        log.info("device %s: %s -> %s", self.device,
                 STATE_NAMES[self._state], STATE_NAMES[state])
        self._state = state
        self._export_state()
        if self._c_transitions is not None:
            self._c_transitions.inc(labels={"to": STATE_NAMES[state]})

    # -- canary -------------------------------------------------------------
    def _device_dispatch(self) -> np.ndarray:
        """One tiny dispatch through the real serving path — PRECOMPILED
        (the probe rides the smallest warmed bucket), so the canary
        measures the device, not an XLA compile. Any compile it DOES pay
        (the retry after a cache-clearing reinit rung) bills to
        ``heal.canary`` — the label is set here, on whichever sacrificial
        thread actually runs the dispatch, because the compile-stage
        contextvar does not cross the watchdog's thread boundary."""
        from ccfd_tpu.observability.profile import compile_stage

        scorer = self.scorer
        with compile_stage("heal.canary"):
            pipelined = getattr(scorer, "score_pipelined", None)
            if callable(pipelined):
                # the row Scorer: score_pipelined is the pure device path
                # (score() would take the host tier at canary batch sizes)
                return np.asarray(pipelined(self._probe_x, depth=1))
            return np.asarray(scorer.score(self._probe_x))

    def _run_canary(self, parity: bool = False) -> tuple[bool, str]:
        """(passed, reason). Bounded by the PR 6 watchdog; with
        ``parity`` the device output must also agree with the host
        forward within ``parity_tol`` (the re-promotion gate's proof the
        healed device computes the same model, not just answers)."""
        try:
            if self.overload is not None:
                out = self.overload.bounded_dispatch(
                    self._device_dispatch, deadline_s=self.canary_deadline_s)
            else:
                out = self._own_dispatcher.call(
                    self._device_dispatch, self.canary_deadline_s)
        except Exception as e:  # noqa: BLE001 - every failure mode counts
            self.canary_failures += 1
            if self._c_canary is not None:
                self._c_canary.inc(labels={"outcome": "fail"})
            return False, f"canary: {type(e).__name__}: {e}"
        out = np.asarray(out)
        if out.shape != (len(self._probe_x),) or not np.isfinite(out).all():
            self.canary_failures += 1
            if self._c_canary is not None:
                self._c_canary.inc(labels={"outcome": "fail"})
            return False, "canary: invalid response shape/values"
        if parity and getattr(self.scorer, "has_host_forward", False):
            host = np.asarray(self.scorer.host_score(self._probe_x))
            delta = float(np.max(np.abs(out - host)))
            if delta > self.parity_tol:
                self.canary_failures += 1
                if self._c_canary is not None:
                    self._c_canary.inc(labels={"outcome": "fail"})
                return False, f"parity: max |device-host| {delta:.4f}"
        if self._c_canary is not None:
            self._c_canary.inc(labels={"outcome": "pass"})
        return True, ""

    # -- telemetry signals --------------------------------------------------
    def _collect_signals(self) -> list[str]:
        """Quarantine evidence from the PR 10 planes; each entry is one
        strike-worthy reason."""
        reasons: list[str] = []
        tele = self.telemetry
        if tele is not None:
            try:
                for dev, kinds in tele.device_memory().items():
                    used, limit = kinds.get("bytes_in_use"), kinds.get(
                        "bytes_limit")
                    if used and limit and used / limit >= self.oom_ratio:
                        reasons.append(
                            f"device_oom: {dev} {used}/{limit} "
                            f">= {self.oom_ratio:.2f}")
                        break
            except Exception:  # noqa: BLE001 - telemetry must not crash heal
                pass
            failures = tele.h2d_failures()
            if failures > self._prev_put_failures:
                reasons.append(
                    f"put_fail: {failures - self._prev_put_failures} "
                    "staging failures since last tick")
            self._prev_put_failures = failures
        prof = self.profiler
        if prof is not None:
            now = self._clock()
            counts = prof.compile_counts()
            if self._prev_compile_at is not None:
                dt = max(1e-6, now - self._prev_compile_at)
                serving = sum(
                    counts.get(s, 0) - self._prev_compile.get(s, 0)
                    for s in counts
                    if s not in NON_SERVING_COMPILE_STAGES)
                if serving / dt >= self.compile_storm_per_s:
                    reasons.append(
                        f"compile_storm: {serving} serving-stage compiles "
                        f"in {dt:.1f}s")
            self._prev_compile = counts
            self._prev_compile_at = now
        br = self.breaker
        if br is not None:
            opens = br.opens
            if br.state == "open" or opens > self._prev_breaker_opens:
                reasons.append("breaker: scorer edge open/tripped")
            self._prev_breaker_opens = opens
        return reasons

    # -- transitions --------------------------------------------------------
    def _quarantine(self, reasons: list[str]) -> None:
        self.quarantines += 1
        self._last_reasons = reasons[:8]
        now = self._clock()
        if self._state in (QUARANTINED, PROBATION):
            # re-quarantined MID-heal (warm step or probation canary
            # failed): that is a failed ladder attempt, so escalate the
            # rung and deepen the backoff — resetting here would loop a
            # canary-pass/warm-fail device at rung 0 forever, never
            # reaching the reinit/respawn rungs that could actually fix
            # it (no promotion happened, so the flap streak stays put)
            self._rung_idx += 1
            self._heal_attempt += 1
        else:
            # flap hysteresis: a device re-quarantined shortly after a
            # re-promotion earns a harder backoff each round, so a
            # flapping attachment cannot thrash serving at the heal
            # ladder's base rate
            if (self._last_promote_at is not None
                    and now - self._last_promote_at <= self.flap_window_s):
                self._flap_streak += 1
            else:
                self._flap_streak = 0
            self._rung_idx = 0
            self._heal_attempt = self._flap_streak
        self._next_heal_at = now + backoff_s(
            self._heal_attempt, self.backoff_base_s, self.backoff_cap_s,
            self._rng)
        self._set_state(QUARANTINED)
        log.warning("device %s QUARANTINED: %s", self.device, reasons)
        if self.recorder is not None:
            try:
                self.recorder.incident({
                    "type": "device_quarantine",
                    "device": self.device,
                    "signals": self._last_reasons,
                })
            except Exception:  # noqa: BLE001 - evidence, not control flow
                pass

    def _heal_step(self) -> None:
        """One heal-ladder attempt, backoff-gated. Escalates one rung per
        failure; the last rung (respawn) repeats until it works."""
        now = self._clock()
        if now < self._next_heal_at:
            return
        rung = RUNGS[min(self._rung_idx, len(RUNGS) - 1)]
        if self._c_attempts is not None:
            self._c_attempts.inc(labels={"rung": rung})
        try:
            if rung == "reinit":
                self._reinit()
            elif rung == "respawn":
                self._respawn()
        except Exception as e:  # noqa: BLE001 - a failed rung is a failed
            log.warning("heal rung %s raised: %r", rung, e)  # attempt
            self._escalate(now)
            return
        ok, reason = self._run_canary()
        if ok:
            self._enter_probation()
            return
        log.info("heal rung %s: canary still failing (%s)", rung, reason)
        self._escalate(now)

    def _escalate(self, now: float) -> None:
        self._rung_idx += 1
        self._heal_attempt += 1
        self._next_heal_at = now + backoff_s(
            self._heal_attempt, self.backoff_base_s, self.backoff_cap_s,
            self._rng)

    def _reinit(self) -> None:
        """Rung 2: backend re-probe/reinit. The default drops every jax
        compilation cache entry and live trace state the wedge might have
        poisoned; the warm step recompiles the inventory BEFORE serving
        returns, so this never moves compile cost onto the hot path."""
        if self._reinit_fn is not None:
            self._reinit_fn()
            return
        import jax

        jax.clear_caches()

    def _respawn(self) -> None:
        """Rung 3: supervised scorer respawn with checkpoint restore. The
        operator wires the lifecycle controller's champion-checkpoint
        restore here; the default re-publishes the scorer's own params
        through ``swap_params`` — fresh device buffers for every tree
        (a device-side state scrub even without a lifecycle)."""
        if self._respawn_fn is not None:
            self._respawn_fn()
            return
        import jax

        params = jax.tree.map(np.asarray, self.scorer.params)
        self.scorer.swap_params(params)

    def _enter_probation(self) -> None:
        self._probation_passes = 0
        self._set_state(PROBATION)
        self._warm()

    def _warm(self) -> None:
        """Precompile the full executable inventory (the row bucket
        ladder / the seq (L, B) grid — whatever ``warmup`` covers) under
        the ``heal.warm`` compile-stage label. This is what makes the
        re-promotion WARM: every compile bills here, and the drills
        assert zero serving-stage compiles after the flip."""
        from ccfd_tpu.observability.profile import compile_stage

        try:
            with compile_stage("heal.warm"):
                self.scorer.warmup()
        except Exception as e:  # noqa: BLE001 - a failed warm is a failed
            log.warning("heal warm step failed: %r", e)  # probation
            self._quarantine([f"warm: {type(e).__name__}: {e}"])

    def _probation_step(self) -> None:
        ok, reason = self._run_canary(parity=True)
        if not ok:
            log.warning("probation canary failed (%s); re-quarantining",
                        reason)
            self._quarantine([f"probation: {reason}"])
            return
        self._probation_passes += 1
        if self._probation_passes < self.probation_canaries:
            return
        # warm re-promotion flip: serving returns to the device
        self._last_promote_at = self._clock()
        self.repromotions += 1
        # re-baseline every diffed signal at the flip: the quarantine era
        # legitimately produced compiles (a reinit rung clears the jax
        # caches; its canary recompiles untagged), put failures and
        # breaker trips — diffing the first healthy tick against the
        # PRE-quarantine baseline would read that history as fresh
        # evidence and re-quarantine a healed device
        if self.profiler is not None:
            self._prev_compile = self.profiler.compile_counts()
            self._prev_compile_at = self._clock()
        if self.telemetry is not None:
            self._prev_put_failures = self.telemetry.h2d_failures()
        if self.breaker is not None:
            self._prev_breaker_opens = self.breaker.opens
        if self.breaker is not None:
            # the breaker's window is full of quarantine-era failures,
            # and from OPEN record_success() is a state no-op: a residual
            # cooldown (consecutive_opens backoff can reach tens of
            # seconds) would keep refusing the healed device AND read as
            # fresh quarantine evidence next tick. The probation gate (N
            # canaries + parity) outranks a half-open probe, so close the
            # scorer edge outright.
            try:
                close = getattr(self.breaker, "force_close", None)
                if callable(close):
                    close()
                else:
                    self.breaker.record_success()
            except Exception:  # noqa: BLE001
                pass
        self._strikes = 0
        self._set_state(HEALTHY)
        log.info("device %s re-promoted (warm) after %d canaries",
                 self.device, self._probation_passes)
        if self.recorder is not None:
            try:
                self.recorder.incident({
                    "type": "device_repromote",
                    "device": self.device,
                    "canaries": self._probation_passes,
                })
            except Exception:  # noqa: BLE001
                pass

    # -- the supervised tick ------------------------------------------------
    def tick(self) -> str:
        """One supervision cycle; returns the (possibly new) state name."""
        with self._mu:
            state = self._state
            if state in (HEALTHY, SUSPECT):
                reasons = self._collect_signals()
                ok, reason = self._run_canary()
                if not ok:
                    reasons.append(reason)
                if reasons:
                    self._strikes += 1
                    self._last_reasons = reasons[:8]
                    if self._strikes >= self.suspect_strikes:
                        self._quarantine(reasons)
                    else:
                        self._set_state(SUSPECT)
                else:
                    self._strikes = 0
                    if state == SUSPECT:
                        self._set_state(HEALTHY)
            elif state == QUARANTINED:
                self._heal_step()
            elif state == PROBATION:
                self._probation_step()
            return STATE_NAMES[self._state]

    def status(self) -> dict[str, Any]:
        with self._mu:
            return {
                "device": self.device,
                "domain": self.domain,
                "state": STATE_NAMES[self._state],
                "strikes": self._strikes,
                "reasons": list(self._last_reasons),
                "rung": RUNGS[min(self._rung_idx, len(RUNGS) - 1)],
                "quarantines": self.quarantines,
                "repromotions": self.repromotions,
                "canary_failures": self.canary_failures,
                "flap_streak": self._flap_streak,
            }

    # -- supervised-service surface ----------------------------------------
    def reset(self) -> None:
        self._stop.clear()

    def stop(self) -> None:
        self._stop.set()

    def run(self, interval_s: float = 5.0) -> None:
        while not self._stop.wait(interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - one bad tick must not kill
                log.exception("heal tick failed")  # the supervision loop
