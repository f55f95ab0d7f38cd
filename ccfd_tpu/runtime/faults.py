"""Network-edge fault injection: degraded RPC hops, seeded and installable.

``runtime/chaos.py`` kills whole components; the far more common production
failure is a *sick edge* — a scorer endpoint that answers slowly, a
partitioned bus, a flaky engine hop. The reference has no story for either
(SURVEY.md §5: its resilience is k8s restartPolicy + Kafka redelivery).
This module makes degraded edges injectable on every client hop the
framework owns — router↔scorer (`serving/client.py` and the in-process
score_fn), router↔engine (`process/client.py` and the in-process
``EngineClient``), services↔bus (`bus/client.py`), producer↔store
(`store/client.py`) — so the circuit breakers and the router's degradation
ladder (`runtime/breaker.py`, `router/router.py`) are *exercised* in CI and
soaks instead of trusted.

Model: a ``FaultPlan`` maps edge names to ``FaultSpec``s (latency + jitter,
error rate, blackhole/partition, corrupt-response, slow-drip) and is parsed
from ``CCFD_FAULTS``::

    CCFD_FAULTS="scorer:latency=50,jitter=20,error=0.05;engine:blackhole"

A ``FaultInjector`` binds one edge of the plan around a client (or a bare
callable) and perturbs every call while the plan is ACTIVE. Plans are
seeded — victim timing and error draws are replayable — and activation is
a thread-safe toggle so the ChaosMonkey can drive fault *storms* (windows
of degradation) on a schedule, the edge-level analog of its kill schedule.

Injected failures raise :class:`InjectedFault` (a ``ConnectionError``), so
every client's existing transport-error handling — retries, breakers, the
router's tier ladder — engages exactly as it would for the real thing.
"""

from __future__ import annotations

import binascii
import random
import threading
import time
from typing import Any, Callable, Iterable, Mapping

import numpy as np


class InjectedFault(ConnectionError):
    """A fault-plan failure. Subclasses ConnectionError so client retry /
    breaker paths treat it exactly like a real transport error."""


# fault kinds a spec can carry; parse-time validation names them
_KINDS = ("latency", "jitter", "error", "blackhole", "corrupt", "drip",
          "stall")


class FaultSpec:
    """One edge's degradation profile. All times in milliseconds.

    - ``latency_ms`` fixed added delay per call
    - ``jitter_ms`` extra uniform delay in [0, jitter_ms)
    - ``error_rate`` probability a call raises :class:`InjectedFault`
    - ``blackhole`` the peer is partitioned: every call stalls ``stall_ms``
      (the SYN-timeout analog, bounded so tests stay fast) then raises
    - ``corrupt_rate`` probability a *response* comes back mangled (float
      arrays go NaN — silent corruption the validation layers must catch;
      anything else raises, the decode-error analog)
    - ``drip_ms`` slow drip: added delay GROWS by drip_ms per call while
      the plan is active (a degrading endpoint), capped at ``drip_cap_ms``
    """

    __slots__ = ("latency_ms", "jitter_ms", "error_rate", "blackhole",
                 "corrupt_rate", "drip_ms", "drip_cap_ms", "stall_ms")

    def __init__(
        self,
        latency_ms: float = 0.0,
        jitter_ms: float = 0.0,
        error_rate: float = 0.0,
        blackhole: bool = False,
        corrupt_rate: float = 0.0,
        drip_ms: float = 0.0,
        drip_cap_ms: float = 1000.0,
        stall_ms: float = 250.0,
    ):
        if not 0.0 <= error_rate <= 1.0:
            raise ValueError(f"error_rate {error_rate} outside [0, 1]")
        if not 0.0 <= corrupt_rate <= 1.0:
            raise ValueError(f"corrupt_rate {corrupt_rate} outside [0, 1]")
        for name, v in (("latency_ms", latency_ms), ("jitter_ms", jitter_ms),
                        ("drip_ms", drip_ms), ("drip_cap_ms", drip_cap_ms),
                        ("stall_ms", stall_ms)):
            if v < 0:
                raise ValueError(f"{name} must be >= 0, got {v}")
        self.latency_ms = float(latency_ms)
        self.jitter_ms = float(jitter_ms)
        self.error_rate = float(error_rate)
        self.blackhole = bool(blackhole)
        self.corrupt_rate = float(corrupt_rate)
        self.drip_ms = float(drip_ms)
        self.drip_cap_ms = float(drip_cap_ms)
        self.stall_ms = float(stall_ms)

    @staticmethod
    def parse(body: str) -> "FaultSpec":
        """``"latency=50,jitter=20,error=0.1,blackhole"`` -> FaultSpec.
        Bare ``blackhole``/``corrupt`` flags take their default strength."""
        kw: dict[str, Any] = {}
        for item in body.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, val = item.partition("=")
            key = key.strip()
            if key not in _KINDS:
                raise ValueError(
                    f"unknown fault kind {key!r}; known: {_KINDS}")
            if key == "blackhole":
                kw["blackhole"] = (val.strip().lower()
                                   not in ("0", "false", "no")
                                   if sep else True)
            elif key == "corrupt":
                kw["corrupt_rate"] = float(val) if sep else 1.0
            elif key == "error":
                kw["error_rate"] = float(val)
            elif key == "stall":
                kw["stall_ms"] = float(val)
            else:  # latency / jitter / drip
                kw[f"{key}_ms"] = float(val)
        return FaultSpec(**kw)

    def __repr__(self) -> str:  # debugging / soak reports
        parts = [f"{k}={getattr(self, k)}" for k in self.__slots__
                 if getattr(self, k)]
        return f"FaultSpec({', '.join(parts)})"


class FaultPlan:
    """Edge name -> FaultSpec, with a thread-safe activation toggle.

    ``"*"`` is the wildcard edge (applies to any edge without its own
    spec). A plan parsed from env starts ACTIVE (the operator asked for
    standing degradation); a plan handed to the ChaosMonkey for storm
    scheduling is usually built with ``active=False`` and toggled.
    """

    def __init__(self, specs: Mapping[str, FaultSpec] | None = None,
                 seed: int = 0, active: bool = True):
        self.specs = dict(specs or {})
        self.seed = int(seed)
        self._active = threading.Event()
        if active:
            self._active.set()
        self.activations = 0

    @staticmethod
    def from_string(text: str, seed: int = 0,
                    active: bool = True) -> "FaultPlan":
        """``"edge:kind=v,kind;edge2:kind"`` -> FaultPlan. Empty text means
        an empty (no-op) plan."""
        specs: dict[str, FaultSpec] = {}
        for part in text.split(";"):
            part = part.strip()
            if not part:
                continue
            edge, sep, body = part.partition(":")
            edge = edge.strip()
            if not edge or not sep:
                raise ValueError(
                    f"CCFD_FAULTS entry {part!r}: expected edge:spec")
            specs[edge] = FaultSpec.parse(body)
        return FaultPlan(specs, seed=seed, active=active)

    @staticmethod
    def from_env(env: Mapping[str, str] | None = None,
                 seed: int = 0) -> "FaultPlan":
        import os

        e = os.environ if env is None else env
        return FaultPlan.from_string(e.get("CCFD_FAULTS", ""), seed=seed)

    # -- activation (ChaosMonkey storm windows) ---------------------------
    @property
    def active(self) -> bool:
        return self._active.is_set()

    def activate(self) -> None:
        self.activations += 1
        self._active.set()

    def deactivate(self) -> None:
        self._active.clear()

    def spec_for(self, edge: str) -> FaultSpec | None:
        return self.specs.get(edge) or self.specs.get("*")

    def injector(self, edge: str, registry=None) -> "FaultInjector | None":
        """Injector bound to one edge, or None when the plan has nothing
        for it — callers then skip wrapping entirely (zero overhead)."""
        spec = self.spec_for(edge)
        if spec is None:
            return None
        return FaultInjector(self, edge, spec, registry=registry)


class FaultInjector:
    """Applies one edge's FaultSpec around calls.

    Deterministic per (plan seed, edge): the RNG seeds from
    ``seed ^ crc32(edge)`` so two runs with the same plan draw the same
    error sequence per edge regardless of edge iteration order.
    """

    def __init__(self, plan: FaultPlan, edge: str, spec: FaultSpec,
                 registry=None):
        self.plan = plan
        self.edge = edge
        self.spec = spec
        self._rng = random.Random(
            plan.seed ^ binascii.crc32(edge.encode()))
        self._mu = threading.Lock()
        self._calls_active = 0  # drip ramp position
        self.injected = 0       # lifetime count, any kind
        self._c_injected = None
        if registry is not None:
            self._c_injected = registry.counter(
                "faults_injected_total",
                "fault-plan perturbations by edge and kind",
            )

    def _count(self, kind: str) -> None:
        self.injected += 1
        if self._c_injected is not None:
            self._c_injected.inc(labels={"edge": self.edge, "kind": kind})

    def before(self) -> bool:
        """Pre-call perturbation: delay, blackhole, error draw. Returns
        whether the caller should corrupt the response (pass the flag to
        :meth:`after` — per-call state stays on the caller's stack so
        concurrent calls through one injector don't cross-attribute)."""
        if not self.plan.active:
            with self._mu:
                self._calls_active = 0  # drip ramp resets between storms
            return False
        s = self.spec
        with self._mu:
            n = self._calls_active
            self._calls_active = n + 1
            jitter = self._rng.random() * s.jitter_ms
            err_draw = self._rng.random()
            corrupt = self._rng.random() < s.corrupt_rate
        delay_ms = s.latency_ms + jitter + min(s.drip_ms * n, s.drip_cap_ms)
        if delay_ms > 0:
            self._count("latency")
            time.sleep(delay_ms / 1e3)
        if s.blackhole:
            self._count("blackhole")
            time.sleep(s.stall_ms / 1e3)
            raise InjectedFault(
                f"edge {self.edge!r} blackholed (injected partition)")
        if err_draw < s.error_rate:
            self._count("error")
            raise InjectedFault(f"edge {self.edge!r} injected error")
        return corrupt

    def after(self, result: Any, corrupt: bool) -> Any:
        """Post-call perturbation: corrupt the response in flight."""
        if not corrupt or not self.plan.active:
            return result
        self._count("corrupt")
        if isinstance(result, np.ndarray) and np.issubdtype(
                result.dtype, np.floating):
            # silent corruption: the payload decodes but the numbers are
            # garbage — exactly what response validation must catch
            return np.full_like(result, np.nan)
        raise InjectedFault(
            f"edge {self.edge!r} returned an undecodable response "
            "(injected corruption)")

    def run(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> Any:
        corrupt = self.before()
        return self.after(fn(*args, **kwargs), corrupt)

    def wrap_fn(self, fn: Callable[..., Any]) -> Callable[..., Any]:
        """Bare-callable edge (e.g. the router's in-process score_fn)."""
        def wrapped(*args: Any, **kwargs: Any) -> Any:
            return self.run(fn, *args, **kwargs)

        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapped

    def wrap(self, obj: Any, methods: Iterable[str] | None = None) -> Any:
        """Proxy an object, perturbing the named public methods (all public
        callables when ``methods`` is None). Everything else delegates, so
        the proxy keeps the wrapped client's full surface (e.g. the
        router's ``definitions`` probe on an engine)."""
        from ccfd_tpu.runtime.breaker import MethodProxy

        return MethodProxy(obj, self.run,
                           frozenset(methods) if methods else None)


# ---------------------------------------------------------------------------
# Device faults: the accelerator itself as a fallible component.
#
# The edge faults above perturb RPC hops; the failure taxonomy the heal
# ladder (runtime/heal.py) defends against lives BELOW every edge — the
# device wedges mid-dispatch, the allocator runs out of HBM, XLA re-traces
# in a storm, a host->device staging put fails. These inject at the three
# seams the serving stack owns (all drillable on CPU CI):
#
# - ``dispatch`` — the scorer's device-dispatch loop (Scorer.score_pipelined
#   / SeqScorer's chunk loop): ``device_hang`` stalls the dispatch past its
#   watchdog deadline; ``compile_stall`` stalls AND bills a synthetic
#   backend_compile event to the active compile_stage label, so the
#   compile-storm signal the DeviceSupervisor watches actually moves.
# - ``put`` — the staging seam (Scorer._put_batch / SeqScorer._put_hist):
#   ``put_fail`` raises, and the telemetry plane counts the failure
#   (ccfd_h2d_put_failures_total — the supervisor's put-failure signal).
# - telemetry — ``device_oom`` overlays allocator pressure onto
#   DeviceTelemetry.device_memory() (bytes_in_use ~= bytes_limit), the
#   OOM-pressure signal, since CPU backends report no allocator stats.
#
# A plan installs process-wide (install_device_faults) because the seams
# sit inside compiled-dispatch helpers no injector proxy can wrap; the
# activation toggle has the FaultPlan interface, so the ChaosMonkey (and
# tools/chaos_soak.py --device-faults) schedules device-fault storms with
# the same machinery that drives edge storms.
# ---------------------------------------------------------------------------

DEVICE_FAULT_KINDS = ("device_hang", "compile_stall", "device_oom",
                      "put_fail")


class DeviceFaultSpec:
    """Parameters for one device-fault kind. Times in milliseconds.

    - ``device_hang``: every dispatch stalls ``hang_ms`` (default 400 —
      comfortably past the CI-scale watchdog deadlines the drills use).
    - ``compile_stall``: every dispatch stalls ``stall_ms`` and records a
      synthetic backend_compile of that duration (a re-trace storm).
    - ``device_oom``: reported allocator pressure ``oom_ratio`` of
      bytes_limit (default 0.99 — past any sane quarantine threshold).
    - ``put_fail``: a staging put raises with probability ``rate``
      (default 1.0).
    """

    __slots__ = ("hang_ms", "stall_ms", "oom_ratio", "rate")

    def __init__(self, hang_ms: float = 400.0, stall_ms: float = 50.0,
                 oom_ratio: float = 0.99, rate: float = 1.0):
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"rate {rate} outside [0, 1]")
        if not 0.0 <= oom_ratio <= 1.0:
            raise ValueError(f"oom_ratio {oom_ratio} outside [0, 1]")
        for name, v in (("hang_ms", hang_ms), ("stall_ms", stall_ms)):
            if v < 0:
                raise ValueError(f"{name} must be >= 0, got {v}")
        self.hang_ms = float(hang_ms)
        self.stall_ms = float(stall_ms)
        self.oom_ratio = float(oom_ratio)
        self.rate = float(rate)

    @staticmethod
    def parse(body: str) -> "DeviceFaultSpec":
        """``"ms=400"`` / ``"ratio=0.95,rate=0.5"`` -> DeviceFaultSpec.
        ``ms`` sets both hang and stall times (one knob per kind in
        practice); empty body takes every default."""
        kw: dict[str, float] = {}
        for item in body.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, val = item.partition("=")
            key = key.strip()
            if not sep:
                raise ValueError(
                    f"device-fault option {item!r}: expected key=value")
            if key == "ms":
                kw["hang_ms"] = kw["stall_ms"] = float(val)
            elif key == "ratio":
                kw["oom_ratio"] = float(val)
            elif key == "rate":
                kw["rate"] = float(val)
            else:
                raise ValueError(
                    f"unknown device-fault option {key!r}; "
                    f"known: ms, ratio, rate")
        return DeviceFaultSpec(**kw)


class DeviceFaultPlan:
    """Active device-fault kinds + the FaultPlan activation interface
    (``activate``/``deactivate``/``active``/``activations``) so storm
    schedulers drive device faults exactly like edge faults."""

    def __init__(self, kinds: Mapping[str, DeviceFaultSpec] | None = None,
                 seed: int = 0, active: bool = True):
        for k in (kinds or {}):
            if k not in DEVICE_FAULT_KINDS:
                raise ValueError(
                    f"unknown device fault {k!r}; known: "
                    f"{DEVICE_FAULT_KINDS}")
        self.kinds = dict(kinds or {})
        self._rng = random.Random(seed)
        self._active = threading.Event()
        if active:
            self._active.set()
        self.activations = 0
        self.injected: dict[str, int] = {}
        self._oom_counted_epoch = -1  # activation epoch last counted

    @staticmethod
    def from_string(text: str, seed: int = 0,
                    active: bool = True) -> "DeviceFaultPlan":
        """``"device_hang:ms=400;put_fail"`` -> DeviceFaultPlan (the
        CCFD_DEVICE_FAULTS syntax). Empty text means an empty plan."""
        kinds: dict[str, DeviceFaultSpec] = {}
        for part in text.split(";"):
            part = part.strip()
            if not part:
                continue
            kind, _sep, body = part.partition(":")
            kinds[kind.strip()] = DeviceFaultSpec.parse(body)
        return DeviceFaultPlan(kinds, seed=seed, active=active)

    @property
    def active(self) -> bool:
        return self._active.is_set()

    def activate(self) -> None:
        self.activations += 1
        self._active.set()

    def deactivate(self) -> None:
        self._active.clear()

    def spec(self, kind: str) -> DeviceFaultSpec | None:
        """The kind's spec while the plan is ACTIVE, else None."""
        if not self._active.is_set():
            return None
        return self.kinds.get(kind)

    def _count(self, kind: str) -> None:
        self.injected[kind] = self.injected.get(kind, 0) + 1


_DEVICE_PLAN: DeviceFaultPlan | None = None


def install_device_faults(plan: DeviceFaultPlan | None) -> None:
    """Install (or, with None, clear) the process-wide device-fault plan
    the scorer seams consult. Process-wide because the seams live inside
    dispatch helpers built long before any injector could wrap them."""
    global _DEVICE_PLAN
    _DEVICE_PLAN = plan


def device_faults() -> DeviceFaultPlan | None:
    return _DEVICE_PLAN


def device_seam(seam: str) -> None:
    """Fault hook the scorer seams call: ``dispatch`` before each device
    dispatch, ``put`` before each staging put. No-op (one None check) with
    no active plan. ``put_fail`` raises :class:`InjectedFault` so the
    caller's transport-error handling (breaker, ladder, telemetry failure
    count) engages exactly as for a real staging failure."""
    plan = _DEVICE_PLAN
    if plan is None or not plan.active:
        return
    if seam == "dispatch":
        s = plan.spec("device_hang")
        if s is not None:
            plan._count("device_hang")
            time.sleep(s.hang_ms / 1e3)
        s = plan.spec("compile_stall")
        if s is not None:
            plan._count("compile_stall")
            # a re-trace storm: the dispatch pays a compile it shouldn't,
            # and the compile-attribution plane must SEE it (that rate is
            # the signal the DeviceSupervisor quarantines on)
            from ccfd_tpu.observability.profile import (
                record_synthetic_compile,
            )

            record_synthetic_compile(s.stall_ms / 1e3)
            time.sleep(s.stall_ms / 1e3)
    elif seam == "put":
        s = plan.spec("put_fail")
        if s is not None and plan._rng.random() < s.rate:
            plan._count("put_fail")
            raise InjectedFault("staging put failed (injected put_fail)")


# ---------------------------------------------------------------------------
# Storage faults: the DISK as a fallible component.
#
# The device class above injects at the scorer seams; the storage class
# injects at the durable-state seam every persistent writer/reader now
# shares (runtime/durability.py atomic_write_bytes). The taxonomy is the
# classic storage failure set, each drillable on CPU CI:
#
# - ``torn_write``  — the process dies mid-write: a prefix lands in the
#   tmp file, the rename never happens (orphan tmp for the startup
#   sweep; the artifact keeps its previous bytes).
# - ``rename_lost`` — data written and fsynced but the rename's metadata
#   never commits (power cut before the journal): the caller believes
#   the write succeeded, the artifact silently keeps its OLD contents.
# - ``bitrot``      — latent media corruption after a successful write:
#   the landed file gets a flipped byte, which the checksummed read side
#   must quarantine and recover from (last-good generation).
# - ``enospc``      — the volume is full: the write raises ENOSPC.
# - ``fsync_fail``  — the sync fails (dying disk, thin-provisioned
#   volume): the write raises EIO before the rename.
# - ``slow_disk``   — degraded I/O: every write stalls ``ms``.
#
# Same activation surface as the other plans, so the ChaosMonkey storm-
# schedules storage degradation windows with the machinery that already
# drives edge and device storms (CCFD_STORAGE_FAULTS env / CR
# ``chaos.storage_faults``; tools/chaos_soak.py --storage-faults).
# ---------------------------------------------------------------------------

STORAGE_FAULT_KINDS = ("torn_write", "rename_lost", "bitrot", "enospc",
                       "fsync_fail", "slow_disk")


class StorageFaultSpec:
    """Parameters for one storage-fault kind.

    - ``rate`` probability the fault fires per write (default 1.0)
    - ``ms``   added latency for ``slow_disk`` (default 25)
    - ``frac`` fraction of the payload a ``torn_write`` lands (default
      0.5 — enough bytes that a frame header parses but the checksum
      cannot)
    """

    __slots__ = ("rate", "ms", "frac")

    def __init__(self, rate: float = 1.0, ms: float = 25.0,
                 frac: float = 0.5):
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"rate {rate} outside [0, 1]")
        if not 0.0 <= frac <= 1.0:
            raise ValueError(f"frac {frac} outside [0, 1]")
        if ms < 0:
            raise ValueError(f"ms must be >= 0, got {ms}")
        self.rate = float(rate)
        self.ms = float(ms)
        self.frac = float(frac)

    @staticmethod
    def parse(body: str) -> "StorageFaultSpec":
        """``"rate=0.5,ms=10,frac=0.3"`` -> StorageFaultSpec; empty body
        takes every default."""
        kw: dict[str, float] = {}
        for item in body.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, val = item.partition("=")
            key = key.strip()
            if not sep:
                raise ValueError(
                    f"storage-fault option {item!r}: expected key=value")
            if key not in ("rate", "ms", "frac"):
                raise ValueError(
                    f"unknown storage-fault option {key!r}; "
                    f"known: rate, ms, frac")
            kw[key] = float(val)
        return StorageFaultSpec(**kw)


class StorageFaultPlan:
    """Active storage-fault kinds + the FaultPlan activation interface,
    so storm schedulers drive disk degradation exactly like edge and
    device faults."""

    def __init__(self, kinds: Mapping[str, StorageFaultSpec] | None = None,
                 seed: int = 0, active: bool = True):
        for k in (kinds or {}):
            if k not in STORAGE_FAULT_KINDS:
                raise ValueError(
                    f"unknown storage fault {k!r}; known: "
                    f"{STORAGE_FAULT_KINDS}")
        self.kinds = dict(kinds or {})
        self._rng = random.Random(seed)
        self._mu = threading.Lock()
        self._active = threading.Event()
        if active:
            self._active.set()
        self.activations = 0
        self.injected: dict[str, int] = {}

    @staticmethod
    def from_string(text: str, seed: int = 0,
                    active: bool = True) -> "StorageFaultPlan":
        """``"bitrot;torn_write:rate=0.5"`` -> StorageFaultPlan (the
        CCFD_STORAGE_FAULTS syntax). Empty text means an empty plan."""
        kinds: dict[str, StorageFaultSpec] = {}
        for part in text.split(";"):
            part = part.strip()
            if not part:
                continue
            kind, _sep, body = part.partition(":")
            kinds[kind.strip()] = StorageFaultSpec.parse(body)
        return StorageFaultPlan(kinds, seed=seed, active=active)

    @property
    def active(self) -> bool:
        return self._active.is_set()

    def activate(self) -> None:
        self.activations += 1
        self._active.set()

    def deactivate(self) -> None:
        self._active.clear()

    def draw(self, kind: str) -> StorageFaultSpec | None:
        """The kind's spec when the plan is active AND its rate draw
        fires — one call per write per kind (runtime/durability.py)."""
        if not self._active.is_set():
            return None
        s = self.kinds.get(kind)
        if s is None:
            return None
        with self._mu:
            if self._rng.random() >= s.rate:
                return None
            self.injected[kind] = self.injected.get(kind, 0) + 1
        return s


_STORAGE_PLAN: StorageFaultPlan | None = None


def install_storage_faults(plan: StorageFaultPlan | None) -> None:
    """Install (or, with None, clear) the process-wide storage-fault plan
    the durability seam consults. Process-wide for the same reason the
    device plan is: the seam sits inside constructors and module-level
    helpers no injector proxy could wrap."""
    global _STORAGE_PLAN
    _STORAGE_PLAN = plan


def storage_faults() -> StorageFaultPlan | None:
    return _STORAGE_PLAN


def device_oom_overlay() -> float | None:
    """The injected allocator-pressure ratio, or None. Consulted by
    DeviceTelemetry.device_memory() so the OOM signal is drillable on
    backends that report no allocator stats (CPU CI)."""
    plan = _DEVICE_PLAN
    if plan is None:
        return None
    s = plan.spec("device_oom")
    if s is None:
        return None
    # one injection per activation window, not per read: device_memory()
    # runs on every scrape / heal tick, and a read-rate
    # artifact would make injected[] counts incomparable across kinds
    if plan._oom_counted_epoch != plan.activations:
        plan._oom_counted_epoch = plan.activations
        plan._count("device_oom")
    return s.oom_ratio
