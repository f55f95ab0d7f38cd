"""Device & transfer telemetry: the measured side of the H2D/HBM story.

Every latency layer the SLO plane decomposes (observability/slo.py) is
measured EXCEPT the host↔device one: PR 9's BudgetLedger ships an explicit
``h2d`` placeholder ("not separately measurable until telemetry exists"),
and ROADMAP item 1's pinned-host staging work has no number to beat. This
module is that telemetry — backend-agnostic, so CPU CI runs exercise the
identical plumbing the TPU run reports from:

- **Per-device memory gauges** — ``ccfd_device_memory_bytes{device,kind}``
  from each device's allocator stats (``bytes_in_use`` /
  ``peak_bytes_in_use`` / ``bytes_limit`` where the backend reports them)
  plus a ``live_buffer_bytes`` kind computed from ``jax.live_arrays()``
  on every backend — the HBM-density denominator ROADMAP item 4 needs.
- **Measured H2D transfer accounting** — the Scorer's staging path
  (``serving/scorer.py _put_batch`` / the fused wire) times each
  host→device put and feeds :meth:`record_h2d`:
  ``ccfd_h2d_bytes_total`` + the ``ccfd_h2d_seconds`` histogram + a
  :class:`~ccfd_tpu.observability.profile.LatencyDigest` the BudgetLedger
  reads live — the ``h2d`` budget layer stops being a reservation the
  moment a telemetry-armed scorer serves traffic.
- **Executable inventory** — registered sources (the row Scorer's bucket
  ladder, the SeqScorer's (L, B) grid with per-executable dispatch
  counts) rendered into one document, next to the per-stage compile
  attribution the profiler's ``backend_compile`` hook collects
  (:func:`~ccfd_tpu.observability.profile.compile_stage`).

One instance per platform (operator ``device:`` block, ``CCFD_DEVICE=0``
kill switch). ``set_default``/``get_default`` exist for harnesses
that build scorers deep inside helpers; the operator always passes the
instance explicitly.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Mapping

from ccfd_tpu.observability.profile import LatencyDigest

# H2D puts are µs..ms scale; the default request-latency ladder starts at
# 5 ms and would fold every transfer into the first bucket
H2D_BUCKETS = (25e-6, 1e-4, 5e-4, 1e-3, 5e-3, 0.025, 0.1, 0.5, 2.5)

_DEFAULT: "DeviceTelemetry | None" = None


def set_default(telemetry: "DeviceTelemetry | None") -> None:
    """Install a process-default telemetry plane (harness hook;
    scorers built with ``telemetry=None`` pick it up). Pass None to
    clear."""
    global _DEFAULT
    _DEFAULT = telemetry


def get_default() -> "DeviceTelemetry | None":
    return _DEFAULT


class DeviceTelemetry:
    """Collects device memory, H2D transfer and executable-inventory
    evidence; see the module docstring. Thread-safe; a scorer staging a
    batch pays two ``perf_counter`` reads plus one counter increment."""

    def __init__(self, registry=None, sample_every: int = 8):
        self.registry = registry
        self._mu = threading.Lock()
        self._h2d_digest = LatencyDigest()
        self._h2d_bytes = 0
        # Transfer-time sampling: device_put is ASYNC on accelerator
        # backends (it returns after enqueueing, before bytes move), so a
        # truthful transfer time requires blocking on the put. Blocking
        # every put would cost the host its H2D/compute pipelining, so
        # only every Nth put per call site is synced+timed; the rest stay
        # async and count bytes only. 1 = time every put (tests, CPU
        # harnesses).
        self.sample_every = max(1, int(sample_every))
        self._put_seq = 0
        self._put_failures = 0
        self._sources: dict[str, Callable[[], Any]] = {}
        self._g_mem = self._c_bytes = self._h_seconds = None
        self._c_put_fail = None
        if registry is not None:
            self._g_mem = registry.gauge(
                "ccfd_device_memory_bytes",
                "per-device memory by kind: allocator bytes_in_use/"
                "peak_bytes_in_use/bytes_limit where the backend reports "
                "them, plus live_buffer_bytes summed from jax.live_arrays "
                "on every backend",
            )
            self._c_bytes = registry.counter(
                "ccfd_h2d_bytes_total",
                "bytes staged host->device on the scorer dispatch path "
                "(measured, not estimated; CPU runs count the same puts)",
            )
            self._h_seconds = registry.histogram(
                "ccfd_h2d_seconds",
                "wall time of one host->device staging put on the scorer "
                "dispatch path",
                buckets=H2D_BUCKETS,
            )
            self._c_put_fail = registry.counter(
                "ccfd_h2d_put_failures_total",
                "host->device staging puts that raised (real transfer "
                "failures and injected put_fail device faults alike) — "
                "one of the DeviceSupervisor's quarantine signals",
            )

    # -- H2D transfer accounting ------------------------------------------
    def record_h2d(self, nbytes: int, seconds: float | None = None) -> None:
        """One staging transfer: ``nbytes`` always counts; ``seconds``
        (when the caller could time the put — the row scorer's explicit
        staging) additionally lands in the histogram and the ledger's
        digest. Callers that only know bytes (the seq path's implicit
        transfer inside the jitted call) pass None."""
        with self._mu:
            self._h2d_bytes += int(nbytes)
            if seconds is not None:
                self._h2d_digest.add(float(seconds))
        if self._c_bytes is not None:
            self._c_bytes.inc(int(nbytes))
            if seconds is not None:
                self._h_seconds.observe(float(seconds))

    def h2d_bytes(self) -> int:
        with self._mu:
            return self._h2d_bytes

    def h2d_count(self) -> int:
        with self._mu:
            return self._h2d_digest.count

    def h2d_digest(self) -> LatencyDigest:
        """A consistent copy of the per-transfer digest — what the
        BudgetLedger's ``h2d`` layer reads when this plane is armed."""
        with self._mu:
            return self._h2d_digest.copy()

    def record_h2d_failure(self) -> None:
        """One failed staging put (the put raised before bytes landed)."""
        with self._mu:
            self._put_failures += 1
        if self._c_put_fail is not None:
            self._c_put_fail.inc()

    def h2d_failures(self) -> int:
        with self._mu:
            return self._put_failures

    # -- device memory ------------------------------------------------------
    @staticmethod
    def device_memory() -> dict[str, dict[str, int]]:
        """Per-device memory stats. Allocator stats where the backend
        reports them (TPU/GPU); ``live_buffer_bytes`` from the live-array
        walk everywhere (CPU included), so the gauge family always has
        series and the CPU CI run exercises the full path."""
        import jax

        out: dict[str, dict[str, int]] = {}
        try:
            devices = jax.local_devices()
        # ccfd-lint: disable=counted-drops -- nothing to drop: no jax backend means no devices to report; the empty dict IS the report
        except Exception:  # noqa: BLE001 - no backend at all
            return out
        for d in devices:
            entry: dict[str, int] = {}
            try:
                stats = d.memory_stats()
            # ccfd-lint: disable=counted-drops -- CPU backends have no allocator stats by design; absent keys read as absent on the board
            except Exception:  # noqa: BLE001 - cpu raises/returns None
                stats = None
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if stats and k in stats:
                    entry[k] = int(stats[k])
            out[f"{d.platform}:{d.id}"] = entry
        try:
            for arr in jax.live_arrays():
                devs = list(arr.devices())
                share = int(arr.nbytes) // max(1, len(devs))
                for d in devs:
                    label = f"{d.platform}:{d.id}"
                    entry = out.setdefault(label, {})
                    entry["live_buffer_bytes"] = (
                        entry.get("live_buffer_bytes", 0) + share)
        # ccfd-lint: disable=counted-drops -- best-effort live-buffer attribution; the allocator gauges above still carry the load-bearing series
        except Exception:  # noqa: BLE001 - telemetry must never raise
            pass
        for entry in out.values():
            entry.setdefault("live_buffer_bytes", 0)
        # injected allocator pressure (runtime/faults.py device_oom): CPU
        # backends report no allocator stats, so the OOM-pressure signal
        # the heal supervisor watches would be undrillable in CI without
        # this overlay — the synthetic bytes ride the same keys the TPU
        # allocator reports, so the watcher's math is identical
        from ccfd_tpu.runtime.faults import device_oom_overlay

        ratio = device_oom_overlay()
        if ratio is not None:
            limit = 16 * 1024**3  # a plausible HBM size; only the RATIO
            for entry in out.values():  # matters to the pressure signal
                entry.setdefault("bytes_limit", limit)
                entry["bytes_in_use"] = int(
                    ratio * entry.get("bytes_limit", limit))
        return out

    def peak_memory_bytes(self) -> int | None:
        """Max peak_bytes_in_use across devices; None when no backend
        reports allocator stats (CPU)."""
        peaks = [e["peak_bytes_in_use"]
                 for e in self.device_memory().values()
                 if "peak_bytes_in_use" in e]
        return max(peaks) if peaks else None

    def refresh(self, mem: Mapping[str, Mapping[str, int]] | None = None,
                ) -> None:
        """Refresh the memory gauges (the exporter scrape is the sampling
        clock, same contract as the RSS gauge). ``mem`` lets a caller that
        already paid the live-array walk (``snapshot``) reuse it."""
        if self._g_mem is None:
            return
        if mem is None:
            mem = self.device_memory()
        for device, kinds in mem.items():
            for kind, val in kinds.items():
                self._g_mem.set(float(val),
                                labels={"device": device, "kind": kind})

    # -- executable inventory -----------------------------------------------
    def register_executable_source(self, name: str,
                                   fn: Callable[[], Any]) -> None:
        """``fn()`` -> a JSON-safe description of a component's compiled
        executable set (the row scorer's bucket list, the seq (L, B)
        grid with dispatch counts)."""
        with self._mu:
            self._sources[name] = fn

    def executable_inventory(self) -> dict[str, Any]:
        with self._mu:
            sources = dict(self._sources)
        out: dict[str, Any] = {}
        for name, fn in sources.items():
            try:
                out[name] = fn()
            # ccfd-lint: disable=counted-drops -- the error string lands IN the snapshot: recorded evidence, not a swallow
            except Exception as e:  # noqa: BLE001 - a dead source is evidence
                out[name] = {"error": repr(e)[:120]}
        return out

    # -- export -------------------------------------------------------------
    def snapshot(self) -> dict[str, Any]:
        """The device section of /debug, incident bundles and flight-
        recorder snapshots. Pays the live-array walk once (gauges refresh
        from the same read)."""
        mem = self.device_memory()
        self.refresh(mem)
        with self._mu:
            h2d = {
                "bytes_total": self._h2d_bytes,
                "transfer": self._h2d_digest.to_dict(),
            }
        return {
            "memory": mem,
            "h2d": h2d,
            "executables": self.executable_inventory(),
        }


def timed_put(telemetry: "DeviceTelemetry | None", nbytes: int, put_fn):
    """Run one staging put, feeding its bytes (always) and wall time
    (every ``telemetry.sample_every``-th put) to ``telemetry`` — the
    single helper every staging call site shares, so the disabled path
    costs one ``is None`` check.

    Timed samples BLOCK until the array is committed on device:
    device_put is asynchronous on accelerator backends, and timing the
    enqueue alone would report microseconds for a millisecond transfer.
    Unsampled puts stay fully async, so the host keeps its H2D/compute
    pipelining on the other N-1 of every N puts."""
    if telemetry is None:
        return put_fn()
    with telemetry._mu:
        telemetry._put_seq += 1
        timed = telemetry._put_seq % telemetry.sample_every == 0
    if not timed:
        try:
            out = put_fn()
        except Exception:
            telemetry.record_h2d_failure()
            raise
        # bytes count only after the put lands (matching the timed
        # branch): a failed put must not inflate ccfd_h2d_bytes_total
        telemetry.record_h2d(nbytes)
        return out
    import time

    import jax

    t0 = time.perf_counter()
    try:
        out = put_fn()
        jax.block_until_ready(out)
    except Exception:
        telemetry.record_h2d_failure()
        raise
    telemetry.record_h2d(nbytes, time.perf_counter() - t0)
    return out
