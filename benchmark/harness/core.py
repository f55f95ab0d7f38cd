"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line.

``run_cell`` is everything ``run.py`` does after it has found the chip, so
that a test can drive it on the CPU with the timed path broken underneath
and see ``correct`` come out false. The deployment (the system under
test, built through the program's own entry points) and the generator
(the traffic) are the modules that the cell's configuration and traffic
files name; nothing here knows a cell, a configuration or a mix by name.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, NoReturn

import numpy as np

from benchmark.harness import heartbeat
from benchmark.harness import manifest as manifest_mod

MISS_MS = 1e12  # what a percentile made of misses is printed as


@dataclasses.dataclass
class Outcome:
    """What a generator saw in one window."""

    t0: float  # window start, on time.perf_counter
    seconds: float
    # due -> verdict per request or record due in the window; inf = a miss.
    # Empty where the cell's traffic has no due instants (saturated bus).
    latency_ms: np.ndarray
    attempted: int
    failed: int
    rows_in_window: int  # rows whose verdict arrived inside the window
    served_rows: np.ndarray  # table row of every served verdict compared
    served_proba: np.ndarray  # its served fraud probability
    late_ms: np.ndarray  # how late the generator sent or produced
    statuses: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)
    # where verdicts arrive in few large batches: the seconds from the first
    # batch's stamp inside the window to the last one's, and the rows of the
    # batches after the first up to the last (a count of whole batches over
    # a fixed window moves in steps of one batch)
    rate_span: tuple[float, int] | None = None
    # where a verdict depends on what was served before it (a keyed
    # history): every served verdict in the order it was consumed, as
    # arrays ``customer``, ``row`` (of the table) and ``proba``
    stream: dict | None = None


def check_line(name: str, value: Any, op: str, limit: Any, ok: bool) -> str:
    return (f"CHECK {name}: {value!r} {op} {limit!r}"
            f" -> {'ok' if ok else 'FAIL'}")


class Checks:
    """Every number compared, printed beside its limit as it is compared."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, float, str, float, bool]] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        self._add(name, value, "<=", limit,
                  bool(np.isfinite(value)) and value <= limit)

    def exactly(self, name: str, value: Any, want: Any) -> None:
        self._add(name, value, "==", want, value == want)

    def at_least(self, name: str, value: float, limit: float) -> None:
        self._add(name, value, ">=", limit,
                  bool(np.isfinite(value)) and value >= limit)

    def _add(self, name, value, op, limit, ok) -> None:
        self.rows.append((name, value, op, limit, ok))
        print(check_line(name, value, op, limit, ok), flush=True)

    @property
    def ok(self) -> bool:
        return all(r[4] for r in self.rows)

    def compared(self) -> dict:
        """``{name: [value, op, limit, ok]}`` of every number compared,
        for the result line."""
        def plain(v):
            return v.item() if isinstance(v, np.generic) else v

        return {name: [plain(value), op, plain(limit), ok]
                for name, value, op, limit, ok in self.rows}


def percentile(sample: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of a sample that may hold ``inf`` misses:
    no interpolation, so a miss is never averaged into a finite number."""
    if len(sample) == 0:
        return float("nan")
    s = np.sort(np.asarray(sample, np.float64))
    return float(s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))])


def printable(value: float) -> float:
    return MISS_MS if not np.isfinite(value) else float(value)


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring`` (a listener
    cannot be taken off again, so one is kept for the process)."""

    _instance: "CompileCounter | None" = None

    def __init__(self) -> None:
        self.count = 0
        self.cache_hits = 0
        self.cache_misses = 0

    @classmethod
    def armed(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax.monitoring as monitoring

            me = cls._instance = cls()

            def on_duration(event: str, _secs: float, **_kw) -> None:
                if event.endswith("backend_compile_duration"):
                    me.count += 1

            def on_event(event: str, **_kw) -> None:
                if event.endswith("/compilation_cache/cache_hits"):
                    me.cache_hits += 1
                elif event.endswith("/compilation_cache/cache_misses"):
                    me.cache_misses += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
        return cls._instance


class GcLog:
    """Every pass of the process's garbage collector, ``(start, generation,
    seconds)``: the service raises the youngest generation's threshold
    (``utils/gctune.py``), so passes are rare and the oldest generation's
    is long; a stall in the tail is laid beside them."""

    def __init__(self) -> None:
        self.passes: list[tuple[float, int, float]] = []
        self._began = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._began = now
        else:
            self.passes.append((self._began, int(info["generation"]),
                                now - self._began))

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def report(self, t0: float, t1: float) -> str:
        parts = []
        for gen in (0, 1, 2):
            inside = [(s, t) for t, g, s in self.passes
                      if g == gen and t0 <= t < t1]
            if inside:
                longest, at = max(inside)
                parts.append(f"gen{gen} n {len(inside)} longest_ms "
                             f"{longest * 1e3:.2f} at_s {at - t0:.2f}")
        return "; ".join(parts) or "none"


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            # live arrays, and what the runtime set aside for the compiled
            # programs' temporaries: the TPU runtime counts those under
            # "reserved", apart from "in use" (PERF.md has the probe)
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the place the environment
    names, else at a fixed path inside the checkout (the path is part of
    the cache key). The program's own rule (``utils/compile_cache``) is
    the same one; the benchmark sets it itself so that it holds whatever
    a later PR does to that module, and on any backend."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    target = placed or os.path.join(root, ".jax_cache")
    os.makedirs(target, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", target)
    return target


def check_outputs(checks: Checks, config: dict, outcome: Outcome, *,
                  seed: int, root: str) -> None:
    """The served probabilities against the configuration's plain
    reference on the same seeded rows, each number beside the limit the
    configuration's file gives it. The reference module says which served
    verdicts it holds against what (``served_and_expected``), by which
    numbers (``compare``) and, where it has them, which misplaced answers
    those numbers are shown to catch (``miss_controls``)."""
    ref_doc = config["reference"]
    ref = manifest_mod.load_kind("reference", ref_doc["module"])
    t = time.perf_counter()
    served, expect, note = ref.served_and_expected(
        config, outcome, seed=seed, root=root)
    numbers = ref.compare(served, expect)
    checks.at_least("rows_compared", len(served),
                    int(ref_doc["min_rows_compared"]))
    limits = ref_doc["limits"]
    for name, limit in limits.items():
        checks.at_most(name, numbers[name], float(limit))
    # what decides nothing: the numbers ``compare`` gives that this
    # configuration sets no limit on, and every number again with the
    # answers misplaced (``miss_controls``, where the reference has them),
    # which puts the room above each limit on record beside the room below
    for name, value in numbers.items():
        if name not in limits:
            print(f"INFO compared {name}: {value!r}", flush=True)
    miss_controls = getattr(ref, "miss_controls", None)
    if miss_controls is not None:
        for label, (s, e) in miss_controls(served, expect).items():
            print(f"INFO miss_control {label}: " + ", ".join(
                f"{k} {v!r}" for k, v in ref.compare(s, e).items()),
                flush=True)
    print(f"INFO reference {ref_doc['module']}: {note}, "
          f"{time.perf_counter() - t:.2f}s", flush=True)


def read_metrics(cell: manifest_mod.Cell, metrics: list, obs: dict) -> dict:
    """Each metric through the reader its file names. A reader that finds
    nothing to read returns None: the metric is then left out where the
    manifest leaves its cells open, and is an error where the manifest
    lists this cell for it (a result line without it would be refused)."""
    out = {}
    for m in metrics:
        doc = cell.metric_docs[m.name]
        reader = manifest_mod.load_kind("readers", doc["reader"])
        value = reader.read(obs, doc.get("args", {}))
        if value is None:
            if m.workloads is None:
                continue
            raise RuntimeError(
                f"metric {m.name}: reader {doc['reader']} found nothing "
                f"to read in cell {cell.name}")
        out[m.name] = {"value": printable(float(value)), "unit": m.unit}
    return out


def run_cell(cell: manifest_mod.Cell, *, seed: int, seconds: float,
             trace: bool, t_start: float, root: str, control: bool = False,
             marks: list[tuple[str, float]] | None = None,
             sabotage: Callable[[Any], None] | None = None) -> dict:
    """Run ``cell`` once and return the result line as a dict.

    ``marks`` are the instants ``run.py`` passed on its way here, for the
    set-up's split by phase.
    ``control`` serves the configuration's lower-precision control in the
    flagship's place (the run that must come out not correct).
    ``sabotage(deployment)`` lets a test break the timed path after it is
    built and before it is warmed.
    """
    deployment_mod = manifest_mod.load_kind("deployments",
                                            cell.deployment_kind)
    generator_mod = manifest_mod.load_kind("generators", cell.generator_kind)
    workdir = os.path.join(root, ".benchwork", f"run_{os.getpid()}")
    os.makedirs(workdir, exist_ok=True)
    compiles = CompileCounter.armed()
    gc_log = GcLog()
    dep = deployment_mod.Deployment(cell.config, root=root, control=control,
                                    traced=trace, seed=seed)
    gen = None
    observer = None
    witness = None
    phases = [("start", t_start), *(marks or [])]

    def mark(name: str) -> None:
        phases.append((name, time.perf_counter()))

    try:
        mark("harness")
        handles = dep.start()
        mark("deployment")
        if sabotage is not None:
            sabotage(dep)
        gen = generator_mod.Generator(
            cell.traffic, seed=seed, root=root, workdir=workdir,
            handles=handles, table_rows=int(cell.config["table_rows"]))
        gen.prepare()
        mark("generator")
        if trace:
            from benchmark.harness import tracing

            observer = tracing.Observer(dep, workdir, cell.config)
        witness = heartbeat.Heartbeat()
        gen.warm()
        before = dep.counters()
        compiles_before = compiles.count
        if observer is not None:
            observer.arm(seconds)
        mark("warm")
        setup_s = time.perf_counter() - t_start
        outcome = gen.run(seconds)
        pauses = heartbeat.pauses_in(witness.stop(), outcome.t0,
                                     outcome.t0 + outcome.seconds)
        if observer is not None:
            observer.disarm()
        after = dep.counters()
        compiles_in_window = compiles.count - compiles_before
    finally:
        if gen is not None:
            gen.close()
        dep.stop()
        gc_log.close()
        if witness is not None:
            witness.kill()
    device = device_info()  # the program's peak, before the reference runs

    checks = Checks()
    checks.exactly("compiles_in_window", compiles_in_window, 0)
    checks.at_least("attempted", outcome.attempted, 1)
    dep.check_guarantees(checks, before, after, outcome)
    check_outputs(checks, cell.config, outcome, seed=seed, root=root)

    print("INFO setup_s by phase: " + ", ".join(
        f"{name} {t - t_prev:.2f}" for (_, t_prev), (name, t)
        in zip(phases, phases[1:])), flush=True)
    late = outcome.late_ms[np.isfinite(outcome.late_ms)]
    print(f"INFO window {outcome.seconds}s attempted {outcome.attempted} "
          f"failed {outcome.failed} rows_in_window {outcome.rows_in_window} "
          f"statuses {outcome.statuses} generator_late_ms p50 "
          f"{percentile(late, 50):.4f} p99 {percentile(late, 99):.4f} max "
          f"{late.max() if len(late) else float('nan'):.4f} "
          f"compile_cache hits {compiles.cache_hits} misses "
          f"{compiles.cache_misses}", flush=True)
    if len(outcome.latency_ms):
        print("INFO latency_ms " + " ".join(
            f"p{q:g} {printable(percentile(outcome.latency_ms, q)):.4f}"
            for q in (50, 90, 95, 99, 99.9, 100)), flush=True)
    print("INFO gc_in_window " + gc_log.report(
        outcome.t0, outcome.t0 + outcome.seconds), flush=True)
    print(f"INFO machine_pauses_in_window n {len(pauses)} total_ms "
          f"{sum(p for _, p in pauses) * 1e3:.1f} (at_s, ms) "
          + " ".join(f"({t - outcome.t0:.2f}, {p * 1e3:.0f})"
                     for t, p in pauses), flush=True)
    for k, v in sorted(outcome.extra.items()):
        print(f"INFO {k} {v}", flush=True)

    result: dict = {"correct": checks.ok, "attempted": int(outcome.attempted),
                    "failed": int(outcome.failed), "metrics": {},
                    "device": device}
    obs = {"outcome": outcome, "before": before, "after": after,
           "setup_s": setup_s, "config": cell.config, "pauses": pauses}
    metrics = cell.end_to_end
    if trace:
        obs.update(observer.observations(outcome))
        device["busy_s"] = obs["trace"].busy_s
        device["window_s"] = obs["trace"].window_s
        result["breakdown"] = obs["trace"].breakdown()
        metrics = cell.per_layer
    result["metrics"] = read_metrics(cell, metrics, obs)
    result["compared"] = checks.compared()  # last in the line
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def fail(message: str, code: int) -> NoReturn:
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    sys.exit(code)
