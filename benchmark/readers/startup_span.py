"""Set-up from the inside: the program's own start-up trace
(``ccfd_tpu/observability/trace.py::startup``), read after the window, in
seconds of this run's ``setup_s`` (``obs["setup_s"]``, which the benchmark
takes itself from outside and stays the yardstick).

The record is a root span ``startup`` from the process's start and, under
it, one closed span a ``startup.*`` phase, each with its ``perf_counter``
start (``t0``, the clock of ``run.py``'s ``T_START``), its duration and its
stats; ``ready_at`` (the first router started) and ``first_verdict_at``
(its first routed batch left). Only spans that began inside set-up count:
what a window opens later (an inventory first asked for there) is not
set-up's. ``{"part": ...}``:

- ``head``: ``startup.head``, the process's start to the program's first
  phase: interpreter, imports, the TPU runtime, the benchmark's draw of the
  weights: what the program does not own;
- ``weights``: ``startup.weights``;
- ``trace_lower``: ``trace_s`` + ``lower_s`` summed over
  ``startup.executable`` and ``startup.inventory`` (JAX's
  ``jaxpr_trace_duration``, outermost jits only, and
  ``jaxpr_to_mlir_module_duration``): Python work no cache saves;
- ``compile``: their ``compile_s`` (``backend_compile_duration`` without a
  cache hit; 0 on a warm start); ``cache_load``: their ``cache_load_s``
  (the same event after a hit: key, read, deserialisation);
- ``first_run``: each ``startup.executable`` less its four parts: the first
  run on zeros and its wait;
- ``store``: ``startup.store`` + ``startup.restore``;
- ``to_first_verdict``: ``ready_at`` to ``first_verdict_at``;
- ``covered_pct``: the union of those stretches (``startup.head``, the
  ``weights`` / ``executable`` / ``inventory`` / ``store`` / ``restore``
  spans and ready-to-first-verdict), clipped to set-up, over ``setup_s``.

Once a run it prints ``INFO startup``: the parts in seconds and what lies
between the first verdict and the window (the benchmark's warm-up traffic:
the served path, no start-up phase), the longest stretches no span owns by
the span that follows them, then a line a span (when it began, its length,
its stats; of an executable or inventory JAX's parts, ``traces`` /
``retraced``, ``cache_hit``).

A program without the record (an older commit under this benchmark) reads
``args["absent"]`` where the metric's file gives one, else None. The nine
files give 0.0, the seconds such a program's spans account for: they list
their cells, and ``harness/core.py::read_metrics`` raises on a None in a
listed cell."""

import sys

PARTS = ("head", "weights", "trace_lower", "compile", "cache_load",
         "first_run", "store", "to_first_verdict")
BILLED = ("startup.executable", "startup.inventory")
SPANNED = ("startup.head", "startup.weights", "startup.store",
           "startup.restore", *BILLED)
JAX_PARTS = ("trace_s", "lower_s", "compile_s", "cache_load_s")

_reported = None  # the record INFO startup was printed for


def record():
    """The program's start-up record, or None where it has none or never
    opened it."""
    try:
        from ccfd_tpu.observability import trace
    except ImportError:
        return None
    rec = getattr(trace, "startup", None)
    return rec if rec is not None and rec.root is not None else None


def window(obs: dict, rec) -> tuple[float, float]:
    """Set-up on ``perf_counter``: from ``run.py``'s ``T_START`` (the
    record's root, a tick or two earlier, where another entry point runs
    the harness) for ``setup_s``."""
    begin = getattr(sys.modules.get("__main__"), "T_START", None)
    if begin is None:
        begin = rec.root.t0
    return begin, begin + obs["setup_s"]


def _billed_s(span, keys) -> float:
    return sum(float(span.attrs.get(k, 0.0)) for k in keys)


def parts(rec, end: float) -> dict:
    """The eight parts in seconds over the spans that began before
    ``end``."""
    spans = [s for s in rec.spans() if s.t0 < end]
    held = {name: sum(s.duration_s for s in spans if s.name == name)
            for name in SPANNED}
    billed = [s for s in spans if s.name in BILLED]
    verdict = 0.0
    if rec.ready_at is not None and rec.first_verdict_at is not None \
            and rec.ready_at < end:
        verdict = max(0.0, rec.first_verdict_at - rec.ready_at)
    return {
        "head": held["startup.head"],
        "weights": held["startup.weights"],
        "trace_lower": sum(_billed_s(s, ("trace_s", "lower_s"))
                           for s in billed),
        "compile": sum(_billed_s(s, ("compile_s",)) for s in billed),
        "cache_load": sum(_billed_s(s, ("cache_load_s",)) for s in billed),
        "first_run": sum(max(0.0, s.duration_s - _billed_s(s, JAX_PARTS))
                         for s in billed if s.name == "startup.executable"),
        "store": held["startup.store"] + held["startup.restore"],
        "to_first_verdict": verdict,
    }


def covered_s(rec, begin: float, end: float) -> float:
    """Seconds of [begin, end] under the union of the named stretches."""
    cuts = [(s.t0, s.t0 + s.duration_s) for s in rec.spans()
            if s.name in SPANNED]
    if rec.ready_at is not None and rec.first_verdict_at is not None:
        cuts.append((rec.ready_at, rec.first_verdict_at))
    total, upto = 0.0, begin
    for a, b in sorted(cuts):
        a, b = max(a, upto), min(b, end)
        if b > a:
            total += b - a
            upto = b
    return total


def _report(rec, obs: dict, begin: float, end: float) -> None:
    got = parts(rec, end)
    print("INFO startup s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in got.items())
        + f"; covered {covered_s(rec, begin, end):.3f} of setup_s "
        f"{obs['setup_s']:.3f}; first verdict to the window "
        f"{end - (rec.first_verdict_at or end):.3f} (warm-up traffic "
        f"through the served path); spans {len(rec.spans())}", flush=True)
    gaps, upto = [], begin
    for s in sorted(rec.spans(), key=lambda s: s.t0):
        if s.name != "startup" and s.t0 < end:
            gaps.append((s.t0 - upto, s.name))
            upto = max(upto, s.t0 + s.duration_s)
    print("INFO startup unowned before: " + ", ".join(
        f"{name} {gap:.3f}" for gap, name in sorted(gaps, reverse=True)[:6]
        if gap > 0.0), flush=True)
    for s in rec.spans():
        if s.name not in BILLED and s.t0 < end:
            print(f"INFO {s.name} at_s {s.t0 - begin:.3f} span_s "
                  f"{s.duration_s:.3f} " + " ".join(
                      f"{k} {v}" for k, v in s.attrs.items()), flush=True)
    for s in rec.spans():
        if s.name in BILLED and s.t0 < end:
            a = s.attrs
            print(f"INFO {s.name} L {a.get('l_bucket')} B "
                  f"{a.get('b_bucket')} at_s {s.t0 - begin:.3f} span_s "
                  f"{s.duration_s:.3f} " + " ".join(
                      f"{k} {float(a.get(k, 0.0)):.3f}" for k in JAX_PARTS)
                  + f" cache_read_s {float(a.get('cache_read_s', 0.0)):.3f}"
                  f" cache_hit {a.get('cache_hit')} compiles "
                  f"{a.get('compiles')} traces {a.get('traces')} retraced "
                  f"{a.get('retraced')}", flush=True)


def read(obs: dict, args: dict):
    global _reported
    rec = record()
    if rec is None:
        return args.get("absent")
    begin, end = window(obs, rec)
    if _reported is not rec:
        _reported = rec
        _report(rec, obs, begin, end)
    if args["part"] == "covered_pct":
        return 100.0 * covered_s(rec, begin, end) / obs["setup_s"]
    return parts(rec, end)[args["part"]]
