"""The traced run's instruments, all outside the program: a delegating
wrapper around the ``score`` callable (a host-clock span per call, and a
``TraceAnnotation`` so that the profiler's trace can tell the idle gaps
inside a dispatch from those between dispatches), and a device trace of a
steady slice of the window. End-to-end metrics are taken with all of this
off (``--trace 0``).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark.reduce import trace as trace_mod

SPAN_NAME = "bench.score"
SLICE_S = 2.5  # traces are large and tracing slows the host


class ScoreSpans:
    """``(start, seconds, rows)`` of every ``score`` call, host clock."""

    def __init__(self) -> None:
        self.records: list[tuple[float, float, int]] = []

    def wrap(self, inner):
        import jax

        def score(*args):  # (x) or (records, x): the rows come last
            rows = len(args[-1])
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_NAME, rows=rows):
                out = inner(*args)  # returns host memory: synchronised
            self.records.append((t, time.perf_counter() - t, rows))
            return out

        return score

    def within(self, t0: float, t1: float) -> np.ndarray:
        rec = np.asarray(self.records, np.float64).reshape(-1, 3)
        return rec[(rec[:, 0] >= t0) & (rec[:, 0] < t1)]


class Observer:
    def __init__(self, dep, workdir: str, config: dict):
        self.dep = dep
        self.config = config
        self.logdir = os.path.join(workdir, "trace")
        self.spans = ScoreSpans()
        dep.wrap_score(self.spans.wrap)
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def arm(self, seconds: float) -> None:
        """Trace ``SLICE_S`` seconds (half the window if it is short)
        from a quarter of the way into the window."""
        import jax

        length = min(SLICE_S, seconds / 2.0)
        start_at = time.perf_counter() + 0.25 + seconds / 4.0

        def body() -> None:
            try:
                time.sleep(max(0.0, start_at - time.perf_counter()))
                # host spans yes, the Python function tracer no: it slows
                # the interpreter several times over and the served path
                # with it
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(self.logdir,
                                         profiler_options=options)
                try:
                    time.sleep(length)
                finally:
                    jax.profiler.stop_trace()
            except BaseException as e:  # noqa: BLE001 - raised in disarm()
                self._error = e

        self._thread = threading.Thread(target=body, daemon=True,
                                        name="bench-trace")
        self._thread.start()

    def disarm(self) -> None:
        self._thread.join(timeout=300.0)
        if self._thread.is_alive() or self._error is not None:
            raise RuntimeError(f"the device trace failed: {self._error!r}")

    def observations(self, outcome) -> dict:
        """What only the traced run has, for the readers."""
        t = self.config["trace"]
        planes = trace_mod.load(self.logdir, SPAN_NAME)
        summary = trace_mod.reduce(
            planes, op_line=t["op_line"],
            kernel_patterns=t["kernel_patterns"], span_name=SPAN_NAME)
        return {
            "trace": summary,
            "spans": self.spans.within(outcome.t0,
                                       outcome.t0 + outcome.seconds),
            "profiler": getattr(self.dep, "profiler", None),
        }
