"""GC tuning for the service hot loops.

jax registers a gc callback that runs XLA's own garbage collection on EVERY
Python gc pass (jax/_src/lib/__init__.py, jax issue #14882). The router
decodes tens of thousands of records per second into short-lived Python
objects, so the default gen-0 threshold (700 allocations) fires collections
hundreds of times per second — and each one pays the XLA callback plus a
scan of every tracked object. Profiled on a 1-core CPU host this was
one of the largest single consumers in the pipeline loop (a count:
~2,200 collections in a 6 s window).

``tune_for_service()`` raises the gen-0 threshold so collections amortize
over far more allocations (the hot loops' churn is flat per batch — no
cycles accumulate between polls; long-lived state is ``gc.freeze()``-d out
of scanning entirely). Cycles still collect, just ~100x less often.

Env: CCFD_GC_THRESHOLD overrides the gen-0 threshold (0 = leave Python's
defaults untouched).
"""
from __future__ import annotations

import contextlib
import gc
import os

from ccfd_tpu.observability import trace


def tune_for_service(gen0: int | None = None) -> bool:
    """Apply service GC tuning; returns True when applied."""
    env = os.environ.get("CCFD_GC_THRESHOLD", "").strip()
    if env:
        try:
            gen0 = int(env)
        except ValueError:
            gen0 = None  # malformed: fall through to the default
    if gen0 is None:
        gen0 = 100_000
    if gen0 <= 0:
        return False
    # a ``startup.gc`` phase where the process's start-up trace is open
    # (one that built a scorer); the JAX-free services call this too, and
    # a phase would import JAX there
    record = trace.startup
    with (record.phase("startup.gc") if record.root is not None
          else contextlib.nullcontext()):
        # collect once so freeze() moves a clean startup set to the
        # permanent generation (imports, compiled-executable wrappers,
        # registries)
        gc.collect()
        gc.freeze()
        _, g1, g2 = gc.get_threshold()
        gc.set_threshold(gen0, g1, g2)
    return True
