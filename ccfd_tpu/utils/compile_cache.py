"""Persistent XLA compilation cache for every jax entry point.

A cold start compiles the scorer's whole bucket ladder (and every other
executable a command touches); JAX's persistent compilation cache keeps
the compiled executables on disk keyed by HLO + compile options + platform,
so only the FIRST process pays. Compile time is set-up time, reported
apart from any steady-state number.

Where the cache lives is decided outside the program when it can be:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; ``enable()``
  sets no directory in code and returns that value.
- unset: one fixed path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored). The directory is part of the cache key's context, so it
  carries no host fingerprint, pid, time or temp name — a directory that
  moves never hits. On the CPU backend (``JAX_PLATFORMS=cpu``) this
  default stays OFF: XLA:CPU's RELOAD of a persisted executable is not
  trustworthy — a donated multi-device executable written by a previous
  process can reload as one that computes garbage (observed with the
  8-virtual-device sharded train step) — and CPU compiles cost seconds.
- ``CCFD_COMPILE_CACHE=0`` (or ``off``): disabled wherever it would have
  lived. tier-1 sets this (tests/conftest.py) for the same reload hazard.

``enable()`` is called by the CLI for jax-using commands and by
chip_smoke.py.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable() -> str | None:
    """Turn on jax's persistent compilation cache; returns the directory
    in use, or None when switched off."""
    import jax

    if os.environ.get("CCFD_COMPILE_CACHE", "").strip().lower() in (
            "0", "off", "false", "no"):
        # off means off even where JAX_COMPILATION_CACHE_DIR is set
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    target = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not target and jax.default_backend() == "cpu":
        return None  # the XLA:CPU reload hazard; placing a directory opts in
    # cache even quick compiles: the scorer's small buckets compile fast
    # but are recompiled by every process that starts
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    if target:
        return target  # placed from outside: jax reads the variable itself
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
