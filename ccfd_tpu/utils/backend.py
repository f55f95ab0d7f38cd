"""The one rule for which JAX backend a command runs on.

The system is written for a TPU and its speed is only ever claimed there;
the CPU backend exists for tier-1 and the ``tools/*_smoke.py`` drills. So a
JAX command either was told to use the CPU, by ``JAX_PLATFORMS=cpu`` in the
environment, or it runs on a TPU — it never finds no chip and quietly
serves from the CPU. ``cli.main`` (for the JAX commands),
``chip_smoke.py`` and ``__graft_entry__`` all start with
:func:`require_backend`; nothing else chooses a platform, and JAX reads
``JAX_PLATFORMS`` itself.
"""

from __future__ import annotations

import functools
import os
import sys


@functools.cache
def require_backend() -> str:
    """Initialise JAX's default backend and return its platform name:
    ``"cpu"`` when the environment says ``JAX_PLATFORMS=cpu`` (announced
    on stderr), else ``"tpu"``. Raises when JAX initialised anything else
    — a process that meant to hold the chip must not carry on without it.
    Cached: the backend cannot change within a process, and the CPU
    notice is printed once."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("[ccfd_tpu] JAX_PLATFORMS=cpu: running on the CPU backend "
              "(kernels in interpret mode; no device number comes from "
              "this process)", file=sys.stderr)
        return jax.default_backend()
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"JAX initialised the {backend!r} backend but this command "
            "runs on a TPU: no chip was found, or another process holds "
            "it (one process per chip). Set JAX_PLATFORMS=cpu to run on "
            "the CPU on purpose.")
    return backend
