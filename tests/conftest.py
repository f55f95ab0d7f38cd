"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors the CI strategy in SURVEY.md §4: multi-chip sharding logic is
exercised on `--xla_force_host_platform_device_count=8` CPU devices; real-TPU
runs happen through benchmark/run.py and chip_smoke.py, not in unit tests.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may preset tpu

# The persistent XLA compile cache is process-global state with a known
# wrong-results RELOAD on XLA:CPU (utils/compile_cache.py): any test that
# drives the CLI's jax commands would switch it on for every later jit in
# the process, and a cache entry written by a previous run then reloads
# the 8-device donated train step as a garbage executable — the historical
# order-dependent test_partition flake. Force it off so tier-1 numerics
# are order-independent (also where the environment places a cache with
# JAX_COMPILATION_CACHE_DIR); test_compile_cache opts back in explicitly.
os.environ.setdefault("CCFD_COMPILE_CACHE", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# CCFD_LOCKCHECK=1 arms the runtime lock-order sanitizer BEFORE anything
# constructs a lock: every threading.Lock/RLock created by ccfd_tpu code
# from here on records its acquisition order, and an inversion raises
# LockOrderError at the acquire that closes the cycle (analysis/
# lockcheck.py — the dynamic half of the lock-order lint rule). The
# import is deliberately pre-jax and jax-free.
_LOCKCHECK_GRAPH = None
if os.environ.get("CCFD_LOCKCHECK"):
    from ccfd_tpu.analysis import lockcheck as _lockcheck

    _LOCKCHECK_GRAPH = _lockcheck.install()

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def dataset():
    from ccfd_tpu.data.ccfd import synthetic_dataset

    return synthetic_dataset(n=4000, fraud_rate=0.05, seed=0)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def as_on_the_chip(monkeypatch):
    """A ``pallas_call`` made under it goes to Mosaic, as on the TPU, and
    not to the interpreter this backend would give it: for a test that
    compiles a kernel for a chip that is described and not attached. Call
    the kernel's entry unjitted (``__wrapped__``) under it: a jit's trace
    cache does not know the answer changed."""
    from ccfd_tpu.ops import kernels

    monkeypatch.setattr(kernels, "interpreted", lambda: False)


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_gate():
    """With CCFD_LOCKCHECK=1, fail the session if any lock-order
    inversion was recorded — including ones swallowed by worker threads
    whose LockOrderError never reached a test."""
    yield
    if _LOCKCHECK_GRAPH is not None:
        v = _LOCKCHECK_GRAPH.violations
        assert not v, (
            f"lock-order inversions recorded during the run: "
            f"{[x['cycle'] for x in v]}"
        )
