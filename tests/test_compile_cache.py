"""Persistent-compile-cache plumbing (ccfd_tpu/utils/compile_cache.py).

The cache itself is XLA's; what we own — and test — is where it lives and
the kill switch. The location is placed from outside when
JAX_COMPILATION_CACHE_DIR is set (jax reads it; the code sets nothing) and
is otherwise ONE fixed path inside the checkout: the directory is part of
what a hit depends on, so it must not vary with the host, the process or
the working directory. On the CPU backend that default stays off, and
CCFD_COMPILE_CACHE=0 is the off switch tier-1 relies on (XLA:CPU reloads
of donated multi-device executables are wrong).
"""

import os
import subprocess
import sys
from unittest import mock

import jax
import pytest

from ccfd_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def _restore_jax_cache_config():
    """enable() mutates process-global jax config; put it back so later
    tests in the session don't write cache artifacts anywhere."""
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_env_var_places_the_cache_and_code_sets_no_directory(
    tmp_path, _restore_jax_cache_config
):
    placed = str(tmp_path / "placed")
    updates = []
    real_update = jax.config.update
    with mock.patch.dict(os.environ, {"CCFD_COMPILE_CACHE": "",
                                      "JAX_COMPILATION_CACHE_DIR": placed}), \
         mock.patch.object(jax.config, "update",
                           side_effect=lambda k, v: (updates.append(k),
                                                     real_update(k, v))):
        assert compile_cache.enable() == placed
    assert "jax_compilation_cache_dir" not in updates
    assert not os.path.exists(placed)  # jax creates it, not this module


def test_unset_uses_one_fixed_path_inside_the_checkout(
    tmp_path, _restore_jax_cache_config
):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "CCFD_COMPILE_CACHE")}
    with mock.patch.dict(os.environ, env, clear=True):
        assert jax.default_backend() == "cpu"
        assert compile_cache.enable() is None  # XLA:CPU reload hazard
        with mock.patch("jax.default_backend", return_value="tpu"):
            target = compile_cache.enable()
    assert target == os.path.join(REPO, ".jax_cache") == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == target
    # small buckets compile in well under a second and must be cached
    assert jax.config.jax_persistent_cache_min_compile_time_secs <= 0.1
    # the same path from two more processes in two working directories
    env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    code = ("import jax; jax.default_backend = lambda: 'tpu'; "
            "from ccfd_tpu.utils.compile_cache import enable; "
            "print(enable())")
    seen = {
        subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120,
                       check=True).stdout.strip()
        for cwd in (str(tmp_path), REPO)
    }
    assert seen == {target}


def test_off_switch(tmp_path, _restore_jax_cache_config):
    for off in ("0", "off"):
        with mock.patch.dict(os.environ, {
                "CCFD_COMPILE_CACHE": off,
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}):
            assert compile_cache.enable() is None
        assert jax.config.jax_enable_compilation_cache is False
