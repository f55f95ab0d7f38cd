"""The one backend rule (ccfd_tpu/utils/backend.py): ``JAX_PLATFORMS=cpu``
means the CPU, said once on stderr; anything else must come up on a TPU,
and a process that found no chip stops instead of serving from the CPU."""

import pytest

from ccfd_tpu.utils.backend import require_backend


@pytest.fixture(autouse=True)
def _uncached():
    require_backend.cache_clear()
    yield
    require_backend.cache_clear()


def test_cpu_request_is_accepted_and_announced_once(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_backend() == "cpu"
    assert require_backend() == "cpu"
    assert capsys.readouterr().err.count("JAX_PLATFORMS=cpu") == 1


@pytest.mark.parametrize("env", [None, "", "tpu", "tpu,cpu"])
def test_cpu_backend_without_the_request_raises(monkeypatch, env):
    """The backend here IS the cpu (conftest); without JAX_PLATFORMS=cpu in
    the environment that is jax quietly falling back, and the rule
    refuses it."""
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    with pytest.raises(RuntimeError, match="runs on a TPU"):
        require_backend()


def test_tpu_backend_is_accepted(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    assert require_backend() == "tpu"


def test_cli_applies_the_rule_to_jax_commands_only():
    from ccfd_tpu.cli import _is_jax_command

    for argv in (["serve"], ["demo"], ["train"], ["fleet", "member", "--spec",
                 "x"], ["replay", "--live"]):
        assert _is_jax_command(argv), argv
    # the fleet supervisor spawns the members that need the chip; it and
    # the jax-free services must not initialise a backend themselves
    for argv in (["fleet", "up"], ["fleet", "status", "--peers", "x"],
                 ["replay"], ["doctor"], ["bus"], ["lint"], []):
        assert not _is_jax_command(argv), argv
