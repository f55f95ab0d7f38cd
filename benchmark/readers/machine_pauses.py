"""Milliseconds of the window in which the whole machine stood still, as
the harness's witness process saw it (``harness/heartbeat.py``): the sum of
its lost steps. A tail that moves with this number is the machine's."""


def read(obs: dict, args: dict):
    return sum(seconds for _, seconds in obs["pauses"]) * 1e3
