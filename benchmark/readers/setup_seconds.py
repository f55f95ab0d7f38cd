"""Seconds from the start of the process to the first measured request."""


def read(obs: dict, args: dict):
    return obs["setup_s"]
