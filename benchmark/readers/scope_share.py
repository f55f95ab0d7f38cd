"""Share of the device's busy time spent under some of the program's named
scopes, in %: the summed time of the operations under ``scopes`` over the
summed time of every operation, both inside the programs the capture holds
whole (``reduce/scopes.py``). None where no operation carries a scope."""

from benchmark.reduce import scopes


def read(obs: dict, args: dict):
    cap = scopes.of(obs)
    if cap is None or cap.busy_s <= 0:
        return None
    under = cap.seconds_under(args["scopes"])
    if under <= 0:
        return None
    return 100.0 * under / cap.busy_s
