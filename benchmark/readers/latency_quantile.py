"""A nearest-rank quantile of the window's due -> verdict latencies, over
every request or record that was due in the window. A miss (failed, shed,
refused, never answered) is in the sample as +inf, so it is never averaged
into a finite number and it pushes every quantile above it outwards."""

from benchmark.harness import core


def read(obs: dict, args: dict):
    lat = obs["outcome"].latency_ms
    if len(lat) == 0:
        return None
    return core.percentile(lat, float(args["percent"]))
