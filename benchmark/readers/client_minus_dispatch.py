"""The client's median latency less the median ``score`` call: what the
socket, the JSON decode, the take queue, the respond and the encode cost,
timed from outside the program until it has spans of its own there."""

import numpy as np


def read(obs: dict, args: dict):
    lat = obs["outcome"].latency_ms
    lat = lat[np.isfinite(lat)]
    if len(lat) == 0 or len(obs["spans"]) == 0:
        return None
    return float(np.median(lat) - np.median(obs["spans"][:, 1]) * 1e3)
