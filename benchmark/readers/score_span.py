"""Median seconds of the ``score`` call in the window, from the benchmark's
own wrapper around the callable (host clock; the call returns host memory,
so the device work is inside it), in milliseconds."""

import numpy as np


def read(obs: dict, args: dict):
    spans = obs["spans"]
    if len(spans) == 0:
        return None
    return float(np.quantile(spans[:, 1], float(args.get("quantile", 0.5)))
                 * 1e3)
