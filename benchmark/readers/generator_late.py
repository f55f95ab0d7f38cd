"""How late the generator sent a request, or produced a tick, against its
schedule: a quantile of (sent - due), in milliseconds. A starved generator
must not be read as a fast server."""

import numpy as np


def read(obs: dict, args: dict):
    late = obs["outcome"].late_ms
    late = late[np.isfinite(late)]
    if len(late) == 0:
        return None
    return float(np.quantile(late, float(args.get("quantile", 0.99))))
