"""Share of the traced slice in which no operation ran on the device:
1 - (union of the device operations' intervals) / (the slice)."""


def read(obs: dict, args: dict):
    return obs["trace"].idle_share_pct
