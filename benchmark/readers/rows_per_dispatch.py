"""Rows scored over device dispatches between the two readings of the
program's counters (after warm-up, after the drain): the scorer's own
per-bucket dispatch counts (``executable_grid()``), and for the rows the
router's ``transaction_incoming_total`` where there is a router, else the
rows of the requests the window sent."""


def read(obs: dict, args: dict):
    before, after = obs["before"], obs["after"]
    dispatches = (sum(after["dispatches"].values())
                  - sum(before["dispatches"].values()))
    if dispatches <= 0:
        return None
    if "consumed" in after:
        rows = after["consumed"] - before["consumed"]
    else:
        rows = len(obs["outcome"].served_rows)
    return rows / dispatches
