"""Verdicts per second of the window. Where the generator gives a span
(the bus, whose verdicts arrive in whole micro-batches), the rows of the
batches after the first inside the window up to the last, over the time
between those two batches' stamps: all the window's completions but the
first batch, over the time they took. Else the rows whose verdict arrived
inside the window, over the whole window."""


def read(obs: dict, args: dict):
    outcome = obs["outcome"]
    if outcome.rate_span is not None:
        seconds, rows = outcome.rate_span
        return rows / seconds if seconds > 0 else None
    return outcome.rows_in_window / outcome.seconds
