"""Mean time a router batch spends in given phases of the program, or
in none on a given host line, from the traced run's capture
(``reduce/host_spans.py``), in milliseconds a batch:

- ``{"spans": [...], "per": "router.score"}``: the summed time of the
  named phases, on whichever host line holds them;
- ``{"line": "router.decode", "unowned": true, "needs": "router.await",
  "per": "router.score"}``: the line that holds ``router.decode`` (the
  router's loop thread) less the union of every phase on it: the stretches
  of the loop no phase owns. ``needs``: a line without that phase belongs
  to a program whose loop line is not closed, and is not read.

Both over **whole periods**: from the first start of a ``per`` phase in
the capture to the last, n - 1 periods between n starts, every phase
clipped to that stretch. The slice's edges cut a batch each, and a
``hybrid_moe`` cell's slice holds about ten: sums over the whole slice
divided by a count of batches would be off by a tenth. Over whole periods
the loop line's phases and its unowned time add up to the period exactly
(``worker_period``'s ``period``: the same stretch over the same count),
as long as the line's outermost phases do not overlap, which the program's
tests hold.

None where the capture holds fewer than two ``per`` phases, or none of
the named ones anywhere (a program without them: an older commit under
this benchmark)."""

from benchmark.reduce import host_spans

Periods = tuple[float, float, int]  # first start, last start, periods


def periods(cap, per: str = host_spans.WORKER_SPAN) -> Periods | None:
    starts = sorted(e.start_ns for e in cap.named(per))
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


def clipped(events, lo: float, hi: float) -> list:
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def named_ms(cap, names, window: Periods) -> float:
    """The named phases' time inside the periods, a period."""
    lo, hi, n = window
    events = [e for name in names for e in cap.named(name)]
    return host_spans.total_ns(clipped(events, lo, hi)) / n / 1e6


def unowned_ms(line, window: Periods) -> float:
    """The periods less the union of the line's phases, a period."""
    lo, hi, n = window
    owned = host_spans.union(clipped(line, lo, hi))
    return (hi - lo - host_spans.total_ns(owned)) / n / 1e6


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    window = periods(cap, args["per"])
    if window is None:
        return None
    if args.get("unowned"):
        line = cap.line_of(args["line"])
        if line is None or not any(e.name == args["needs"] for e in line):
            return None
        return unowned_ms(line, window)
    if not any(cap.named(name) for name in args["spans"]):
        return None
    return named_ms(cap, args["spans"], window)
