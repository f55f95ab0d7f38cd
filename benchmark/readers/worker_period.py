"""A router batch's period on the score worker's line (the host line that
holds ``router.score``), from the traced run's capture
(``reduce/host_spans.py``), in milliseconds a batch:

- ``{"part": "period"}``: mean start-to-start of consecutive
  ``router.score`` phases, both in the capture (one the slice's edge cut
  is not): the last start less the first, over the pairs;
- ``{"part": "gap"}``: mean end-to-start of the same pairs: the worker
  stands outside ``router.score``. ``period`` = the mean ``router.score``
  of the pairs' first phases + ``gap``, exactly;
- ``{"stat": "handoff_ns"}``: the mean of that stat (nanoseconds) over
  every ``router.score`` in the capture: from the loop thread's ``submit``
  to the worker's first statement, read by the program on its own clock.

The program also reads ``idle_ns`` on the worker (its last return to
this start): the mean over the pairs' second phases is printed beside
``gap``, which it must agree with to a fraction of a millisecond (the two
annotations' own cost lies between them).

Once a run it prints ``INFO period ms a batch``: the period, its two
parts and the hand-over; then, over the same whole periods
(``phase_mean``), each phase of the loop thread's line and the time on it
that no phase owns, which add up to the period; then the pairs counted.

None where the capture holds fewer than two ``router.score`` phases, or
they carry no ``handoff_ns`` (an older commit of the program under this
benchmark: its loop line is not closed, so the period has no owners to
name)."""

from benchmark.readers import phase_mean
from benchmark.reduce import host_spans

HANDOFF = "handoff_ns"  # what marks a program whose period has owners
LOOP_PHASES = ("router.signals", "router.poll", "router.admit",
               "router.decode", "router.submit", "router.await",
               "router.force", "router.route", "router.commit")


def _parts(scores: list) -> dict:
    """ms a batch over the consecutive pairs of ``scores`` (by start)."""
    n = len(scores) - 1
    first, second = scores[:-1], scores[1:]
    return {
        "period": (scores[-1].start_ns - scores[0].start_ns) / n / 1e6,
        "score": sum(e.dur_ns for e in first) / n / 1e6,
        "gap": sum(b.start_ns - a.end_ns
                   for a, b in zip(first, second)) / n / 1e6,
        "idle_ns": sum(e.stats["idle_ns"] for e in second) / n / 1e6,
        "handoff": sum(e.stats[HANDOFF]
                       for e in scores) / len(scores) / 1e6,
    }


def _report(cap, parts: dict, pairs: int) -> None:
    window = phase_mean.periods(cap)
    line = (f"period {parts['period']:.3f} score {parts['score']:.3f} "
            f"gap {parts['gap']:.3f} (idle_ns {parts['idle_ns']:.3f}) "
            f"handoff {parts['handoff']:.3f}")
    loop = cap.line_of(host_spans.LOOP_SPAN)
    if loop is not None:
        line += " | loop: " + " ".join(
            f"{name.split('.')[1]} "
            f"{phase_mean.named_ms(cap, [name], window):.3f}"
            for name in LOOP_PHASES)
        line += f" unowned {phase_mean.unowned_ms(loop, window):.3f}"
    print(f"INFO period ms a batch: {line} | pairs {pairs}", flush=True)


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    worker = cap.line_of(host_spans.WORKER_SPAN)
    if worker is None:
        return None
    scores = sorted((e for e in worker if e.name == host_spans.WORKER_SPAN),
                    key=lambda e: e.start_ns)
    if len(scores) < 2 or any(HANDOFF not in e.stats for e in scores):
        return None
    parts = _parts(scores)
    if not getattr(cap, "period_reported", False):
        cap.period_reported = True
        _report(cap, parts, len(scores) - 1)
    if "stat" in args:
        return sum(e.stats[args["stat"]] for e in scores) / len(scores) / 1e6
    return parts[args["part"]]
