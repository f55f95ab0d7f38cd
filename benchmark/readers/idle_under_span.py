"""Share of the traced slice in which the device was idle AND the score
worker (the host line that holds ``router.score``) was in a given place,
from the capture (``reduce/host_spans.py``), in % of the slice:

- ``{"spans": [...]}``: the worker's innermost open phase was one of these;
- ``{"inside": "router.score", "except": [...]}``: ``router.score`` was
  open and the innermost phase was none of those;
- ``{"outside": "router.score"}``: it was not open, so the worker waited
  for the router's loop thread.

Shares that partition the worker's time this way add up to the slice's
idle share (``device_idle.sat``: the same operations' intervals over the
same window). The device's stamps and the host's differ by up to about a
millisecond inside one capture, and a phase has two boundaries: with some
16 batches in a 2.5 s slice that is up to 16 x 2 x 1 ms = 32 ms, about 1.3
points, moved between neighbouring shares. It is not corrected for. A
phase that the slice's edge cut is not in the capture: its stretch counts
under ``inside`` (where the line's other phases show ``router.score`` was
open) and the first run prints how long those stretches were (``cut``).

Once a run it prints ``INFO idle_by_phase``: the idle share under every
innermost phase of the worker's line, ``cut``, and ``starved`` (no
``router.score`` open) split by the loop thread's innermost phase
(``router.poll`` / ``decode`` / ``route`` / ``commit``, ``none`` between
them); before it ``INFO capture``, whatever the program: the capture's size
and the events other than phases and device operations that took most time
(the runtime's own: ``Linearize`` is the host's side of the transfer in), as
``plane|line|name n mean_ms``. None where the capture holds no
``router.score``."""

from benchmark.reduce import host_spans


def _where(cap, worker, args: dict) -> list:
    stretches = host_spans.innermost(worker)
    if "spans" in args:
        return [(a, b) for a, b, name in stretches if name in args["spans"]]
    if "outside" in args:
        return host_spans.complement(
            host_spans.worker_open(cap, worker, args["outside"]),
            cap.lo_ns, cap.hi_ns)
    excepted = [(a, b) for a, b, name in stretches if name in args["except"]]
    return host_spans.intersect(
        host_spans.worker_open(cap, worker, args["inside"]),
        host_spans.complement(excepted, cap.lo_ns, cap.hi_ns))


def _report(cap, worker) -> None:
    open_ = host_spans.worker_open(cap, worker)
    shares = host_spans.idle_by_innermost(cap, worker, open_)
    shares["cut"] = shares.pop("none")
    starved = host_spans.complement(open_, cap.lo_ns, cap.hi_ns)
    line = " ".join(f"{k} {v:.2f}" for k, v in sorted(shares.items()))
    line += f" starved {host_spans.idle_share_pct(cap, starved):.2f}"
    loop = cap.line_of(host_spans.LOOP_SPAN)
    if loop is not None:
        under = host_spans.idle_by_innermost(cap, loop, starved)
        line += " (loop thread: " + " ".join(
            f"{k} {v:.2f}" for k, v in sorted(under.items())) + ")"
    print(f"INFO idle_by_phase % of slice: {line}", flush=True)


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    worker = cap.line_of(host_spans.WORKER_SPAN)
    if not cap.reported:
        cap.reported = True
        top = sorted(cap.others.items(), key=lambda kv: -kv[1][1])[:12]
        print(f"INFO capture {cap.size_bytes} bytes; other events: "
              + "; ".join(f"{'|'.join(key)[:120]} {n} {ns / n / 1e6:.3f}"
                          for key, (n, ns) in top), flush=True)
        if worker is not None:
            _report(cap, worker)
    if worker is None:
        return None
    return host_spans.idle_share_pct(cap, _where(cap, worker, args))
