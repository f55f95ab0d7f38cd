"""The program's own phases in the traced run's capture, beside the device.

The program marks each phase of the served path with
``ccfd_tpu/observability/trace.py::phase``: a ``TraceAnnotation`` per
router batch or per dispatch (``router.poll`` / ``decode`` / ``route`` /
``commit`` on the router's loop thread; ``router.score`` > ``seq.score`` >
``seq.gather`` / ``seq.pad`` / ``seq.enqueue`` / ``seq.wait`` /
``seq.commit`` on its score worker), each with stats set at close. They are
events of the profiler's host plane, in the same capture and on the same
timeline as the device's ``XLA Ops``. ``load`` reads that capture once a
run and keeps what the new readers need: the program's phases line by line
with their stats, the score worker's line, and the device's idle
intervals inside the slice.

``reduce/trace.py::load`` keeps the stats of one span name only, and
``obs`` carries neither its planes nor the capture's path, so the readers
find the capture where ``harness/core.py::run_cell`` puts it:
``<root>/.benchwork/run_<pid>/trace``, which still exists when the readers
run (``capture_dir``). A test hands a recorded capture in ``obs["capture"]``.

A host line is named by the OS thread's name (``python3``), not by
Python's: the score worker is the line that holds ``router.score``, the
loop thread the line that holds ``router.decode``. A program without these
phases (an older commit under this benchmark) gives a capture with no such
line, and every reader then returns None.

A phase that was open when the capture started or stopped is not in it;
its finished children are. So a ``seq.score`` in the capture is a batch
wholly inside the slice, and phases of the worker's line outside every
recorded ``router.score`` show that one was open, cut by the slice's edge.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os

from benchmark.reduce.trace import DEVICE_PLANE, Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIXES = ("router.", "seq.")
WORKER_SPAN = "router.score"
LOOP_SPAN = "router.decode"
BATCH_SPAN = "seq.score"

Interval = tuple[float, float]


def capture_dir() -> str:
    """Where ``run_cell`` has the traced run's profiler write."""
    return os.path.join(ROOT, ".benchwork", f"run_{os.getpid()}", "trace")


@dataclasses.dataclass
class Capture:
    lo_ns: float  # the slice: first event's start to last event's end,
    hi_ns: float  # over all planes, as reduce/trace.py takes its window
    lines: list[list[Event]]  # host lines that hold a phase, phases only
    busy: list[list[Interval]]  # per device: its operations' intervals
    size_bytes: int
    # (plane, line, event name) -> [count, summed ns] of every event that
    # is not a phase and not on the device's operation line: the runtime's
    # own events (transfers, launches), for a look by hand
    others: dict
    reported: bool = False  # the INFO lines are printed once a run

    @property
    def window_ns(self) -> float:
        return self.hi_ns - self.lo_ns

    def line_of(self, name: str) -> list[Event] | None:
        """The one line that holds ``name``; None where no line does."""
        found = [line for line in self.lines
                 if any(e.name == name for e in line)]
        if len(found) > 1:
            raise ValueError(f"{name} is on {len(found)} host lines: the "
                             "attribution is written for one score worker")
        return found[0] if found else None

    def named(self, name: str) -> list[Event]:
        return [e for line in self.lines for e in line if e.name == name]

    def idle(self) -> list[list[Interval]]:
        """Per device, the intervals of the slice in which no operation
        ran on it."""
        return [complement(union(b), self.lo_ns, self.hi_ns)
                for b in self.busy]


def _path(path: str) -> str:
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return found[-1]
    return path


@functools.lru_cache(maxsize=1)
def load(path: str, op_line: str = "XLA Ops") -> Capture:
    """The capture at ``path``: a profiler log directory (its newest
    ``.xplane.pb``), such a file, or a ``.textproto`` recording."""
    from jax.profiler import ProfileData

    path = _path(path)
    if path.endswith(".textproto"):
        with open(path, encoding="utf-8") as f:
            profile = ProfileData.from_text_proto(f.read())
    else:
        profile = ProfileData.from_file(path)
    lo, hi = float("inf"), float("-inf")
    lines, busy, others = [], [], {}
    for plane in profile.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            phases, ops = [], []
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                lo, hi = min(lo, start), max(hi, start + dur)
                if device and line.name == op_line:
                    ops.append((start, start + dur))
                elif not device and e.name.startswith(PREFIXES):
                    phases.append(Event(e.name, start, dur,
                                        {k: v for k, v in e.stats}))
                else:
                    tally = others.setdefault(
                        (plane.name, line.name, e.name), [0, 0.0])
                    tally[0] += 1
                    tally[1] += dur
            if phases:
                lines.append(sorted(phases, key=lambda e: e.start_ns))
            if device and line.name == op_line:
                busy.append(ops)
    return Capture(lo_ns=lo, hi_ns=hi, lines=lines, busy=busy,
                   size_bytes=os.path.getsize(path), others=others)


def of(obs: dict) -> Capture | None:
    """The run's capture for a reader; None where the run left none."""
    path = obs.get("capture") or capture_dir()
    if not os.path.exists(path):
        return None
    return load(path, obs["config"]["trace"]["op_line"])


# -- interval arithmetic: sorted, disjoint lists of (start_ns, end_ns) ------

def union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def complement(intervals: list[Interval], lo: float, hi: float
               ) -> list[Interval]:
    out, at = [], lo
    for a, b in intervals:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def intersect(xs: list[Interval], ys: list[Interval]) -> list[Interval]:
    """What two sorted, disjoint lists both cover."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def total_ns(xs: list[Interval]) -> float:
    return sum(b - a for a, b in xs)


def innermost(line: list[Event]) -> list[tuple[float, float, str]]:
    """``(start_ns, end_ns, name)`` stretches of a line, each named by the
    innermost phase open in it (the phases of one thread nest); stretches
    with no phase open are left out."""
    out: list[tuple[float, float, str]] = []
    stack: list[Event] = []
    at = 0.0

    def emit(upto: float) -> None:
        if upto > at:
            out.append((at, upto, stack[-1].name))

    for e in sorted(line, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            emit(stack[-1].end_ns)
            at = max(at, stack.pop().end_ns)
        if stack:
            emit(e.start_ns)
        stack.append(e)
        at = e.start_ns
    while stack:
        emit(stack[-1].end_ns)
        at = max(at, stack.pop().end_ns)
    return out


def worker_open(cap: Capture, worker: list[Event],
                span: str = WORKER_SPAN) -> list[Interval]:
    """The stretches of the slice in which ``span``, the outermost phase
    of the worker's line, was open: the recorded ones; any stretch with a
    phase of the line open; and the two that the slice's edges cut. At
    the start, phases before the first recorded ``span`` show that one was
    open from the slice's start to the last of them. At the end, phases
    after the last recorded one show the same from the first of them on;
    and where the capture stopped inside the batch's first phase there is
    none, but the loop thread's ``router.decode`` of a batch, closed while
    the last recorded ``span`` ran or after, shows that the worker had a
    batch to go straight on with: open from the later of the two ends."""
    spans = [(e.start_ns, e.end_ns) for e in worker]
    recorded = [e for e in worker if e.name == span]
    first = min(e.start_ns for e in recorded)
    last = max(recorded, key=lambda e: e.end_ns)
    head = [e.end_ns for e in worker if e.end_ns <= first]
    if head:
        spans.append((cap.lo_ns, max(head)))
    tail = [e.start_ns for e in worker if e.start_ns >= last.end_ns]
    decoded = [e.end_ns for e in cap.named(LOOP_SPAN)
               if e.end_ns > last.start_ns]
    if decoded:
        tail.append(max(last.end_ns, min(decoded)))
    if tail:
        spans.append((min(tail), cap.hi_ns))
    return union(spans)


def idle_share_pct(cap: Capture, where: list[Interval]) -> float:
    """Share of the slice in which the device was idle inside ``where``
    (sorted, disjoint), in %: the mean over the devices."""
    idle = cap.idle()
    return 100.0 * sum(total_ns(intersect(d, where)) for d in idle) / (
        len(idle) * cap.window_ns)


def idle_by_innermost(cap: Capture, line: list[Event],
                      within: list[Interval]) -> dict[str, float]:
    """The idle share of ``within`` by the innermost phase open on
    ``line``, in % of the slice; ``none`` where no phase was open."""
    by_name: dict[str, list[Interval]] = {}
    for a, b, name in innermost(line):
        by_name.setdefault(name, []).append((a, b))
    covered = union([iv for ivs in by_name.values() for iv in ivs])
    by_name["none"] = complement(covered, cap.lo_ns, cap.hi_ns)
    return {name: idle_share_pct(cap, intersect(ivs, within))
            for name, ivs in by_name.items()}
