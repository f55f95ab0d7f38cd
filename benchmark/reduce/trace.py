"""From a profiler trace to the device's numbers: busy and idle time,
kernel time, the operations that took most time (containers left out: a
``while`` holds the operations listed beside it), the idle gaps by what the
host was doing, and a kernel's share of its roofline.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. ``load`` turns
it into plain tuples, so that the arithmetic below runs the same on a
chip's trace and on the small recorded one under ``fixtures/`` that the
tests hold it to. Every PR's device numbers come through here, and no PR
that claims a gain can change it.

What a TPU trace looks like (looked at by hand, PERF.md has the listing):
one plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` holds one event
per device operation, back to back inside a program and apart between
programs; ``XLA Modules`` holds one event per executed program. Host
threads are lines of the ``/host:CPU`` plane; the benchmark's
``TraceAnnotation`` around each ``score`` call is an event there, with the
call's rows as a stat.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# an operation that only holds others (a scan, a tile loop: its event spans
# its body's events on the same line): its time is its children's, not work
CONTAINER = re.compile(r"[\s)}\]](while|call|conditional)\(")
CHILDLESS = "container without children in the capture: "  # a name's mark
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _planes(profile, want_stats_of: str | None) -> dict:
    planes: dict = {}
    for plane in profile.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                stats = {}
                if want_stats_of is not None and e.name == want_stats_of:
                    stats = {k: v for k, v in e.stats}
                events.append(Event(e.name, float(e.start_ns),
                                    float(e.duration_ns), stats))
    return planes


def load(path: str, span_name: str | None = None) -> dict:
    """``{plane: {line: [Event]}}`` of an ``.xplane.pb`` file, of the
    newest one under a profiler log directory, or of a ``.textproto``
    recording of an XSpace."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".textproto"):
        with open(path, encoding="utf-8") as f:
            profile = ProfileData.from_text_proto(f.read())
    else:
        profile = ProfileData.from_file(path)
    return _planes(profile, span_name)


_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])[^ ]* ([\w\-]+)\(")


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line; keep the
    result's name, type and shape and the opcode: ``%fused_mlp_score.1
    custom-call f32[4096,1]``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name[:96]


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


@dataclasses.dataclass
class TraceSummary:
    window_s: float  # the traced window, first event to last, all planes
    busy_s: float  # union of device operations, mean over the chips
    n_devices: int
    # device operation -> summed seconds, all chips; a container whose
    # children the capture lists is in ``container_seconds`` instead, one
    # whose children it does not list stays here under a marked name
    op_seconds: dict
    kernel_s: float  # summed seconds of the operations matching the patterns
    kernel_events: int
    span_rows: int  # rows of the score spans inside the trace
    span_count: int
    gap_seconds: dict  # what the host was doing -> idle seconds
    container_seconds: dict = dataclasses.field(default_factory=dict)

    @property
    def idle_share_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(planes: dict, *, op_line: str, kernel_patterns: list[str],
           span_name: str = "bench.score") -> TraceSummary:
    """The device's numbers from ``load``'s planes. ``kernel_patterns`` are
    regular expressions (kept as data with the configuration) searched in
    the names of the operations on ``op_line``."""
    device = {name: lines for name, lines in planes.items()
              if DEVICE_PLANE.match(name)}
    if not device:
        raise ValueError(f"no TPU device plane among {sorted(planes)}")
    every = [e for lines in planes.values() for evs in lines.values()
             for e in evs]
    lo = min(e.start_ns for e in every)
    hi = max(e.end_ns for e in every)
    pats = [re.compile(p) for p in kernel_patterns]
    op_seconds: dict = {}
    container_seconds: dict = {}
    named: dict = {}  # event name -> (short name, container, kernel)
    busy = []
    kernel_s, kernel_events = 0.0, 0
    busy_intervals: list[tuple[float, float]] = []
    for lines in device.values():
        ops = lines.get(op_line, [])
        ivals = [(e.start_ns, e.end_ns) for e in ops]
        busy.append(union_s(ivals))
        busy_intervals.extend(ivals)
        # a container starts no later than its children and ends no
        # earlier: in this order the event after it is its first child
        ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
        for at, e in enumerate(ops):
            if e.name not in named:  # few names, many events
                named[e.name] = (short_name(e.name),
                                 bool(CONTAINER.search(e.name)),
                                 any(p.search(e.name) for p in pats))
            key, container, kernel = named[e.name]
            into = op_seconds
            if container:
                if at + 1 < len(ops) and ops[at + 1].start_ns < e.end_ns:
                    into = container_seconds
                else:
                    key = CHILDLESS + key
            into[key] = into.get(key, 0.0) + e.dur_ns / 1e9
            if kernel:
                kernel_s += e.dur_ns / 1e9
                kernel_events += 1
    if not busy_intervals:
        raise ValueError(
            f"no operation on line {op_line!r} of {sorted(device)}: the "
            "traced window drove nothing on the device")
    spans = [e for name, lines in planes.items() if name not in device
             for evs in lines.values() for e in evs if e.name == span_name]
    # Idle time by what the host was doing. The device's clock and the
    # host's differ by up to about a millisecond inside one trace (a
    # program can be stamped before the call that launched it), so gaps
    # are not matched to spans one by one: every device operation belongs
    # to some score call, hence the idle time inside dispatches is the time
    # a call was open less the time the device ran, and the rest of the
    # window, with no call open, is idle between dispatches.
    open_s = union_s([(e.start_ns, e.end_ns) for e in spans])
    busy_s = sum(busy) / len(busy)
    window_s = (hi - lo) / 1e9
    gap_seconds = {
        "inside a dispatch": max(0.0, open_s - busy_s),
        "between dispatches": max(0.0, window_s - max(open_s, busy_s)),
    }
    rows = sum(int(e.stats.get("rows", 0)) for e in spans)
    return TraceSummary(
        window_s=window_s, busy_s=busy_s,
        n_devices=len(device), op_seconds=op_seconds, kernel_s=kernel_s,
        kernel_events=kernel_events, span_rows=rows, span_count=len(spans),
        gap_seconds=gap_seconds, container_seconds=container_seconds)


def peaks_of(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that
    is not in the table is an error, not a default."""
    with open(PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"{PEAKS_FILE} with its source")
    return table[device_kind]


def roofline_share(flop: float, bytes_moved: float, kernel_s: float,
                   device_kind: str, n_devices: int = 1,
                   flop_peak: str = "bf16_flop_s") -> tuple[float, str]:
    """``(share in %, which bound)``: the least time the chips could take
    for ``flop`` operations and ``bytes_moved`` bytes, over the time the
    kernel took. Over 105% means the operations or bytes are counted too
    high or the time leaves out part of the work: that raises."""
    peaks = peaks_of(device_kind)
    if kernel_s <= 0:
        raise ValueError("kernel time is not above 0")
    t_flop = flop / (peaks[flop_peak] * n_devices)
    t_bytes = bytes_moved / (peaks["hbm_bytes_s"] * n_devices)
    share = 100.0 * max(t_flop, t_bytes) / kernel_s
    if share > 105.0:
        raise ValueError(
            f"roofline share {share:.1f}% is over 105%: operations or bytes "
            "counted too high, or kernel time leaves out part of the work")
    return share, "compute" if t_flop >= t_bytes else "bandwidth"
