"""Device time by the program's named scopes, from the traced run's capture.

The program wraps parts of its device program in ``jax.named_scope``
(``kda``, ``mla``, ``moe.experts`` ...). A TPU capture names an ``XLA Ops``
event by its HLO instruction (``%fusion.123 = ...``) and gives it no scope;
the scope is in the instruction's ``metadata.op_name``
(``jit(apply_serving)/kda/dot_general``), and the capture carries every
executed module's ``HloProto`` in the event metadata of its
``/host:metadata`` plane. ``jax.profiler.ProfileData`` does not expose
event metadata, so ``instruction_scopes`` reads the ``.xplane.pb`` itself:
a few fields of the protobuf wire format, no generated classes, nothing
but the standard library (field numbers from ``xplane.proto`` and
``hlo.proto``, given where they are used).

``load`` gives, per device, the programs wholly inside the capture (the
``XLA Modules`` events) and the seconds of device operations under each
scope inside them; an operation outside every recorded program (one the
capture's edge cut) is left out, so time and work cover the same
programs. A ``while`` (a scan, the expert layer's tile loop) is itself an
event that spans its body's events: containers are skipped, so that no
second is counted twice. What the programs did (tokens, rows, pairs) comes from the
program's own ``seq.wait`` phases in the same capture
(``reduce/host_spans.py``): their mean per dispatch, times the programs
counted here. A capture of a program without such scopes or phases (an
older commit) gives empty sums and the readers return None.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re

from benchmark.reduce import host_spans
from benchmark.reduce.trace import CONTAINER, DEVICE_PLANE

MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
_INSTRUCTION = re.compile(r"^%([\w.\-]+)")


# -- protobuf wire format ------------------------------------------------------

def _varint(buf: bytes, at: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of one message: an int for
    varints and fixed widths, the bytes of a length-delimited field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {wire} in a capture")
        yield number, wire, value


def _sub(buf: bytes, number: int) -> list[bytes]:
    return [v for n, w, v in fields(buf) if n == number and w == 2]


def instruction_scopes(xspace: bytes) -> dict[str, str]:
    """``{HLO instruction name: metadata.op_name}`` over every module the
    capture holds. XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4
    (map entry: value = 2); XEventMetadata.stats = 5; XStat.bytes_value =
    6; HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2."""
    names: dict[str, str] = {}
    for plane in _sub(xspace, 1):
        if not any(v == METADATA_PLANE.encode() for v in _sub(plane, 2)):
            continue
        for entry in _sub(plane, 4):
            for event_metadata in _sub(entry, 2):
                for stat in _sub(event_metadata, 5):
                    for hlo_proto in _sub(stat, 6):
                        _instructions_of(hlo_proto, names)
    return names


def _instructions_of(hlo_proto: bytes, names: dict[str, str]) -> None:
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name = op_name = None
                for n, w, v in fields(instruction):
                    if n == 1 and w == 2:
                        name = v.decode("utf-8", "replace")
                    elif n == 7 and w == 2:
                        found = _sub(v, 2)
                        op_name = found[0].decode("utf-8", "replace") \
                            if found else None
                if name and op_name:
                    names[name] = op_name


# -- the capture by scope ----------------------------------------------------------

@dataclasses.dataclass
class ScopedCapture:
    programs: int  # XLA Modules events, summed over the devices
    n_devices: int
    busy_s: float  # device operations inside those programs, summed
    # op_name of each operation -> its summed seconds inside the programs
    op_name_seconds: dict

    def seconds_under(self, scopes: list[str]) -> float:
        """Summed seconds of the operations whose op_name has one of
        ``scopes`` as a path component (``moe.`` matches every scope that
        starts so)."""
        def under(op_name: str) -> bool:
            parts = op_name.split("/")
            return any(part == s or (s.endswith(".") and part.startswith(s))
                       for part in parts for s in scopes)

        return sum(s for name, s in self.op_name_seconds.items()
                   if under(name))


@functools.lru_cache(maxsize=1)
def load(path: str, op_line: str = "XLA Ops") -> ScopedCapture:
    from jax.profiler import ProfileData

    path = host_spans._path(path)
    if path.endswith(".textproto"):  # a recording, for the tests
        with open(path, encoding="utf-8") as f:
            raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            raw = f.read()
    op_names = instruction_scopes(raw)
    profile = ProfileData.from_serialized_xspace(raw)
    programs = n_devices = 0
    busy = 0.0
    seconds: dict[str, float] = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        n_devices += 1
        lines = {line.name: line for line in plane.lines}
        if MODULE_LINE not in lines or op_line not in lines:
            continue
        whole = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns))
                       for e in lines[MODULE_LINE].events)
        programs += len(whole)
        at = 0
        scope_of: dict[str, str] = {}
        for e in lines[op_line].events:  # in start order
            start = float(e.start_ns)
            while at < len(whole) and whole[at][1] < start:
                at += 1
            if at == len(whole):
                break
            if start < whole[at][0]:
                continue  # before the first whole program
            name = e.name
            op_name = scope_of.get(name)
            if op_name is None:
                m = _INSTRUCTION.match(name)
                op_name = scope_of[name] = (
                    "<container>" if CONTAINER.search(name)
                    else op_names.get(m.group(1) if m else name, ""))
            if op_name == "<container>":
                continue
            dur = float(e.duration_ns) / 1e9
            busy += dur
            seconds[op_name] = seconds.get(op_name, 0.0) + dur
    return ScopedCapture(programs=programs, n_devices=n_devices,
                         busy_s=busy, op_name_seconds=seconds)


def of(obs: dict) -> ScopedCapture | None:
    path = obs.get("capture") or host_spans.capture_dir()
    if not os.path.exists(path):
        return None
    return load(path, obs["config"]["trace"]["op_line"])


def work(obs: dict) -> dict | None:
    """What the capture's programs did: ``dispatches``, ``rows``,
    ``tokens``, ``pairs`` (all expert layers), ``tokens_per_row``: the mean
    of the program's ``seq.wait`` phases in the capture (each closes one
    dispatch and carries its counts) times the programs on the device
    plane. None where the capture has no such phase."""
    cap, scoped = host_spans.of(obs), of(obs)
    if cap is None or scoped is None or scoped.programs == 0:
        return None
    waits = [e for e in cap.named("seq.wait") if "pairs_served" in e.stats]
    if not waits:
        return None
    n = float(len(waits))
    serving = obs["config"]["serving"]
    per_row = int(serving["length"]) * int(obs["config"]["num_features"])
    return {
        "dispatches": scoped.programs,
        "rows": scoped.programs * sum(e.stats["rows"] for e in waits) / n,
        "tokens": scoped.programs * sum(e.stats["routed_tokens"]
                                        for e in waits) / n,
        "pairs": scoped.programs * sum(e.stats["pairs_served"]
                                       for e in waits) / n,
        "tokens_per_row": per_row,
    }
