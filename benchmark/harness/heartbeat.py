"""A witness for pauses of the whole machine: a process of its own that
imports nothing of JAX or the program, sleeps ``STEP_S`` at a time and
keeps every step that took over ``GAP_S`` longer. Where it loses the same
hundred milliseconds at the same instant as the served path, the machine
stood still, not the program (``time.perf_counter`` is one clock for every
process of a Linux host). Run as a script it beats until its standard
input closes, then prints what it kept."""

from __future__ import annotations

import json
import subprocess
import sys
import time

STEP_S = 0.002
GAP_S = 0.02


class Heartbeat:
    def __init__(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, __file__], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)

    def stop(self) -> list[tuple[float, float]]:
        """``(start, seconds)`` of every pause seen since the start."""
        out, _ = self.proc.communicate("", timeout=30.0)
        return [(float(t), float(s)) for t, s in json.loads(out)]

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.communicate()


def pauses_in(pauses: list[tuple[float, float]], t0: float,
              t1: float) -> list[tuple[float, float]]:
    return [(t, s) for t, s in pauses if t0 <= t < t1]


def main() -> int:
    import select

    kept = []
    last = time.perf_counter()
    while not select.select([sys.stdin], [], [], STEP_S)[0]:
        now = time.perf_counter()
        if now - last > STEP_S + GAP_S:
            kept.append((last, now - last - STEP_S))
        last = now
    print(json.dumps(kept), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
