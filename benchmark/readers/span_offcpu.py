"""Share of a phase's wall time that its thread spent off the CPU, from the
traced run's capture (``reduce/host_spans.py``): 100 x (1 - summed
``cpu_ns`` / summed duration) over every ``span`` event that carries the
stat (the program reads the thread's CPU clock at both ends of a phase
while a capture runs), held to [0, 100]. On the scorer's worker thread,
which neither sleeps nor waits for the device inside ``seq.gather``, off
the CPU means waiting for the interpreter lock.

The thread's CPU clock moves in steps (``cpu_clock_step_ms``, 10 ms where
the kernel charges a thread by the tick): a phase that a tick fell into is
charged the whole step, so short phases read more CPU time than wall time
and the raw share falls below 0; it is a share, so that reads 0. Where the
summed duration of the slice's phases is under one step, the clock cannot
tell any of it from none: nothing to read. None, too, where no such event
is in the capture."""

from benchmark.reduce import host_spans


def share_pct(wall_ns: float, cpu_ns: float, step_ns: float) -> float | None:
    if wall_ns <= 0 or wall_ns < step_ns:
        return None
    return min(100.0, max(0.0, 100.0 * (1.0 - cpu_ns / wall_ns)))


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    events = [e for e in cap.named(args["span"]) if "cpu_ns" in e.stats]
    return share_pct(sum(e.dur_ns for e in events),
                     sum(e.stats["cpu_ns"] for e in events),
                     float(args.get("cpu_clock_step_ms", 0.0)) * 1e6)
