"""Share of a phase's wall time that its thread spent off the CPU, from the
traced run's capture (``reduce/host_spans.py``): 100 x (1 - summed
``cpu_ns`` / summed duration) over every ``span`` event that carries the
stat (the program reads the thread's CPU clock at both ends of a phase
while a capture runs). On the scorer's worker thread, which neither sleeps
nor waits for the device inside ``seq.gather``, off the CPU means waiting
for the interpreter lock. None where no such event is in the capture."""

from benchmark.reduce import host_spans


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    events = [e for e in cap.named(args["span"]) if "cpu_ns" in e.stats]
    wall = sum(e.dur_ns for e in events)
    if wall <= 0:
        return None
    return 100.0 * (1.0 - sum(e.stats["cpu_ns"] for e in events) / wall)
