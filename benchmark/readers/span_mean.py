"""Mean time a router batch spends in one of the scorer's phases, from the
traced run's capture (``reduce/host_spans.py``): the summed durations of
the ``span`` events inside each ``seq.score`` span of the capture (a batch
wholly inside the slice; one cut by the slice's edge is not in it, and its
phases are left out), averaged over those batches, in milliseconds. None
where the capture holds no ``seq.score``."""

from benchmark.reduce import host_spans


def read(obs: dict, args: dict):
    cap = host_spans.of(obs)
    if cap is None:
        return None
    per_batch = [
        sum(e.dur_ns for e in line if e.name == args["span"]
            and batch.start_ns <= e.start_ns and e.end_ns <= batch.end_ns)
        for line in cap.lines for batch in line
        if batch.name == host_spans.BATCH_SPAN]
    if not per_batch:
        return None
    return sum(per_batch) / len(per_batch) / 1e6
