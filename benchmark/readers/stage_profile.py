"""The program's own ``StageProfiler`` (handed to ``Router`` in the traced
run only). ``mode`` ``quantile``: a quantile of one stage's component, in
milliseconds; its digest has geometric buckets (2^(1/4) apart), so the
quantile is interpolated, about 9% off at worst. ``mode`` ``per_row``:
the summed time of the ``stages``' component over the rows of the first
stage, in microseconds a row (sums are exact)."""


def read(obs: dict, args: dict):
    prof = obs["profiler"]
    if prof is None:
        return None
    digests = [prof.digest(stage, args["component"])
               for stage in args["stages"]]
    if any(d is None or d.count == 0 for d in digests):
        return None
    if args["mode"] == "quantile":
        return digests[0].quantile(float(args["quantile"])) * 1e3
    rows = prof.snapshot()["stages"][args["stages"][0]]["rows"]
    if rows <= 0:
        return None
    return sum(d.sum for d in digests) / rows * 1e6
