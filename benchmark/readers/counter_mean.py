"""Mean of a timing the program keeps as a sum and a count, over the
window: ``(sum_after - sum_before) / (count_after - count_before)``, times
``scale``. The program's histogram of it is bucketed, so its quantiles are
interpolated; its sum and count are exact, which is why this reads the
mean."""


def read(obs: dict, args: dict):
    before, after = obs["before"], obs["after"]
    if args["sum"] not in after or args["count"] not in after:
        return None
    n = after[args["count"]] - before[args["count"]]
    if n <= 0:
        return None
    return (after[args["sum"]] - before[args["sum"]]) / n * float(
        args.get("scale", 1.0))
