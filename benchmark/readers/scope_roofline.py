"""A part of a language model's program's share of its roofline, from the
device trace: the larger of operations over peak FLOP/s and bytes over
peak bytes/s for what the capture's programs really did (tokens, rows and
the pairs the program counted: ``reduce/scopes.py::work``), over the summed
device time of the operations under the part's ``scopes`` inside those
programs (``part`` ``backbone``: every operation of the programs).
Operations and bytes come from the cost functions the configuration names:
``costs.kind`` -> ``benchmark/reduce/costs_<kind>.py`` with ``part(config,
work, name)`` and ``backbone(config, work)``, so one metric name serves
every model that has the layer, and a further model's shares are a cost
file and, for scopes of its own, metric files. A ``costs.kind`` with no
such file is an error, not another model's costs. Which of the two bounds
it is printed on an INFO line. None where the capture's program has no
such scopes or phases."""

from benchmark.harness import manifest
from benchmark.reduce import scopes, trace


def costs_of(config: dict):
    """The cost module ``config`` names: ``reduce/costs_<costs.kind>.py``."""
    return manifest.load_kind("reduce", "costs_" + manifest.check_name(
        config["costs"]["kind"], "costs.kind"))


def read(obs: dict, args: dict):
    import jax

    work, cap = scopes.work(obs), scopes.of(obs)
    if work is None or cap is None:
        return None
    costs = costs_of(obs["config"])
    part = args["part"]
    if part == "backbone":
        seconds = cap.busy_s
        flop, moved = costs.backbone(obs["config"], work)
    else:
        seconds = cap.seconds_under(args["scopes"])
        flop, moved = costs.part(obs["config"], work, part)
    if seconds <= 0:
        return None
    share, bound = trace.roofline_share(
        flop, moved, seconds, jax.devices()[0].device_kind,
        n_devices=cap.n_devices,
        flop_peak=obs["config"]["costs"]["flop_peak"])
    print(f"INFO {obs['config']['costs']['kind']}.{part}_roofline "
          f"{share:.4f}% bound by {bound}: "
          f"{flop / 1e12:.3f} TFLOP, {moved / 1e9:.3f} GB, {seconds:.6f}s in "
          f"{cap.programs} programs ({work['tokens']:.0f} tokens, "
          f"{work['pairs']:.0f} pairs)", flush=True)
    return share
