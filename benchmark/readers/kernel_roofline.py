"""The scoring kernel's share of its roofline, from the device trace: the
larger of operations over peak FLOP/s and bytes over peak bytes/s for the
rows really dispatched in the traced slice (operations and bytes from
shapes, ``reduce/costs.py``), over the summed device time of the
operations that the configuration's kernel patterns match. Which of the
two bounds it is printed on an INFO line."""

from benchmark.reduce import costs, trace


def read(obs: dict, args: dict):
    import jax

    summary = obs["trace"]
    c = obs["config"]["costs"]
    if summary.kernel_events == 0 or summary.span_rows == 0:
        return None
    flop, moved = costs.of(c, summary.span_rows, summary.span_count)
    share, bound = trace.roofline_share(
        flop, moved, summary.kernel_s, jax.devices()[0].device_kind,
        flop_peak=c["flop_peak"])
    print(f"INFO kernel_roofline {share:.4f}% bound by {bound}: "
          f"{summary.span_rows} rows in {summary.span_count} dispatches, "
          f"{summary.kernel_events} kernel events, {summary.kernel_s:.6f}s",
          flush=True)
    return share
