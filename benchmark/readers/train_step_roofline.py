"""The least time one train step could take on this chip (benchmark/
roofline.py) over the device time it took, in %."""

from benchmark import harness, roofline


def read(ctx):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    least = roofline.train_step_least_seconds(
        ctx.cell.config["flags"], len(ctx.devices), peaks)["seconds"]
    return 100.0 * least / (ctx.trace["busy_s"] / steps)
