"""Device busy time in the traced window over the optimizer steps in it."""


def read(ctx):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    return 1e3 * ctx.trace["busy_s"] / steps
