"""Share of the traced window in which no op ran on the device, in %."""


def read(ctx):
    t = ctx.trace
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
