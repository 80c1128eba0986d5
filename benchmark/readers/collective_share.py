"""Device time inside collective ops over device busy time, in %."""


def read(ctx):
    t = ctx.trace
    if not t or t["devices"] < 2 or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
