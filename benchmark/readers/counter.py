"""A counter the driver kept, scaled; or the ratio of two of them."""


def read(ctx, name, scale=1.0, per=None):
    value = ctx.counters.get(name)
    if value is None:
        return None
    if per is not None:
        denom = ctx.counters.get(per)
        if not denom:
            return None
        value = value / denom
    return value * scale
