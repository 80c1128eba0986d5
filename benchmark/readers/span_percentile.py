"""A percentile of the durations of one of the program's spans, in ms."""

import numpy as np


def read(ctx, span, percentile):
    durs = [e["dur"] / 1e3 for e in ctx.spans if e["name"] == span]
    if not durs:
        return None
    return float(np.percentile(durs, percentile))
