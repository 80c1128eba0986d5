"""Share of the window that some span of the given names covers, in %: the
union of their intervals over the window. For spans of one thread's phases it
is how busy that thread was.

``ctx.spans`` holds the spans that START inside the window
(``harness.spans_in``): one that runs past the window's close is cut there,
and one that straddles its opening is not in the list at all, so the share
leaves out what such a span covers (at most one span of each thread)."""

from benchmark import xplane


def read(ctx, spans):
    lo, hi = float(ctx.window[0]), float(ctx.window[1])
    intervals = [(e["ts"] * 1e3, min((e["ts"] + e["dur"]) * 1e3, hi))
                 for e in ctx.spans if e["name"] in spans]
    if not intervals or hi <= lo:
        return None
    return 100.0 * sum(b - a for a, b in xplane.union(intervals)) / (hi - lo)
