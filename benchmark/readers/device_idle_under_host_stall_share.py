"""Share of the traced window in which the first device ran no op *and* a
``host.stall`` span of the program's pulse lay over it, in %: the part of
``device_idle_share.train`` that a stall of the host process explains. What
is left of the idle share is the program's own.

The idle intervals are the reader's own pass over the window's trace file
(``scope_device_ms.newest_trace``; ``Context`` carries the reduction, not
the gaps): the first device plane's ops line, clipped to the window, as
``xplane.reduce`` takes the gaps it names. The stalls are cut at the
window's close; one that straddles its opening is not in ``ctx.spans``.

0.0 where the pulse ran and no stall fell in the window (the trace is then
not read again). None without a device trace, or where the window holds no
span of the pulse at all (a program without it)."""

from typing import List, Sequence, Tuple

from benchmark import xplane
from benchmark.readers import host_pulse, scope_device_ms

Interval = Tuple[float, float]


def first_device_idle(path: str, window_ns: Tuple[int, int]
                      ) -> List[Interval]:
    """Intervals of the window, in wall-clock ns, in which no op ran on the
    first device of the trace at ``path``."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    shift = xplane.profile_start_ns(profile)
    lo, hi = float(window_ns[0]), float(window_ns[1])
    planes = [p for p in profile.planes if xplane.DEVICE_PLANE.match(p.name)]
    if not planes:
        return []
    plane = min(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    line = next(ln for ln in plane.lines if ln.name == xplane.OPS_LINE)
    busy = []
    for ev in line.events:
        a = ev.start_ns + shift
        b = a + ev.duration_ns
        if b > lo and a < hi:
            busy.append((max(a, lo), min(b, hi)))
    return xplane.gaps(xplane.union(busy), lo, hi)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    lo, hi = float(ctx.window[0]), float(ctx.window[1])
    if not ctx.trace or not ctx.trace["devices"] or hi <= lo:
        return None
    stalled = xplane.union(host_pulse.stalls(ctx))
    if not stalled:
        return 0.0 if host_pulse.pulses(ctx) else None
    path = scope_device_ms.newest_trace(ctx.cell.name)
    if path is None:
        return None
    return 100.0 * overlap(first_device_idle(path, ctx.window),
                           stalled) / (hi - lo)
