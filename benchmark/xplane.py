"""Reduction of a profiler trace (``.xplane.pb``) to what the benchmark
reports: device busy time as the union of op intervals, per-op totals, the
longest idle gaps named for the program span that covers them, and the time
in collectives.

Read with ``jax.profiler.ProfileData`` alone. The trace's clock starts at
the profiler's start, which the trace records as wall-clock time
(``profile_start_time`` of its ``Task Environment`` plane): that ties it to
the wall clock of the program's spans.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
#: The opcode in an op's HLO text: the first ``word(`` after the `` = ``.
OPCODE = re.compile(r"([a-z][a-z0-9\-]*)\(")
#: Spans an idle gap may be named for, in the program's own words: what the
#: fit thread, the staging thread, the collector or the server was doing
#: while the device had nothing to run.
GAP_SPANS = ("stage.wait", "stage.transfer", "train.dispatch", "serve.batch",
             "serve.handoff_wait", "serve.flush", "stage.input_wait",
             "train.log_sync", "host.gc")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged copy of ``intervals`` (overlaps and touches joined)."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of the merged ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def name_gap(gap: Interval, spans: Sequence[dict]) -> str:
    """The program span (of ``GAP_SPANS``) that covers most of ``gap``;
    ``host.other`` when none overlaps it. Span ``ts``/``dur`` are in
    microseconds of wall clock, the gap in nanoseconds of the same clock."""
    cover: Dict[str, float] = {}
    for e in spans:
        if e["name"] not in GAP_SPANS:
            continue
        a, b = e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > 0:
            cover[e["name"]] = cover.get(e["name"], 0.0) + ov
    return max(cover, key=cover.get) if cover else "host.other"


HLO_TEXT = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")
#: Ops that only contain others: their own time is what their children leave.
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]* = ")


def op_label(name: str) -> str:
    """``%fusion.267 = f32[16881344,32]{...} fusion(...)`` ->
    ``fusion.267_f32_16881344_32``: the op's name and its result's type, as
    one short token (today's op names are numbers; the shape says more)."""
    m = HLO_TEXT.match(name)
    if m:
        name = f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', '_')}"
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name.strip())[:64].strip("_")


def is_collective(name: str) -> bool:
    """Whether the op is a collective, by its opcode (its name is whatever
    the program called it: the gradient all-reduce is ``psum_invariant``)."""
    m = OPCODE.search(name.partition(" = ")[2])
    return bool(m and COLLECTIVE.match(m.group(1)))


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Time in each op that is not inside an op it contains (the ops line
    nests: a ``while`` spans the ops of its body), summed by event name."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, float, str]] = []
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out[stack[-1][2]] -= min(b, stack[-1][1]) - a
        out[name] = out.get(name, 0.0) + (b - a)
        stack.append((a, b, name))
    return out


def profile_start_ns(profile) -> float:
    """Wall-clock time (ns since the epoch) of the trace's time zero."""
    for plane in profile.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return float(stats["profile_start_time"])
    raise RuntimeError("the trace does not say when it started")


def reduce(path: str, *, window_ns: Tuple[int, int],
           spans: Sequence[dict] = ()) -> dict:
    """Reduce the trace at ``path`` over the wall-clock window ``window_ns``.

    Returns ``busy_s`` (mean over device planes of the union of op
    intervals), ``window_s``, ``device_ops`` ([name, seconds] by own time,
    mean over devices), ``idle_gaps`` ([span name, seconds], longest first,
    on the first device), ``collective_s`` (mean over devices) and
    ``devices`` (planes reduced)."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    shift = profile_start_ns(profile)    # trace clock -> wall clock, ns
    lo, hi = float(window_ns[0]), float(window_ns[1])
    busy_total = coll_total = 0.0
    ops: Dict[str, float] = {}
    first_gaps: List[Interval] = []
    planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for k, plane in enumerate(planes):
        line = next((ln for ln in plane.lines if ln.name == OPS_LINE), None)
        if line is None:
            raise RuntimeError(f"{plane.name} has no {OPS_LINE!r} line")
        events = []
        for ev in line.events:
            a = ev.start_ns + shift
            b = a + ev.duration_ns
            if b > lo and a < hi:
                events.append((max(a, lo), min(b, hi), ev.name))
        for name, t in self_times(events).items():
            if CONTAINERS.match(name):
                continue
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + t
            if is_collective(name):
                coll_total += t
        busy = union((a, b) for a, b, _ in events)
        busy_total += sum(b - a for a, b in busy)
        if k == 0:
            first_gaps = gaps(busy, lo, hi)
    n = max(len(planes), 1)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(first_gaps, key=lambda g: g[0] - g[1])[:5]
    return {
        "devices": len(planes),
        "busy_s": busy_total / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "collective_s": coll_total / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in top_gaps],
    }
