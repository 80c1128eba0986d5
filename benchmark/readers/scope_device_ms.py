"""Device time per optimizer step in the phases of the train step, in ms.

The program names the phases with ``jax.named_scope`` (``embed``, ``fm``,
``tower``, ``loss``, ``l2``, ``opt``), which reach every HLO instruction's
``op_name``. A profiler event carries the instruction's text up to its
operands and nothing of its ``op_name`` (its stats are a device offset and a
duration, whatever ``enable_hlo_proto`` says: my chip run, PR 25), so the
program supplies the map: ``Trainer.step_hlo_text()`` is the compiled step,
and ``profiling.hlo_op_scopes`` reads {instruction name: innermost scope, ""
for none} from it. A fusion belongs to the scope of its root op, whose
``op_name`` the compiler puts on the fusion.

The map is keyed by what the trace and the text both say of an instruction:
its name and its (first) result's type and dimensions, ``fusion.267
f32[16881344,32]`` (``op_key``). The text comes from a second compilation,
so a name alone would not do: a compilation that numbered its fusions
another way would keep the set of names and charge the time to the wrong
phase in silence. An op whose name is there with another result is an op
the map does not know.

``read(ctx, scopes)``: own time (``xplane.self_times``: an op's time less
what the ops it contains take) of the non-collective ops whose scope is in
``scopes``, clipped to the window, mean over device planes, over the steps
in the window. ``scopes: []`` means the ops in no known scope. The four
metrics and the collectives' own time add up to the busy time.

None, and the metric is left out, where there is nothing to read: no device
trace; a program that has no ``step_hlo_text`` (the parent of PR 25); a map
that does not know the trace's ops (more than ``UNKNOWN_LIMIT`` of their
time: it is of another program); or, for a named scope, a map with no scope
in it (an executable cached before the scopes were added keeps its old
names: the persistent cache's key leaves debug info out). In the last case
only the unscoped metric is reported, and it is the whole step.

The map costs one more compilation of the step, after the window (9.6 to
14.2 s: my chip runs, PR 25). It is not a fetch from the persistent cache:
the Pallas kernels' payload carries the Python call sites they were lowered
from, so the cache's key differs between the fit's call and this one. The
trace's executable itself is out of a reader's reach: ``Context`` carries
neither the driver's trainer nor its compiled step (PERF.md §7).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional, Sequence, Tuple

from benchmark import harness, xplane

#: ``%fusion.267 = (f32[16881344,32]{0,1:T(8,128)}, ...) fusion(...)``, as a
#: trace's event and (indented, or after ``ROOT``) a line of the compiled
#: text have it -> ``fusion.267`` and ``f32[16881344,32]``.
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(*(\w+\[[\d,]*\])")
UNSCOPED, UNKNOWN = "", "?"
#: Share of the ops' time the map may not know before it counts as the map
#: of another program.
UNKNOWN_LIMIT = 0.01

#: trace path -> own seconds by scope (None: a map of another program), of
#: the newest trace only: the cell's four metrics read the same one.
_reduced: Dict[str, Optional[Dict[str, float]]] = {}


def newest_trace(cell: str) -> Optional[str]:
    """The traced window's file. ``Context`` has no path to it; it is still
    in the run's work directory when the readers run."""
    found = glob.glob(os.path.join(
        harness.ROOT, ".bench_work", glob.escape(cell) + ".*", "trace",
        "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def op_key(text: str) -> str:
    """``fusion.267 f32[16881344,32]`` of an instruction's text; the text
    itself where it is no instruction with an array in its result."""
    m = INSTRUCTION.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def own_seconds(path: str, window_ns: Tuple[int, int]
                ) -> Tuple[Dict[str, float], float]:
    """({``op_key``: own seconds}, collectives' own seconds) inside the
    window, mean over device planes; containers (``while``) left out, as
    ``xplane.reduce`` leaves them out of its ops."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    shift = xplane.profile_start_ns(profile)
    lo, hi = float(window_ns[0]), float(window_ns[1])
    ops: Dict[str, float] = {}
    collective = 0.0
    planes = [p for p in profile.planes if xplane.DEVICE_PLANE.match(p.name)]
    for plane in planes:
        line = next(ln for ln in plane.lines if ln.name == xplane.OPS_LINE)
        events = []
        for ev in line.events:
            a = ev.start_ns + shift
            b = a + ev.duration_ns
            if b > lo and a < hi:
                events.append((max(a, lo), min(b, hi), ev.name))
        for name, t in xplane.self_times(events).items():
            if xplane.CONTAINERS.match(name):
                continue
            if xplane.is_collective(name):
                collective += t
                continue
            key = op_key(name)
            ops[key] = ops.get(key, 0.0) + t
    n = max(len(planes), 1) * 1e9
    return {k: v / n for k, v in ops.items()}, collective / n


def by_scope(ops: Dict[str, float], op_scopes: Dict[str, str]
             ) -> Optional[Dict[str, float]]:
    """Own seconds summed by scope (``UNSCOPED`` for ops in none); None when
    the map does not know the ops."""
    out: Dict[str, float] = {}
    for name, t in ops.items():
        scope = op_scopes.get(name, UNKNOWN)
        out[scope] = out.get(scope, 0.0) + t
    if out.pop(UNKNOWN, 0.0) > UNKNOWN_LIMIT * sum(ops.values()):
        return None
    return out


def value_ms(times: Optional[Dict[str, float]], scopes: Sequence[str],
             steps: float) -> Optional[float]:
    if times is None:
        return None
    if not scopes:
        return 1e3 * times.get(UNSCOPED, 0.0) / steps
    if not any(scope != UNSCOPED for scope in times):
        return None
    return 1e3 * sum(times.get(s, 0.0) for s in scopes) / steps


def keyed_scopes(hlo_text: str, scopes: Dict[str, str]) -> Dict[str, str]:
    """{``op_key``: scope} over the lines of a compiled program's text,
    ``scopes`` being the program's {instruction name: scope} of it."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m and m.group(1) in scopes:
            out[op_key(line)] = scopes[m.group(1)]
    return out


def program_op_scopes(ctx) -> Optional[Dict[str, str]]:
    """The map from the program: a trainer built as the driver built its
    own, compiling the step for the shapes the window ran (after the compile
    counter has closed)."""
    from benchmark.drivers import _program

    trainer = _program.build_trainer(
        _program.make_config(dict(ctx.cell.config["flags"])), ctx.devices)
    supplies = getattr(trainer, "step_hlo_text", None)
    if supplies is None:
        return None
    from deepfm_tpu.utils import profiling

    text = supplies()
    return keyed_scopes(text, profiling.hlo_op_scopes(text))


def read(ctx, scopes):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    path = newest_trace(ctx.cell.name)
    if path is None:
        return None
    if path not in _reduced:
        op_scopes = program_op_scopes(ctx)
        if op_scopes is None:
            return None
        ops, _ = own_seconds(path, ctx.window)
        _reduced.clear()
        _reduced[path] = by_scope(ops, op_scopes)
    return value_ms(_reduced[path], scopes, steps)
