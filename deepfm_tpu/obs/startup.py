"""The start-up record: what the process did before its first dispatch.

``obs.trace`` can only see what happens after ``configure()``, and the
launcher configures it after the imports, the compile cache and the
distributed bootstrap: most of a short run's set-up. This module keeps those
phases whether tracing is on or off, so that one log line can say where
set-up went and an exported trace shows them on the timeline of
``train.dispatch``.

The record is one bounded list (``CAPACITY`` entries; what does not fit is
counted, like the ring's ``dropped``) of complete phases ``(name, t0_ns,
t1_ns, tid, attrs)`` on ``time.time_ns()``: the tracer's clock, hence the
device trace's. ``Tracer.events()`` merges it in, so a phase is stored once,
here, and is in the export of whichever tracer is installed when the export
is made. Its origin is the start of the process (``/proc/self/stat``; the
import of this module where that cannot be read).

Phases (``phase()`` / ``importing()`` / ``first_fit()``; TUNING §17):

- ``setup.import`` (``module=``): inclusive and nested by containment. An
  import that another thread makes (the benchmark's driver imports
  ``train.tasks`` beside JAX's start) carries that thread's ``tid``: readers
  take unions over time.
- ``setup.distributed``, ``setup.backend``, ``setup.trainer``,
  ``setup.state`` (``source=init|checkpoint``, ``restored_step``),
  ``setup.pipeline``, ``setup.first_batch`` (entry of ``fit`` until the first
  staged superbatch is in hand) and ``setup.first_dispatch`` (the process's
  first ``train.dispatch``: trace, lower, compile or fetch, enqueue). The
  first dispatch closes the record: later ``setup.*`` phases are not kept.
- ``compile.trace`` / ``compile.lower`` / ``compile.backend`` (``fun_name=``,
  JAX's own start and end; ``cache=hit|miss`` where the persistent cache said)
  and ``compile.cache_fetch`` (inside the ``compile.backend`` that fetched),
  from ``jax.monitoring`` (``listen_to_jax()``). While the record is open they
  go into it; afterwards they are ordinary tracer spans (nothing when tracing
  is off), so a recompilation in the middle of a run is on the timeline with
  the function that caused it.

Stdlib-only at import time, as the rest of ``obs``.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

CAPACITY = 256

#: ``(name, t0_ns, t1_ns, tid, attrs)``
Phase = Tuple[str, int, int, int, Dict]

#: ``jax.monitoring`` time spans -> span names.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAID = {"/jax/compilation_cache/cache_hits": "hit",
               "/jax/compilation_cache/cache_misses": "miss"}
#: JAX times the tracing of every inner ``jit`` of a program as well (some
#: hundreds of ``compile.trace`` of tens of microseconds in the smallest
#: trainer's set-up, each inside the outer function's, so that no union over
#: time misses them): a trace shorter than this is not kept.
MIN_TRACE_NS = 1_000_000


def _process_start_ns(fallback_ns: int) -> int:
    """When the kernel started this process, on the wall clock: its start in
    clock ticks since boot (field 22 of ``/proc/self/stat``) over the boot
    time that ``/proc/uptime`` implies. 10 ms fine."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces: count from its ')'
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        now = time.time_ns()
        start = now - int((uptime_s - ticks / os.sysconf("SC_CLK_TCK")) * 1e9)
    except (OSError, ValueError, IndexError):
        return fallback_ns
    # a clock that disagrees with the kernel's (a time namespace) is no origin
    return start if 0 < start <= fallback_ns else fallback_ns


_lock = threading.Lock()
_phases: List[Phase] = []
_dropped = 0
_open = True
_origin_ns = _process_start_ns(time.time_ns())
_listening = False
_cache_said = threading.local()


class _NullPhase:
    __slots__ = ()

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def add(self, **attrs) -> "_NullPhase":
        return self


_NULL = _NullPhase()


class _Phase:
    __slots__ = ("_name", "_attrs", "_t0")

    def __init__(self, name: str, attrs: Dict):
        self._name = name
        self._attrs = attrs
        self._t0 = 0

    def __enter__(self) -> "_Phase":
        self._t0 = time.time_ns()
        return self

    def add(self, **attrs) -> "_Phase":
        """Attributes known only inside the phase (``restored_step``)."""
        self._attrs.update(attrs)
        return self

    def __exit__(self, *exc) -> bool:
        _keep(self._name, self._t0, time.time_ns(), self._attrs)
        return False


def _keep(name: str, t0_ns: int, t1_ns: int, attrs: Dict) -> bool:
    """Store one phase; False once the record is closed."""
    global _dropped
    with _lock:
        if not _open:
            return False
        if len(_phases) >= CAPACITY:
            _dropped += 1
        else:
            _phases.append((name, t0_ns, t1_ns, threading.get_ident(), attrs))
        return True


def phase(name: str, **attrs):
    """``with phase("setup.trainer"):`` — one phase of start-up on the
    calling thread. A shared no-op once the record is closed."""
    return _Phase(name, attrs) if _open else _NULL


def importing(module: str):
    """``with importing("orbax.checkpoint"): import orbax.checkpoint`` — a
    ``setup.import`` phase where this is the import that does the work: a
    module that is loaded already (or that another thread is loading) costs
    nothing here, and is stamped where it was paid."""
    if not _open or module in sys.modules:
        return _NULL
    return _Phase("setup.import", {"module": module})


def imported(module: str, t0_ns: int) -> None:
    """A module's own import, from the stamp at its top to this call at the
    end of its imports: for a root that callers import by name, where no
    caller of the program's brackets it."""
    _keep("setup.import", t0_ns, time.time_ns(), {"module": module})


def close() -> None:
    global _open
    with _lock:
        _open = False


def reset() -> None:
    """An empty, open record (tests; ``obs.trace.reset`` calls this)."""
    global _open, _dropped
    with _lock:
        _phases.clear()
        _dropped = 0
        _open = True


def phases() -> List[Phase]:
    """A snapshot of the record, in the order the phases ended."""
    with _lock:
        return list(_phases)


def process_start_ns() -> int:
    return _origin_ns


def dropped() -> int:
    return _dropped


def events() -> List[Dict]:
    """The record as Chrome trace events: one complete ("X") event a phase
    and, before them, the process's start as an instant
    (``setup.process_start``). Nothing while the record is empty."""
    held = phases()
    if not held:
        return []
    pid = os.getpid()
    out = [{"name": "setup.process_start", "ph": "i", "s": "p",
            "ts": _origin_ns / 1e3, "pid": pid, "tid": held[0][3]}]
    for name, t0, t1, tid, attrs in held:
        ev = {"name": name, "ph": "X", "ts": t0 / 1e3,
              "dur": (t1 - t0) / 1e3, "pid": pid, "tid": tid}
        if attrs:
            ev["args"] = dict(attrs)
        out.append(ev)
    return out


# --------------------------------------------------------------------------
# The fit loop's two phases.
# --------------------------------------------------------------------------

class _FirstFit:
    """Handed to the ``fit`` that may make the process's first dispatch."""

    __slots__ = ("_t0", "_t_batch")

    def __init__(self) -> None:
        self._t0 = self._t_batch = time.time_ns()

    def batch_in_hand(self) -> None:
        """The first staged superbatch is in the loop's hand:
        ``setup.first_batch`` ends."""
        self._t_batch = time.time_ns()
        _keep("setup.first_batch", self._t0, self._t_batch, {})

    def dispatched(self, steps: int) -> str:
        """The first dispatch is enqueued: ``setup.first_dispatch`` ends, the
        record closes, and the operator's line comes back."""
        _keep("setup.first_dispatch", self._t_batch, time.time_ns(),
              {"steps": int(steps)})
        close()
        return log_line()


def first_fit() -> Optional[_FirstFit]:
    """None once some ``fit`` of this process has dispatched."""
    return _FirstFit() if _open else None


# --------------------------------------------------------------------------
# Compilation, by name.
# --------------------------------------------------------------------------

def _compile_span(name: str, t0_ns: int, t1_ns: int, attrs: Dict) -> None:
    if not _keep(name, t0_ns, t1_ns, attrs):
        from . import trace  # noqa: PLC0415  (trace imports this module)

        trace.complete(name, t0_ns, t1_ns, **attrs)


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kw) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    if name == "compile.trace" and end_time - start_time < MIN_TRACE_NS / 1e9:
        return
    attrs = {"fun_name": str(kw.get("fun_name", "?"))}
    if name == "compile.backend":
        said = _cache_said.__dict__.pop("cache", None)
        if said is not None:
            attrs["cache"] = said
    _compile_span(name, int(start_time * 1e9), int(end_time * 1e9), attrs)


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == _CACHE_FETCH:
        # JAX reports a fetch when it has it, on the thread that compiles
        t1 = time.time_ns()
        _compile_span("compile.cache_fetch", t1 - int(duration_secs * 1e9),
                      t1, {"cache": "hit"})


def _on_event(event: str, **kw) -> None:
    said = _CACHE_SAID.get(event)
    if said is not None:
        # both come inside ``compile_or_get_cached``, which the backend's
        # span wraps: kept for the span that ends next on this thread
        _cache_said.cache = said


def listen_to_jax() -> None:
    """Register the listeners that turn JAX's own compile timings into
    ``compile.*`` spans. Idempotent; imports ``jax.monitoring`` when called."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring as monitoring  # noqa: PLC0415

    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


# --------------------------------------------------------------------------
# Reading the record: the operator's line.
# --------------------------------------------------------------------------

def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Nanoseconds that at least one of the intervals covers."""
    total, end = 0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


#: The line's phases, in order: label -> span name.
_LINE = (("import", "setup.import"), ("distributed", "setup.distributed"),
         ("backend", "setup.backend"), ("trainer", "setup.trainer"),
         ("state", "setup.state"), ("pipeline", "setup.pipeline"),
         ("first batch", "setup.first_batch"),
         ("first dispatch", "setup.first_dispatch"))
#: A third-party import is named in the line from this many seconds.
_NAMED_IMPORT_S = 0.5


def summary(held: Optional[List[Phase]] = None,
            origin_ns: Optional[int] = None,
            open_ns: Optional[int] = None) -> Dict:
    """Seconds of start-up by phase, from the record (or from ``held``, a
    list of phases read back from a trace): ``total_s`` from the process's
    start to the end of ``setup.first_dispatch`` (of the last phase where
    there was none), ``phases`` {span name: union over time, all threads},
    ``imports`` {module: inclusive seconds}, ``first_dispatch`` (the
    ``compile.*`` unions and cache counts inside it) and ``uncovered_s``:
    the part of the total under no span of any thread. With ``open_ns``,
    when a measured window opened (the benchmark's reader), only phases that
    ended by then count, and ``warmup_s`` is the time from the end of
    ``setup.first_dispatch`` to it: None where there was no such dispatch,
    or no ``open_ns``."""
    held = phases() if held is None else held
    origin_ns = _origin_ns if origin_ns is None else origin_ns
    if open_ns is not None:
        held = [p for p in held if p[2] <= open_ns]
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for name, t0, t1, _, _ in held:
        by_name.setdefault(name, []).append((t0, t1))
    first = by_name.get("setup.first_dispatch")
    end_ns = first[-1][1] if first else max(
        (t1 for _, _, t1, _, _ in held), default=origin_ns)
    clipped = [(max(t0, origin_ns), min(t1, end_ns))
               for _, t0, t1, _, _ in held if t0 < end_ns and t1 > origin_ns]
    imports: Dict[str, float] = {}
    for name, t0, t1, _, attrs in held:
        if name == "setup.import":
            module = attrs.get("module", "?")
            imports[module] = imports.get(module, 0.0) + (t1 - t0) / 1e9
    inside = {"hit": 0, "miss": 0}
    stages: Dict[str, List[Tuple[int, int]]] = {}
    if first:
        f0, f1 = first[-1]
        for name, t0, t1, _, attrs in held:
            if name.startswith("compile.") and t0 < f1 and t1 > f0:
                stages.setdefault(name, []).append((t0, t1))
                if name == "compile.backend" and "cache" in attrs:
                    inside[attrs["cache"]] += 1
    return {
        "total_s": (end_ns - origin_ns) / 1e9,
        "phases": {name: union_ns(iv) / 1e9 for name, iv in by_name.items()},
        "imports": imports,
        "first_dispatch": {
            **{name: union_ns(stages.get(name, ())) / 1e9
               for name in ("compile.trace", "compile.lower",
                            "compile.backend")},
            "cache_hits": inside["hit"], "cache_misses": inside["miss"]},
        "uncovered_s": (end_ns - origin_ns - union_ns(clipped)) / 1e9,
        "warmup_s": ((open_ns - end_ns) / 1e9
                     if first and open_ns is not None else None),
        "dropped": _dropped,
    }


def log_line(held: Optional[List[Phase]] = None,
             origin_ns: Optional[int] = None) -> str:
    """``start-up 31.2 s: import 14.1 (orbax.checkpoint 11.0) · backend 3.2
    · … · first dispatch 9.8 (trace 1.1, lower 1.9, backend 6.6, cache 3 hit
    / 1 miss) · uncovered 0.6``: seconds, each phase the union over time of
    its spans; in the import's brackets the third-party packages that took
    ``_NAMED_IMPORT_S`` or more (inclusive, largest first); in the first
    dispatch's brackets JAX's tracing, lowering and backend compile (or
    cache fetch) inside it. TUNING §17 says how to read it."""
    s = summary(held, origin_ns)
    parts = []
    for label, name in _LINE:
        if name not in s["phases"]:
            continue
        part = f"{label} {s['phases'][name]:.1f}"
        if name == "setup.import":
            named = sorted(((sec, mod) for mod, sec in s["imports"].items()
                            if sec >= _NAMED_IMPORT_S
                            and not mod.startswith("deepfm_tpu")),
                           reverse=True)
            if named:
                part += " (" + ", ".join(f"{mod} {sec:.1f}"
                                         for sec, mod in named) + ")"
        elif name == "setup.first_dispatch":
            fd = s["first_dispatch"]
            part += (f" (trace {fd['compile.trace']:.1f}, lower "
                     f"{fd['compile.lower']:.1f}, backend "
                     f"{fd['compile.backend']:.1f}, cache "
                     f"{fd['cache_hits']} hit / {fd['cache_misses']} miss)")
        parts.append(part)
    parts.append(f"uncovered {s['uncovered_s']:.1f}")
    line = f"start-up {s['total_s']:.1f} s: " + " · ".join(parts)
    if s["dropped"]:
        line += f" ({s['dropped']} phases over the record's {CAPACITY} dropped)"
    return line
