"""Seconds of set-up by phase, from the program's start-up record.

The program keeps what it did before its first dispatch whether tracing is on
or off (``deepfm_tpu.obs.startup``: ``setup.*`` phases and JAX's own
``compile.*`` timings, on the wall clock, with the thread each ran on). The
record is read through its accessor, not from ``ctx.spans``: those are the
events inside the window, and set-up is everything before it.

``what``:

- ``union``: the time that at least one span named in ``spans`` covers, over
  all threads (the driver imports ``train.tasks`` beside JAX's start), of the
  spans that end before the window opens. A phase that did not happen reads 0.
- ``warmup``: window open minus the end of ``setup.first_dispatch``: the
  dispatches that settle the device, the check's probe, the profiler's start.
- ``uncovered``: the end of ``setup.first_dispatch`` minus the process's
  start, minus the union of every span of the record: what no span covers.

So ``uncovered`` + the union of every span + ``warmup`` is window open minus
process start. The arithmetic is the program's own (``startup.summary`` with
the window's opening as its cut, ``startup.union_ns`` the one union), so the
launcher's line and these metrics cannot drift apart. None where the program
has no such record (an older program), or the record saw no first dispatch.
"""

import importlib


def _startup():
    """The program's module; None in a program from before the record."""
    try:
        return importlib.import_module("deepfm_tpu.obs.startup")
    except ImportError:
        return None


def read(ctx, what, spans=()):
    startup = _startup()
    if startup is None:
        return None
    return phase_seconds(startup.phases(), startup.process_start_ns(),
                         ctx.window[0], what, spans)


def phase_seconds(phases, origin_ns, open_ns, what, spans=()):
    """``phases`` are ``(name, t0_ns, t1_ns, tid, attrs)``."""
    startup = _startup()
    s = startup.summary(phases, origin_ns, open_ns)
    if s["warmup_s"] is None:
        return None
    if what == "warmup":
        return s["warmup_s"]
    if what == "uncovered":
        return s["uncovered_s"]
    if what != "union":
        raise ValueError(f"startup_phase_s cannot read {what!r}")
    return startup.union_ns((t0, t1) for name, t0, t1, _, _ in phases
                            if name in spans and t1 <= open_ns) / 1e9
