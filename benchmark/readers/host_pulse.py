"""What the program's host pulse (``deepfm_tpu.obs.trace``: ``host.pulse``
once a second, ``host.stall`` for every beat of the pulse thread that woke
more than 20 ms late) says of the window, in ms.

``read(ctx, attribute)`` folds one attribute of the window's ``host.pulse``
spans: those that end inside the window (one that runs past its close
carries what the process did after it: stopping the profiler, closing the
pipeline), or, in a window shorter than two pulses, those that start in it.

- ``late_ms_max``: the largest, together with the window's ``host.stall``
  spans cut at the window's close (a stall in the window's last, unfinished
  second is in no whole pulse): how long the process last went without
  running Python, whichever thread suffered.
- ``runq_ms`` (any other attribute): the sum: how much of those seconds the
  pulse thread stood runnable without a CPU.

None where the window holds no ``host.pulse`` (a program without the pulse),
or none that carries the attribute (a host without the source)."""


def pulses(ctx):
    """The window's ``host.pulse`` spans, whole seconds first."""
    found = [e for e in ctx.spans if e["name"] == "host.pulse"]
    close = float(ctx.window[1])
    return [e for e in found
            if (e["ts"] + e["dur"]) * 1e3 <= close] or found


def stalls(ctx):
    """[(start, end)] in ns of the window's ``host.stall`` spans, cut at its
    close (``ctx.spans`` holds the spans that start inside the window)."""
    close = float(ctx.window[1])
    return [(e["ts"] * 1e3, min((e["ts"] + e["dur"]) * 1e3, close))
            for e in ctx.spans if e["name"] == "host.stall"]


def read(ctx, attribute):
    values = [e["args"][attribute] for e in pulses(ctx)
              if attribute in e.get("args", {})]
    if not values:
        return None
    if attribute == "late_ms_max":
        return max(values + [(b - a) / 1e6 for a, b in stalls(ctx)])
    return sum(values)
