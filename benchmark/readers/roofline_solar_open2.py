"""Three shares of the chip's peaks for a ``--model solar_open2`` train step,
in %, from ``benchmark/roofline_solar_open2.py``'s counts and ``peaks.json``:

* ``share="attn_scores"``: the least time of the full layers' causal score
  and value products (forward and twice that backward, over the bf16 peak,
  or their operands' bytes over the peak bandwidth, the larger) over the own
  device time of the ops under the scope ``attn_scores``;
* ``share="kda_scan"``: the least time of the delta-rule recurrence's own
  work over the own device time of the ops under the scope ``kda_scan``;
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

The forward's recomputation is in every time and in no count: a share reads
low, never high. None where there is nothing to read: no trace, a driver
that counted no pairs, or, for a scope's share, a step's text with no such
scope in it (a program from before the scope).
"""

from benchmark import harness, roofline_solar_open2
from benchmark.readers import scope_device_ms

#: share -> (the scope whose own time it is over, its count)
SCOPED = {"attn_scores": roofline_solar_open2.attn_scores_least_seconds,
          "kda_scan": roofline_solar_open2.kda_scan_least_seconds}


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_solar_open2.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share not in SCOPED:
        raise ValueError(f"unknown share {share!r}")
    scope_ms = scope_device_ms.read(ctx, [share])
    if not scope_ms:
        return None
    return 100.0 * SCOPED[share](flags, peaks)["seconds"] / (scope_ms / 1e3)
