"""One share of the chip's peaks for a ``--model sdar_moe`` train step, in
%, from ``benchmark/roofline_sdar_moe.py``'s counts and ``peaks.json``:

* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

(The expert layer's share is ``readers/roofline_moe.py``'s, for every cell
with experts.) None where there is nothing to read: no trace, or a driver
that counted no pairs (a program without the expert layer's counters).
"""

from benchmark import harness, roofline_sdar_moe


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share != "step":
        raise ValueError(f"unknown share {share!r}")
    least = roofline_sdar_moe.train_step_least_seconds(
        flags, pairs, peaks)["seconds"]
    return 100.0 * least / (ctx.trace["busy_s"] / steps)
