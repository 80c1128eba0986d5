"""Two shares of the chip's peaks for a ``--model kimi_linear`` train step,
in %, from ``benchmark/roofline_kimi_linear.py``'s counts and ``peaks.json``:

* ``share="kda_scan"``: the least time of the delta-rule recurrence's own
  work (its FLOPs over the bf16 peak or its inputs' and output's bytes over
  the peak bandwidth, the larger; forward and backward) over the own device
  time of the ops under the scope ``kda_scan`` (the forward's recomputation
  is in the time and not in the count: the share reads low, never high);
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

None where there is nothing to read: no trace, a driver that counted no
pairs, or, for ``kda_scan``, a step's text with no such scope in it.
"""

from benchmark import harness, roofline_kimi_linear
from benchmark.readers import scope_device_ms


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_kimi_linear.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share != "kda_scan":
        raise ValueError(f"unknown share {share!r}")
    scan_ms = scope_device_ms.read(ctx, ["kda_scan"])
    if not scan_ms:
        return None
    least = roofline_kimi_linear.kda_scan_least_seconds(flags, peaks)
    return 100.0 * least["seconds"] / (scan_ms / 1e3)
