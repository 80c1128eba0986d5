"""Three shares of the chip's peaks for a ``--model afmoe`` train step, in
%, from ``benchmark/roofline_afmoe.py``'s counts and ``peaks.json``:

* ``share="attn_scores"``: the least time of the full layers' causal score
  and value products (every allowed pair over the head's 128 lanes, 32 query
  heads; forward and twice that backward, over the bf16 peak, or their
  operands' bytes over the peak bandwidth, the larger) over the own device
  time of the ops under the scope ``attn_scores``;
* ``share="attn_scores_window"``: the same of the windowed layers' band over
  the scope ``attn_scores_window``: the band's partly masked edge blocks are
  in the time and in no count;
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

The forward's recomputation is in every time and in no count: a share reads
low, never high, and the same whatever implements the scores: a scope's time
is found by the scope the program gives its ops and by nothing of their
names. None where there is nothing to read: no trace, a driver that counted
no pairs, or a step's text with no such scope in it (a program from before
the scope). (The expert layers' share is ``readers/roofline_moe.py``'s.)
"""

from benchmark import harness, roofline_afmoe
from benchmark.readers import scope_device_ms


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_afmoe.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share not in roofline_afmoe.MASKS:
        raise ValueError(f"unknown share {share!r}")
    scope_ms = scope_device_ms.read(ctx, [share])
    if not scope_ms:
        return None
    return 100.0 * roofline_afmoe.attn_scores_least_seconds(
        flags, peaks, share)["seconds"] / (scope_ms / 1e3)
