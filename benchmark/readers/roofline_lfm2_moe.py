"""Three shares of the chip's peaks for a ``--model lfm2_moe`` train step,
in %, from ``benchmark/roofline_lfm2_moe.py``'s counts and ``peaks.json``:

* ``share="conv"``: the least time of the convolution mixers as the step
  runs them (their two products forward, recomputed and backward at the
  bf16 peak plus their elementwise passes' bytes at the peak bandwidth) over
  the own device time of the ops under the scopes ``conv`` and, inside it,
  ``conv_taps``;
* ``share="attn_scores"``: the least time of the full layers' causal score
  and value products at the head's real 64 lanes (forward and twice that
  backward, over the bf16 peak, or their operands' bytes over the peak
  bandwidth, the larger) over the own device time of the ops under the scope
  ``attn_scores``: a lane the kernel pads is in the time and in no count;
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

The forward's recomputation is in every time and, but for the convolution
mixer's, in no count: a share reads low, never high. None where there is
nothing to read: no trace, a driver that counted no pairs, or, for a scope's
share, a step's text with no such scope in it (a program from before the
scope). (The expert layers' share is ``readers/roofline_moe.py``'s.)
"""

from benchmark import harness, roofline_lfm2_moe
from benchmark.readers import scope_device_ms

#: share -> (the scopes whose own time it is over, its count)
SCOPED = {"conv": (["conv", "conv_taps"],
                   roofline_lfm2_moe.conv_least_seconds),
          "attn_scores": (["attn_scores"],
                          roofline_lfm2_moe.attn_scores_least_seconds)}


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_lfm2_moe.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share not in SCOPED:
        raise ValueError(f"unknown share {share!r}")
    scopes, least = SCOPED[share]
    scope_ms = scope_device_ms.read(ctx, scopes)
    if not scope_ms:
        return None
    return 100.0 * least(flags, peaks)["seconds"] / (scope_ms / 1e3)
