"""Two shares of the chip's peaks for a ``--model glm4_moe_lite`` train
step, in %, from ``benchmark/roofline_glm4_moe_lite.py``'s counts and
``peaks.json``:

* ``share="attn_scores"``: the least time of every block's causal score and
  value products at the heads' own widths (keys and values 256 wide, 5 held
  heads, the stack's layers and the module's block; forward and twice that
  backward, over the bf16 peak, or their operands' bytes over the peak
  bandwidth, the larger) over the own device time of the ops under the scope
  ``attn_scores``;
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

The forward's recomputation is in every time and in no count: a share reads
low, never high. None where there is nothing to read: no trace, a driver
that counted no pairs, or a step's text with no ``attn_scores`` scope in it.
(The expert blocks' share is ``readers/roofline_moe.py``'s.)
"""

from benchmark import harness, roofline_glm4_moe_lite
from benchmark.readers import scope_device_ms


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_glm4_moe_lite.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share != "attn_scores":
        raise ValueError(f"unknown share {share!r}")
    scope_ms = scope_device_ms.read(ctx, ["attn_scores"])
    if not scope_ms:
        return None
    return 100.0 * roofline_glm4_moe_lite.attn_scores_least_seconds(
        flags, peaks)["seconds"] / (scope_ms / 1e3)
