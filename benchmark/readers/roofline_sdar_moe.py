"""Two shares of the chip's peaks for a ``--model sdar_moe`` train step, in
%, from ``benchmark/roofline_sdar_moe.py``'s counts and ``peaks.json``:

* ``share="moe_matmul"``: the least time of the expert layer's grouped
  products (their FLOPs on the pairs the run really routed to the experts
  held here, forward and backward, over the bf16 peak) over the device time
  of the grouped-product kernels themselves (the ops ``GROUPED`` names; the
  forward's recomputation is in the time and not in the count, and so are
  the buffer's spare rows: the share reads low, never high);
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

None where there is nothing to read: no trace, a driver that counted no
pairs (a program without the expert layer's counters), or, for
``moe_matmul``, a trace with no grouped-product op in it.
"""

import re

from benchmark import harness, roofline_sdar_moe
from benchmark.readers import scope_device_ms

#: An op of the trace that is a grouped product: XLA's TPU backend compiles
#: ``jax.lax.ragged_dot`` to a kernel it names ``ragged-dot-*``.
GROUPED = re.compile(r"^ragged-dot(?!-metadata)")


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not ctx.trace or not ctx.trace["devices"] or not steps or not pairs:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_sdar_moe.train_step_least_seconds(
            flags, pairs, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share != "moe_matmul":
        raise ValueError(f"unknown share {share!r}")
    path = scope_device_ms.newest_trace(ctx.cell.name)
    if path is None:
        return None
    ops, _ = scope_device_ms.own_seconds(path, ctx.window)
    seconds = sum(t for key, t in ops.items() if GROUPED.match(key))
    if not seconds:
        return None
    least = roofline_sdar_moe.moe_matmul_flops(flags, pairs) \
        / peaks["bf16_flops_per_s"]
    return 100.0 * least / (seconds / steps)
