"""The expert layer's share of the chip's bf16 peak in a train step, in %:
the least time of the routed pairs' grouped products over the own device
time of the ops under the scope ``moe``.

* The count is of the mathematics: ``3 x forward_flops(flags,
  pairs)["experts"]`` of the cell's own ``benchmark/roofline_<model>.py``
  (three products a held pair, forward and twice that backward), ``pairs``
  the (position, expert) pairs the run really routed to the experts held
  here. No recomputation, no spare rows of the buffer, no router.
* The time is the whole layer's as the step runs it (``train_moe_device_ms``:
  norm, router, the pairs' sort, the rows' way there and back, the products
  and the pass the backward recomputes), found by the scope the program
  gives its ops and by nothing of their names: it reads the same work
  whatever kernel, tiling or buffer size implements the layer.

So the share reads low, never high. It goes through
``scope_device_ms.read``, which reduces a traced window once for all the
scope metrics of a cell: no compilation and no pass over the trace of its
own. None where there is nothing to read: no trace, a driver that counted no
pairs, or a step's text without the scope.
"""

import importlib

from benchmark import harness
from benchmark.readers import scope_device_ms

SCOPES = ["moe"]


def least_seconds(flags: dict, pairs: float, peaks: dict) -> float:
    """The grouped products' FLOPs of one step, forward and backward, at the
    bf16 peak."""
    counts = importlib.import_module(f"benchmark.roofline_{flags['model']}")
    return 3.0 * counts.forward_flops(flags, pairs)["experts"] \
        / peaks["bf16_flops_per_s"]


def read(ctx):
    pairs = ctx.counters.get("moe_pairs_held_per_step")
    if not pairs:
        return None
    moe_ms = scope_device_ms.read(ctx, SCOPES)
    if not moe_ms:
        return None
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    return 100.0 * least_seconds(ctx.cell.config["flags"], pairs, peaks) \
        / (moe_ms / 1e3)
