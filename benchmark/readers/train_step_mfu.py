"""The whole train step's share of the chip's bf16 peak, in %: the FLOPs the
mathematics of one step needs on one chip (``train_step_least_seconds(...)
["flops"]`` of the model's own count, ``benchmark/roofline_<model>.py``:
matrix products forward and backward, nothing recomputed, on the pairs
really routed where the model has experts), times the steps of the traced
window, over the peak rate times **all** of the window's time, idle and
collectives included.

It stands beside the kernels' and scopes' ``*_roofline`` shares and bounds
them: a change that takes a kernel off the path leaves that kernel's share
silent, and this one still says what the chip did with the window. A step
the bytes bound (the DeepFM cells': a tower of 170,016 weights under a
2.16 GB table) reads a small number, never 0.

The count's arguments are found by their names (``flags``, ``peaks``,
``chips`` = the cell's chips, ``pairs`` = the driver's
``moe_pairs_held_per_step``), so a later model brings its module and no edit
here. None where there is nothing to read: no trace, no steps, or a count
that wants pairs from a driver that counted none.
"""

import importlib
import inspect

from benchmark import harness

#: ``--model`` -> the module of its counts, where it is not
#: ``roofline_<model>``.
MODULES = {"deepfm": "roofline"}


def step_flops(ctx, peaks: dict):
    flags = ctx.cell.config["flags"]
    model = flags["model"]
    counts = importlib.import_module(
        "benchmark." + MODULES.get(model, f"roofline_{model}"))
    have = {"flags": flags, "peaks": peaks, "chips": len(ctx.devices),
            "pairs": ctx.counters.get("moe_pairs_held_per_step")}
    wants = inspect.signature(counts.train_step_least_seconds).parameters
    if "pairs" in wants and not have["pairs"]:
        return None
    return counts.train_step_least_seconds(
        **{name: have[name] for name in wants})["flops"]


def read(ctx):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    flops = step_flops(ctx, peaks)
    if not flops:
        return None
    return 100.0 * flops * steps \
        / (peaks["bf16_flops_per_s"] * ctx.trace["window_s"])
