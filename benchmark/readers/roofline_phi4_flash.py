"""Four shares of the chip's peaks for a ``--model phi4_flash`` train step,
in %, from ``benchmark/roofline_phi4_flash.py``'s counts and ``peaks.json``:

* ``share="mamba_scan"``: the least time of the selective recurrences' own
  work (its operations over the bf16 peak or its inputs' and output's bytes
  over the peak bandwidth, the larger; forward and backward) over the own
  device time of the ops under the scope ``mamba_scan``;
* ``share="attn_scores"``: the least time of the attention layers' masked
  score and value products (both maps of every query pair on the pairs the
  window and the causal mask allow, at the heads' real lanes) over the own
  device time of the ops under the scope ``attn_scores``;
* ``share="matmul"``: the least time of the step's dense matrix products
  (their FLOPs over the bf16 peak) over the own device time of the scopes
  that hold them, ``mamba``, ``gmu``, ``attn``, ``mlp`` and ``head`` (the
  scan and the scores have scopes of their own inside and are in neither
  the count nor the time; the elementwise work of those blocks is in the
  time and in no count);
* ``share="step"``: the least time of the whole step (the larger of its
  matrix products' FLOPs over the peak rate and its parameters' bytes over
  the peak bandwidth) over its device time.

The forward's recomputation is in every time and in no count: a share reads
low, never high. None where there is nothing to read: no trace, or, for a
scope's share, a step's text with no such scope in it (a program from
before the scope).
"""

from benchmark import harness, roofline_phi4_flash
from benchmark.readers import scope_device_ms

MATMUL_SCOPES = ["mamba", "gmu", "attn", "mlp", "head"]
#: share -> (the scopes whose own time it is over, its least seconds)
SCOPED = {
    "mamba_scan": (["mamba_scan"],
                   roofline_phi4_flash.mamba_scan_least_seconds),
    "attn_scores": (["attn_scores"],
                    roofline_phi4_flash.attn_scores_least_seconds),
    "matmul": (MATMUL_SCOPES, lambda flags, peaks: {
        "seconds": roofline_phi4_flash.matmul_flops(flags)
        / peaks["bf16_flops_per_s"]})}


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    flags = ctx.cell.config["flags"]
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_phi4_flash.train_step_least_seconds(
            flags, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share not in SCOPED:
        raise ValueError(f"unknown share {share!r}")
    scopes, least = SCOPED[share]
    scope_ms = scope_device_ms.read(ctx, scopes)
    if not scope_ms:
        return None
    return 100.0 * least(flags, peaks)["seconds"] / (scope_ms / 1e3)
