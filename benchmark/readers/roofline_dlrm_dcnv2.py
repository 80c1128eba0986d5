"""Two shares of the chip's peaks for a DLRM-DCNv2 train step, in %, from
``benchmark/roofline_dlrm_dcnv2.py``'s counts and ``peaks.json``:

* ``share="matmul"``: the least time of the step's matrix products (their
  FLOPs over the bf16 peak) over the device time the step spent in the
  scopes that hold them, ``bottom``, ``cross`` and ``tower`` (the elementwise
  work of those blocks is in the time and not in the count, so the share
  reads low, never high);
* ``share="step"``: the least time of the whole step (the larger of those
  FLOPs over the peak rate and the touched rows' bytes over the peak
  bandwidth) over its device time, as ``train_step_roofline`` is for DeepFM.

None where there is nothing to read: no trace, or (for ``matmul``) a program
whose compiled step names none of the three scopes.
"""

from benchmark import harness, roofline_dlrm_dcnv2
from benchmark.readers import scope_device_ms

MATMUL_SCOPES = ["bottom", "cross", "tower"]


def read(ctx, share):
    steps = ctx.counters.get("steps_in_window")
    if not ctx.trace or not ctx.trace["devices"] or not steps:
        return None
    flags, chips = ctx.cell.config["flags"], len(ctx.devices)
    peaks = harness.peaks_for(ctx.devices[0].device_kind)
    if share == "step":
        least = roofline_dlrm_dcnv2.train_step_least_seconds(
            flags, chips, peaks)["seconds"]
        return 100.0 * least / (ctx.trace["busy_s"] / steps)
    if share != "matmul":
        raise ValueError(f"unknown share {share!r}")
    ms = scope_device_ms.read(ctx, MATMUL_SCOPES)
    if not ms:
        return None
    least = roofline_dlrm_dcnv2.matmul_flops(flags, chips) \
        / peaks["bf16_flops_per_s"]
    return 100.0 * least / (1e-3 * ms)
