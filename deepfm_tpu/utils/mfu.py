"""MFU (model FLOPs utilization) against a published device peak.

An MFU is only meaningful relative to the peak it is divided by, and a peak
exists only for a device whose spec sheet gives one. For a recognized TPU
that is the chip's dense bf16 peak. For anything else — the CPU backend, an
accelerator not in the table — there is no number: a host has no spec-sheet
peak, and an MFU against a made-up one reads as a device metric that nobody
measured.
"""
from typing import Optional

# Dense bf16 peak FLOP/s per chip by device_kind (public spec sheets).
# Matched by substring against jax's device_kind.
PEAK_FLOPS_BF16 = {
    "v6e": 918e12, "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12, "v5 lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """Dense bf16 peak FLOP/s of one chip of ``device_kind`` (as
    ``jax.Device.device_kind`` reports it), or None when it is not a TPU
    with a spec-sheet entry."""
    low = device_kind.lower()
    if "tpu" not in low:
        return None
    for key, peak in PEAK_FLOPS_BF16.items():
        if key in low:
            return peak
    return None


def mfu_pct(flops_per_example: float, examples_per_sec_per_chip: float,
            device_kind: str) -> Optional[float]:
    """Achieved model FLOP/s over the chip's peak, in percent — or None
    when ``device_kind`` has no published peak. The caller passes the kind
    of the device that produced the throughput (a parent process reporting
    a child's number must not look at its own devices)."""
    peak = peak_flops(device_kind)
    if peak is None:
        return None
    return round(100.0 * flops_per_example * examples_per_sec_per_chip / peak, 4)
