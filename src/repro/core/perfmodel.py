"""Published device peaks: the one table of them in the program.

:data:`PEAKS` is keyed by ``jax.Device.device_kind``; :data:`MODEL_CHIP`
(one v5e) is the chip that ``core/tuning`` quotes its roofline against and
``launch/dryrun`` divides its compiled costs by.  Times and shares on the
chip come from the benchmark's device trace (``bench/``), never from here.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Published per-chip peaks of one accelerator, with their source."""

    flops_per_s: float           # bf16 dense FLOP/s
    hbm_bytes_per_s: float       # HBM bandwidth
    source: str


#: The one table of device peaks, keyed by ``jax.Device.device_kind``.  A
#: device that is not here (the CPU among them) has no peak: its roofline
#: share is "not measured", never a share of some other chip's peak.
PEAKS = {
    "TPU v5 lite": DevicePeak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 '
               'TFLOP/s bf16, 16 GB HBM at 819 GB/s)'),
}

#: the chip the dry run and the tuning layer quote against (one v5e).
MODEL_CHIP = PEAKS["TPU v5 lite"]
PEAK_FLOPS = MODEL_CHIP.flops_per_s
HBM_BW = MODEL_CHIP.hbm_bytes_per_s
