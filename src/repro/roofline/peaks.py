"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

One table for every roofline in the repo.  A device kind that is not in it
has no peaks: the launch telemetry then records no prediction and reports no
efficiency ratio, rather than pricing one chip against another's peak.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s, MXU
    hbm_bytes_per_s: float
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e" (system architecture)'),
}


def peaks_for(kind: str) -> Optional[ChipPeaks]:
    """The table entry for a ``device_kind`` string, or None."""
    return PEAKS.get(kind)


def local_peaks() -> Optional[ChipPeaks]:
    """Peaks of the first local device, or None when its kind is unknown."""
    import jax

    return peaks_for(jax.devices()[0].device_kind)
