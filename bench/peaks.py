"""Chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table is an error: the benchmark never prices one
chip against another's peaks.

``int32_ops`` is the ceiling for the counting kernel, whose work is VPU
integer AND, compare and add, not MXU matrix products.  No int32 vector
rate is published for the v5e, so it is derived from published numbers and
taken high on purpose, so that a share of it can only understate:

* clock: 197e12 bf16 FLOP/s / (4 MXUs x 128 x 128 x 2 FLOP) = 1.503 GHz;
* one VPU operation acts on a vreg of 8 sublanes x 128 lanes = 1,024 int32;
* at most 4 vector ALU operations issue per bundle (the largest plausible
  number of VALU slots);
* 1.503e9 x 1,024 x 4 = 6.155e12 int32 operations per second.

A roofline share above 100% means this ceiling is too low: fix the table.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

V5E_CLOCK_HZ = 197e12 / (4 * 128 * 128 * 2)


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # FLOP/s on the MXUs
    int32_ops: float           # int32 vector operations per second (VPU)
    hbm_bytes_per_s: float
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        int32_ops=V5E_CLOCK_HZ * 8 * 128 * 4,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM; int32 ceiling derived in bench/peaks.py'),
}


class UnknownDevice(LookupError):
    pass


def peaks_for(kind: str) -> ChipPeaks:
    """The peaks of ``kind``; raises :class:`UnknownDevice` for a kind the
    table does not hold."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {kind!r} is not in bench/peaks.py") from None
