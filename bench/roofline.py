"""Operations and bytes of one ``itemset_count`` launch, from its unpadded
shapes, and the least time the chip could take for it.

For a launch over ``n`` rows of ``w`` packed words with ``c`` class columns
and ``k`` target itemsets (the formulas of the repository's kernel model):

* operations ``n * k * (2w + c)``: per (row, target) pair, ``w`` ANDs and
  ``w`` compares, then ``c`` masked adds;
* bytes ``4 * (n*w + n*c + k*w + k*c)``: one pass over the bitmap and the
  weights, the targets, and the (k, c) result.

The shapes are the unpadded ones the program was asked to count, so a
later tiling or padding choice changes the time, never the work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from .peaks import ChipPeaks


def kernel_ops(n: int, k: int, w: int, c: int) -> float:
    return float(n) * float(k) * (2.0 * w + c)


def kernel_bytes(n: int, k: int, w: int, c: int) -> float:
    return 4.0 * (float(n) * w + float(n) * c + float(k) * w + float(k) * c)


def least_seconds(n: int, k: int, w: int, c: int,
                  peaks: ChipPeaks) -> Tuple[float, str]:
    """(least time of the launch, the bound that sets it: "ops" or
    "bytes")."""
    t_ops = kernel_ops(n, k, w, c) / peaks.int32_ops
    t_bytes = kernel_bytes(n, k, w, c) / peaks.hbm_bytes_per_s
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def roofline_share(launches: Iterable[Tuple[int, int, int, int]],
                   kernel_seconds: float, peaks: ChipPeaks
                   ) -> Tuple[float, str]:
    """(percent of the roofline, the bound that set most of the least time)
    for ``launches`` that took ``kernel_seconds`` of device time.  Raises
    ValueError when there is nothing to read."""
    least = {"ops": 0.0, "bytes": 0.0}
    for n, k, w, c in launches:
        t, bound = least_seconds(n, k, w, c, peaks)
        least[bound] += t
    total = least["ops"] + least["bytes"]
    if total <= 0.0 or kernel_seconds <= 0.0:
        raise ValueError("no launch or no kernel time to read")
    bound = "ops" if least["ops"] >= least["bytes"] else "bytes"
    return 100.0 * total / kernel_seconds, bound
