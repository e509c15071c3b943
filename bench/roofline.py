"""Operations and bytes of one ``itemset_count`` launch, from the work its
targets need, and the least time the chip could take for it.

This is the least work of a containment test, whatever kernel does it.  A
launch counts ``k`` real target itemsets over ``n`` rows of ``w`` packed
words with ``c`` class columns.  Target ``t`` holds its items in ``w_t``
distinct words (``held = sum of w_t``), and together the targets touch
``touched`` distinct words:

* operations ``n * (2 * held + k * c)``: per row, two operations (combine
  and test) for each word a target holds, then ``c`` adds per target;
* bytes ``4 * (n * touched + n * c + held + k * c)``: the touched words of
  every row, the weights, the targets' words, and the (k, c) result.

A word that no target holds decides nothing, so a kernel that reads or
tests it does work beyond the model.  With every target holding all ``w``
words (``held = k * w``, ``touched = w``, the default) the model is the
dense one: ``n * k * (2w + c)`` operations and ``4 * (nw + nc + kw + kc)``
bytes.  The shapes are the unpadded ones the program was asked to count,
so a later tiling or padding choice changes the time, never the work.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple

from .peaks import ChipPeaks


def kernel_ops(n: int, k: int, w: int, c: int,
               held: Optional[int] = None) -> float:
    held = k * w if held is None else held
    return float(n * (2 * held + k * c))


def kernel_bytes(n: int, k: int, w: int, c: int, held: Optional[int] = None,
                 touched: Optional[int] = None) -> float:
    held = k * w if held is None else held
    touched = w if touched is None else touched
    return float(4 * (n * touched + n * c + held + k * c))


def least_seconds(n: int, k: int, w: int, c: int, peaks: ChipPeaks,
                  held: Optional[int] = None, touched: Optional[int] = None
                  ) -> Tuple[float, str]:
    """(least time of the launch, the bound that sets it: "ops" or
    "bytes")."""
    t_ops = kernel_ops(n, k, w, c, held) / peaks.int32_ops
    t_bytes = kernel_bytes(n, k, w, c, held, touched) / peaks.hbm_bytes_per_s
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def roofline_share(launches: Iterable[Sequence[Optional[int]]],
                   kernel_seconds: float, peaks: ChipPeaks
                   ) -> Tuple[float, str]:
    """(percent of the roofline, the bound that set most of the least time)
    for ``launches`` that took ``kernel_seconds`` of device time.  Each
    launch is ``(n, k, w, c)`` or ``(n, k, w, c, held, touched)``.  Raises
    ValueError when there is nothing to read."""
    least = {"ops": 0.0, "bytes": 0.0}
    for n, k, w, c, *words in launches:
        t, bound = least_seconds(n, k, w, c, peaks, *words)
        least[bound] += t
    total = least["ops"] + least["bytes"]
    if total <= 0.0 or kernel_seconds <= 0.0:
        raise ValueError("no launch or no kernel time to read")
    bound = "ops" if least["ops"] >= least["bytes"] else "bytes"
    return 100.0 * total / kernel_seconds, bound
