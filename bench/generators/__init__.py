"""Data generators of the benchmark's configurations, found by name.

A configuration file names its generator (``"generator": "bernoulli"``); the
module ``bench/generators/<name>.py`` has ``generate(cfg, seed)``, which
returns flat arrays: ``items`` (item codes ``0 .. n_items - 1``, row-major,
of any integer type up to int32, so at most 2^31 items; a row holds each of
its items once, ascending), ``row_ptr`` (int64 row offsets), ``classes``
(int32), ``n_items`` and ``base_rows``; an item is its code.  The program
is handed rows made by :func:`rows_between`; the references read the flat
arrays, and widen the codes wherever they compute with them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def rows_between(data: Dict, lo: int, hi: int) -> Tuple[List[list],
                                                        np.ndarray]:
    """Rows ``lo`` to ``hi`` as the program takes them: a list of item
    lists of Python ints, and each row's class."""
    ptr = data["row_ptr"]
    flat = data["items"][ptr[lo]:ptr[hi]].tolist()
    offs = (ptr[lo:hi + 1] - ptr[lo]).tolist()
    rows = list(map(flat.__getitem__, map(slice, offs[:-1], offs[1:])))
    return rows, data["classes"][lo:hi]
