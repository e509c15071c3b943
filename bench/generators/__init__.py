"""Data generators of the benchmark's configurations, found by name.

A configuration file names its generator (``"generator": "bernoulli"``); the
module ``bench/generators/<name>.py`` has ``generate(cfg, seed)``, which
returns flat arrays: ``items`` (int16 item codes, row-major), ``row_ptr``
(int64 row offsets), ``classes`` (int32), ``n_items`` and ``base_rows``;
an item is its code.  The program is handed rows made by :func:`rows_between`; the
references read the flat arrays.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

import numpy as np


def load(name: str):
    return importlib.import_module(f"bench.generators.{name}")


def rows_between(data: Dict, lo: int, hi: int) -> Tuple[List[list],
                                                        np.ndarray]:
    """Rows ``lo`` to ``hi`` as the program takes them: a list of item
    lists of Python ints, and each row's class."""
    ptr = data["row_ptr"]
    flat = data["items"][ptr[lo]:ptr[hi]].tolist()
    offs = (ptr[lo:hi + 1] - ptr[lo]).tolist()
    rows = list(map(flat.__getitem__, map(slice, offs[:-1], offs[1:])))
    return rows, data["classes"][lo:hi]


def flat_pairs(data: Dict, hi: int) -> Tuple[np.ndarray, np.ndarray]:
    """(row, item code) of every held cell of rows ``0`` to ``hi``."""
    ptr = data["row_ptr"]
    rows = np.repeat(np.arange(hi, dtype=np.int32), np.diff(ptr[:hi + 1]))
    return rows, data["items"][:ptr[hi]].astype(np.int32)
