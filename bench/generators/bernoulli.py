"""The paper's §4.3 Bernoulli simulation, drawn by geometric gaps.

Every (row, item) cell holds the item with probability ``p_x``; the class of
a row is 1 with probability ``p_y``.  Instead of one uniform draw per cell
(4.1G draws at 4M rows x 1,024 items), the generator draws the gaps between
held cells from a geometric law, which gives the same distribution at about
``rows * items * p_x`` draws.  Rows are drawn in fixed blocks, each from its
own stream of the seed, so the output for a seed never depends on the number
of threads.

The result is the flat arrays of ``bench.generators``; the first ``rows``
rows are the base and the ``append_rows`` after them the append.  Items are
the ints ``0 .. items - 1``, ascending inside a row: int16 codes up to
2^15 items, int32 above.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np

BLOCK_ROWS = 131_072


def _code_type(items: int) -> type:
    return np.int16 if items <= 1 << 15 else np.int32


def _block(seed: int, index: int, rows: int, items: int,
           p_x: float) -> Tuple[np.ndarray, np.ndarray]:
    """(item of every held cell, held cells per row) of one block."""
    rng = np.random.default_rng([seed, 1, index])
    cells = rows * items
    mean = cells * p_x
    pos: List[np.ndarray] = []
    last = -1
    while last < cells - 1:
        draw = int(mean + 8 * np.sqrt(mean) + 64) if not pos else \
            int((cells - last) * p_x + 8 * np.sqrt(mean) + 64)
        gaps = rng.geometric(p_x, size=draw)
        p = np.cumsum(gaps) + last
        pos.append(p)
        last = int(p[-1])
    flat = np.concatenate(pos)
    flat = flat[:np.searchsorted(flat, cells)]
    row = flat // items
    return ((flat - row * items).astype(_code_type(items)),
            np.bincount(row, minlength=rows))


def generate(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Draw the base and the append of a ``sim``-style configuration."""
    items = int(cfg["items"])
    total = int(cfg["rows"]) + int(cfg.get("append_rows", 0))
    p_x = float(cfg["p_x"])
    starts = list(range(0, total, BLOCK_ROWS))
    workers = min(8, os.cpu_count() or 1, max(1, len(starts)))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        parts = list(ex.map(
            lambda i: _block(seed, i, min(BLOCK_ROWS, total - starts[i]),
                             items, p_x), range(len(starts))))
    lens = np.concatenate([n for _, n in parts]) if parts else \
        np.zeros(0, np.int64)
    row_ptr = np.zeros(total + 1, np.int64)
    np.cumsum(lens, out=row_ptr[1:])
    classes = (np.random.default_rng([seed, 2]).random(total)
               < float(cfg["p_y"])).astype(np.int32)
    return {"items": (np.concatenate([a for a, _ in parts]) if parts
                      else np.zeros(0, _code_type(items))),
            "row_ptr": row_ptr, "classes": classes,
            "n_items": items, "base_rows": int(cfg["rows"])}
