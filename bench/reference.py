"""The plain reference: exact per-class counts and the itemsets frequent in
the minority class, from one ascending row-id list per item, intersected in
numpy.

It reads the generators' flat arrays and imports nothing of the program: no
encoder, no bitmap, no kernel.  Rows are numbered in the order the program
received them (base first, then each append), so the counts of the first
``n_rows`` rows are the counts at the version that held them.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

import numpy as np

def min_count(theta: float, n_rows: int) -> int:
    """``count >= theta * n_rows`` as a whole number of rows, guarded
    against float noise and never under 1 (the paper's support rule)."""
    return max(1, math.ceil(theta * n_rows - 1e-9))


def _member(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Mask of the entries of sorted ``a`` that occur in sorted ``b``."""
    if b.shape[0] == 0:
        return np.zeros(a.shape[0], bool)
    idx = np.minimum(np.searchsorted(b, a), b.shape[0] - 1)
    return b[idx] == a


def _pair_counts(rows: np.ndarray, items: np.ndarray, n_items: int,
                 mc: int) -> Dict[tuple, int]:
    """The pairs ``(a, b)``, ``a < b``, that at least ``mc`` rows hold,
    with those counts, ascending.  ``rows`` and ``items`` are cells sorted
    by row and, in a row, by item.  Each pair of a row is one code
    ``a * n_items + b``: the cells ``d`` apart in a row, for d = 1, 2, ...
    until no row is that long.  The codes are counted by ``np.bincount``
    where a count per possible pair is no larger than the codes, and by
    ``np.unique`` otherwise, so nothing of size ``n_items ** 2`` is made
    for a wide vocabulary."""
    n = int(n_items)
    items = items.astype(np.int64)
    codes = []
    for d in range(1, rows.shape[0]):
        same = rows[d:] == rows[:-d]
        if not same.any():
            break
        codes.append(items[:-d][same] * n + items[d:][same])
    codes = np.concatenate(codes) if codes else np.zeros(0, np.int64)
    if codes.shape[0] >= n * n:
        counts = np.bincount(codes, minlength=n * n)
        keys = np.flatnonzero(counts >= mc)
        counts = counts[keys]
    else:
        keys, counts = np.unique(codes, return_counts=True)
        keep = counts >= mc
        keys, counts = keys[keep], counts[keep]
    a, b = np.divmod(keys, n)
    return dict(zip(zip(a.tolist(), b.tolist()), counts.tolist()))


class HostReference:
    """Per-class counts from one ascending row-id list per item."""

    def __init__(self, rows: np.ndarray, items: np.ndarray,
                 classes: np.ndarray, n_items: int, n_classes: int):
        # a stable sort keeps each item's rows ascending
        order = np.argsort(items, kind="stable")
        self.rows = np.ascontiguousarray(rows[order])
        self.items = np.ascontiguousarray(items[order])
        per_item = np.bincount(items, minlength=n_items)
        self.ends = np.cumsum(per_item)
        self.starts = self.ends - per_item
        self.classes = np.asarray(classes, np.int64)
        self.n_items = n_items
        self.n_classes = n_classes

    def rows_of(self, item: int) -> np.ndarray:
        return self.rows[self.starts[item]:self.ends[item]]

    def rows_with(self, itemset: Iterable[int], n_rows: int) -> np.ndarray:
        rows = None
        for a in sorted(set(itemset),
                        key=lambda a: self.ends[a] - self.starts[a]):
            r = self.rows_of(a)
            rows = r if rows is None else rows[_member(rows, r)]
        if rows is None:                       # the empty itemset
            rows = np.arange(n_rows)
        return rows[:rows.searchsorted(n_rows)]

    def counts(self, itemset: Iterable[int], n_rows: int) -> np.ndarray:
        """(C,) counts of the rows among the first ``n_rows`` that hold
        every item of ``itemset``."""
        return np.bincount(self.classes[self.rows_with(itemset, n_rows)],
                           minlength=self.n_classes)

    def frequent(self, target: int, mc: int,
                 max_level: Optional[int] = None) -> Dict[tuple, int]:
        """The itemsets whose count in class ``target`` over every row of
        the reference is at least ``mc``, level by level, with that count.
        Itemsets of more than ``max_level`` items are not sought."""
        top = self.n_items if max_level is None else max_level
        hit = self.classes[self.rows] == target
        c1 = np.bincount(self.items[hit], minlength=self.n_items)
        found: Dict[tuple, int] = {(a,): int(c1[a])
                                   for a in range(self.n_items)
                                   if c1[a] >= mc}
        if top < 2:
            return found
        # the target rows' cells by row, each row's items ascending (the
        # stable sort keeps the item order the constructor made)
        rows, items = self.rows[hit], self.items[hit]
        by_row = np.argsort(rows, kind="stable")
        level = _pair_counts(rows[by_row], items[by_row], self.n_items, mc)
        size = 2
        while level:
            found.update(level)
            if size >= top:
                break
            # apriori join of sorted k-sets sharing a (k-1)-prefix
            prev = sorted(level)
            cands = []
            for i, p in enumerate(prev):
                for q in prev[i + 1:]:
                    if p[:-1] != q[:-1]:
                        break
                    c = p + q[-1:]
                    if all(c[:j] + c[j + 1:] in level
                           for j in range(len(c))):
                        cands.append(c)
            n = self.classes.shape[0]
            level = {}
            for c in cands:
                n1 = int(self.counts(c, n)[target])
                if n1 >= mc:
                    level[c] = n1
            size += 1
        return found


def from_data(data: Dict, n_rows: int, n_classes: int = 2
              ) -> HostReference:
    """The reference over the first ``n_rows`` rows of a generator's flat
    arrays."""
    ptr = data["row_ptr"]
    rows = np.repeat(np.arange(n_rows, dtype=np.int32),
                     np.diff(ptr[:n_rows + 1]))
    items = data["items"][:ptr[n_rows]]
    return HostReference(rows, items, data["classes"][:n_rows],
                         int(data["n_items"]), n_classes)


def minority_frequent(data: Dict, n_rows: int, theta: float, target: int,
                      max_level: Optional[int] = None, n_classes: int = 2
                      ) -> Dict[tuple, int]:
    """The itemsets frequent in class ``target`` over the first ``n_rows``
    rows of a generator's flat arrays (count ``>= min_count(theta,
    n_rows)``), with their counts in that class: the target list of the
    Minority-Report Algorithm's count step.  Only the rows of that class
    are read."""
    ptr = data["row_ptr"]
    keep = np.flatnonzero(data["classes"][:n_rows] == target)
    lens = ptr[keep + 1] - ptr[keep]
    firsts = np.cumsum(lens) - lens
    cells = np.repeat(ptr[keep] - firsts, lens) + np.arange(lens.sum())
    rows = np.repeat(np.arange(keep.shape[0], dtype=np.int32), lens)
    ref = HostReference(rows, data["items"][cells],
                        np.full(keep.shape[0], target, np.int64),
                        int(data["n_items"]), max(n_classes, target + 1))
    return ref.frequent(target, min_count(theta, n_rows), max_level)
