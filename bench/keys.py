"""Target itemsets and request schedules, drawn from the seed.

An itemset of at most 4 items over at most 2^15 item codes is held as one
int64 code (15 bits an item, ascending, empty slots all ones), so that
distinct itemsets are found with ``np.unique`` and not with Python sets.
Only the open-loop mix packs keys so: a serve mix over more than 32,767
items needs the packing widened first.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

_BITS = 15
_EMPTY = (1 << _BITS) - 1


def draw_itemsets(rng: np.random.Generator, n: int, n_items: int,
                  lo: int, hi: int) -> np.ndarray:
    """(n, hi) item codes, each row ``lo`` to ``hi`` distinct items drawn
    uniformly (size uniform too), ascending, padded with -1."""
    sizes = rng.integers(lo, hi + 1, n)
    out = np.full((n, hi), -1, np.int64)
    todo = np.arange(n)
    while todo.size:
        draw = rng.integers(0, n_items, (todo.size, hi))
        mask = np.arange(hi)[None, :] < sizes[todo, None]
        draw = np.where(mask, draw, n_items + np.arange(hi)[None, :])
        draw.sort(axis=1)
        ok = np.all(np.diff(draw, axis=1) != 0, axis=1)
        rows = todo[ok]
        out[rows] = np.where(draw[ok] >= n_items, -1, draw[ok])
        todo = todo[~ok]
    return out


def codes_of(sets: np.ndarray) -> np.ndarray:
    """One int64 code per itemset row (rows ascending, padded with -1)."""
    code = np.zeros(sets.shape[0], np.int64)
    for j in range(sets.shape[1]):
        col = np.where(sets[:, j] < 0, _EMPTY, sets[:, j])
        code = (code << _BITS) | col
    return code


def distinct_itemsets(rng: np.random.Generator, n: int, n_items: int,
                      lo: int, hi: int) -> np.ndarray:
    """``n`` distinct itemsets in the order first drawn."""
    out = np.zeros((0, hi), np.int64)
    seen = np.zeros(0, np.int64)
    while out.shape[0] < n:
        need = n - out.shape[0]
        sets = draw_itemsets(rng, need + need // 4 + 64, n_items, lo, hi)
        codes = codes_of(sets)
        _, first = np.unique(codes, return_index=True)
        first.sort()
        sets, codes = sets[first], codes[first]
        fresh = ~np.isin(codes, seen)
        out = np.concatenate([out, sets[fresh][:need]])
        seen = np.concatenate([seen, codes[fresh][:need]])
    return out


def as_tuples(sets: np.ndarray) -> List[tuple]:
    """Itemset rows as tuples of Python items (what a client sends)."""
    return [tuple(a for a in r if a >= 0) for r in sets.tolist()]


class Zipf:
    """Ranks ``0 .. n-1`` with P(rank r) proportional to 1 / (r + 1)^theta
    (YCSB's request law at theta = 0.99), drawn by inverting the CDF."""

    def __init__(self, n: int, theta: float):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, rng.random(size)),
                          self.cdf.shape[0] - 1)


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Arrival offsets in ``[0, seconds)`` of a Poisson process."""
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[:np.searchsorted(t, seconds)]


def requests(rng: np.random.Generator, zipf: Zipf, n: int, lo: int,
             hi: int) -> Tuple[np.ndarray, np.ndarray]:
    """(key ranks of all requests, offsets): request ``i`` asks for ranks
    ``ranks[offs[i]:offs[i+1]]``; each request holds ``lo`` to ``hi``
    keys."""
    per = rng.integers(lo, hi + 1, n)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(per, out=offs[1:])
    return zipf.draw(rng, int(offs[-1])), offs
