"""Bitmap encoding of transaction databases — the TPU-native data layout.

The FP-tree's two benefits are (a) prefix compression (shared work across
transactions sharing prefixes) and (b) frequency-ordered arrangement.  On TPU
we realize the same benefits in a dense layout:

  * each transaction -> a packed row of ``W = ceil(M/32)`` uint32 words, items
    mapped to bit positions in support-DESCENDING order (same discipline as the
    FP-tree arrangement; makes equal-prefix rows byte-identical early, so the
    dedup below collapses exactly the paths an FP-tree would merge);
  * duplicate rows are collapsed into a single row with an integer weight
    (per class: an (U, C) weight matrix) — the FP-tree compression analogue;
  * column projection drops items absent from the target set before any device
    work — the GFP-growth conditional-tree data reduction (#4) analogue.

All functions are host-side numpy (data-pipeline stage); the arrays they
produce are the device inputs of the counting kernel.
"""
from __future__ import annotations

import array
import itertools
from dataclasses import dataclass
from typing import Dict, Hashable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Item = Hashable


@dataclass(frozen=True)
class ItemVocab:
    """item -> bit column, support-descending (column 0 = most frequent)."""

    items: Tuple[Item, ...]

    @property
    def size(self) -> int:
        return len(self.items)

    @property
    def n_words(self) -> int:
        return max(1, (len(self.items) + 31) // 32)

    def col(self, item: Item) -> int:
        return self._index()[item]

    def _index(self) -> Dict[Item, int]:
        idx = getattr(self, "_idx", None)
        if idx is None:
            idx = {a: i for i, a in enumerate(self.items)}
            object.__setattr__(self, "_idx", idx)
        return idx

    def __contains__(self, item: Item) -> bool:
        return item in self._index()

    @staticmethod
    def from_transactions(
        transactions: Iterable[Sequence[Item]],
        min_count: int = 1,
        counts: Optional[Dict[Item, int]] = None,
    ) -> "ItemVocab":
        if counts is None:
            if not isinstance(transactions, Sequence):
                transactions = list(transactions)
            flat = _IntItems.of(transactions)
            counts = (flat.transaction_counts() if flat is not None
                      else _transaction_counts(transactions))
        return _vocab_from_counts(counts, min_count)


def _vocab_from_counts(counts: Dict[Item, int], min_count: int) -> ItemVocab:
    items = [a for a, c in counts.items() if c >= min_count]
    items.sort(key=lambda a: (-counts[a], repr(a)))
    return ItemVocab(tuple(items))


def _transaction_counts(transactions) -> Dict[Item, int]:
    """item -> number of transactions holding it (any hashable items)."""
    counts: Dict[Item, int] = {}
    for t in transactions:
        for a in set(t):
            counts[a] = counts.get(a, 0) + 1
    return counts


class _IntItems:
    """The items of a transaction list, flattened into int64 codes — the
    vectorised path of the encoders.  Only integer items take it (Python or
    numpy ints, bools): for them numpy equality is the dict equality the
    loops use.  ``of`` returns None for anything else."""

    def __init__(self, values: np.ndarray, lens: np.ndarray, flat: list):
        self.values = values              # (T,) int64, one per item occurrence
        self.lens = lens                  # (N,) items per transaction
        self.flat = flat                  # the item objects, flattened
        self._pairs = None

    @classmethod
    def of(cls, transactions: Sequence[Sequence[Item]]
           ) -> Optional["_IntItems"]:
        try:
            lens = np.fromiter(map(len, transactions), np.int64,
                               len(transactions))
        except TypeError:                 # unsized transactions
            return None
        flat = list(itertools.chain.from_iterable(transactions))
        try:
            # array('q') takes exactly the integers (and bools): floats,
            # strings and oversized ints raise
            values = np.frombuffer(array.array("q", flat), np.int64)
        except (TypeError, OverflowError):
            return None
        return cls(values, lens, flat)

    def pairs(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(sorted distinct values, row, code): one (row, code) per item a
        transaction holds — repeats inside a transaction dropped."""
        if self._pairs is None:
            v = self.values
            lo, hi = int(v.min()), int(v.max())
            if lo >= 0 and hi < 4 * v.shape[0] + 4096:
                present = np.zeros(hi + 1, bool)
                present[v] = True
                uniq = np.flatnonzero(present)
                codes = (np.cumsum(present) - 1)[v]
            else:
                uniq, codes = np.unique(v, return_inverse=True)
                codes = codes.reshape(-1)
            rows = np.repeat(np.arange(self.lens.shape[0]), self.lens)
            # rows whose codes strictly increase hold no repeat
            same_row = rows[1:] == rows[:-1]
            if np.any(np.diff(codes)[same_row] <= 0):
                key = np.unique(rows * uniq.shape[0] + codes)
                rows, codes = key // uniq.shape[0], key % uniq.shape[0]
            self._pairs = (uniq, rows, codes)
        return self._pairs

    def objects(self, uniq: np.ndarray) -> List[Item]:
        """The first-seen item object of every distinct value (the object
        the dict in the loop would have kept as its key)."""
        m = 1 << 16
        while True:
            seen, first = np.unique(self.values[:m], return_index=True)
            if seen.shape[0] == uniq.shape[0] or m >= self.values.shape[0]:
                return [self.flat[i] for i in first]
            m *= 4

    def transaction_counts(self) -> Dict[Item, int]:
        if self.values.shape[0] == 0:
            return {}
        uniq, _, codes = self.pairs()
        per_item = np.bincount(codes, minlength=uniq.shape[0])
        return dict(zip(self.objects(uniq), per_item.tolist()))

    def bitmap(self, vocab: ItemVocab) -> np.ndarray:
        out = np.zeros((self.lens.shape[0], vocab.n_words), np.uint32)
        if self.values.shape[0] == 0:
            return out
        uniq, rows, codes = self.pairs()
        idx = vocab._index()
        col_of = np.array([idx.get(a, -1) for a in uniq.tolist()], np.int64)
        cols = col_of[codes]
        if col_of.min() < 0:              # items outside the vocab
            keep = cols >= 0
            rows, cols = rows[keep], cols[keep]
        # each (row, column) bit occurs once, so adding the bits ORs them
        np.add.at(out.reshape(-1), rows * vocab.n_words + (cols >> 5),
                  np.left_shift(np.uint32(1), (cols & 31).astype(np.uint32)))
        return out


def encode_bitmap(
    transactions: Sequence[Sequence[Item]],
    vocab: ItemVocab,
) -> np.ndarray:
    """-> (N, W) uint32 packed bitmap (items outside vocab are dropped)."""
    flat = _IntItems.of(transactions)
    if flat is not None:
        return flat.bitmap(vocab)
    out = np.zeros((len(transactions), vocab.n_words), dtype=np.uint32)
    idx = vocab._index()
    for i, t in enumerate(transactions):
        for a in set(t):
            c = idx.get(a)
            if c is not None:
                out[i, c >> 5] |= np.uint32(1) << np.uint32(c & 31)
    return out


def vocab_and_bitmap(transactions: Sequence[Sequence[Item]]
                     ) -> Tuple[ItemVocab, np.ndarray]:
    """``ItemVocab.from_transactions`` and ``encode_bitmap`` under it, with
    one pass over the items instead of two."""
    flat = _IntItems.of(transactions)
    if flat is None:
        vocab = ItemVocab.from_transactions(transactions)
        return vocab, encode_bitmap(transactions, vocab)
    vocab = _vocab_from_counts(flat.transaction_counts(), 1)
    return vocab, flat.bitmap(vocab)


def transaction_lists(transactions: Iterable[Sequence[Item]]
                      ) -> List[List[Item]]:
    """``transactions`` as a list of lists.  Transactions that already are
    lists are shared, not copied: the encoders only read them, and at
    millions of rows the copy is most of the load time."""
    return [t if type(t) is list else list(t) for t in transactions]


def encode_targets(
    itemsets: Sequence[Sequence[Item]],
    vocab: ItemVocab,
) -> np.ndarray:
    """-> (K, W) uint32 target masks.  Raises if a target item is outside the
    vocab (the TIS-tree 'does not need to include itemsets ... containing items
    which do not appear in the FP-tree'; callers filter first)."""
    k = len(itemsets)
    w = vocab.n_words
    out = np.zeros((k, w), dtype=np.uint32)
    idx = vocab._index()
    for i, s in enumerate(itemsets):
        for a in set(s):
            c = idx[a]
            out[i, c >> 5] |= np.uint32(1) << np.uint32(c & 31)
    return out


def dedup_rows(
    bits: np.ndarray,
    weights: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """FP-compression analogue: collapse identical rows, summing weights.

    bits: (N, W) uint32;  weights: (N, C) int — defaults to ones (C=1).
    -> (unique_bits (U, W), weights (U, C) int32)
    """
    n = bits.shape[0]
    if weights is None:
        weights = np.ones((n, 1), dtype=np.int32)
    if weights.ndim == 1:
        weights = weights[:, None]
    if n and bits.shape[1]:
        # rows as big-endian byte strings: bytewise order is the same
        # lexicographic word order np.unique(axis=0) sorts by, and string
        # sorting is several times faster than its structured-row sort
        w = bits.shape[1]
        rows = np.ascontiguousarray(bits, dtype=">u4").view(f"S{4 * w}")
        keys, inv = np.unique(rows.reshape(-1), return_inverse=True)
        uniq = np.frombuffer(keys.tobytes(), ">u4").reshape(-1, w)
    else:
        uniq, inv = np.unique(bits, axis=0, return_inverse=True)
    agg = np.zeros((uniq.shape[0], weights.shape[1]), dtype=np.int64)
    np.add.at(agg, inv.reshape(-1), weights)
    if np.any(agg > np.iinfo(np.int32).max):
        raise OverflowError("per-row class weights exceed int32")
    return uniq.astype(np.uint32), agg.astype(np.int32)


def class_weights(classes: Sequence[int], n_classes: int = 2) -> np.ndarray:
    """One-hot (N, C) int32 class indicator — the multi-class counter columns
    (paper §4.1: 'per class counters on each node of a single tree')."""
    y = np.asarray(classes, dtype=np.int64)
    if y.min() < 0 or y.max() >= n_classes:
        raise ValueError("class id out of range")
    out = np.zeros((y.shape[0], n_classes), dtype=np.int32)
    out[np.arange(y.shape[0]), y] = 1
    return out


def project_columns(
    bits: np.ndarray,
    vocab: ItemVocab,
    keep_items: Sequence[Item],
) -> Tuple[np.ndarray, ItemVocab]:
    """GFP data-reduction (#4) analogue: repack keeping only ``keep_items``.

    Preserves the relative (support-descending) order of the kept items.
    -> (projected (N, W') uint32, sub-vocab)
    """
    keep = [a for a in vocab.items if a in set(keep_items)]
    sub = ItemVocab(tuple(keep))
    cols = np.array([vocab.col(a) for a in keep], dtype=np.int64)
    n = bits.shape[0]
    out = np.zeros((n, sub.n_words), dtype=np.uint32)
    for new_c, old_c in enumerate(cols):
        bit = (bits[:, old_c >> 5] >> np.uint32(old_c & 31)) & np.uint32(1)
        out[:, new_c >> 5] |= bit.astype(np.uint32) << np.uint32(new_c & 31)
    return out, sub


def pad_words(bits: np.ndarray, n_words: int) -> np.ndarray:
    """Zero-extend packed rows (N, W) -> (N, n_words).

    A tail-extended vocab (``extend_vocab``) only APPENDS bit columns, so rows
    encoded under the old vocab stay valid at the new width with zero bits in
    the new columns — this is the re-encode-free append path of the serving
    store."""
    w = bits.shape[1]
    if w == n_words:
        return bits
    if w > n_words:
        raise ValueError(f"cannot shrink packed rows from {w} to {n_words} words")
    out = np.zeros((bits.shape[0], n_words), dtype=np.uint32)
    out[:, :w] = bits
    return out


def extend_vocab(
    transactions: Sequence[Sequence[Item]],
    vocab: ItemVocab,
) -> ItemVocab:
    """Tail-extend ``vocab`` with items unseen so far (incremental appends).

    Existing items keep their bit columns (already-encoded rows stay valid —
    see ``pad_words``); new items are appended batch-frequency-descending,
    mirroring the ``IncrementalMiner`` tail extension of its ``ItemOrder``.
    Returns ``vocab`` itself when the batch introduces nothing new.
    """
    counts: Dict[Item, int] = {}
    for t in transactions:
        for a in set(t):
            if a not in vocab:
                counts[a] = counts.get(a, 0) + 1
    if not counts:
        return vocab
    new = sorted(counts, key=lambda a: (-counts[a], repr(a)))
    return ItemVocab(vocab.items + tuple(new))


def decode_row(row: np.ndarray, vocab: ItemVocab) -> List[Item]:
    """Inverse of encode for tests/debug."""
    out: List[Item] = []
    for c, a in enumerate(vocab.items):
        if (int(row[c >> 5]) >> (c & 31)) & 1:
            out.append(a)
    return out
