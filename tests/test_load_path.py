"""The bulk load path — synthetic generation, vocab, bitmap encode, row
dedup — against the straightforward loops it replaced: same output, bit for
bit, for every seed and item type."""
import numpy as np
import pytest

import repro.data.synth as synth
from repro.data import bernoulli_db
from repro.mining.encode import (ItemVocab, dedup_rows, encode_bitmap,
                                 vocab_and_bitmap)


def _bernoulli_loop(n, m, p_x, p_y, seed):
    rng = np.random.default_rng(seed)
    mat = rng.random((n, m)) < p_x
    y = (rng.random(n) < p_y).astype(np.int32)
    return [np.flatnonzero(row).tolist() for row in mat], y


def _vocab_loop(transactions, min_count=1):
    counts = {}
    for t in transactions:
        for a in set(t):
            counts[a] = counts.get(a, 0) + 1
    items = [a for a, c in counts.items() if c >= min_count]
    items.sort(key=lambda a: (-counts[a], repr(a)))
    return ItemVocab(tuple(items))


def _bitmap_loop(transactions, vocab):
    out = np.zeros((len(transactions), vocab.n_words), np.uint32)
    idx = vocab._index()
    for i, t in enumerate(transactions):
        for a in set(t):
            c = idx.get(a)
            if c is not None:
                out[i, c >> 5] |= np.uint32(1) << np.uint32(c & 31)
    return out


@pytest.mark.parametrize("chunk_draws", [1 << 23, 1000, 7])
@pytest.mark.parametrize("n,m,p_x,p_y,seed", [
    (0, 5, 0.3, 0.2, 1), (1, 1, 0.5, 0.5, 2), (1000, 24, 0.15, 0.05, 3),
    (3000, 1024, 0.04, 0.01, 7), (333, 50, 0.2, 0.3, 0)])
def test_bernoulli_db_chunks_match_one_draw(monkeypatch, chunk_draws, n, m,
                                            p_x, p_y, seed):
    monkeypatch.setattr(synth, "_BERNOULLI_CHUNK_DRAWS", chunk_draws)
    tx, y = bernoulli_db(n, m, p_x, p_y, seed)
    want_tx, want_y = _bernoulli_loop(n, m, p_x, p_y, seed)
    assert tx == want_tx
    assert y.dtype == want_y.dtype and np.array_equal(y, want_y)
    assert all(type(a) is int for t in tx[:50] for a in t)


def _cases():
    rng = np.random.default_rng(11)
    ints = [rng.choice(60, size=rng.integers(0, 9)).tolist()
            for _ in range(400)]                      # repeats inside rows
    return {
        "ints_with_repeats": ints,
        "sorted_ints": [sorted(set(t)) for t in ints],
        "negative_and_large": [[-5, 3, 1 << 40], [1 << 40, -5], [], [7]],
        "numpy_ints": [[np.int64(3), np.int32(5)], [3, 5, 9], [np.int64(9)]],
        "beyond_int64": [[1 << 70, 2], [2, 3], [1 << 70]],
        "strings": [["a", "b"], ["b", "c", "b"], ["c"]],
        "mixed": [[1, "x"], ["x", 2.5], [1]],
        "empty_rows": [[], [], []],
        "no_rows": [],
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_vocab_and_bitmap_match_loops(name):
    tx = _cases()[name]
    vocab = ItemVocab.from_transactions(tx)
    want = _vocab_loop(tx)
    assert vocab.items == want.items
    assert [repr(a) for a in vocab.items] == [repr(a) for a in want.items]
    assert np.array_equal(encode_bitmap(tx, vocab), _bitmap_loop(tx, want))
    one_pass_vocab, one_pass_bits = vocab_and_bitmap(tx)
    assert [repr(a) for a in one_pass_vocab.items] == \
        [repr(a) for a in want.items]
    assert np.array_equal(one_pass_bits, _bitmap_loop(tx, want))
    # a vocab that misses items (min_count) leaves their bits unset
    narrow = ItemVocab.from_transactions(tx, min_count=2)
    assert narrow.items == _vocab_loop(tx, min_count=2).items
    assert np.array_equal(encode_bitmap(tx, narrow), _bitmap_loop(tx, narrow))


@pytest.mark.parametrize("w", [1, 3, 32])
def test_dedup_rows_matches_unique_rows(w):
    rng = np.random.default_rng(w)
    bits = rng.integers(0, 4, size=(500, w)).astype(np.uint32) << np.uint32(30)
    bits[::3] = bits[1::3][:bits[::3].shape[0]]
    weights = rng.integers(1, 5, size=(500, 2)).astype(np.int32)
    uniq, agg = dedup_rows(bits, weights)
    want_u, inv = np.unique(bits, axis=0, return_inverse=True)
    want_w = np.zeros((want_u.shape[0], 2), np.int64)
    np.add.at(want_w, inv.reshape(-1), weights)
    assert np.array_equal(uniq, want_u) and uniq.dtype == np.uint32
    assert np.array_equal(agg, want_w)
