"""The benchmark's generators and plain references, at tiny sizes, against
the repository's host results."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import keys as K  # noqa: E402
from bench import reference  # noqa: E402
from bench.generators import bernoulli, rows_between  # noqa: E402

BIG_SEED = 2**31 + 12345


def _sim(seed, rows=3000, items=48, p_x=0.1, append=500):
    return bernoulli.generate({"rows": rows, "items": items, "p_x": p_x,
                               "p_y": 0.2, "append_rows": append}, seed)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_bernoulli_is_a_function_of_the_seed(seed):
    a, b = _sim(seed), _sim(seed)
    for key in ("items", "row_ptr", "classes"):
        assert np.array_equal(a[key], b[key])
    c = _sim(seed + 1)
    assert not np.array_equal(a["items"], c["items"][:a["items"].shape[0]])


def test_bernoulli_density_and_layout():
    d = _sim(3, rows=20_000, items=100, p_x=0.05, append=0)
    n_cells = 20_000 * 100
    assert d["items"].shape[0] == pytest.approx(0.05 * n_cells, rel=0.03)
    assert d["row_ptr"][-1] == d["items"].shape[0]
    rows, _ = rows_between(d, 0, 20_000)
    assert all(r == sorted(set(r)) for r in rows[:500])
    assert abs(d["classes"].mean() - 0.2) < 0.02
    # blocks of rows are independent streams: a block boundary changes
    # nothing in the law, only which stream draws
    per_row = np.diff(d["row_ptr"])
    assert per_row.mean() == pytest.approx(5.0, rel=0.03)


@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_reference_counts_equal_the_host_oracle(seed):
    from repro.core import brute_force_counts

    d = _sim(seed)
    n = 3500
    rows, y = rows_between(d, 0, n)
    ref = reference.from_data(d, n)
    rng = np.random.default_rng(seed % 1000)
    sets = K.distinct_itemsets(rng, 60, 48, 1, 3)
    targets = K.as_tuples(sets)
    for cls in (0, 1):
        want = brute_force_counts(rows, targets,
                                  weights=(y == cls).astype(int).tolist())
        for t in targets:
            assert ref.counts(t, n)[cls] == want[tuple(sorted(t, key=repr))]
    # a prefix of the rows is the state before the append
    base = rows_between(d, 0, 3000)[0]
    t = targets[0]
    assert ref.counts(t, 3000).sum() == \
        brute_force_counts(base, [t])[tuple(sorted(t, key=repr))]


@pytest.mark.parametrize("seed,n", [(4, 3000), (5, 3300), (BIG_SEED, 3100)])
def test_minority_frequent_equals_minority_report(seed, n):
    """Every level of the reference's minority-frequent list is the
    antecedent set of the paper's Minority-Report rules at confidence 0,
    with the same minority counts."""
    from repro.core import minority_report

    d = _sim(seed, rows=3000, items=24, p_x=0.3, append=300)
    rows, y = rows_between(d, 0, n)
    theta = 0.01
    want = minority_report(rows, y, target_class=1, min_support=theta,
                           min_confidence=0.0).rules
    got = reference.minority_frequent(d, n, theta, 1)
    assert got == {tuple(sorted(r.antecedent)): r.count for r in want}
    assert max(len(k) for k in got) >= 3
    # a cap on the level keeps the lower levels whole
    two = reference.minority_frequent(d, n, theta, 1, max_level=2)
    assert two == {k: v for k, v in got.items() if len(k) <= 2}


@pytest.mark.parametrize("lo,hi", [(1, 3), (2, 4)])
def test_distinct_itemsets_are_distinct_and_sized(lo, hi):
    rng = np.random.default_rng(9)
    sets = K.distinct_itemsets(rng, 5000, 64, lo, hi)
    tuples = K.as_tuples(sets)
    assert len(set(tuples)) == 5000
    sizes = {len(t) for t in tuples}
    assert sizes == set(range(lo, hi + 1))
    assert all(list(t) == sorted(set(t)) for t in tuples)


def test_zipf_and_arrivals():
    rng = np.random.default_rng(11)
    z = K.Zipf(1000, 0.99)
    draws = z.draw(rng, 200_000)
    top = np.bincount(draws, minlength=1000)
    assert top[0] > top[1] > top[10] > top[500]
    t = K.poisson_arrivals(rng, 1000.0, 5.0)
    assert t.shape[0] == pytest.approx(5000, rel=0.1)
    assert np.all(np.diff(t) > 0) and t[-1] < 5.0


def _wide(seed, rows=4000, items=40_000):
    """Flat arrays over a vocabulary wider than int16 holds: rows of 1-12
    items, half of them from 40 hot codes spread over the vocabulary (most
    above 32,767), half uniform; the codes int32."""
    rng = np.random.default_rng(seed)
    hot = np.concatenate([rng.choice(32_768, 8, replace=False),
                          32_768 + rng.choice(items - 32_768, 32,
                                              replace=False)])
    out = []
    for _ in range(rows):
        n = int(rng.integers(1, 13))
        pick = np.where(rng.random(n) < 0.5, rng.choice(hot, n),
                        rng.integers(0, items, n))
        out.append(np.unique(pick))
    row_ptr = np.zeros(rows + 1, np.int64)
    np.cumsum([r.shape[0] for r in out], out=row_ptr[1:])
    return {"items": np.concatenate(out).astype(np.int32),
            "row_ptr": row_ptr,
            "classes": (rng.random(rows) < 0.3).astype(np.int32),
            "n_items": items, "base_rows": rows}, out, hot


@pytest.mark.parametrize("seed", [21, 22, BIG_SEED])
def test_wide_reference_equals_brute_force(seed):
    """Codes above 2^15: the pair lister counts each row's pairs without
    anything of size n_items^2, and equals a count over Python sets."""
    import itertools
    import tracemalloc
    from collections import Counter

    d, rows, hot = _wide(seed)
    n = len(rows)
    theta = 0.002
    mc = reference.min_count(theta, n)
    tracemalloc.start()
    got = reference.minority_frequent(d, n, theta, 1, max_level=2)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert peak < 64 << 20          # an n_items^2 array is 1.6e9 cells
    want = Counter()
    for r, y in zip(rows, d["classes"].tolist()):
        if y == 1:
            r = r.tolist()
            want.update((a,) for a in r)
            want.update(itertools.combinations(r, 2))
    assert got == {k: v for k, v in want.items() if v >= mc}
    assert sum(len(k) == 2 for k in got) > 100
    assert any(k[0] > 32_767 for k in got if len(k) == 2)

    ref = reference.from_data(d, n)
    sets = [tuple(sorted(hot[:2])), tuple(sorted(hot[-3:])), (int(hot[-1]),),
            tuple(sorted(hot[[0, 20]]))]
    y = d["classes"]
    for s in sets:
        holds = np.array([set(s) <= set(r.tolist()) for r in rows])
        assert ref.counts(s, n).tolist() == \
            np.bincount(y[holds], minlength=2).tolist()
