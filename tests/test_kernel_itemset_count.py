"""Per-kernel validation: shape/dtype sweeps + property tests, Pallas kernel
(interpret mode on CPU) vs the pure-jnp ref.py oracle vs brute force."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _pbt import given, settings, strategies as st  # hypothesis or offline shim

from repro.core import brute_force_counts
from repro.kernels.itemset_count import (itemset_counts, itemset_counts_ref,
                                         itemset_counts_ref_blocked)
from repro.kernels.itemset_count.kernel import itemset_counts_pallas


from _testutil import random_problem


def _random_problem(rng, n, k, w, c, density=0.3):
    tx, tgt, wts = random_problem(rng, n, k, w, c, density)
    return jnp.asarray(tx), jnp.asarray(tgt), jnp.asarray(wts)


SHAPES = [
    # (N, K, W, C, block_k, block_n)
    (1, 1, 1, 1, 8, 128),
    (128, 8, 1, 1, 8, 128),
    (200, 5, 2, 2, 8, 128),          # padding on both axes
    (1024, 256, 4, 2, 256, 1024),    # exact blocks
    (1500, 300, 4, 3, 256, 512),     # multi-tile + ragged
    (4096, 64, 8, 1, 64, 2048),
    (333, 17, 16, 4, 16, 128),
    (777, 130, 33, 2, 128, 256),     # odd word count
]


@pytest.mark.parametrize("n,k,w,c,bk,bn", SHAPES)
def test_kernel_matches_ref_shapes(n, k, w, c, bk, bn):
    rng = np.random.default_rng(n * 7 + k)
    tx, tgt, wts = _random_problem(rng, n, k, w, c)
    got = itemset_counts(tx, tgt, wts, block_k=bk, block_n=bn)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_blocked_ref_matches_ref():
    rng = np.random.default_rng(0)
    tx, tgt, wts = _random_problem(rng, 1000, 40, 3, 2)
    a = itemset_counts_ref(tx, tgt, wts)
    b = itemset_counts_ref_blocked(tx, tgt, wts, block_n=256)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_raw_layout_exact_blocks():
    """Direct pallas_call path (pre-padded, transposed layouts)."""
    rng = np.random.default_rng(3)
    tx, tgt, wts = _random_problem(rng, 512, 64, 4, 2)
    got = itemset_counts_pallas(tx.T, tgt, wts.T, block_k=32, block_n=128,
                                interpret=True)               # (K, C)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_weight_vector_promotion():
    rng = np.random.default_rng(4)
    tx, tgt, _ = _random_problem(rng, 64, 4, 2, 1)
    w1 = jnp.ones((64,), jnp.int32)
    out = itemset_counts(tx, tgt, w1)
    assert out.shape == (4, 1)


def test_empty_inputs():
    tx = jnp.zeros((0, 2), jnp.uint32)
    tgt = jnp.zeros((3, 2), jnp.uint32)
    w = jnp.zeros((0, 2), jnp.int32)
    assert itemset_counts(tx, tgt, w).shape == (3, 2)
    assert itemset_counts(jnp.zeros((5, 2), jnp.uint32),
                          jnp.zeros((0, 2), jnp.uint32),
                          jnp.ones((5, 1), jnp.int32)).shape == (0, 1)


def test_huge_word_count_falls_back():
    """W > MAX_KERNEL_WORDS uses the blocked jnp path, still exact."""
    rng = np.random.default_rng(5)
    tx, tgt, wts = _random_problem(rng, 100, 7, 80, 2)
    got = itemset_counts(tx, tgt, wts)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=1, max_value=300),   # n
    st.integers(min_value=1, max_value=40),    # k
    st.integers(min_value=1, max_value=4),     # w
    st.integers(min_value=1, max_value=4),     # c
    st.integers(min_value=0, max_value=2 ** 31 - 1),
)
def test_kernel_property_random(n, k, w, c, seed):
    rng = np.random.default_rng(seed)
    tx, tgt, wts = _random_problem(rng, n, k, w, c)
    got = itemset_counts(tx, tgt, wts, block_k=32, block_n=128)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_kernel_equals_bruteforce_semantics(seed):
    """End-to-end semantic check against the set-containment oracle."""
    from repro.mining import ItemVocab, class_weights, encode_bitmap, encode_targets

    rng = np.random.default_rng(seed)
    m, n = 20, 120
    db = [[i for i in range(m) if rng.random() < 0.3] for _ in range(n)]
    y = rng.integers(0, 2, n)
    vocab = ItemVocab.from_transactions(db)
    targets = [sorted(rng.choice(m, size=rng.integers(1, 4), replace=False).tolist())
               for _ in range(10)]
    targets = [[a for a in t if a in vocab] for t in targets]
    targets = [t for t in targets if t]
    if not targets:
        return
    got = np.asarray(itemset_counts(
        jnp.asarray(encode_bitmap(db, vocab)),
        jnp.asarray(encode_targets(targets, vocab)),
        jnp.asarray(class_weights(y, 2)), block_k=16, block_n=128))
    db0 = [t for t, c in zip(db, y) if c == 0]
    db1 = [t for t, c in zip(db, y) if c == 1]
    for i, t in enumerate(targets):
        key = tuple(sorted(set(t), key=repr))
        assert got[i, 0] == brute_force_counts(db0, [t])[key]
        assert got[i, 1] == brute_force_counts(db1, [t])[key]


def test_anti_monotone_counts():
    """count(superset) <= count(subset) must hold for kernel outputs."""
    rng = np.random.default_rng(9)
    from repro.mining import ItemVocab, encode_bitmap, encode_targets
    m, n = 16, 200
    db = [[i for i in range(m) if rng.random() < 0.4] for _ in range(n)]
    vocab = ItemVocab.from_transactions(db)
    subs = [[a] for a in range(m) if a in vocab]
    sups = [s + [(s[0] + 1) % m] for s in subs]
    sups = [[a for a in t if a in vocab] for t in sups]
    tx = jnp.asarray(encode_bitmap(db, vocab))
    w = jnp.ones((n, 1), jnp.int32)
    c_sub = np.asarray(itemset_counts(tx, jnp.asarray(encode_targets(subs, vocab)), w))
    c_sup = np.asarray(itemset_counts(tx, jnp.asarray(encode_targets(sups, vocab)), w))
    assert (c_sup <= c_sub).all()


@pytest.mark.parametrize("accum", ["vpu_int32", "mxu_f32"])
def test_accum_variants_exact(accum):
    """Both reduction paths (VPU int32 / MXU f32 §Perf variant) are exact."""
    rng = np.random.default_rng(11)
    tx, tgt, wts = _random_problem(rng, 1111, 77, 5, 3)
    got = itemset_counts(tx, tgt, wts, accum=accum, block_k=32, block_n=256)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mxu_f32_bound_enforced():
    tx = jnp.zeros((1, 1), jnp.uint32)
    tgt = jnp.zeros((1, 1), jnp.uint32)
    w = jnp.ones((1, 1), jnp.int32)
    # fine under the bound
    itemset_counts(tx, tgt, w, accum="mxu_f32")


@pytest.mark.parametrize("n,k,w,c,bk,bn", [
    (64, 8, 2, 2, 8, 128),
    (1111, 77, 5, 3, 32, 256),       # multi-tile + ragged on both axes
    (2048, 256, 4, 1, 256, 1024),    # exact blocks
])
def test_mxu_f32_differential_parity(n, k, w, c, bk, bn):
    """MXU f32 == VPU int32 == jnp oracle, element for element."""
    rng = np.random.default_rng(n + k)
    tx, tgt, wts = _random_problem(rng, n, k, w, c)
    got_mxu = itemset_counts(tx, tgt, wts, accum="mxu_f32",
                             block_k=bk, block_n=bn)
    got_vpu = itemset_counts(tx, tgt, wts, accum="vpu_int32",
                             block_k=bk, block_n=bn)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got_mxu), np.asarray(got_vpu))
    np.testing.assert_array_equal(np.asarray(got_mxu), np.asarray(want))


def test_mxu_f32_exact_near_2p24_bound():
    """Counts just below the 2^24 f32-exactness bound stay bit-exact: every
    partial sum is an integer < 2^24, each exactly representable in f32."""
    n = 8
    tx = jnp.asarray(np.full((n, 1), 0xFFFFFFFF, np.uint32))  # contain all
    tgt = np.zeros((3, 1), np.uint32)
    tgt[1, 0] = 1
    tgt[2, 0] = 0b11
    tgt = jnp.asarray(tgt)
    wts = jnp.asarray(np.full((n, 1), (1 << 21) - 1, np.int32))
    got_mxu = itemset_counts(tx, tgt, wts, accum="mxu_f32")
    got_vpu = itemset_counts(tx, tgt, wts, accum="vpu_int32")
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got_mxu), np.asarray(got_vpu))
    np.testing.assert_array_equal(np.asarray(got_mxu), np.asarray(want))
    # the count itself sits 8 below the bound — and is odd-valued, so any
    # f32 rounding above 2^24 would have been visible
    assert int(np.asarray(got_mxu)[0, 0]) == (1 << 24) - 8


def test_mxu_f32_row_bound_raises_value_error():
    """2^24 unit-weight rows in one launch put the class weight sum at the
    f32 bound: an explicit mxu_f32 request is rejected (ops.py exactness
    guard); the streaming engine re-establishes the bound per chunk
    instead.  A real ValueError with the weights' shape — not a bare assert
    that ``python -O`` strips — and raised BEFORE any device work."""
    n = 1 << 24
    tx = jnp.zeros((n, 1), jnp.uint32)
    tgt = jnp.zeros((1, 1), jnp.uint32)
    w = jnp.ones((n, 1), jnp.int32)
    with pytest.raises(ValueError, match=r"< 2\^24.*\(16777216, 1\)"):
        itemset_counts(tx, tgt, w, accum="mxu_f32")


@pytest.mark.parametrize("weight", [257, 4099, (1 << 16) + 1, (1 << 23) - 3])
def test_mxu_f32_exact_for_weights_above_bf16(weight):
    """Dedup multiplicities above 2^8 are not bf16-representable: the MXU
    path must not round them (explicit HIGHEST precision).  Odd weights
    make any rounding visible."""
    rng = np.random.default_rng(weight)
    tx, tgt, _ = _random_problem(rng, 300, 20, 2, 2)
    wts = np.ones((300, 2), np.int32)
    wts[:1, 0] = weight                   # class-0 sum stays < 2^24
    wts = jnp.asarray(wts)
    got = itemset_counts(tx, tgt, wts, accum="mxu_f32", block_k=8,
                         block_n=128)
    want = itemset_counts_ref(tx, tgt, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mxu_f32_guard_on_weight_sum_over_few_rows():
    """A deduped store can hold few rows with huge multiplicities: 4 rows of
    weight 2^22 + 1 sum past 2^24 although N is tiny.  An explicit mxu_f32
    request raises; a tuned mxu_f32 pick falls back to the exact VPU path."""
    from repro.roofline import autotune
    from repro.roofline.autotune import (LaunchConfig, TableEntry,
                                         TuningTable)
    from repro.roofline.kernel_model import geometry_bucket

    tx = jnp.asarray(np.full((4, 1), 0xFFFFFFFF, np.uint32))
    tgt = jnp.asarray(np.array([[1], [3]], np.uint32))
    wts = jnp.asarray(np.full((4, 1), (1 << 22) + 1, np.int32))
    with pytest.raises(ValueError, match=r"< 2\^24"):
        itemset_counts(tx, tgt, wts, accum="mxu_f32")
    bucket = geometry_bucket(4, 2, 1, 1)
    autotune.set_active_table(TuningTable("cpu", {bucket: TableEntry(
        LaunchConfig(block_k=64, accum="mxu_f32", source="table"), us=1.0,
        efficiency=0.0)}))
    try:
        got = itemset_counts(tx, tgt, wts)
    finally:
        autotune.set_active_table(None)
    assert np.asarray(got).tolist() == [[4 * ((1 << 22) + 1)]] * 2


def test_mxu_f32_guard_on_the_mesh_launch():
    """The sharded launch traces the kernel inside shard_map, where the
    weights are abstract: a tuned mxu_f32 pick must still be checked
    against the weight sum before tracing.  An odd class total just past
    2^24 has no f32 representation, so an unchecked f32 sum would be off."""
    import jax

    from repro.mining.distributed import (place_rows,
                                          resident_distributed_counts)
    from repro.roofline import autotune
    from repro.roofline.autotune import LaunchConfig, TableEntry, TuningTable
    from repro.roofline.kernel_model import geometry_bucket

    mesh = jax.make_mesh((1,), ("data",))
    tx = np.full((4, 1), 0xFFFFFFFF, np.uint32)
    wts = np.full((4, 1), (1 << 22) + 1, np.int32)
    wts[3] += 1                                  # total 2^24 + 5, odd
    tgt = np.array([[1], [3]], np.uint32)
    bits_d, w_d = place_rows(tx, wts, mesh)
    autotune.set_active_table(TuningTable("cpu", {
        geometry_bucket(4, 2, 1, 1): TableEntry(
            LaunchConfig(block_k=64, accum="mxu_f32", source="table"),
            us=1.0, efficiency=0.0)}))
    try:
        got = resident_distributed_counts(bits_d, tgt, w_d, mesh)
    finally:
        autotune.set_active_table(None)
    assert np.asarray(got).tolist() == [[(1 << 24) + 5]] * 2


def test_mxu_f32_bound_comes_from_the_weights_owner(monkeypatch):
    """A store, a DenseDB and a mesh placement check a tuned mxu_f32 pick
    against the weight bound they keep, never by reading their device
    weights back: with the host read made to fail, the counts still come
    out, equal to the default path's."""
    import jax

    from repro.kernels.itemset_count import ops
    from repro.mining.backend import DenseBackend
    from repro.mining.encode import encode_targets
    from repro.roofline import autotune
    from repro.serve import ShardedDB, VersionedDB

    tx = [[0, 1], [1, 2], [0, 1, 2], [2]] * 8
    y = [0, 1] * 16
    store = VersionedDB(tx, classes=y, n_classes=2, merge_ratio=1e9)
    store.append([[0, 1, 2]] * 3, classes=[1, 1, 0])
    meshed = ShardedDB(tx, classes=y, n_classes=2, n_shards=2,
                       mesh=jax.make_mesh((1,), ("data",)))
    backend = DenseBackend(store.base)
    probes = [(0,), (1, 2), (0, 1, 2)]
    masks = encode_targets(probes, store.vocab)
    want = (store.counts(probes), meshed.counts(probes),
            backend.counts(masks))

    def no_host_read(weights):
        raise AssertionError("the mxu_f32 check read the weights back")

    monkeypatch.setattr(ops, "weight_sum_bound", no_host_read)
    monkeypatch.setattr(autotune, "resolve_launch_config",
                        lambda *a: autotune.LaunchConfig(accum="mxu_f32",
                                                         source="table"))
    np.testing.assert_array_equal(store.counts(probes), want[0])
    np.testing.assert_array_equal(meshed.counts(probes), want[1])
    np.testing.assert_array_equal(backend.counts(masks), want[2])
