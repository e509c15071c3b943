"""The benchmark's peaks table and roofline arithmetic."""
import pytest

from bench import roofline
from bench.peaks import PEAKS, UnknownDevice, peaks_for

V5E = "TPU v5 lite"


def test_unknown_device_kind_raises():
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")


def test_v5e_entry_and_int32_ceiling():
    p = peaks_for(V5E)
    assert p.bf16_flops == 197e12
    assert p.hbm_bytes_per_s == 819e9
    clock = 197e12 / (4 * 128 * 128 * 2)
    assert p.int32_ops == pytest.approx(clock * 1024 * 4)
    assert p.int32_ops == pytest.approx(6.155e12, rel=1e-3)
    assert p.source


DENSE_SHAPES = [(4_040_000, 16_384, 32, 2), (4_000_000, 256, 32, 2),
                (22_500, 930, 4, 2), (1, 1, 1, 1)]


def _dense_before(n, k, w, c):
    """The dense model's arithmetic as it stood before targets were priced
    by the words they hold: ops, bytes, least time and its bound."""
    p = PEAKS[V5E]
    ops = float(n) * float(k) * (2.0 * w + c)
    nbytes = 4.0 * (float(n) * w + float(n) * c + float(k) * w
                    + float(k) * c)
    t_ops, t_bytes = ops / p.int32_ops, nbytes / p.hbm_bytes_per_s
    least = (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
    return ops, nbytes, least


@pytest.mark.parametrize("words", ["default", "every word held"])
@pytest.mark.parametrize("n,k,w,c", DENSE_SHAPES)
def test_counts_equal_the_formulas(n, k, w, c, words):
    """Targets that hold all ``w`` words (``held = k * w``, ``touched =
    w``, given or left to the default) give exactly the dense formulas."""
    extra = () if words == "default" else (k * w, w)
    assert roofline.kernel_ops(n, k, w, c, *extra[:1]) == \
        n * k * (2 * w + c)
    assert roofline.kernel_bytes(n, k, w, c, *extra) == \
        4 * (n * w + n * c + k * w + k * c)


@pytest.mark.parametrize("n,k,w,c", DENSE_SHAPES)
def test_dense_targets_give_the_old_numbers_exactly(n, k, w, c):
    p = PEAKS[V5E]
    ops, nbytes, least = _dense_before(n, k, w, c)
    assert roofline.kernel_ops(n, k, w, c, k * w) == ops
    assert roofline.kernel_bytes(n, k, w, c, k * w, w) == nbytes
    assert roofline.least_seconds(n, k, w, c, p, k * w, w) == least
    assert roofline.least_seconds(n, k, w, c, p) == least
    share = roofline.roofline_share([(n, k, w, c, k * w, w)], 1.0, p)
    assert share == roofline.roofline_share([(n, k, w, c)], 1.0, p)
    assert share == (100.0 * least[0], least[1])


def test_pairs_in_two_words_by_hand():
    """16,384 pairs over 4,040,000 rows of 32 words and 2 classes, each
    pair's items in 2 words, the pairs touching every word: per row 2 x
    32,768 word tests and 32,768 adds."""
    p = PEAKS[V5E]
    n, k, w, c = 4_040_000, 16_384, 32, 2
    held, touched = 2 * k, 32
    ops = 4_040_000 * (2 * 32_768 + 32_768)             # 3.971e11
    nbytes = 4 * (4_040_000 * 32 + 4_040_000 * 2 + 32_768 + 32_768)
    assert roofline.kernel_ops(n, k, w, c, held) == ops
    assert roofline.kernel_bytes(n, k, w, c, held, touched) == nbytes
    least, bound = roofline.least_seconds(n, k, w, c, p, held, touched)
    assert bound == "ops" and least == pytest.approx(ops / 6.155e12,
                                                     rel=1e-3)
    assert least == pytest.approx(0.06452, rel=1e-3)
    # against the dense pricing of the same launch: 66 ops a pair, not 6
    assert roofline.kernel_ops(n, k, w, c) / ops == pytest.approx(11.0)


def test_touched_words_set_the_bytes_term():
    """64 targets of one word each over 4M rows: 256 ops a row.  Touching
    all 32 words, a row's 34 words of bytes set the bound; touching 2, its
    4 words take less time than the ops, which then set it."""
    p = PEAKS[V5E]
    n, k, w, c = 4_000_000, 64, 32, 2
    ops = 4_000_000 * (2 * 64 + 64 * 2)
    assert roofline.kernel_ops(n, k, w, c, 64) == ops
    wide = 4 * (4_000_000 * 32 + 4_000_000 * 2 + 64 + 128)
    narrow = 4 * (4_000_000 * 2 + 4_000_000 * 2 + 64 + 128)
    assert roofline.kernel_bytes(n, k, w, c, 64, 32) == wide
    assert roofline.kernel_bytes(n, k, w, c, 64, 2) == narrow
    assert roofline.least_seconds(n, k, w, c, p, 64, 32) == \
        (wide / 819e9, "bytes")
    least, bound = roofline.least_seconds(n, k, w, c, p, 64, 2)
    assert bound == "ops" and least == pytest.approx(ops / p.int32_ops)
    assert narrow / 819e9 < least < wide / 819e9


def test_share_is_ops_bound_for_a_wide_target_block():
    p = PEAKS[V5E]
    shape = (4_000_000, 16_384, 32, 2)
    least, bound = roofline.least_seconds(*shape, p)
    assert bound == "ops"
    assert least == pytest.approx(roofline.kernel_ops(*shape) / p.int32_ops)
    share, which = roofline.roofline_share([shape], 4 * least, p)
    assert which == "ops" and share == pytest.approx(25.0)


def test_share_is_bytes_bound_for_a_single_target():
    p = PEAKS[V5E]
    shape = (4_000_000, 1, 32, 2)
    least, bound = roofline.least_seconds(*shape, p)
    assert bound == "bytes"
    assert least == pytest.approx(roofline.kernel_bytes(*shape)
                                  / p.hbm_bytes_per_s)
    share, which = roofline.roofline_share([shape, shape], 2 * least, p)
    assert which == "bytes" and share == pytest.approx(100.0)


def test_share_sums_each_launch_by_its_own_bound():
    p = PEAKS[V5E]
    a, b = (4_000_000, 16_384, 32, 2), (4_000_000, 1, 32, 2)
    ta, _ = roofline.least_seconds(*a, p)
    tb, _ = roofline.least_seconds(*b, p)
    share, which = roofline.roofline_share([a, b], 10 * (ta + tb), p)
    assert share == pytest.approx(10.0) and which == "ops"


@pytest.mark.parametrize("launches,seconds", [([], 1.0),
                                              ([(10, 10, 1, 1)], 0.0)])
def test_share_with_nothing_to_read_raises(launches, seconds):
    with pytest.raises(ValueError):
        roofline.roofline_share(launches, seconds, PEAKS[V5E])
