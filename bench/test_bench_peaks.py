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


@pytest.mark.parametrize("n,k,w,c", [(4_040_000, 16_384, 32, 2),
                                     (4_000_000, 256, 32, 2),
                                     (22_500, 930, 4, 2), (1, 1, 1, 1)])
def test_counts_equal_the_formulas(n, k, w, c):
    assert roofline.kernel_ops(n, k, w, c) == n * k * (2 * w + c)
    assert roofline.kernel_bytes(n, k, w, c) == \
        4 * (n * w + n * c + k * w + k * c)


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
