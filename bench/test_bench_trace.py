"""The reduction from a profiler trace to device metrics: on synthetic
events, and on a small trace recorded on a v5e (``bench/testdata/``)."""
import glob
import os

import pytest

from bench import trace_reduce as T

FIXTURE = glob.glob(os.path.join(os.path.dirname(__file__), "testdata",
                                 "small_trace", "**", "*.xplane.pb"),
                    recursive=True)


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(2, 3), (0, 1), (1, 2)], [(0, 3)]),
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),
    ([(5, 5), (1, 2)], [(1, 2)]),
])
def test_union(intervals, merged):
    assert T.union(intervals) == merged


def test_gaps_and_clip():
    busy = T.union(T.clip([(-5, 1), (2, 3), (9, 20)], 0, 10))
    assert busy == [(0, 1), (2, 3), (9, 10)]
    assert T.gaps(busy, 0, 10) == [(1, 2), (3, 9)]
    assert T.gaps([], 0, 10) == [(0, 10)]


def test_label_is_the_innermost_open_span():
    spans = [("outer", 0, 100), ("inner", 10, 20), ("later", 50, 60)]
    assert T.label_at(15, spans) == "inner"
    assert T.label_at(30, spans) == "outer"
    assert T.label_at(55, spans) == "later"
    assert T.label_at(150, spans) == "none"


def _events():
    # two devices; the window is [0, 100) ns of the bench.window mark
    return {
        "devices": {
            "/device:TPU:0": [("itemset_count", "itemset_count", 10, 40),
                              ("copy.1", "copy.1", 40, 50),
                              ("itemset_count", "itemset_count", 80, 120)],
            "/device:TPU:1": [("itemset_count", "itemset_count", 0, 30)],
        },
        "host": [("bench.window", 0, 100), ("bench.query", 50, 79)],
    }


def test_reduce_shares_and_breakdown():
    r = T.reduce(_events(), "itemset_count")
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0 busy 60 ns of the window, device 1 busy 30 ns: mean 45
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["kernel_s"] == pytest.approx((30 + 20 + 30) / 2 * 1e-9)
    assert r["other_ops_s"] == pytest.approx(10 / 2 * 1e-9)
    ops = dict((n, s) for n, s in r["breakdown"]["device_ops"])
    assert ops["itemset_count"] == pytest.approx(80e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.query", pytest.approx(30e-9)]
    assert gaps[1] == ["none", pytest.approx(10e-9)]
    assert len(gaps) <= 10 and len(r["breakdown"]["device_ops"]) <= 10


def test_reduce_takes_spans_for_labels():
    r = T.reduce(_events(), "itemset_count",
                 spans=[("serve.flush", 0, 9)])
    assert ["serve.flush", pytest.approx(10e-9)] in \
        r["breakdown"]["idle_gaps"]


def test_reduce_without_device_ops_raises():
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "host": [("bench.window", 0, 10)]}, "k")


def test_short_name_drops_layouts_and_attributes():
    name = ('%itemset_count.1 = s32[256,2]{1,0:T(8,128)S(1)} custom-call('
            'u32[32,200704]{1,0:T(8,128)} %tx_bits_t.1), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert T.short_name(name) == ("itemset_count.1 = s32[256,2] custom-call("
                                  "u32[32,200704] tx_bits_t.1)")


def test_fixture_is_one_recorded_on_a_v5e():
    assert len(FIXTURE) == 1
    assert os.path.getsize(FIXTURE[0]) < 1 << 20


def test_reduction_of_the_recorded_trace():
    """``bench/record_trace.py`` on a v5e: three 10-key queries over a
    200,000-row store plus a 2,000-row delta, each in a ``bench.query``
    annotation, with ``bench.idle`` sleeps between them."""
    events = T.load(FIXTURE[0])
    assert list(events["devices"]) == ["/device:TPU:0"]
    names = [n for n, _, _ in events["host"]]
    assert names.count("bench.query") == 3 and names.count("bench.idle") == 3
    r = T.reduce(events, "itemset_count")
    assert 0 < r["kernel_s"] <= r["busy_s"] <= r["window_s"]
    assert r["kernel_s"] + r["other_ops_s"] == pytest.approx(r["ops_s"])
    assert 0.0 < r["idle_share"] < 1.0
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0].startswith("itemset_count") and "{" not in ops[0][0]
    # two launches a query: the base segment (200,000 rows padded to
    # 200,704) and the delta (2,000 rows padded to 2,048)
    kernels = [n for n, _ in ops if n.startswith("itemset_count")]
    assert any("u32[32,200704]" in n for n in kernels)
    assert any("u32[32,2048]" in n for n in kernels)
    gaps = r["breakdown"]["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    assert gaps[0][0] == "bench.idle"
    assert all(g[1] > 0 for g in gaps)
