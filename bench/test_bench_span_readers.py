"""The readers of the program's own spans, on hand-built windows: the host
part of a count call, the host part of a query job, a submit's wait for
the lock, and a request's wait in the queue."""
import itertools
from types import SimpleNamespace

import pytest

from bench import harness

_ids = itertools.count(1)


def span(name, t0, t1, parent=None, **attrs):
    return SimpleNamespace(name=name, span_id=next(_ids),
                           parent_id=None if parent is None
                           else parent.span_id,
                           t0=t0, t1=t1, attrs=attrs)


def read(metric, spans, **ctx):
    return harness.load_reader(metric).read(
        {"spans": spans, "t_open": 0.0, "t_close": 100.0, **ctx})


def count_call(t0, prepare, launch, wait, record, parent=None):
    """A ``kernel.count`` span and its four parts, back to back."""
    call = span("kernel.count", t0, t0 + prepare + launch + wait + record,
                parent, n=4_000_000, k=256, w=32, c=2)
    out, t = [call], t0
    for name, d in (("kernel.prepare", prepare), ("kernel.launch", launch),
                    ("kernel.wait", wait), ("kernel.record", record)):
        out.append(span(name, t, t + d, call))
        t += d
    return out


@pytest.mark.parametrize("cell", ["serve", "bulk"])
def test_count_host_max_sees_a_freeze_in_the_launch_only(cell):
    metric = f"count_host_max_ms.{cell}"
    calm = count_call(1.0, 0.001, 0.002, 0.045, 0.0005)
    in_launch = count_call(2.0, 0.001, 0.800, 0.045, 0.0005)
    in_wait = count_call(3.0, 0.001, 0.002, 0.800, 0.0005)
    assert read(metric, calm) == pytest.approx(3.5)
    assert read(metric, calm + in_wait) == pytest.approx(3.5)
    assert read(metric, calm + in_launch + in_wait) == \
        pytest.approx(801.5)


def test_job_host_is_the_query_less_its_device_waits():
    spans = []
    for i, (host, wait) in enumerate([(0.15, 2.0), (0.17, 2.1),
                                      (0.20, 2.0)]):
        t = 10.0 * i
        q = span("serve.query", t, t + host + wait)
        spans.append(q)
        spans.append(span("serve.keys", t, t + 0.01, q))
        count = span("serve.count", t + 0.02, t + 0.04 + wait, q)
        spans.append(count)
        spans += count_call(t + 0.03, 0.001, 0.002, wait, 0.0005, count)
    assert read("job_host_p50_ms.bulk", spans) == pytest.approx(170.0)


def test_submit_wait_p95_reads_the_instants():
    spans = [span("serve.submit", t, t, ticket=i, n_queries=1,
                  wait_ms=float(i))
             for i, t in enumerate(range(1, 41))]
    # nearest rank: the 38th of 40 waits (0 .. 39 ms)
    assert read("submit_wait_p95_ms.serve", spans) == pytest.approx(37.0)


def test_queue_wait_pairs_a_submit_with_the_next_drain():
    spans = [span("serve.submit", 1.000, 1.000, ticket=0, wait_ms=0.0),
             span("serve.submit", 1.010, 1.010, ticket=1, wait_ms=0.0),
             span("serve.dedup", 1.030, 1.031, n_requests=2),
             span("serve.submit", 1.040, 1.040, ticket=2, wait_ms=0.0),
             span("serve.dedup", 1.045, 1.046, n_requests=0),
             span("serve.dedup", 1.090, 1.091, n_requests=1)]
    # waits 30, 20 and 50 ms (the empty drain answers nobody)
    assert read("queue_wait_p50_ms.serve", spans) == pytest.approx(30.0)


def test_spans_outside_the_window_are_not_read():
    early = count_call(-5.0, 0.001, 0.900, 0.045, 0.0005)
    calm = count_call(1.0, 0.001, 0.002, 0.045, 0.0005)
    assert read("count_host_max_ms.serve", early + calm) == \
        pytest.approx(3.5)


@pytest.mark.parametrize("metric", [
    "submit_wait_p95_ms.serve", "queue_wait_p50_ms.serve",
    "count_host_max_ms.serve", "count_host_max_ms.bulk",
    "job_host_p50_ms.bulk"])
def test_nothing_to_read_reads_none(metric):
    assert read(metric, []) is None
    assert harness.load_reader(metric).read({}) is None


def test_a_program_without_the_new_spans_reads_none():
    """A program whose count op has no parts and whose submits carry no
    wait: the readers that need them find nothing."""
    call = span("kernel.count", 1.0, 1.05, n=10, k=8, w=1, c=1)
    query = span("serve.query", 1.0, 1.1)
    submit = span("serve.submit", 1.0, 1.0, ticket=0, n_queries=1)
    for metric in ("count_host_max_ms.serve", "job_host_p50_ms.bulk",
                   "submit_wait_p95_ms.serve"):
        assert read(metric, [call, query, submit]) is None


def _flush(t0, n_masks, words=None, program_words=None, base=4_000_000,
           delta=40_000):
    """A ``serve.count`` span with its targets' words (recorded by the
    benchmark's instant beneath it, or by the span itself) and a base and
    a delta launch of 256 targets beneath it."""
    from bench.target_words import INSTANT

    count = span("serve.count", t0, t0 + 0.03, n_masks=n_masks,
                 **(program_words or {}))
    out = [count]
    if words is not None:
        out.append(span(INSTANT, t0 + 0.001, t0 + 0.001, count, **words))
    store = span("store.counts", t0 + 0.002, t0 + 0.03, count)
    out.append(store)
    for i, n in enumerate((base, delta)):
        out.append(span("kernel.count", t0 + 0.003 + 0.01 * i,
                        t0 + 0.012 + 0.01 * i, store, n=n, k=256, w=32,
                        c=2))
    return out


def _least(n, k, w, c, held, touched):
    from bench import roofline
    from bench.peaks import PEAKS

    return roofline.least_seconds(n, k, w, c, PEAKS["TPU v5 lite"], held,
                                  touched)[0]


def _roofline_ctx(spans, kernel_s):
    return {"spans": spans, "trace": {"kernel_s": kernel_s},
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("where", ["benchmark instant", "program span"])
def test_roofline_prices_each_launch_by_its_targets_words(where, capsys):
    from bench.readings import launches, priced_launches, roofline

    words = {"mask_words": 141, "union_words": 31}
    spans = _flush(1.0, 72, **({"words": words}
                               if where == "benchmark instant"
                               else {"program_words": words}))
    # base and delta launches share the flush's 72 targets and words
    assert priced_launches({"spans": spans}) == [
        (4_000_000, 72, 32, 2, 141, 31), (40_000, 72, 32, 2, 141, 31)]
    assert [r[4] for r in launches({"spans": spans})] == [72, 72]
    least = _least(4_000_000, 72, 32, 2, 141, 31) + \
        _least(40_000, 72, 32, 2, 141, 31)
    assert roofline(_roofline_ctx(spans, 0.02)) == \
        pytest.approx(100 * least / 0.02)
    assert "2 launches, 0 of them priced as dense" in capsys.readouterr().err


def test_the_programs_own_record_comes_before_the_benchmarks():
    from bench.readings import priced_launches

    spans = _flush(1.0, 72, words={"mask_words": 999, "union_words": 32},
                   program_words={"mask_words": 141, "union_words": 31})
    assert {p[4:] for p in priced_launches({"spans": spans})} == {(141, 31)}


def test_words_are_held_to_the_launch_width():
    from bench.readings import priced_launches

    spans = _flush(1.0, 2, words={"mask_words": 90, "union_words": 40})
    assert {p[4:] for p in priced_launches({"spans": spans})} == \
        {(64, 32)}


def test_a_launch_without_a_record_of_words_is_priced_dense(capsys):
    from bench.readings import priced_launches, roofline

    recorded = _flush(1.0, 72, words={"mask_words": 141, "union_words": 31})
    bare = _flush(2.0, 100)
    orphan = span("kernel.count", 3.0, 3.01, n=4_000_000, k=256, w=32, c=2)
    spans = recorded + bare + [orphan]
    priced = priced_launches({"spans": spans})
    assert [p[4:] for p in priced] == [(141, 31)] * 2 + [(None, None)] * 3
    assert [p[1] for p in priced] == [72, 72, 100, 100, 256]
    least = sum(_least(*p) for p in priced)
    dense = _least(4_000_000, 100, 32, 2, 100 * 32, 32)
    assert dense == _least(4_000_000, 100, 32, 2, None, None)
    assert roofline(_roofline_ctx(spans, 1.0)) == pytest.approx(100 * least)
    assert "5 launches, 3 of them priced as dense" in \
        capsys.readouterr().err
