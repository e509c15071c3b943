"""Telemetry layer battery: registry exactness, span structure, exports,
zero-overhead-when-disabled, and the perf gate's self-test.

Everything here is fast-tier: tiny DBs, short thread storms, no slow marks.
"""
import json
import subprocess
import sys
import threading
import tracemalloc
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs import (REGISTRY, TRACER, counter_total, counter_value,
                       hist_get, hist_merge, hist_quantile, nearest_rank)
from repro.obs.export import prometheus_text, start_metrics_server
from repro.serve import CountServer
from repro.serve.batcher import MicroBatcher
from repro.serve.cache import CountCache, check_cache_ledger

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Every test starts from an empty registry/ring with default switches
    and leaves the same behind."""
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# metrics registry exactness
# ---------------------------------------------------------------------------

def test_histogram_bucket_boundaries_exact():
    h = REGISTRY.histogram("t_bounds_ms", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.0001, 2.0, 5.0, 5.1, 100.0):
        h.observe(v)
    got = hist_get(REGISTRY.snapshot(), "t_bounds_ms")
    assert got["buckets"] == [1.0, 2.0, 5.0]
    # bucket i holds v <= buckets[i]; boundary values land IN their bucket
    assert got["counts"] == [2, 2, 1, 2]
    assert got["count"] == 7 == sum(got["counts"])
    assert got["sum"] == pytest.approx(0.5 + 1.0 + 1.0001 + 2.0 + 5.0
                                       + 5.1 + 100.0)


def test_observe_many_matches_per_item_observe():
    a = REGISTRY.histogram("t_many_ms", buckets=(1.0, 10.0), kind="bulk")
    b = REGISTRY.histogram("t_many_ms", kind="single")
    values = [0.2, 1.0, 3.7, 9.9, 10.0, 250.0]
    a.observe_many(values)
    for v in values:
        b.observe(v)
    snap = REGISTRY.snapshot()
    bulk = hist_get(snap, "t_many_ms", "kind=bulk")
    single = hist_get(snap, "t_many_ms", "kind=single")
    assert bulk["counts"] == single["counts"]
    assert bulk["count"] == single["count"] == len(values)
    assert bulk["sum"] == pytest.approx(single["sum"])


def test_cross_thread_counter_merge_is_exact():
    c = REGISTRY.counter("t_cross_total")
    h = REGISTRY.histogram("t_cross_ms", buckets=(1.0,))
    n_threads, per_thread = 8, 500

    def work():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = REGISTRY.snapshot()
    # thread-confined shards: no lost updates, the merge is exact
    assert counter_total(snap, "t_cross_total") == n_threads * per_thread
    assert hist_get(snap, "t_cross_ms")["count"] == n_threads * per_thread
    assert REGISTRY.n_shards >= n_threads


def test_counters_allow_negative_and_restore_rolls_back():
    b = MicroBatcher()
    b.submit("a", [(1, 2), (2, 3)])
    b.submit("b", [(2, 1)])          # canonical dup of (1, 2)
    plan = b.take()
    assert counter_value(REGISTRY.snapshot(),
                         "serve_deduped_queries_total") == 1
    b.restore(plan.requests)
    snap = REGISTRY.snapshot()
    # drain-time mirrors rolled back: a re-take must count each request once
    assert counter_value(snap, "serve_requests_total") == 0
    assert counter_value(snap, "serve_queries_total") == 0
    assert counter_value(snap, "serve_deduped_queries_total") == 0
    b.take()
    snap = REGISTRY.snapshot()
    assert counter_value(snap, "serve_requests_total") == 2
    assert counter_value(snap, "serve_queries_total") == 3
    assert counter_value(snap, "serve_deduped_queries_total") == 1


def test_exclusive_gauge_is_one_hot():
    REGISTRY.set_gauge("t_decision", 1, exclusive=True, backend="dense")
    REGISTRY.set_gauge("t_decision", 1, exclusive=True, backend="gfp")
    sets = REGISTRY.snapshot()["gauges"]["t_decision"]
    assert sets == {"backend=gfp": 1}


def test_histogram_bucket_grid_is_per_name():
    REGISTRY.histogram("t_grid_ms", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        REGISTRY.histogram("t_grid_ms", buckets=(3.0,))


def test_nearest_rank_percentiles():
    assert nearest_rank([], 0.5) is None
    assert nearest_rank([7.0], 0.95) == 7.0
    # the old lat[int(p * n)] indexing overshot: p50 of [1, 2] read 2
    assert nearest_rank([1.0, 2.0], 0.50) == 1.0
    assert nearest_rank([1.0, 2.0], 0.51) == 2.0
    assert nearest_rank(list(range(1, 101)), 0.95) == 95
    with pytest.raises(ValueError):
        nearest_rank([1.0], 1.5)


def test_hist_quantile_conservative_bound():
    h = REGISTRY.histogram("t_q_ms", buckets=(1.0, 10.0, 100.0))
    h.observe_many([0.5] * 90 + [50.0] * 10)
    merged = hist_merge(REGISTRY.snapshot(), "t_q_ms")
    assert hist_quantile(merged, 0.5) == 1.0     # true 0.5 <= bound 1.0
    assert hist_quantile(merged, 0.95) == 100.0  # true 50 <= bound 100


# ---------------------------------------------------------------------------
# zero overhead when disabled
# ---------------------------------------------------------------------------

def test_disabled_hot_path_allocates_nothing():
    c = REGISTRY.counter("t_noalloc_total")
    h = REGISTRY.histogram("t_noalloc_ms")
    obs.disable_all()
    obs_dir = str(Path(obs.__file__).parent)

    def hot():
        for _ in range(200):
            c.inc()
            h.observe(1.0)
            with TRACER.span("t.noalloc"):
                pass
            TRACER.instant("t.noalloc")

    hot()                                   # warm up any lazy imports
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    leaks = [s for s in after.compare_to(before, "lineno")
             if s.size_diff > 0
             and s.traceback[0].filename.startswith(obs_dir)]
    assert not leaks, [str(s) for s in leaks]
    # and nothing was recorded either
    obs.configure(metrics=True)
    snap = REGISTRY.snapshot()
    assert counter_value(snap, "t_noalloc_total") == 0
    assert hist_get(snap, "t_noalloc_ms") is None
    assert TRACER.spans() == []


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_error_attr():
    obs.configure(tracing=True)
    with TRACER.span("outer", {"a": 1}) as outer:
        with TRACER.span("inner") as inner:
            TRACER.instant("mark", {"k": "v"})
        with pytest.raises(RuntimeError):
            with TRACER.span("boom"):
                raise RuntimeError("x")
    spans = {s.name: s for s in TRACER.spans()}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["mark"].parent_id == inner.span_id
    assert spans["boom"].attrs["error"] == "RuntimeError"
    assert spans["outer"].parent_id is None
    assert spans["outer"].t1 >= spans["inner"].t1 >= spans["inner"].t0
    assert "outer" in TRACER.summary()


def test_trace_chain_submit_flush_kernel_under_concurrent_async(rng):
    obs.configure(tracing=True)
    tx = [sorted(rng.choice(16, size=3, replace=False).tolist())
          for _ in range(300)]
    with CountServer(tx, async_flush=True, min_batch=4,
                     max_delay_ms=5.0) as server:
        def client(cid):
            futs = [server.submit_async(f"c{cid}", [(i % 16, (i + 1) % 16)])
                    for i in range(6)]
            for f in futs:
                assert f.result(timeout=30).shape == (1, 1)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    doc = TRACER.chrome_trace()
    events = doc["traceEvents"]
    assert events and all({"name", "ph", "ts", "pid", "tid"} <= set(e)
                          for e in events)
    json.dumps(doc)                         # valid JSON end to end
    by_id = {e["args"]["span_id"]: e for e in events}
    # the full chain: submit instants link by ticket, flush > count > kernel
    submits = [e for e in events if e["name"] == "serve.submit"]
    assert submits and all(e["ph"] == "i" and "ticket" in e["args"]
                           for e in submits)
    flushes = [e for e in events if e["name"] == "serve.flush"]
    assert flushes and all(e["ph"] == "X" for e in flushes)
    kernels = [e for e in events if e["name"] == "kernel.count"]
    assert kernels
    for k in kernels:
        count = by_id[k["args"]["parent_id"]]
        assert count["name"] == "serve.count"
        flush = by_id[count["args"]["parent_id"]]
        assert flush["name"] == "serve.flush"
        assert flush["args"]["trigger"] in ("occupancy", "deadline",
                                            "manual", "drain", "sync")


def test_span_ring_is_bounded():
    t = obs.Tracer(enabled=True, ring_spans=8)
    for i in range(32):
        with t.span(f"s{i}"):
            pass
    spans = t.spans()
    assert len(spans) == 8
    assert [s.name for s in spans] == [f"s{i}" for i in range(24, 32)]


# ---------------------------------------------------------------------------
# instrumented serving stack
# ---------------------------------------------------------------------------

def _tiny_server(rng, **kw):
    tx = [sorted(rng.choice(12, size=3, replace=False).tolist())
          for _ in range(200)]
    return tx, CountServer(tx, **kw)


def test_server_stats_expose_kernel_efficiency(rng):
    tx, server = _tiny_server(rng)
    server.submit("a", [(0, 1), (2,)])
    server.flush()
    stats = server.stats()
    tele = stats["telemetry"]
    assert tele["enabled"]
    eff = tele["kernel_efficiency"]
    assert eff, "no kernel launch was recorded"
    for geom, rec in eff.items():
        assert rec["launches"] >= 1
        assert rec["measured_s"] > 0
        # the CPU is not in the peaks table: no prediction, no ratio
        assert rec["predicted_s"] is None
        assert rec["efficiency"] is None
        assert geom.startswith("n")
    snap = tele["metrics"]
    assert counter_value(snap, "serve_requests_total") == 1
    assert counter_value(snap, "serve_queries_total") == 2
    assert counter_value(snap, "serve_flushes_total", trigger="sync") == 1
    assert hist_merge(snap, "serve_queue_wait_ms")["count"] == 1
    assert "kernel launches" in obs.summary_line(snap)


def test_cache_registry_mirrors_published_at_drain_points(rng):
    tx, server = _tiny_server(rng)
    for _ in range(3):                       # 1 cold + 2 warm rounds
        server.submit("a", [(0, 1), (1, 2)])
        server.flush()
    s = server.cache.stats()
    assert s["hits"] == 4 and s["misses"] == 2 and s["inserts"] == 2
    snap = REGISTRY.snapshot()
    # flush/stats are the publish points: mirrors agree exactly there
    assert counter_value(snap, "cache_hits_total", cache="CountCache") == 4
    assert counter_value(snap, "cache_misses_total", cache="CountCache") == 2
    assert counter_value(snap, "cache_inserts_total", cache="CountCache") == 2
    check_cache_ledger(server.cache, miss_driven=True)


def test_check_cache_ledger_under_eviction_and_oversized():
    cache = CountCache(capacity=4, max_bytes=64)
    version = 0
    for i in range(8):                       # get-miss-compute-put discipline
        key = (i,)
        if cache.get(key, version) is None:
            cache.put(key, version, np.full(4, i, np.int32))   # 16 bytes
    assert cache.get((7,), version) is not None
    if cache.get(("big",), version) is None:
        cache.put(("big",), version, np.zeros(64, np.int32))   # > max_bytes
    s = check_cache_ledger(cache, miss_driven=True)
    assert s["evictions"] == 4 and s["oversized_rejects"] == 1
    assert s["size"] == 4
    cache.purge_stale(current_version=1)
    s = check_cache_ledger(cache, miss_driven=True)
    assert s["purged"] == 4 and s["size"] == 0
    # ledger == registry mirror after the stats() publish
    snap = REGISTRY.snapshot()
    for field, name in [("hits", "cache_hits_total"),
                        ("misses", "cache_misses_total"),
                        ("evictions", "cache_evictions_total"),
                        ("inserts", "cache_inserts_total"),
                        ("oversized_rejects", "cache_oversized_rejects_total"),
                        ("purged", "cache_purged_total")]:
        assert counter_value(snap, name, cache="CountCache") == s[field], name


def test_async_stats_thread_safe_under_traffic(rng):
    tx, server = _tiny_server(rng, async_flush=True, min_batch=2,
                              max_delay_ms=2.0)
    errors = []

    def poll():
        try:
            for _ in range(200):
                lat = server.stats()["async"]["flush_latency_ms"]
                for k in ("p50", "p95", "max"):
                    assert lat[k] is None or lat[k] >= 0
        except Exception as e:   # pragma: no cover - the failure signal
            errors.append(e)

    with server:
        poller = threading.Thread(target=poll)
        poller.start()
        futs = [server.submit_async("c", [(i % 12,)]) for i in range(64)]
        for f in futs:
            f.result(timeout=30)
        poller.join()
    assert not errors
    st = server.stats()["async"]
    assert st["flushes"] >= 1
    # exact nearest-rank on the recorded window
    lat = sorted(server._flusher.latencies_ms)
    assert st["flush_latency_ms"]["p50"] == nearest_rank(lat, 0.50)
    assert st["flush_latency_ms"]["p95"] == nearest_rank(lat, 0.95)


# ---------------------------------------------------------------------------
# export + gate
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    REGISTRY.counter("t_exp_total", path="host").inc(3)
    REGISTRY.set_gauge("t_exp_gauge", 2.5)
    h = REGISTRY.histogram("t_exp_ms", buckets=(1.0, 10.0))
    h.observe_many([0.5, 5.0, 50.0])
    text = prometheus_text(REGISTRY.snapshot())
    assert '# TYPE t_exp_total counter' in text
    assert 't_exp_total{path="host"} 3' in text
    assert 't_exp_gauge 2.5' in text
    assert 't_exp_ms_bucket{le="1"} 1' in text
    assert 't_exp_ms_bucket{le="10"} 2' in text
    assert 't_exp_ms_bucket{le="+Inf"} 3' in text
    assert 't_exp_ms_count 3' in text


def test_metrics_http_server_roundtrip():
    REGISTRY.counter("t_http_total").inc(7)
    srv = start_metrics_server(0)
    try:
        port = srv.server_address[1]
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "t_http_total 7" in text
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert snap["counters"]["t_http_total"][""] == 7
    finally:
        srv.shutdown()


def test_summary_line_states():
    assert obs.summary_line() == "telemetry: no activity"
    obs.configure(metrics=False)
    assert obs.summary_line() == "telemetry: disabled"


def test_perfgate_self_test_passes_and_catches_regressions():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perfgate.py"), "--self-test"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "injected regression caught" in proc.stdout
    assert "self-test OK" in proc.stdout
