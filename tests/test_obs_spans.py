"""Spans inside the count op and the count service: the parts of
``kernel.count``, the waits of a submit and of the flusher thread, the
compile spans, the program's spans on a recorded JAX profile, and the
launch counter without kernel timing."""
import glob
import os
import threading
import time
import tracemalloc
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.itemset_count import itemset_counts
from repro.obs import REGISTRY, TRACER, counter_total, counter_value, \
    hist_get
from repro.serve import CountServer

KERNEL_PARTS = ["kernel.prepare", "kernel.launch", "kernel.wait",
                "kernel.record"]


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.reset()
    yield
    obs.reset()


def _problem(rng, n=300, k=5, w=2, c=2):
    tx = rng.integers(0, 1 << 32, size=(n, w), dtype=np.uint64) \
        .astype(np.uint32)
    tgt = (tx[:k] & tx[k:2 * k]).astype(np.uint32)
    wts = rng.integers(0, 3, size=(n, c)).astype(np.int32)
    return jnp.asarray(tx), jnp.asarray(tgt), jnp.asarray(wts)


def _server(rng, **kw):
    tx = [sorted(rng.choice(12, size=3, replace=False).tolist())
          for _ in range(200)]
    return CountServer(tx, **kw)


def _children(parent):
    return sorted((s for s in TRACER.spans()
                   if s.parent_id == parent.span_id), key=lambda s: s.t0)


@pytest.mark.parametrize("timing,parts", [
    (True, KERNEL_PARTS),
    (False, [p for p in KERNEL_PARTS if p != "kernel.wait"]),
])
def test_kernel_count_has_its_parts_in_order(rng, timing, parts):
    obs.configure(tracing=True, kernel_timing=timing)
    np.asarray(itemset_counts(*_problem(rng)))
    (call,) = [s for s in TRACER.spans() if s.name == "kernel.count"]
    assert call.attrs == {"n": 300, "k": 5, "w": 2, "c": 2}
    kids = _children(call)
    assert [s.name for s in kids] == parts
    assert all(s.attrs == {} for s in kids)
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0
    assert call.t0 <= kids[0].t0 and kids[-1].t1 <= call.t1
    assert sum(s.t1 - s.t0 for s in kids) <= call.t1 - call.t0


def test_launches_are_counted_without_kernel_timing(rng):
    obs.configure(kernel_timing=False)
    server = _server(rng)
    for i in range(3):
        server.submit("a", [(i, i + 1)])
        server.flush()
    snap = REGISTRY.snapshot()
    # the two counters launches_per_flush reads both grow
    assert counter_total(snap, "kernel_launches_total") >= 3
    assert counter_value(snap, "serve_flushes_total", trigger="sync") == 3
    # the time of a launch is still only measured with timing on
    assert counter_total(snap, "kernel_measured_s_total") == 0


def _hold_lock(server, seconds, held):
    def hold():
        with server._lock:
            held.set()
            time.sleep(seconds)
    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(10)
    return t


@pytest.mark.parametrize("method", ["submit", "submit_async"])
def test_submit_waits_for_the_server_lock(rng, method):
    obs.configure(tracing=True)
    with _server(rng, async_flush=True, max_delay_ms=1.0) as server:
        held = threading.Event()
        holder = _hold_lock(server, 0.05, held)
        answer = getattr(server, method)("a", [(0, 1)])
        holder.join(10)
        assert not holder.is_alive()
        if method == "submit_async":
            answer.result(timeout=30)
        else:
            server.flush()
    (sub,) = [s for s in TRACER.spans() if s.name == "serve.submit"]
    assert sub.t0 == sub.t1                   # an instant, not a span
    assert sub.attrs["wait_ms"] >= 45
    h = hist_get(REGISTRY.snapshot(), "serve_submit_wait_ms")
    assert h["count"] == 1 and h["sum"] == pytest.approx(
        sub.attrs["wait_ms"])


def test_flusher_thread_spans(rng):
    obs.configure(tracing=True)
    with _server(rng, async_flush=True, min_batch=2,
                 max_delay_ms=2.0) as server:
        flusher = server._flusher._thread.ident
        futs = [server.submit_async("a", [(i, i + 1)]) for i in range(6)]
        for f in futs:
            f.result(timeout=30)
        time.sleep(0.02)                     # the flusher parks again
    spans = TRACER.spans()
    on_flusher = {s.name for s in spans if s.tid == flusher}
    assert {"serve.park", "serve.lock_wait", "serve.dispatch",
            "serve.flush"} <= on_flusher
    # the flusher's top-level spans: futures are fulfilled right after the
    # flush that answered them
    top = sorted((s for s in spans if s.tid == flusher
                  and s.parent_id is None), key=lambda s: s.t0)
    for prev, s in zip(top, top[1:]):
        if s.name == "serve.dispatch":
            assert prev.name == "serve.flush" and prev.t1 <= s.t0
            assert s.attrs["n_tickets"] >= 1


def test_a_read_sees_the_spans_still_open():
    obs.configure(tracing=True)
    parked, release = threading.Event(), threading.Event()

    def park():
        with TRACER.span("serve.park"):
            parked.set()
            release.wait(10)

    t = threading.Thread(target=park)
    t.start()
    assert parked.wait(10)
    with TRACER.span("done"):
        pass
    before = time.perf_counter()
    spans = TRACER.spans()
    after = time.perf_counter()
    assert [s.name for s in spans] == ["done", "serve.park"]
    open_park = spans[1]
    assert open_park.attrs == {"open": True}
    assert open_park.t0 < before <= open_park.t1 <= after
    release.set()
    t.join(10)
    assert not t.is_alive()
    # once closed it is in the ring, once and unmarked
    assert [(s.name, s.attrs) for s in TRACER.spans()] == \
        [("done", {}), ("serve.park", {})]


def test_query_names_its_host_steps(rng):
    obs.configure(tracing=True)
    server = _server(rng)
    server.query([(0, 1), (1, 2), (1, 0)])
    spans = TRACER.spans()
    by_id = {s.span_id: s for s in spans}
    (query,) = [s for s in spans if s.name == "serve.query"]
    kids = [s.name for s in sorted(spans, key=lambda s: s.t0)
            if s.parent_id == query.span_id]
    assert kids == ["serve.keys", "serve.cache_probe", "serve.count",
                    "serve.cache_fill", "serve.reply"]
    (masks,) = [s for s in spans if s.name == "serve.masks"]
    assert by_id[masks.parent_id].name == "serve.count"
    # all keys cached now: no count, and no instant in its place
    server.query([(0, 1)])
    names = [s.name for s in TRACER.spans()]
    assert names.count("serve.count") == 1
    assert "serve.count_skipped" not in names


def test_new_jit_shape_records_a_compile_span(rng):
    obs.configure(tracing=True)
    before = counter_value(REGISTRY.snapshot(), "jax_compiles_total",
                           stage="backend")
    shape = (3, int(rng.integers(1000, 100000)))
    with TRACER.span("outer") as outer:
        jax.jit(lambda x: x * 2 + 1)(jnp.zeros(shape)).block_until_ready()
    compiles = [s for s in TRACER.spans() if s.name == "jax.compile"]
    assert {s.attrs["stage"] for s in compiles} >= {"lower", "backend"}
    for s in compiles:
        assert s.parent_id == outer.span_id
        assert outer.t0 <= s.t0 < s.t1 <= outer.t1
    snap = REGISTRY.snapshot()
    assert counter_value(snap, "jax_compiles_total", stage="backend") \
        >= before + 1
    assert counter_value(snap, "jax_compiles_total", stage="lower") >= 1


def test_spans_land_on_the_profile_host_plane(rng, tmp_path):
    from jax.profiler import ProfileData

    obs.configure(tracing=True)
    server = _server(rng)
    server.query([(0, 1)])                   # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t_open = time.perf_counter()
            for i in range(3):
                server.submit("a", [(i, i + 2), (i + 1, i + 3)])
                server.flush()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(float(ev.start_ns))
    (mark,) = host["bench.window"]
    shift = mark - t_open * 1e9
    for name in ("serve.flush", "kernel.count"):
        ring = [s.t0 * 1e9 + shift for s in TRACER.spans()
                if s.name == name and s.t0 >= t_open]
        assert len(ring) == 3 and len(host[name]) == 3
        for a, b in zip(sorted(ring), sorted(host[name])):
            assert abs(a - b) < 1e6


def test_disabled_submit_and_query_allocate_nothing_in_obs(rng):
    server = _server(rng)
    obs.disable_all()
    obs_dir = str(Path(obs.__file__).parent)

    def hot(j):
        for i in range(20):
            server.submit("a", [(i % 12, (i + 1) % 12)])
        server.query([(0, 1), (j % 12, (j + 5) % 12, (j + 7) % 12)])

    hot(0)                                  # warm up caches and compiles
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for j in range(1, 4):
        hot(j)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    leaks = [s for s in after.compare_to(before, "lineno")
             if s.size_diff > 0
             and s.traceback[0].filename.startswith(obs_dir)]
    assert not leaks, [str(s) for s in leaks]
    assert TRACER.spans() == []
