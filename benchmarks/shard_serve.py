"""Sharded/async serving benchmark: multi-shard throughput + flush latency.

Two measurements, written together to ``BENCH_shard.json``:

* **throughput** (subprocess pinned to the CPU, 8 forced host devices — never
  a chip, which the parent process may hold): the same micro-batched
  query workload served by the synchronous single-device ``CountServer``
  (the PR-2/PR-3 path) and by sharded stores at 1/2/4/8 shards laid over a
  host mesh (one ``resident_distributed_counts`` psum launch per flush),
  plus the host-loop all-reduce path as a mesh-less reference.  Every
  configuration's answers are asserted bit-identical to the baseline's.

* **async flush latency** (in-process): requests trickled through
  ``submit_async`` against a ``max_delay_ms`` deadline; the recorded
  distribution is the queue wait of each flushed batch's oldest request —
  the quantity the deadline trigger bounds (``latency_bounded`` allows a
  scheduler-jitter margin on top of the budget).

  PYTHONPATH=src python -m benchmarks.shard_serve [--json BENCH_shard.json]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

from .common import Row

ROWS, ITEMS, POOL = 32768, 48, 256
BATCHES = [16, 64]
SHARDS = [1, 2, 4, 8]
MAX_DELAY_MS = 50.0
JITTER_MARGIN_MS = 25.0
HOST_DEVICES = "cpu: 8 forced host devices"

_SUBPROC = r"""
import json, time
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.data import bernoulli_db
from repro.serve import CountServer

ROWS, ITEMS, POOL = %(rows)d, %(items)d, %(pool)d
BATCHES = %(batches)r
SHARDS = %(shards)r

tx, y = bernoulli_db(ROWS, ITEMS, p_x=0.15, p_y=0.05, seed=0)
rng = np.random.default_rng(1)
pool = [tuple(rng.choice(ITEMS, size=rng.integers(1, 4),
                         replace=False).tolist())
        for _ in range(POOL)]


def serve_pool(server, batch):
    results = {}
    for s in range(0, len(pool), batch):
        tickets = [(server.submit(f"c{i %% 8}", [key]), key)
                   for i, key in enumerate(pool[s:s + batch])]
        got = server.flush()
        for ticket, key in tickets:
            results[key] = got[ticket][0]
    return results


def timeit(fn, repeats=3):
    fn()                                     # warmup (compile + place)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


out = []
base_server = CountServer(tx, classes=list(y), cache=False)
want = serve_pool(base_server, BATCHES[0])
base_us = {}
for batch in BATCHES:
    us = timeit(lambda: serve_pool(base_server, batch)) / POOL
    base_us[batch] = us
    out.append({"variant": "single_device", "shards": None, "batch": batch,
                "us_per_query": us, "qps": 1e6 / us})

for n_shards in SHARDS:
    mesh = jax.make_mesh((n_shards,), ("data",),
                         devices=jax.devices()[:n_shards])
    server = CountServer(tx, classes=list(y), cache=False,
                         shards=n_shards, mesh=mesh)
    got = serve_pool(server, BATCHES[0])
    assert all((got[k] == want[k]).all() for k in pool), n_shards
    for batch in BATCHES:
        us = timeit(lambda: serve_pool(server, batch)) / POOL
        out.append({"variant": "sharded_mesh", "shards": n_shards,
                    "batch": batch, "us_per_query": us, "qps": 1e6 / us,
                    "speedup_vs_single": base_us[batch] / us,
                    "beats_single_device": us <= base_us[batch]})

# host-loop all-reduce (no mesh): the portable path, one launch per shard
server = CountServer(tx, classes=list(y), cache=False, shards=2)
got = serve_pool(server, BATCHES[0])
assert all((got[k] == want[k]).all() for k in pool)
us = timeit(lambda: serve_pool(server, BATCHES[-1])) / POOL
out.append({"variant": "sharded_host_loop", "shards": 2,
            "batch": BATCHES[-1], "us_per_query": us, "qps": 1e6 / us,
            "speedup_vs_single": base_us[BATCHES[-1]] / us})
print(json.dumps(out))
"""


def _throughput_records() -> List[dict]:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"      # forced host devices, not the chip
    script = _SUBPROC % {"rows": ROWS, "items": ITEMS, "pool": POOL,
                         "batches": BATCHES, "shards": SHARDS}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return [dict(rec, devices=HOST_DEVICES)
            for rec in json.loads(proc.stdout.strip().splitlines()[-1])]


def _latency_record() -> dict:
    import numpy as np

    from repro.data import bernoulli_db
    from repro.serve import CountServer

    tx, y = bernoulli_db(4096, 24, p_x=0.15, p_y=0.05, seed=2)
    rng = np.random.default_rng(3)
    server = CountServer(tx, classes=list(y), async_flush=True,
                         max_delay_ms=MAX_DELAY_MS, min_batch=8)
    futures = []
    for i in range(48):
        key = tuple(rng.choice(24, size=2, replace=False).tolist())
        futures.append(server.submit_async(f"c{i % 4}", [key]))
        time.sleep(0.005)            # a trickle: deadline does the flushing
    for fut in futures:
        fut.result(timeout=30)
    server.close()
    stats = server.stats()["async"]
    lat = stats["flush_latency_ms"]
    return {"variant": "async_flush", "max_delay_ms": MAX_DELAY_MS,
            "min_batch": 8, "flushes": stats["flushes"],
            "by_trigger": stats["by_trigger"],
            "flush_latency_ms": lat,
            "latency_bounded":
                lat["max"] is not None
                and lat["max"] <= MAX_DELAY_MS + JITTER_MARGIN_MS}


def run(record: List[dict] | None = None) -> List[Row]:
    rows: List[Row] = []
    tag = f"shard[N={ROWS},pool={POOL}]"
    for rec in _throughput_records():
        if record is not None:
            record.append(rec)
        name = (f"{tag}/{rec['variant']}"
                + (f"(shards={rec['shards']})" if rec["shards"] else "")
                + f"/batch={rec['batch']}")
        derived = (f"speedup_vs_single={rec['speedup_vs_single']:.2f}x"
                   if "speedup_vs_single" in rec else "baseline")
        derived += f";devices={HOST_DEVICES}"
        rows.append((name, rec["us_per_query"], derived))
    lat = _latency_record()
    if record is not None:
        record.append(lat)
    d = lat["flush_latency_ms"]
    rows.append((f"{tag}/async_flush", d["p50"] or 0.0,
                 f"p95={d['p95']:.1f}ms;max={d['max']:.1f}ms;"
                 f"bounded={lat['latency_bounded']}"))
    return rows


def main() -> None:
    import argparse

    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_shard.json")
    args = ap.parse_args()

    record: List[dict] = []
    rows = run(record)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    payload = {
        "bench": "shard_serve",
        "backend": jax.default_backend(),
        "problem": {"rows": ROWS, "items": ITEMS, "pool": POOL,
                    "batches": BATCHES, "shards": SHARDS,
                    "max_delay_ms": MAX_DELAY_MS},
        "rows": record,
    }
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.json} ({len(record)} records)")


if __name__ == "__main__":
    main()
