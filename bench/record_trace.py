#!/usr/bin/env python3
"""Record a small profiler trace of the count-serving path on a TPU.

    python3 bench/record_trace.py --out bench/testdata/small_trace

A store of 200,000 Bernoulli rows over 1,024 items serves a few flushes, an
append and a flush over base plus delta, inside ``jax.profiler.trace`` with
the benchmark's own ``TraceAnnotation`` spans around each call.  The trace
(``*.xplane.pb``) is written under ``--out``; the script prints the planes,
lines and the device events with the most time, so that the reduction in
``bench/trace_reduce.py`` can be checked against what the chip writes.  The
committed copy under ``bench/testdata/`` is the fixture of
``bench/test_bench_trace.py``.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--rows", type=int, default=200_000)
    args = ap.parse_args()

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing recorded", file=sys.stderr)
        return 1
    from bench.generators import bernoulli, rows_between
    from repro.serve import CountServer

    data = bernoulli.generate({"rows": args.rows, "items": 1024,
                               "p_x": 0.04, "p_y": 0.01,
                               "append_rows": 2000}, seed=7)
    tx, y = rows_between(data, 0, args.rows)
    tx_app, y_app = rows_between(data, args.rows, args.rows + 2000)
    server = CountServer(tx, classes=y, n_classes=2)
    rng = np.random.default_rng(3)
    keys = [tuple(rng.choice(1024, 2, replace=False).tolist())
            for _ in range(200)]
    server.query(keys[:100])              # compile outside the trace
    server.append(tx_app, classes=y_app)
    server.query(keys[100:150])
    shutil.rmtree(args.out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(args.out, profiler_options=opts):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.query"):
                server.query(keys[150 + 10 * i:160 + 10 * i])
            with jax.profiler.TraceAnnotation("bench.idle"):
                import time
                time.sleep(0.01)
    path = glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    print(f"trace: {path} ({os.path.getsize(path)} bytes)")
    prof = jax.profiler.ProfileData.from_file(path)
    for plane in prof.planes:
        lines = [(line.name, sum(1 for _ in line.events))
                 for line in plane.lines]
        print(f"plane {plane.name!r}: lines {lines[:12]}")
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                agg = collections.Counter()
                for ev in line.events:
                    agg[ev.name] += ev.duration_ns
                print(f"  line {line.name!r}: {agg.most_common(12)}")
                for ev in list(line.events)[:4]:
                    print(f"    event {ev.name!r} start {ev.start_ns} dur "
                          f"{ev.duration_ns} stats {list(ev.stats)[:8]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
