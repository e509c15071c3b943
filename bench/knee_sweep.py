#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop serving cell sustains.

    python3 bench/knee_sweep.py --workload sim4m-serve --seed 5 \\
        --seconds 10 --rates 1000 2000 3000 4000 6000 8000

One set-up of the cell (its configuration and mix), then, for each rate,
``--seconds`` of Poisson arrivals of the mix at that rate.  For each rate
it prints the latency median and 95th percentile, how late the generator
ran, and whether the backlog grew: the 95th percentile of the last third of
the arrivals against that of the first third.  A rate sustains when every
request is answered and the last third's tail stays within twice the
first third's.  The cell's mix then fixes its rate at about four fifths of
the highest rate that sustains.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sim4m-serve")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness
    from bench.drivers import open_loop
    from repro.launch.compile_cache import enable_compile_cache

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    try:
        harness.device_report(int(cell["chips"]))
    except harness.NoDevice as e:
        harness.log(f"no result: {e}")
        return 3
    enable_compile_cache()
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    data = harness.load_generator(cfg["generator"]).generate(cfg, args.seed)
    state = open_loop.setup(cfg, traffic, args.seed, data, args.seconds)
    harness.log(f"set-up {time.perf_counter() - T0:.1f} s")
    rows = []
    for i, rate in enumerate(args.rates):
        state["traffic"] = dict(traffic, rate_per_s=rate)
        arrivals, reqs = open_loop.build_requests(
            state, np.random.default_rng([args.seed, 40, i]), args.seconds)
        out = open_loop.drive(state["server"], arrivals, reqs,
                              int(traffic["clients"]))
        ok = ~np.isnan(out["done"])
        lat = (out["done"] - out["due"]) * 1e3
        third = max(1, len(reqs) // 3)
        first = lat[:third][ok[:third]]
        last = lat[-third:][ok[-third:]]
        row = {
            "rate_per_s": rate, "requests": len(reqs),
            "unanswered": int((~ok).sum()),
            "p50_ms": open_loop.nearest_rank(lat[ok], 0.5),
            "p95_ms": open_loop.nearest_rank(lat[ok], 0.95),
            "p95_first_third_ms": open_loop.nearest_rank(first, 0.95),
            "p95_last_third_ms": open_loop.nearest_rank(last, 0.95),
            "gen_lag_p95_ms": open_loop.nearest_rank(
                (out["submitted"] - out["due"]) * 1e3, 0.95),
            "answered_per_s": float(ok.sum() / (np.nanmax(out["done"])
                                                - out["t_open"])),
        }
        row["sustains"] = bool(row["unanswered"] == 0 and
                               row["p95_last_third_ms"]
                               <= 2 * row["p95_first_third_ms"] + 5)
        rows.append(row)
        print(json.dumps(row), flush=True)
    state["server"].close()
    good = [r["rate_per_s"] for r in rows if r["sustains"]]
    print(json.dumps({"knee_per_s": max(good) if good else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
