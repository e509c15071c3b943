#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload sim4m-serve --seed 7 --seconds 30 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``breakdown`` (``--trace 1`` only) and ``checks`` (each number the
correctness check compared, beside its limit).  Exits non-zero and prints
no result when JAX finds no TPU, fewer chips than the cell asks for, or a
device kind that ``bench/peaks.py`` does not hold.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t0=T0)
    except harness.NoDevice as e:
        harness.log(f"no result: {e}")
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
