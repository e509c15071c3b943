#!/usr/bin/env python3
"""The correctness check's control and planted faults.

    python3 bench/control.py --workload sim4m-serve --seeds 101 102 103

The control breaks the guarantee the configuration states, that a count
is exact at the version that includes every acknowledged append: the
store answers from its base alone, as a store that dropped the delta
would.  A run under it must read ``correct`` false on every seed; the
command prints each run's checks and exits non-zero if one reads true.
The benchmark's own runs never use it.

``altered_answer`` plants the fault of answers altered where they are
produced: every fourth count the store returns is one too high (often
enough that a sampled check meets one).  The tests under ``bench/`` run
both at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _wrap_counts(state, change) -> None:
    store = state["server"].store
    counts = store.counts_masks

    def patched(masks, block_k=None):
        return change(store, counts, masks, block_k)

    store.counts_masks = patched


def stale_base(state) -> None:
    """Counts from the base segment only: the appended rows are lost."""
    def change(store, counts, masks, block_k):
        saved = store._delta_bits
        store._delta_bits = None
        try:
            return counts(masks, block_k=block_k)
        finally:
            store._delta_bits = saved
    _wrap_counts(state, change)


def altered_answer(state) -> None:
    """Every fourth count the store returns is one too high."""
    def change(store, counts, masks, block_k):
        out = counts(masks, block_k=block_k).copy()
        out[::4, -1] += 1
        return out
    _wrap_counts(state, change)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import harness

    bad = 0
    for seed in args.seeds:
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  t0=T0, patch=stale_base)
        harness.log(f"control {args.workload} seed {seed}: correct="
                    f"{result['correct']} checks {result['checks']}")
        bad += bool(result["correct"])
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
