"""The correctness check fails the control and each planted fault: a run
of every cell at a tiny size on the CPU with the timed path broken
underneath reads ``correct`` false."""
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from bench import control, harness  # noqa: E402
from bench._tiny import SECONDS, tiny_sizes  # noqa: E402

CELLS = [c["name"] for c in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["stale_base", "altered_answer"])
def test_broken_path_reads_not_correct(cell, fault):
    r = harness.run_cell(cell, 2**31 + 99, SECONDS, False,
                         t0=time.perf_counter(), require_tpu=False,
                         overrides=tiny_sizes(cell),
                         patch=getattr(control, fault))
    assert r["correct"] is False
    broken = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert broken and "nothing_compared" not in broken
