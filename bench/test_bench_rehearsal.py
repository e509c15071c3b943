"""Each cell of BENCHMARK.json, run through the harness at a tiny size on
the CPU, and the command's refusals."""
import json
import os
import shutil
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench._tiny import SECONDS, TINY  # noqa: E402

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
SEED = 2**31 + 77


def _run(cell, trace=False, root=ROOT, overrides=None):
    return harness.run_cell(cell, SEED, SECONDS, trace,
                            t0=time.perf_counter(), root=root,
                            require_tpu=False,
                            overrides=overrides or TINY[cell])


def test_every_cell_has_tiny_sizes():
    assert set(CELLS) <= set(TINY)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(cell):
    r = _run(cell)
    assert list(r)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in r
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                    "end_to_end")}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in r["device"]
    json.dumps(r)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_per_layer_metrics(cell):
    r = _run(cell, trace=True)
    assert r["correct"] is True
    layer = {m["name"]: m for m in harness.cell_metrics(BENCH, cell,
                                                        "per_layer")}
    assert set(r["metrics"]) <= set(layer)
    # on the CPU no device operation is traced: the device readers find
    # nothing and the metric is left out, never read as 0
    for name in r["metrics"]:
        assert layer[name]["source"] != "device_trace"
    assert "breakdown" not in r


def test_no_tpu_no_result_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_without_the_program_no_result_line(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_device_check_refuses_unknown_kinds_and_missing_chips():
    with pytest.raises(harness.NoDevice):
        harness.device_report(1)            # the CPU: no TPU
    assert harness.device_report(1, require_tpu=False)["platform"] == "cpu"


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A cell added by new files and entries only: no file that is there
    is edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/sim4m.json")))
    cfg.update({"name": "sim_dense", "p_x": 0.2})
    json.dump(cfg, open(tmp_path / "bench/configs/sim_dense.json", "w"))
    mix = json.load(open(os.path.join(ROOT,
                                      "bench/traffic/minority_targets.json")))
    mix["max_level"] = 1
    json.dump(mix, open(tmp_path / "bench/traffic/bulk_items.json", "w"))
    (tmp_path / "bench/layer_metrics/jobs_done.items.py").write_text(
        "def read(ctx):\n    return float(ctx['jobs'])\n")
    bench["configs"].append({"name": "sim_dense", "source": "test",
                             "file": "bench/configs/sim_dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "sim_dense.items",
                               "config": "sim_dense",
                               "traffic": "bulk_items", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "targets_per_s",
                                "unit": "targets/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["sim_dense.items"]})
    bench["per_layer"].append({"name": "jobs_done.items", "unit": "jobs",
                               "better": "higher",
                               "source": "host_clock",
                               "layer": "load generator",
                               "moves": "targets_per_s",
                               "workloads": ["sim_dense.items"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    tiny = {"config": dict(TINY["sim4m-bulk"]["config"], items=600),
            "traffic": dict(TINY["sim4m-bulk"]["traffic"], theta=1e-4,
                            keys_per_job=128)}
    r = _run("sim_dense.items", root=str(tmp_path), overrides=tiny)
    assert r["correct"] is True and "targets_per_s" in r["metrics"]
    r = _run("sim_dense.items", trace=True, root=str(tmp_path),
             overrides=tiny)
    assert r["metrics"]["jobs_done.items"]["value"] >= 1
