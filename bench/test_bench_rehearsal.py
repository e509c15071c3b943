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
from bench._tiny import SECONDS, tiny_sizes  # noqa: E402
from bench.test_bench_contract import check_all  # noqa: E402

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
SEED = 2**31 + 77


def _run(cell, trace=False, root=ROOT, overrides=None):
    return harness.run_cell(cell, SEED, SECONDS, trace,
                            t0=time.perf_counter(), root=root,
                            require_tpu=False,
                            overrides=overrides or tiny_sizes(cell))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_tiny_sizes(cell):
    """``bench/tiny/<cell>.json`` overrides keys that the cell's
    configuration and mix have, and nothing else."""
    sizes = tiny_sizes(cell)
    assert set(sizes) == {"config", "traffic"}
    entry = harness.find_cell(BENCH, cell)
    cfg = harness.load_config(BENCH, entry["config"])
    mix = harness.load_traffic(entry["traffic"])
    assert set(sizes["config"]) <= set(cfg)
    assert set(sizes["traffic"]) <= set(mix)


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


# A generator a new configuration brings: a clickstream over a wide
# vocabulary, popular items scattered over codes far above 2^15.
WIDE_GENERATOR = '''
import numpy as np


def generate(cfg, seed):
    rng = np.random.default_rng([seed, 1])
    items = int(cfg["items"])
    total = int(cfg["rows"]) + int(cfg["append_rows"])
    code_of_rank = np.random.default_rng([seed, 2]).permutation(items)
    w = 1.0 / np.arange(1, items + 1) ** float(cfg["zipf_s"])
    cdf = np.cumsum(w) / w.sum()
    lens = rng.integers(1, int(cfg["max_len"]) + 1, total)
    draws = code_of_rank[np.minimum(np.searchsorted(
        cdf, rng.random(int(lens.sum()))), items - 1)]
    rows = np.split(draws, np.cumsum(lens)[:-1])
    rows = [np.unique(r) for r in rows]
    row_ptr = np.zeros(total + 1, np.int64)
    np.cumsum([r.shape[0] for r in rows], out=row_ptr[1:])
    return {"items": np.concatenate(rows).astype(np.int32),
            "row_ptr": row_ptr,
            "classes": (rng.random(total) < float(cfg["p_y"])).astype(
                np.int32),
            "n_items": items, "base_rows": int(cfg["rows"])}
'''


def _files(root):
    out = {}
    for folder, _, names in os.walk(root):
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


def _appended_only(old, new):
    """``new`` is ``old`` with entries appended to its lists, and cells
    appended to the ``workloads`` lists of its metrics."""
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    assert {k: v for k, v in old.items() if k not in groups} == \
        {k: v for k, v in new.items() if k not in groups}
    for group in groups:
        assert len(new[group]) >= len(old[group])
        for a, b in zip(old[group], new[group]):
            if a != b:
                assert "workloads" in a
                assert dict(a, workloads=None) == dict(b, workloads=None)
                assert b["workloads"][:len(a["workloads"])] == \
                    a["workloads"]


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A cell added as a later change adds it: new files (configuration,
    generator, mix, reader, tiny sizes) and appended entries, the cell's
    name appended to its end-to-end metric's ``workloads``.  The copy
    keeps every rule of the contract, the cell runs correct at the sizes
    its tiny file gives, and no file that was there is edited."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(tmp_path)
    cell = "wide.items"
    files = {
        "bench/configs/wide.json": {
            "name": "wide", "source": "test", "generator": "wide",
            "rows": 990_002, "append_rows": 9_900, "items": 41_270,
            "zipf_s": 1.0, "max_len": 16, "p_y": 0.01, "n_classes": 2,
            "reduced": []},
        "bench/traffic/wide_targets.json": dict(
            json.load(open(os.path.join(
                ROOT, "bench/traffic/minority_targets.json"))),
            max_level=2),
        f"bench/tiny/{cell}.json": {
            "config": {"rows": 3000, "append_rows": 300, "p_y": 0.2,
                       "zipf_s": 1.3},
            "traffic": {"theta": 1e-3, "keys_per_job": 64,
                        "check_answers": 50}}}
    for path, body in files.items():
        (tmp_path / path).write_text(json.dumps(body))
    (tmp_path / "bench/generators/wide.py").write_text(WIDE_GENERATOR)
    (tmp_path / "bench/layer_metrics/jobs_done.wide.py").write_text(
        "def read(ctx):\n    return float(ctx['jobs'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "wide", "source": "test",
                             "file": "bench/configs/wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "wide",
                               "traffic": "wide_targets", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "targets_per_s":
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "jobs_done.wide", "unit": "jobs",
                               "better": "higher",
                               "source": "host_clock",
                               "layer": "load generator",
                               "moves": "targets_per_s",
                               "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    check_all(bench, str(tmp_path))
    tiny = tiny_sizes(cell, str(tmp_path))
    r = _run(cell, root=str(tmp_path), overrides=tiny)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"targets_per_s", "setup_s"}
    r = _run(cell, trace=True, root=str(tmp_path), overrides=tiny)
    assert r["metrics"]["jobs_done.wide"]["value"] >= 1

    after = _files(tmp_path)
    _appended_only(BENCH, json.loads(after["BENCHMARK.json"]))
    for path, body in before.items():
        if path != "BENCHMARK.json" and "__pycache__" not in path:
            assert after[path] == body, path
