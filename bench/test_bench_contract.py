"""BENCHMARK.json keeps to its own rules: names, units, files found by
name, and every metric readable where it is listed.  Each rule is a
``check_*`` function of a benchmark and the root it lies under, so that a
copy of the tree with a cell added is held to the same rules."""
import json
import os
import re

import pytest

from bench.harness import reader_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_top_level(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.isfile(os.path.join(root, bench["command"][1]))


def check_config(bench, root, entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    cfg = json.load(open(os.path.join(root, entry["file"])))
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert os.path.isfile(os.path.join(root, "bench", "generators",
                                       cfg["generator"] + ".py"))
    assert any(c["config"] == entry["name"] for c in bench["workloads"])


def check_cell(bench, root, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    mix = json.load(open(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json")))
    assert os.path.isfile(os.path.join(root, "bench", "drivers",
                                       mix["driver"] + ".py"))
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in m["workloads"] for m in bench["per_layer"])


def check_metric(bench, root, m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert set(m.get("workloads", [])) <= {c["name"]
                                           for c in bench["workloads"]}
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert os.path.isfile(reader_path(m["name"], root))
        if m["name"].startswith("itemset_count_roofline"):
            assert m["unit"] == "%"


def check_unique(bench):
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(bench)) < 64 * 1024


def check_all(bench, root):
    """Every rule, over every entry of ``bench``."""
    check_top_level(bench, root)
    for entry in bench["configs"]:
        check_config(bench, root, entry)
    for cell in bench["workloads"]:
        check_cell(bench, root, cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_metric(bench, root, m)
    check_unique(bench)


def test_top_level_keys():
    check_top_level(BENCH, ROOT)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_files(entry):
    check_config(BENCH, ROOT, entry)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cells(cell):
    check_cell(BENCH, ROOT, cell)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(m):
    check_metric(BENCH, ROOT, m)


def test_names_are_unique():
    check_unique(BENCH)
