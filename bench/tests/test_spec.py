"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, limit file and metric reader is found by name."""
import importlib
import json
import re

import pytest

from bench.lib.spec import BENCH, ROOT, load_benchmark, load_cell

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
BENCHMARK = load_benchmark()
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
MODULE_API = ("program_loss", "init_params", "reference_loss", "make_data",
              "round_counts")


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert (ROOT / BENCHMARK["command"][1]).is_file()
    assert 1 <= BENCHMARK["run_seconds"] <= 51


def test_names_and_metrics():
    names = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    names += WORKLOADS + [c["name"] for c in BENCHMARK["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert hasattr(importlib.import_module(f"bench.metrics.{m['name']}"),
                       "read")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files(workload):
    cell = load_cell(workload)
    assert all(hasattr(cell.module, f) for f in MODULE_API)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "rounds_per_s"}
    assert cell.per_layer
    assert cell.limits["draws_wrong"]["limit"] == 0
    assert cell.limits["cohort_wrong"]["limit"] == 0


@pytest.mark.parametrize("config", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    with open(ROOT / config["file"]) as f:
        data = json.load(f)
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert config["file"].startswith("bench/configs/")
    assert (BENCH / "configs" / f"{config['name']}.py").is_file()
