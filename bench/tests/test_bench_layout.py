"""Every cell's files are found by the names in BENCHMARK.json, and the
file keeps to the shape the benchmark's runs rely on."""
import json
import os
import re

import pytest

from bench import run

SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_found_by_name(cell_name):
    cell, config, traffic = run.cell_files(SPEC, cell_name)
    entry = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert entry["file"].startswith("bench/configs/")
    assert config["name"] == cell["config"]
    assert set(entry["reduced"]) <= set(config["reduced"])
    jobs = run.job_module(traffic["job"])
    assert callable(jobs.Job) and callable(jobs.check) and callable(jobs.control)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert run.rate_metric(SPEC, cell_name)["name"] != "setup_s"
    assert run.reports(e2e["setup_s"], cell_name)
    assert any(run.reports(m, cell_name) for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_found_by_name(metric):
    read = run.metric_reader(metric)
    empty = {"trace": None, "counts": {}, "readings": [], "traced_jobs": 1, "device_kind": "TPU v5 lite"}
    assert read(empty) is None  # nothing to read: nothing reported, never 0


def test_names_units_and_bounds():
    names = [c["name"] for c in SPEC["configs"]] + CELLS
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in layer for layer in layers)
    for c in SPEC["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200


def test_config_files_are_json_under_paths():
    for c in SPEC["configs"]:
        path = os.path.join(run.ROOT, c["file"])
        with open(path) as f:
            config = json.load(f)
        assert config["num_nodes"] == 1 << config["scale"]
        assert config["num_arcs"] == 2 * config["num_edges"]
        assert 1 <= len(c["source"]) <= 200
