"""BENCHMARK.json and the files it names."""

import importlib
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(ROOT, "BENCHMARK.json")
CELLS = MANIFEST["workloads"]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


def cells_of(metric):
    return metric.get("workloads", [c["name"] for c in CELLS])


def test_top_level_keys():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert MANIFEST["paths"] == ["benchmarks"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.exists(os.path.join(ROOT, MANIFEST["command"][1]))


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_resolves_to_files(cell):
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    config = [c for c in MANIFEST["configs"] if c["name"] == cell["config"]]
    assert len(config) == 1
    body = load(ROOT, config[0]["file"])
    assert body["name"] == cell["config"] and body["chips"] == cell["chips"]
    assert body["source"] == config[0]["source"]
    assert body["reduced"] == config[0]["reduced"]
    traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]


def test_names_units_and_sources():
    names = [c["name"] for c in CELLS] + [c["name"] for c in MANIFEST["configs"]]
    names += [c["traffic"] for c in CELLS]
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({(c["config"], c["traffic"]) for c in CELLS}) == len(CELLS)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]


def test_four_chip_share():
    four = [c for c in CELLS if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in CELLS)
    assert len(four) <= max(len(CELLS) // 2, 1)


def test_every_cell_reports_enough():
    for cell in CELLS:
        e2e = [m for m in MANIFEST["end_to_end"] if cell["name"] in cells_of(m)]
        assert len(e2e) >= 2, cell["name"]
        assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])
    for m in MANIFEST["end_to_end"]:
        assert cells_of(m), f"{m['name']} has no cell left"
    used = {c["config"] for c in CELLS}
    assert used == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_layer_metric(metric):
    # the file says how the metric is read and nothing the manifest says
    spec = load(BENCH, "layer_metrics", metric["name"] + ".json")
    assert set(spec) <= {"reader", "arg"}
    assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    arg = spec.get("arg", {})
    if "bytes_fn" in arg:   # a roofline's bytes function is found by name
        module = importlib.import_module(arg.get("bytes_from", "roofline"))
        assert os.path.dirname(module.__file__) == BENCH and callable(getattr(module, arg["bytes_fn"]))
    moved = E2E[metric["moves"]]
    for w in metric["workloads"]:   # each cell reports the metric this one moves
        assert w in cells_of(moved), (metric["name"], w)


def test_nothing_of_the_tree_is_named_as_the_sketch_is():
    # ``sketch*`` is the test data of test_added_cell.py (data/sketch_cell/),
    # which lays it over a copy of this tree: a real cell takes other names
    names = [c["name"] for c in CELLS] + [c["traffic"] for c in CELLS]
    names += [c["name"] for c in MANIFEST["configs"]]
    names += [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    for d in ("configs", "traffic", "drivers", "readers", "layer_metrics", os.path.join("tests", "faults"), ""):
        names += os.listdir(os.path.join(BENCH, d))
    assert [n for n in names if n.startswith(("sketch", "roofline_sketch"))] == []


def test_files_under_paths_are_named_plainly():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__" and not d.startswith(".")]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), ROOT)), f
