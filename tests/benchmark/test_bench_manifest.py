"""`BENCHMARK.json` keeps to the contract's letters, and every name in it
resolves to files that exist."""

import glob
import os
import re

import pytest

from benchmark.harness import cells

MANIFEST = cells.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(cells.BENCH, "workloads", "*.json")))
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile and
    # 1200 s spare have to fit 43200 s with the full 24 cells
    full = (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    names = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", names)) <= names
    if metric in MANIFEST["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        # the metric it moves is reported in every cell this one is in
        assert set(metric.get("workloads", names)) <= set(
            moved.get("workloads", names))
        reader = os.path.join(cells.BENCH, "layer_metrics",
                              metric["name"] + ".py")
        assert os.path.isfile(reader)
        assert callable(cells.load_module("layer_metrics",
                                          metric["name"]).read)


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    sizes = cells.load_json("configs", config["name"])
    assert sizes["reduced"] == config["reduced"]
    assert "assumed" in sizes and "rehearsal" in sizes
    module = cells.load_module("configs", config["name"])
    assert callable(module.build) and module.source
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_resolves(cell):
    """Every `workloads/*.json` names files that exist, whether or not
    `BENCHMARK.json` lists the cell yet."""
    spec = cells.load_json("workloads", cell)
    assert NAME.match(cell)
    assert cell.startswith(spec["config"] + ".")
    assert cell == f"{spec['config']}.{spec['traffic']['kind']}"
    assert os.path.isfile(os.path.join(cells.BENCH, "configs",
                                       spec["config"] + ".json"))
    assert os.path.isfile(os.path.join(cells.BENCH, "configs",
                                       spec["config"] + ".py"))
    assert callable(cells.load_module("drivers", spec["driver"]).run)
    assert spec["chips"] in (1, 4)
    if "reference_check" in spec:
        ref = cells.load_module("reference",
                                spec["reference_check"]["reference"])
        assert callable(ref.forward) and callable(ref.greedy_agreement)
    # the rehearsal's overrides name only keys the cell has
    assert set(spec.get("rehearsal", {})) <= set(spec)


@pytest.mark.parametrize("entry", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["name"] in CELLS
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert 1 <= len(entry["why"]) <= 200
    cell = cells.Cell(entry["name"])
    assert cell.chips == entry["chips"]
    e2e = cell.metric_names("end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metric_names("per_layer")


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in MANIFEST["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), cells.ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
