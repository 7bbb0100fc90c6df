"""`BENCHMARK.json` keeps to the contract's letters, and every name in it
resolves to files that exist. The assertions are `manifest_checks`'s, which
`test_bench_additions.py` also runs on a tree that a later PR extends."""

import pytest

import manifest_checks as checks
from benchmark.harness import cells

MANIFEST = cells.manifest()
BENCH = cells.BENCH
CELLS = checks.cell_files(BENCH)
METRICS = checks.metrics(MANIFEST)


def test_manifest_keys_and_command():
    checks.keys_and_command(MANIFEST, BENCH)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    checks.metric_entry(MANIFEST, BENCH, metric)


def test_metric_names_are_unique_and_setup_is_there():
    checks.metric_names_unique(MANIFEST, BENCH)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry(config):
    checks.config_entry(MANIFEST, BENCH, config)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_resolves(cell):
    """Every `workloads/*.json` names files that exist, whether or not
    `BENCHMARK.json` lists the cell yet."""
    checks.cell_file_resolves(MANIFEST, BENCH, cell)


@pytest.mark.parametrize("entry", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry(entry):
    checks.workload_entry(MANIFEST, BENCH, entry)


def test_files_under_paths_have_plain_names():
    checks.plain_names(MANIFEST, BENCH)
