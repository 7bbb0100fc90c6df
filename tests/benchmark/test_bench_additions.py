"""A later PR adds a configuration, its cell and a per-layer metric as new
files and appended entries, and edits nothing that exists: every check of
`manifest_checks` holds on such a tree, and an edit to an older entry (a
cell reordered or dropped within a list) fails at least one of them.

The tree is a copy of `benchmark/` and `BENCHMARK.json` in `tmp_path`,
extended as a `model_config` PR would extend it: a fifth configuration made
from `kimi_vl_a3b`'s two files under a new name, its `fit_ref` cell, one
reader that lists only that cell, and the cell appended to every list that
names all three language-model cells. `cells` is pointed at the tree for the
test and back afterwards."""

import copy
import filecmp
import json
import os
import shutil

import pytest

import manifest_checks as checks
from benchmark.harness import cells

CONFIG = "fifth_lm_a3b"
CELL = f"{CONFIG}.fit_seq8k"
METRIC = "fifth_lm_attention_share.fit"
SOURCE = "kimi_vl_a3b"


def _write(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def _extend(root):
    """Copy the committed benchmark under `root` and add one configuration,
    its cell and one metric; returns (committed manifest, extended one)."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        committed = json.load(f)
    for ext in (".json", ".py"):
        shutil.copy(os.path.join(bench, "configs", SOURCE + ext),
                    os.path.join(bench, "configs", CONFIG + ext))
    spec = checks._json(bench, "workloads", f"{SOURCE}.fit_seq8k")
    _write(os.path.join(bench, "workloads", CELL + ".json"),
           dict(spec, config=CONFIG))
    shutil.copy(os.path.join(bench, "layer_metrics", "mla_time_share.fit.py"),
                os.path.join(bench, "layer_metrics", METRIC + ".py"))

    manifest = copy.deepcopy(committed)
    source = next(c for c in manifest["configs"] if c["name"] == SOURCE)
    manifest["configs"].append(dict(source, name=CONFIG,
                                    file=f"benchmark/configs/{CONFIG}.json"))
    manifest["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "fit_seq8k", "chips": 1,
        "why": "one 8,192-token sequence a step, 2 cached batches: the "
               "configuration a later PR adds"})
    for m in checks.metrics(manifest):
        if set(checks.LM3) <= set(m.get("workloads", [])):
            m["workloads"].append(CELL)
    manifest["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "fit_samples_per_s", "workloads": [CELL]})
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    return committed, manifest


@pytest.fixture
def tree(tmp_path, monkeypatch):
    root = str(tmp_path / "checkout")
    committed, manifest = _extend(root)
    committed_bench = cells.BENCH
    monkeypatch.setattr(cells, "ROOT", root)
    monkeypatch.setattr(cells, "BENCH", os.path.join(root, "benchmark"))
    cells.manifest.cache_clear()
    yield {"root": root, "bench": cells.BENCH, "committed": committed,
           "committed_bench": committed_bench, "manifest": manifest}
    cells.manifest.cache_clear()


def _reread(tree, manifest):
    _write(os.path.join(tree["root"], "BENCHMARK.json"), manifest)
    cells.manifest.cache_clear()
    return cells.manifest()


def test_every_check_holds_on_the_committed_tree():
    assert checks.failed(cells.manifest(), cells.BENCH) == {}


def test_the_extension_adds_files_and_appends_entries_only(tree):
    """What the tree below adds, it adds: no file of the committed benchmark
    differs, and every entry the manifest had is where it was, its lists
    grown at their ends by the new cell and by nothing else."""
    bench, committed_bench = tree["bench"], tree["committed_bench"]
    added = set()
    for top, dirs, files in os.walk(bench):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(top, f), bench)
            mine = os.path.join(committed_bench, rel)
            if os.path.exists(mine):
                assert filecmp.cmp(mine, os.path.join(top, f),
                                   shallow=False), rel
            else:
                added.add(rel)
    assert added == {f"configs/{CONFIG}.json", f"configs/{CONFIG}.py",
                     f"workloads/{CELL}.json", f"layer_metrics/{METRIC}.py"}
    old, new = tree["committed"], cells.manifest()
    assert set(new) == set(old)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[group]) >= len(old[group])
        for was, now in zip(old[group], new[group]):
            grown = now.get("workloads", [])[len(was.get("workloads", [])):]
            assert grown in ([], [CELL]), now["name"]
            assert dict(now, workloads=now.get("workloads", [])[:len(
                was.get("workloads", []))]) == dict(
                was, workloads=was.get("workloads", [])), now["name"]
    assert {k: v for k, v in new.items() if not isinstance(v, list)} == {
        k: v for k, v in old.items() if not isinstance(v, list)}


def test_every_check_holds_on_a_tree_a_later_pr_extends(tree):
    assert checks.failed(cells.manifest(), tree["bench"]) == {}
    # the new cell is held to the rules of every `fit_ref` cell
    assert checks.fit_ref_cells(cells.manifest(), tree["bench"])[-1] == CELL


def test_the_new_cell_reports_its_metrics_and_the_shared_ones(tree):
    cell = cells.Cell(CELL)
    assert cell.metric_names("end_to_end")[:2] == ["fit_samples_per_s",
                                                    "setup_s"]
    names = set(cell.metric_names("per_layer"))
    assert {METRIC, "moe_time_share.fit", "grouped_product_time_share.fit",
            "unscoped_time_share.fit", "fit_mfu"} <= names
    # the older cells' own metrics stay theirs
    assert not (checks.KIMI_OWN | checks.MELLUM_OWN) & names
    assert METRIC not in cells.Cell(checks.KIMI).metric_names("per_layer")
    assert callable(cells.load_module("layer_metrics", METRIC).read)


def _swap(items, a, b):
    items[a], items[b] = items[b], items[a]


def _metric_list(manifest, name):
    return checks.entry(manifest, name)["workloads"]


EDITS = {
    "older_cells_reordered_in_the_rate_list": lambda m: _swap(
        _metric_list(m, "fit_samples_per_s"), 1, 2),
    "older_cell_dropped_from_moe_time_share": lambda m: _metric_list(
        m, "moe_time_share.fit").remove(checks.MELLUM),
    "older_cell_dropped_from_dispatch_mean": lambda m: _metric_list(
        m, "fit_dispatch_mean_ms").remove(checks.KIMI),
    "older_cells_reordered_in_workloads": lambda m: _swap(
        m["workloads"], 0, 1),
    "older_configurations_reordered": lambda m: _swap(m["configs"], 2, 3),
    "older_metric_dropped": lambda m: m["per_layer"].remove(
        checks.entry(m, "lm_head_time_share.fit")),
    "older_metrics_reordered": lambda m: _swap(m["per_layer"], 12, 13),
    "older_bound_loosened": lambda m: checks.entry(
        m, "fit_samples_per_s").update(bound=0.05),
    "new_cell_put_before_an_older_one": lambda m: _swap(
        _metric_list(m, "moe_time_share.fit"), 2, 3),
}


@pytest.mark.parametrize("edit", list(EDITS))
def test_an_edit_to_what_exists_fails_a_check(tree, edit):
    manifest = copy.deepcopy(tree["manifest"])
    EDITS[edit](manifest)
    failures = checks.failed(_reread(tree, manifest), tree["bench"])
    assert failures, f"{edit}: no check failed"
