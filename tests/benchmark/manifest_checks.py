"""What has to hold of `BENCHMARK.json` and of the files its names select,
as functions of a manifest and a benchmark root: the test files call them on
the committed tree, and `test_bench_additions.py` calls every one of them on
a tree that a later PR extends with new files and appended entries. Each
raises AssertionError where the manifest breaks what it guards.

Older entries are pinned by their places, never by a whole list or a count:
the cells, configurations and metrics of a day come first in their lists and
in their order, and every list that named them names them as it did. So a
configuration, a cell or a per-layer metric is new files plus appended
entries, and an edit to what exists still fails a check. A function that
builds a `cells.Cell` or loads a module reads through `cells`, which the
caller points at `bench` (`cells.ROOT`, `cells.BENCH`, then
`cells.manifest.cache_clear()`).
"""

import glob
import json
import os
import re

from benchmark.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

RESNET = "resnet50_b256.fit_cached"
KEYE = "keye_vl2_30b_a3b.fit_seq8k"
MELLUM = "mellum2_12b_a2_5b.fit_seq16k"
KIMI = "kimi_vl_a3b.fit_seq8k"
ALL4 = [RESNET, KEYE, MELLUM, KIMI]
LM3 = [KEYE, MELLUM, KIMI]

# The `fit_ref` cells the fixed count was set for, in the manifest's order,
# each with its count (PERF.md section 4); a later `fit_ref` cell keeps the
# rules of `fixed_count` with a count of its own.
AT_STEP = {KEYE: 320, MELLUM: 160, KIMI: 288}


# ---- reading a tree ----------------------------------------------------------

def _json(bench, kind, name):
    with open(os.path.join(bench, kind, name + ".json")) as f:
        return json.load(f)


def _through_cells(bench):
    assert os.path.abspath(cells.BENCH) == os.path.abspath(bench), (
        "point `cells` at the tree that is checked")


def metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def entry(manifest, name):
    found = [m for m in metrics(manifest) if m["name"] == name]
    assert len(found) == 1, f"{name}: {len(found)} entries"
    return found[0]


def names_for(manifest, group, cell):
    """The names of `group` that the manifest lists for `cell`, in order
    (what `cells.Cell.metric_names` reads, from the manifest given)."""
    return [m["name"] for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def cell_files(bench):
    return sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(bench, "workloads", "*.json")))


def fit_ref_cells(manifest, bench):
    """The cells whose file says `driver: fit_ref`, in manifest order."""
    return [w["name"] for w in manifest["workloads"]
            if _json(bench, "workloads", w["name"])["driver"] == "fit_ref"]


# ---- the contract's letters (test_bench_manifest.py) -------------------------

def keys_and_command(manifest, bench):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile and
    # 1200 s spare have to fit 43200 s with the full 24 cells
    full = (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200


def metric_entry(manifest, bench, metric):
    _through_cells(bench)
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    names = {w["name"] for w in manifest["workloads"]}
    assert set(metric.get("workloads", names)) <= names
    if metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = next(m for m in manifest["end_to_end"]
                     if m["name"] == metric["moves"])
        # the metric it moves is reported in every cell this one is in
        assert set(metric.get("workloads", names)) <= set(
            moved.get("workloads", names))
        reader = os.path.join(bench, "layer_metrics", metric["name"] + ".py")
        assert os.path.isfile(reader)
        assert callable(cells.load_module("layer_metrics",
                                          metric["name"]).read)


def metric_names_unique(manifest, bench):
    names = [m["name"] for m in metrics(manifest)]
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def config_entry(manifest, bench, config):
    _through_cells(bench)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    sizes = _json(bench, "configs", config["name"])
    assert sizes["reduced"] == config["reduced"]
    assert "assumed" in sizes and "rehearsal" in sizes
    module = cells.load_module("configs", config["name"])
    assert callable(module.build) and module.source
    assert any(w["config"] == config["name"] for w in manifest["workloads"])
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def cell_file_resolves(manifest, bench, cell):
    """A `workloads/*.json` names files that exist, whether or not the
    manifest lists the cell yet."""
    _through_cells(bench)
    spec = _json(bench, "workloads", cell)
    assert NAME.match(cell)
    assert cell.startswith(spec["config"] + ".")
    assert cell == f"{spec['config']}.{spec['traffic']['kind']}"
    for ext in (".json", ".py"):
        assert os.path.isfile(os.path.join(bench, "configs",
                                           spec["config"] + ext))
    assert callable(cells.load_module("drivers", spec["driver"]).run)
    assert spec["chips"] in (1, 4)
    if "reference_check" in spec:
        ref = cells.load_module("reference",
                                spec["reference_check"]["reference"])
        assert callable(ref.forward) and callable(ref.greedy_agreement)
    # the rehearsal's overrides name only keys the cell has
    assert set(spec.get("rehearsal", {})) <= set(spec)


def workload_entry(manifest, bench, workload):
    _through_cells(bench)
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    assert workload["name"] in cell_files(bench)
    assert workload["name"] == f"{workload['config']}.{workload['traffic']}"
    assert 1 <= len(workload["why"]) <= 200
    cell = cells.Cell(workload["name"])
    assert cell.chips == workload["chips"]
    e2e = names_for(manifest, "end_to_end", workload["name"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert names_for(manifest, "per_layer", workload["name"])


def plain_names(manifest, bench):
    root = os.path.dirname(os.path.abspath(bench))
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in manifest["paths"]:
        for top, dirs, files in os.walk(os.path.join(root, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(top, f), root)
                assert ok.match(rel) and len(rel) <= 200, rel


# ---- every entry there is, in its place --------------------------------------

def _metric(unit, better, source, layer=None, moves=None, workloads=None,
            bound=None):
    out = {"unit": unit, "better": better, "source": source}
    for key, value in (("layer", layer), ("moves", moves),
                       ("workloads", workloads), ("bound", bound)):
        if value is not None:
            out[key] = value
    return out


_FIT = "fit_samples_per_s"
_KERNEL_SHARE = ("%", "lower", "device_trace", "kernels", _FIT)
# Every entry the manifest held when the pins on whole lists went, in its
# order; each later entry comes after these and each list may grow at its end.
ENTRIES = {
    "end_to_end": [
        ("fit_samples_per_s", _metric("samples/s", "higher", "host_clock",
                                      workloads=ALL4, bound=0.03)),
        ("setup_s", _metric("s", "lower", "host_clock", bound=0.1)),
    ],
    "per_layer": [
        ("compile_cache_misses", _metric("count", "lower", "program_counter",
                                         "compile", "setup_s")),
        ("fit_dispatch_mean_ms", _metric("ms", "lower", "program_counter",
                                         "engine", _FIT, ALL4)),
        ("hbm_gb_per_step.fit", _metric("GB", "lower", "program_counter",
                                        "updater seam + kernels", _FIT, ALL4)),
        ("fit_mfu", _metric("%", "higher", "host_clock", "kernels", _FIT,
                            ALL4)),
        ("pallas_time_share.fit", _metric(*_KERNEL_SHARE, ALL4)),
        ("device_idle_share.fit", _metric("%", "lower", "device_trace",
                                          "device", _FIT, ALL4)),
        ("fit_enqueue_mean_ms", _metric("ms", "lower", "program_span",
                                        "engine", _FIT, ALL4)),
        ("fit_dispatch_self_mean_ms", _metric("ms", "lower", "program_span",
                                              "engine", _FIT, ALL4)),
        ("idle_in_dispatch_share.fit", _metric("%", "lower", "device_trace",
                                               "engine", _FIT, ALL4)),
        ("idle_in_input_wait_share.fit", _metric(
            "%", "lower", "device_trace", "input pipeline", _FIT, ALL4)),
        ("idle_unnamed_share.fit", _metric("%", "lower", "device_trace",
                                           "device", _FIT, ALL4)),
        ("dsa_time_share.fit", _metric(*_KERNEL_SHARE, [KEYE])),
        ("moe_time_share.fit", _metric(*_KERNEL_SHARE, LM3)),
        ("lm_head_time_share.fit", _metric(*_KERNEL_SHARE, LM3)),
        ("moe_expert_load_max_over_mean", _metric(
            "ratio", "lower", "program_counter", "kernels", _FIT, LM3)),
        ("moe_pairs_held_share", _metric("%", "lower", "program_counter",
                                         "kernels", _FIT, LM3)),
        ("swa_time_share.fit", _metric(*_KERNEL_SHARE, [MELLUM])),
        ("full_attn_time_share.fit", _metric(*_KERNEL_SHARE, [MELLUM])),
        ("banded_attention_roofline.fit", _metric(
            "%", "higher", "device_trace", "kernels", _FIT, [MELLUM])),
        ("attn_band_fill_share", _metric("%", "higher", "program_counter",
                                         "kernels", _FIT, [MELLUM])),
        ("mla_time_share.fit", _metric(*_KERNEL_SHARE, [KIMI])),
        ("mla_project_time_share.fit", _metric(*_KERNEL_SHARE, [KIMI])),
        ("moe_shared_time_share.fit", _metric(*_KERNEL_SHARE, [KIMI])),
        ("dense_ffn_time_share.fit", _metric(*_KERNEL_SHARE, [KIMI])),
        ("latent_attention_roofline.fit", _metric(
            "%", "higher", "device_trace", "kernels", _FIT, [KIMI])),
        ("unscoped_time_share.fit", _metric("%", "lower", "device_trace",
                                            "engine", _FIT, ALL4)),
        ("updater_time_share.fit", _metric("%", "lower", "device_trace",
                                           "updater seam + kernels", _FIT,
                                           ALL4)),
        ("grouped_product_time_share.fit", _metric(*_KERNEL_SHARE, LM3)),
    ],
}
CELLS = [(RESNET, "resnet50_b256", "fit_cached"),
         (KEYE, "keye_vl2_30b_a3b", "fit_seq8k"),
         (MELLUM, "mellum2_12b_a2_5b", "fit_seq16k"),
         (KIMI, "kimi_vl_a3b", "fit_seq8k")]
CONFIGS = ["resnet50_b256", "keye_vl2_30b_a3b", "mellum2_12b_a2_5b",
           "kimi_vl_a3b"]


def older_entries_in_their_places(manifest, bench):
    """Each entry above is where it was and says what it said; a list it
    holds may have grown at its end, and only there."""
    for group, then in ENTRIES.items():
        now = manifest[group]
        assert [m["name"] for m in now][:len(then)] == [n for n, _ in then]
        for (name, was), m in zip(then, now):
            listed = m.get("workloads")
            if "workloads" in was:
                assert listed[:len(was["workloads"])] == was["workloads"], name
            else:
                assert listed is None, name
            assert {k: v for k, v in m.items()
                    if k not in ("name", "workloads")} == {
                k: v for k, v in was.items() if k != "workloads"}, name
    assert [(w["name"], w["config"], w["traffic"], w["chips"])
            for w in manifest["workloads"]][:len(CELLS)] == [
        (name, config, traffic, 1) for name, config, traffic in CELLS]
    assert [c["name"] for c in manifest["configs"]][:len(CONFIGS)] == CONFIGS


# ---- the fixed count of the `fit_ref` cells (test_bench_fixed_check.py) -----

def fixed_count(manifest, bench, name):
    """The cell's check is taken at a count of whole sync groups, above its
    rehearsal's own count, and says what a window past it means."""
    _through_cells(bench)
    spec = _json(bench, "workloads", name)
    sizes = _json(bench, "configs", spec["config"])
    group = int(spec["traffic"]["epochs_per_sync"]) * int(
        sizes["staged_batches"])
    check = spec["check"]
    assert check["fault"] is None
    assert check["at_step"] % group == 0
    if name in AT_STEP:
        assert group == 8 and check["at_step"] == AT_STEP[name]
    assert "at_step_passed" in check["at_step_why"]   # says what happens then
    # the rehearsal drives the same path with a small count of its own
    small = spec["rehearsal"]["check"]["at_step"]
    assert 0 < small < check["at_step"]
    merged = cells.Cell(name, rehearsal=True).spec
    assert merged["check"] == dict(check, at_step=small)
    assert small % (int(merged["traffic"]["epochs_per_sync"])
                    * int(sizes["staged_batches"])) == 0


def known_fit_ref_cells_first(manifest, bench):
    listed = fit_ref_cells(manifest, bench)
    assert listed[:len(AT_STEP)] == list(AT_STEP)
    for name in listed:
        fixed_count(manifest, bench, name)


def moe_time_share_listing(manifest, bench):
    """`moe_time_share.fit` lists the three language-model cells first and
    in their order, and only `fit_ref` cells after them."""
    m = entry(manifest, "moe_time_share.fit")
    assert m["workloads"][:len(AT_STEP)] == list(AT_STEP)
    assert set(m["workloads"]) <= set(fit_ref_cells(manifest, bench))
    assert m["source"] == "device_trace" and m["layer"] == "kernels"


def every_listed_metric_has_its_reader(manifest, bench):
    names = [m["name"] for m in manifest["per_layer"]]
    # the metric that read nothing is gone with its reader
    assert "norm_act_time_share.fit" not in names
    assert not os.path.exists(os.path.join(
        bench, "layer_metrics", "norm_act_time_share.fit.py"))
    for name in names:
        assert os.path.isfile(os.path.join(bench, "layer_metrics",
                                           name + ".py")), name


# ---- each language-model cell's own entries ----------------------------------

KEYE_OWN = {"dsa_time_share.fit", "moe_time_share.fit",
            "lm_head_time_share.fit", "moe_expert_load_max_over_mean",
            "moe_pairs_held_share"}


def keye_entries(manifest, bench):
    """(test_bench_keye.py) The cell's own metrics name it first and not the
    cell before it; its file is a `fit_ref` cell's with no fault planted."""
    spec = _json(bench, "workloads", KEYE)
    assert spec["driver"] == "fit_ref" and spec["check"]["fault"] is None
    found = {m["name"]: m for m in manifest["per_layer"]
             if m["name"] in KEYE_OWN}
    assert set(found) == KEYE_OWN
    for m in found.values():
        assert m["workloads"][0] == KEYE and RESNET not in m["workloads"]
        assert m["moves"] == _FIT


MELLUM_OWN = {"swa_time_share.fit", "full_attn_time_share.fit",
              "banded_attention_roofline.fit", "attn_band_fill_share"}
KIMI_OWN = {"mla_time_share.fit", "mla_project_time_share.fit",
            "moe_shared_time_share.fit", "dense_ffn_time_share.fit",
            "latent_attention_roofline.fit"}


def _own_entries(manifest, bench, cell, own, of_the_day):
    """A cell's own metrics name it first and no other cell of its day: the
    entries its issue added for it alone. Later cells may join them."""
    found = {m["name"]: m for m in manifest["per_layer"] if m["name"] in own}
    assert set(found) == own
    for m in found.values():
        assert m["workloads"][0] == cell, m["name"]
        assert not set(m["workloads"]) & (set(of_the_day) - {cell}), m["name"]
        assert m["layer"] == "kernels" and m["moves"] == _FIT, m["name"]
        assert m["unit"] == "%", m["name"]


def mellum_reports(manifest, bench):
    """(test_bench_mellum.py) What the cell's issue listed, and none of its
    sibling's own."""
    assert names_for(manifest, "end_to_end", MELLUM)[:2] == [
        _FIT, "setup_s"]
    names = set(names_for(manifest, "per_layer", MELLUM))
    assert MELLUM_OWN | {"fit_mfu", "lm_head_time_share.fit",
                         "moe_pairs_held_share", "hbm_gb_per_step.fit"} <= names
    # `moe_time_share.fit` reads this cell: its reader finds XLA's
    # `ragged-dot` calls, half of the experts' time here, by name
    assert "moe_time_share.fit" in names
    assert "dsa_time_share.fit" not in names
    _own_entries(manifest, bench, MELLUM, MELLUM_OWN, [RESNET, KEYE, MELLUM])


def kimi_reports(manifest, bench):
    """(test_bench_kimi.py) What the cell's issue listed, and none of its
    siblings' own."""
    assert names_for(manifest, "end_to_end", KIMI)[:2] == [_FIT, "setup_s"]
    names = set(names_for(manifest, "per_layer", KIMI))
    assert KIMI_OWN | {"fit_mfu", "lm_head_time_share.fit",
                       "moe_pairs_held_share", "moe_expert_load_max_over_mean",
                       "hbm_gb_per_step.fit", "pallas_time_share.fit",
                       "device_idle_share.fit"} <= names
    # `moe_time_share.fit` reads this cell (its reader finds XLA's
    # `ragged-dot` calls by name); the siblings' own metrics do not
    assert "moe_time_share.fit" in names
    assert not (MELLUM_OWN | {"dsa_time_share.fit"}) & names
    _own_entries(manifest, bench, KIMI, KIMI_OWN, ALL4)
    assert all(entry(manifest, n)["source"] == "device_trace"
               for n in KIMI_OWN)


# ---- all of them -------------------------------------------------------------

def _each(check, items):
    return lambda manifest, bench: [check(manifest, bench, item)
                                    for item in items(manifest, bench)]


CHECKS = {
    "keys_and_command": keys_and_command,
    "metric_entry": _each(metric_entry, lambda m, b: metrics(m)),
    "metric_names_unique": metric_names_unique,
    "config_entry": _each(config_entry, lambda m, b: m["configs"]),
    "cell_file_resolves": _each(cell_file_resolves,
                                lambda m, b: cell_files(b)),
    "workload_entry": _each(workload_entry, lambda m, b: m["workloads"]),
    "plain_names": plain_names,
    "older_entries_in_their_places": older_entries_in_their_places,
    "known_fit_ref_cells_first": known_fit_ref_cells_first,
    "moe_time_share_listing": moe_time_share_listing,
    "every_listed_metric_has_its_reader": every_listed_metric_has_its_reader,
    "keye_entries": keye_entries,
    "mellum_reports": mellum_reports,
    "kimi_reports": kimi_reports,
}


def failed(manifest, bench) -> dict:
    """{check: what it raised} for every check the tree fails."""
    out = {}
    for name, check in CHECKS.items():
        try:
            check(manifest, bench)
        except Exception as e:  # a missing entry raises as a breach does
            out[name] = f"{type(e).__name__}: {e}"
    return out
