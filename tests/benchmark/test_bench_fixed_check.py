"""PR 34: the `fit_ref` cells are checked at a step count fixed in the cell's
file (`check.at_step`), not wherever the window ended; the numbers of a check,
each beside its limit, in the run's last line; `moe_time_share.fit` finds
XLA's `ragged-dot` calls by name. On the CPU at the rehearsal sizes, on fakes
and on canned traces. No topology and no TPU call in this file."""

import argparse
import json
import logging
import time
import types

import pytest

from benchmark import run as bench_run
from benchmark.drivers import fit, fit_ref
from benchmark.harness import cells, device, fit_check

FIT_REF_CELLS = ["keye_vl2_30b_a3b.fit_seq8k", "mellum2_12b_a2_5b.fit_seq16k",
                 "kimi_vl_a3b.fit_seq8k"]
AT_STEP = {"keye_vl2_30b_a3b.fit_seq8k": 320,
           "mellum2_12b_a2_5b.fit_seq16k": 160,
           "kimi_vl_a3b.fit_seq8k": 288}


# ---- the committed files ----------------------------------------------------

@pytest.mark.parametrize("name", FIT_REF_CELLS)
def test_committed_cell_fixes_a_count_of_whole_sync_groups(name):
    spec = cells.load_json("workloads", name)
    sizes = cells.load_json("configs", spec["config"])
    group = int(spec["traffic"]["epochs_per_sync"]) * int(
        sizes["staged_batches"])
    assert group == 8
    check = spec["check"]
    assert check["fault"] is None
    assert check["at_step"] == AT_STEP[name] and check["at_step"] % group == 0
    assert "at_step_passed" in check["at_step_why"]   # says what happens then
    # the rehearsal drives the same path with a small count of its own
    small = spec["rehearsal"]["check"]["at_step"]
    assert 0 < small < check["at_step"]
    merged = cells.Cell(name, rehearsal=True).spec
    assert merged["check"] == dict(check, at_step=small)
    assert small % (int(merged["traffic"]["epochs_per_sync"])
                    * int(sizes["staged_batches"])) == 0


def test_every_fit_ref_cell_of_the_manifest_is_on_this_files_list():
    listed = [w["name"] for w in cells.manifest()["workloads"]
              if cells.load_json("workloads", w["name"])["driver"]
              == "fit_ref"]
    assert listed == FIT_REF_CELLS


# ---- `advance_to` on a fake program ----------------------------------------

class _FakeNet:
    """Counts steps as the engine does: one `fit(iterator)` is one epoch of
    `steps_per_epoch` steps; `compiles_at` logs a compile as jax does under
    `jax_log_compiles`."""

    def __init__(self, iteration, steps_per_epoch=2, compiles_at=None):
        self.iteration, self.params_tree = iteration, {"w": 0.0}
        self.steps_per_epoch, self.compiles_at = steps_per_epoch, compiles_at
        self.epochs, self.score_reads = 0, 0

    def fit(self, iterator):
        assert iterator == "the window's iterator"
        if self.compiles_at is not None and self.iteration >= self.compiles_at:
            logging.getLogger("jax").warning(
                "Compiling step_fn with global shapes and types [f32[8]]")
            self.compiles_at = None
        self.iteration += self.steps_per_epoch
        self.epochs += 1

    @property
    def score_value(self):
        self.score_reads += 1
        return 0.0


def _built(net):
    return {"net": net, "trainer": net, "iterator": "the window's iterator"}


@pytest.mark.parametrize("after_window,at_step,epochs", [
    (10 + 192, 320, 59), (10 + 8, 320, 151), (318, 320, 1), (14, 64, 25)])
def test_advance_stops_at_the_count_whatever_the_window_took(
        after_window, at_step, epochs):
    net = _FakeNet(after_window)
    out, problems = fit_ref.advance_to(_built(net), at_step, 4)
    assert problems == [] and net.iteration == at_step
    assert net.epochs == epochs and net.score_reads == 0
    assert out["at_step"] == at_step and out["at_step_passed"] is False
    assert out["steps_after_window"] == at_step - after_window
    assert out["advance_seconds"] >= 0.0


@pytest.mark.parametrize("after_window,at_step,passed", [
    (330, 320, True), (320, 320, False), (4106, 64, True), (202, None, False)])
def test_a_window_at_or_past_the_count_or_no_count_is_left_where_it_ended(
        after_window, at_step, passed):
    net = _FakeNet(after_window)
    out, problems = fit_ref.advance_to(_built(net), at_step, 4)
    assert problems == [] and net.iteration == after_window
    assert net.epochs == 0
    assert out == {"at_step": at_step, "steps_after_window": 0,
                   "at_step_passed": passed, "advance_seconds": 0.0}


def test_a_count_that_whole_epochs_cannot_reach_is_a_problem():
    net = _FakeNet(10)
    out, problems = fit_ref.advance_to(_built(net), 31, 4)
    assert net.iteration == 32 and out["steps_after_window"] == 22
    assert len(problems) == 1 and "at_step 31" in problems[0]


def test_a_compile_on_the_way_to_the_count_is_a_problem():
    net = _FakeNet(10, compiles_at=20)
    _, problems = fit_ref.advance_to(_built(net), 64, 4)
    assert net.iteration == 64
    assert problems == ["compiled after the window: ['step_fn']"]
    # and the handler is gone afterwards
    assert not [h for h in logging.getLogger("jax").handlers
                if type(h).__name__ == "CompileNames"]


# ---- the driver on the program, at the rehearsal sizes ----------------------

def _drive(name, seconds, at_step, fault=None):
    """`fit_ref.run` in this process on the cell's rehearsal with
    `check.at_step` as given (None: the key left out) and `check.fault`,
    and what `fit.run` returned to it before the extra steps."""
    cell = cells.Cell(name, rehearsal=True)
    check = {k: v for k, v in cell.spec["check"].items() if k != "at_step"}
    check["fault"] = fault
    if at_step is not None:
        check["at_step"] = at_step
    cell.spec = dict(cell.spec, check=check)
    args = argparse.Namespace(workload=name, seed=2 ** 31 + 34,
                              seconds=seconds, trace=0, trace_dir=None)
    seen, real = {}, fit.run

    def spy(cell, args, clock):
        result = real(cell, args, clock)
        net = result["context"]["built"]["net"]
        seen.update(setup_s=result["setup_s"], window_s=result["window_s"],
                    memory_peak_bytes=result["memory_peak_bytes"],
                    rate=result["end_to_end"]["fit_samples_per_s"],
                    iteration=int(net.iteration), problems=list(
                        result["problems"]),
                    executables=len(net._get_jit("train_step").executables()))
        return result

    fit.run = spy
    try:
        result = fit_ref.run(cell, args, bench_run.Clock(time.perf_counter()))
    finally:
        fit.run = real
    net = result["context"]["built"]["net"]
    seen["executables_after"] = len(
        net._get_jit("train_step").executables())
    seen["iteration_after"] = int(net.iteration)
    return result, seen


@pytest.fixture(scope="module", params=FIT_REF_CELLS)
def runs(request):
    name = request.param
    return {"name": name,
            "short": _drive(name, 0.0, 96), "long": _drive(name, 0.25, 96),
            "passed": _drive(name, 0.0, 8), "none": _drive(name, 0.0, None)}


def test_the_check_is_taken_at_the_count_at_two_window_lengths(runs):
    taken = []
    for key in ("short", "long"):
        result, seen = runs[key]
        check = result["info"]["reference_check"]
        if seen["iteration"] >= 96:
            pytest.skip("this machine's window passed the count")
        assert check["steps_before"] == check["at_step"] == 96
        assert check["at_step_passed"] is False
        assert check["steps_after_window"] == 96 - seen["iteration"] > 0
        assert seen["iteration"] == result["info"]["steps"] + 2 + 4
        assert seen["iteration_after"] == 97     # the check's own step
        taken.append(seen["iteration"])
    # the one-group window stops at 10 steps; the longer one further on:
    # two windows of different lengths, one count
    assert taken[0] == 10 and taken[1] >= taken[0]
    # and from one seed the two checks then read the same numbers
    a, b = (runs[k][0]["info"]["reference_check"] for k in ("short", "long"))
    assert a["loss_program"] == b["loss_program"]
    assert a["grad_rel"] == b["grad_rel"] and a["update_rel"] == b["update_rel"]


def test_a_window_past_the_count_says_so_and_is_checked_where_it_ended(runs):
    result, seen = runs["passed"]
    check = result["info"]["reference_check"]
    assert check["at_step"] == 8 and check["at_step_passed"] is True
    assert check["steps_after_window"] == 0
    assert check["steps_before"] == seen["iteration"] == 10
    # that alone is no problem: the problems are the window's and the
    # check's own, and the check ran (its numbers are there)
    assert not [p for p in result["problems"] if "at_step" in p]
    assert check["grad_rel"] and check["update_rel_max"] > 0
    assert set(result["compared"]) >= set(check["limits"])


def test_a_cell_without_a_count_is_checked_where_its_window_ended(runs):
    result, seen = runs["none"]
    check = result["info"]["reference_check"]
    assert check["at_step"] is None and check["at_step_passed"] is False
    assert check["steps_after_window"] == 0
    assert check["steps_before"] == seen["iteration"] == 10
    # the same state as the run whose window had passed its count
    other = runs["passed"][0]["info"]["reference_check"]
    assert check["loss_program"] == other["loss_program"]
    assert check["update_rel"] == other["update_rel"]


def test_nothing_compiles_between_the_window_and_the_check(runs):
    for key in ("short", "long", "passed", "none"):
        result, seen = runs[key]
        assert not [p for p in result["problems"] if "compiled" in p]
        # the steps on to the count and the check's own step ran the
        # window's compiled train step: no second executable
        assert seen["executables_after"] == seen["executables"] >= 1


def test_set_up_rate_and_memory_peak_are_read_before_the_extra_steps(runs):
    for key in ("short", "long", "passed", "none"):
        result, seen = runs[key]
        assert result["setup_s"] == seen["setup_s"]
        assert result["window_s"] == seen["window_s"]
        assert result["memory_peak_bytes"] == seen["memory_peak_bytes"]
        assert result["end_to_end"]["fit_samples_per_s"] == seen["rate"]
        assert result["problems"][:len(seen["problems"])] == seen["problems"]
    # the peak is the program's footprint: the same with and without them
    assert runs["short"][1]["memory_peak_bytes"] \
        == runs["none"][1]["memory_peak_bytes"]


def test_the_gauges_reported_are_the_windows_last_sync(runs):
    """The extra steps read no score, so the program publishes no gauge in
    them: two runs of one seed whose windows ended at the same step report
    the same gauges, whether or not steps followed."""
    short, none = runs["short"][0]["info"], runs["none"][0]["info"]
    assert short["layer_gauges"] == none["layer_gauges"]
    assert short["layer_gauges"]["dl4j_moe_pairs_held_share"]


@pytest.mark.parametrize("name,fault", [
    (FIT_REF_CELLS[0], "state_unchanged"), (FIT_REF_CELLS[0], "half_positions"),
    (FIT_REF_CELLS[1], "state_unchanged"), (FIT_REF_CELLS[2], "state_unchanged")])
def test_a_run_whose_step_is_broken_comes_out_not_correct_by_the_update(
        name, fault):
    """The rest of a run past the look for a chip, with the timed step broken
    underneath at the fixed count: the state returned unchanged, or half the
    positions left out of the mean. The update's reading says so; the sound
    run of the same seed (the fixture's) has no such problem."""
    result, seen = _drive(name, 0.0, 32, fault=fault)
    check = result["info"]["reference_check"]
    assert check["steps_before"] == 32 and result["correct"] is False
    reading, limit = result["compared"]["update_rel"]
    missed = [p for p in result["problems"] if p.startswith("update of")]
    if fault == "state_unchanged":
        assert reading == pytest.approx(1.0, abs=1e-5)
        assert seen["iteration_after"] == 32       # no step was taken
        assert len(missed) == 1 and reading > limit
    else:
        # the limit is the chip's, set at the published sizes: at these the
        # reading is held against the sound run of the same seed and count
        sound = _drive(name, 0.0, 32)[0]["compared"]["update_rel"][0]
        assert reading > 10 * sound and reading > 0.1
        assert seen["iteration_after"] == 33


def test_the_sound_runs_of_the_fixture_miss_no_update(runs):
    for key in ("short", "long", "passed", "none"):
        result, _ = runs[key]
        assert not [p for p in result["problems"]
                    if p.startswith("update of")]
        reading, limit = result["compared"]["update_rel"]
        assert 0 < reading < limit


# ---- each compared number beside its limit ----------------------------------

NUMBERS = {
    "limits": {"selection_overlap_min": 0.986, "routing_agreement_min": 0.975,
               "loss_rel": 8e-5, "logits_rel": 0.03, "grad_rel": 0.2,
               "update_rel": 0.05},
    "selection_overlap": [0.9931, 0.9923], "routing_agreement": [0.99, 0.9858],
    "loss_rel": 9.2e-5, "loss_rel_given": 4.1e-6, "logits_rel": 0.0063,
    "grad_rel": {"attn0.Wo": 0.091, "out.W": 0.01}, "grad_rel_max": 0.091,
    "update_rel": {"attn0.Wo": 0.0279, "out.W": 0.002},
    "update_rel_max": 0.0279}


def test_compared_pairs_each_reading_with_its_limit():
    assert fit_check.compared(NUMBERS) == {
        "selection_overlap_min": [0.9923, 0.986],
        "routing_agreement_min": [0.9858, 0.975],
        "loss_rel": [9.2e-5, 8e-5], "logits_rel": [0.0063, 0.03],
        "grad_rel": [0.091, 0.2], "update_rel": [0.0279, 0.05],
        "loss_rel_given": [4.1e-6, 8e-5]}


@pytest.mark.parametrize("name", FIT_REF_CELLS)
def test_compared_covers_every_limit_of_the_configuration(name):
    config = cells.load_module("configs", name.split(".")[0])
    numbers = dict(NUMBERS, limits=dict(config.LIMITS))
    got = fit_check.compared(numbers)
    assert set(got) == set(config.LIMITS) | {"loss_rel_given"}
    assert all(got[k][1] == v for k, v in config.LIMITS.items())


def _main_with(monkeypatch, capsys, canned, *extra):
    driver = types.SimpleNamespace(run=lambda cell, args, clock: canned)
    monkeypatch.setattr(cells.Cell, "driver", lambda self: driver)
    monkeypatch.setattr(device, "place_compile_cache", lambda root: None)
    monkeypatch.setattr(device, "require", lambda chips, rehearsal: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert bench_run.main(["--workload", FIT_REF_CELLS[0], "--seed",
                           str(2 ** 31 + 5), "--seconds", "1", "--trace",
                           "0", *extra]) == 0
    out, err = capsys.readouterr()
    return out.strip().splitlines(), err.strip().splitlines()


CANNED = {"correct": False, "attempted": 202, "failed": 0,
          "memory_peak_bytes": 10791597056, "setup_s": 26.1, "window_s": 51.9,
          "end_to_end": {"fit_samples_per_s": 3.6}, "info": {},
          "problems": ["loss_rel 9.2e-05 over 8e-05"],
          "compared": dict(fit_check.compared(NUMBERS),
                           loss_last_over_first=[0.87, 1.05])}


def test_a_miss_is_in_the_last_line_as_numbers_and_ends_standard_error(
        monkeypatch, capsys):
    out, err = _main_with(monkeypatch, capsys, CANNED)
    line = json.loads(out[-1])
    assert line["correct"] is False
    assert line["problems"] == ["loss_rel 9.2e-05 over 8e-05"]   # stays
    assert list(line)[-1] == "check" and list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    assert line["check"] == CANNED["compared"]
    assert line["check"]["loss_rel"] == [9.2e-5, 8e-5]
    assert line["metrics"]["fit_samples_per_s"] == {"value": 3.6,
                                                    "unit": "samples/s"}
    tail = err[-len(CANNED["compared"]):]
    assert "check loss_rel: 9.2e-05 limit 8e-05" in tail
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_a_sound_run_carries_them_too_and_a_driver_without_them_none(
        monkeypatch, capsys):
    sound = dict(CANNED, correct=True, problems=[])
    out, _ = _main_with(monkeypatch, capsys, sound)
    line = json.loads(out[-1])
    assert line["correct"] is True and "problems" not in line
    assert list(line)[-1] == "check" and line["check"] == CANNED["compared"]
    bare = {k: v for k, v in sound.items() if k != "compared"}
    out, err = _main_with(monkeypatch, capsys, bare)
    line = json.loads(out[-1])
    assert "check" not in line and list(line)[-1] == "device"
    assert not [t for t in err if t.startswith("check ")]


def test_a_reading_that_is_not_finite_keeps_the_line_plain_json(
        monkeypatch, capsys):
    broken = dict(CANNED, compared={"loss_rel": [float("nan"), 8e-5],
                                    "grad_rel": [float("inf"), 0.2],
                                    "loss_last_over_first": [None, 1.05]})
    out, err = _main_with(monkeypatch, capsys, broken)
    assert "NaN" not in out[-1] and "Infinity" not in out[-1]
    line = json.loads(out[-1])
    assert line["check"] == {"loss_rel": ["nan", 8e-5],
                             "grad_rel": ["inf", 0.2],
                             "loss_last_over_first": [None, 1.05]}
    assert "check loss_rel: 'nan' limit 8e-05" in err


# ---- `moe_time_share.fit` and XLA's `ragged-dot` ---------------------------

HLO = '''
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(moe.route)/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/jvp(moe.experts)/gather"}
  %ragged-dot-none.3 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/ragged_dot"}
  %ragged-dot-none.4 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(moe.experts))/ragged_dot"}
  %while.5 = bf16[8]{0} while(%a), metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(moe.experts))/while"}
  ROOT %fusion.6 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/jit(main)/lm.head/dot_general"}
}
'''
MS = 1e6
# 100 ms busy. Under `moe.` 0-5 and 5-15; a grouped product with no scope
# 15-40; one under the scope AND so named 40-50; a loop under the scope
# 50-60 whose body's grouped product 52-58 lies inside it; the head 60-100.
EVENTS = [("fusion.1", 0, 5 * MS), ("fusion.2", 5 * MS, 10 * MS),
          ("ragged-dot-none.3 [tpu_custom_call]", 15 * MS, 25 * MS),
          ("ragged-dot-none.4 [tpu_custom_call]", 40 * MS, 10 * MS),
          ("while.5", 50 * MS, 10 * MS),
          ("ragged-dot-none.7 [tpu_custom_call]", 52 * MS, 6 * MS),
          ("fusion.6", 60 * MS, 40 * MS)]


class _Exe:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        if self.text is None:
            raise RuntimeError("no text")
        return self.text


def _context(executables, events):
    reduced = {"busy_s": 0.1, "window_s": 0.125,
               "events": {0: events}} if events else None
    return {"tracer": types.SimpleNamespace(reduced=lambda chips: reduced),
            "executables": executables,
            "cell": types.SimpleNamespace(chips=1)}


def test_moe_time_share_is_the_union_of_the_scope_and_the_ragged_dots():
    read = cells.load_module("layer_metrics", "moe_time_share.fit").read
    # 0-60 ms of 100, each instant once: not 5 + 10 + 25 + 10 + 10 + 6 = 66
    assert read(_context([_Exe(HLO)], EVENTS)) == pytest.approx(60.0)
    # the scope alone, as the reader was before: 0-15 and 40-60
    from benchmark.harness import scope_time
    assert scope_time.scope_share_percent(
        _context([_Exe(HLO)], EVENTS), "moe.") == pytest.approx(35.0)
    # the compiler's names are found without the program's text
    assert read(_context([_Exe(None)], EVENTS)) == pytest.approx(41.0)
    assert read(_context([], EVENTS)) == pytest.approx(41.0)


def test_moe_time_share_reads_nothing_where_there_is_nothing_to_read():
    read = cells.load_module("layer_metrics", "moe_time_share.fit").read
    assert read(_context([_Exe(HLO)], [])) is None          # no trace
    dense = [e for e in EVENTS if e[0] in ("fusion.6",)]
    plain = HLO.replace("moe.", "ffn.")
    assert read(_context([_Exe(plain)], dense)) is None     # no experts
    # a program with the scope whose traced events miss it reads 0 of busy
    assert read(_context([_Exe(HLO)], dense)) == 0.0


@pytest.mark.parametrize("name", FIT_REF_CELLS)
def test_moe_time_share_is_listed_for_every_language_model_cell(name):
    assert "moe_time_share.fit" in cells.Cell(name).metric_names("per_layer")
    entry = next(m for m in cells.manifest()["per_layer"]
                 if m["name"] == "moe_time_share.fit")
    assert entry["workloads"] == FIT_REF_CELLS
    assert entry["source"] == "device_trace" and entry["layer"] == "kernels"


def test_the_metric_that_read_nothing_is_gone_with_its_reader():
    import os

    names = [m["name"] for m in cells.manifest()["per_layer"]]
    assert "norm_act_time_share.fit" not in names and len(names) == 25
    assert not os.path.exists(os.path.join(
        cells.BENCH, "layer_metrics", "norm_act_time_share.fit.py"))
    # every metric listed has its reader, and every reader of a metric that
    # a cell of the manifest reports is listed
    for name in names:
        assert os.path.isfile(os.path.join(cells.BENCH, "layer_metrics",
                                           name + ".py")), name
