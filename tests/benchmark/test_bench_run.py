"""`benchmark/run.py` as a command: it refuses to run off-chip, it refuses to
run without the program, and its rehearsal drives a whole cell on the CPU
without ever printing `correct: true`."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import cells

RUN = [sys.executable, os.path.join("benchmark", "run.py")]


def run(args, cwd=cells.ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(RUN + args, cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)


def test_off_chip_exits_nonzero_and_prints_no_result():
    p = run(["--workload", "resnet50_b256.fit_cached", "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(cells.BENCH), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(["--workload", "resnet50_b256.fit_cached", "--seed", "1",
             "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_cell_exits_nonzero():
    p = run(["--workload", "no_such.cell", "--seed", "1", "--seconds", "1",
             "--trace", "0", "--rehearsal"])
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_drives_a_serving_cell_and_is_never_correct(trace):
    p = run(["--workload", "opt_1_3b.chat_decode", "--seed",
             str(2 ** 31 + 12345), "--seconds", "3", "--trace", str(trace),
             "--rehearsal"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 4 and line["failed"] == 0
    cell = cells.Cell("opt_1_3b.chat_decode")
    if trace:
        # off-chip there is no device plane: the trace's metrics are left
        # out, every other per-layer metric of the cell is there
        sources = {m["name"]: m["source"]
                   for m in cells.manifest()["per_layer"]}
        names = set(cell.metric_names("per_layer"))
        traced = {n for n in names if sources[n] == "device_trace"}
        assert set(line["metrics"]) == names - traced
    else:
        assert set(line["metrics"]) == set(cell.metric_names("end_to_end"))
        assert all(m["value"] > 0 for m in line["metrics"].values())
    info = json.loads(p.stdout.strip().splitlines()[-2])["info"]
    assert info["reference_check"]["ok"], info
    assert info["reference_check"]["tokens_compared"] >= 4
