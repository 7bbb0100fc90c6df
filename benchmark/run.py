#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of `BENCHMARK.json` on the machine it is started on, in one
process, and prints as the last line of its standard output one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`). With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics. The line's last key,
`check`, holds each number the run compared beside its limit (`{name:
[reading, limit]}`), and so do the last lines of standard error.

Off the TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. `--rehearsal` is the only way to run off-chip: the
cell's and the configuration's `rehearsal` sizes, for finding wrong paths
on a CPU; its line always says `"correct": false`.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, to within the interpreter's own

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    """Set-up is everything from process start to the start of the window."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.setup_s = None
        self.phases = {}  # seconds since process start at which each ended

    def mark(self, phase: str) -> None:
        self.phases[phase] = round(time.perf_counter() - self.t_start, 3)

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        return self.setup_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes, allowed off-chip, never correct: true")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
        sys.exit(f"no deeplearning4j_tpu/ beside {os.path.dirname(__file__)}:"
                 " the benchmark runs the program, it is not the program")
    sys.path.insert(0, ROOT)

    from benchmark.harness import cells, device

    device.place_compile_cache(ROOT)
    args.trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(args.trace_dir, ignore_errors=True)
        os.makedirs(args.trace_dir, exist_ok=True)

    cell = cells.Cell(args.workload, rehearsal=args.rehearsal)
    dev = device.require(cell.chips, args.rehearsal)

    from deeplearning4j_tpu import observability as obs

    obs.install_jax_compile_hook()
    clock = Clock(_T_START)
    clock.mark("imports_and_device")
    result = cell.driver().run(cell, args, clock)

    correct = bool(result["correct"]) and dev["platform"] == "tpu" \
        and not args.rehearsal
    dev["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    units = {m["name"]: m["unit"] for m in cells.manifest()["end_to_end"]}
    if args.trace:
        context = result["context"]
        context["setup_s"] = result["setup_s"]
        reduced = context["tracer"].reduced(cell.chips)
        if reduced:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
        line["metrics"] = cells.read_layer_metrics(
            cell.metric_names("per_layer"), context)
    else:
        values = dict(result["end_to_end"], setup_s=result["setup_s"])
        line["metrics"] = {
            name: {"value": float(values[name]), "unit": units[name]}
            for name in cell.metric_names("end_to_end")}
    line["device"] = dev
    if args.rehearsal:
        line["rehearsal"] = "tiny sizes; not a measurement"
    if result.get("problems"):
        line["problems"] = result["problems"]
    # Each number the run compared, beside its limit: `{name: [reading,
    # limit]}`, the line's last key and the last lines of standard error.
    if result.get("compared"):
        # a reading that is not finite travels as its name: the line stays
        # JSON that any parser reads
        line["check"] = {
            name: [r if r is None or math.isfinite(r) else repr(r), limit]
            for name, (r, limit) in result["compared"].items()}
    # An earlier line for the reader: sample counts and what the run saw.
    import jax

    print(json.dumps({"info": result.get("info", {}),
                      "memory_stats": jax.devices()[0].memory_stats(),
                      "window_s": result["window_s"],
                      "setup_s": result["setup_s"],
                      "setup_phases_end_s": clock.phases,
                      "end_to_end": result["end_to_end"]}), flush=True)
    print(json.dumps(line), flush=True)
    for name, (reading, limit) in line.get("check", {}).items():
        print(f"check {name}: {reading!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Threads of the program (staging, HTTP keep-alive handlers) are daemons
    # and end with the process; nothing was started as a child.
    sys.exit(code)
