"""From a name in `BENCHMARK.json` to the files that belong to it.

Nothing here (or anywhere in the harness) branches on the name of a cell, a
configuration or a metric: a name only selects files.

  cell            benchmark/workloads/<cell>.json
  configuration   benchmark/configs/<config>.json  (the sizes as run)
                  benchmark/configs/<config>.py    (`build(sizes, seed, chips)`)
  driver          benchmark/drivers/<driver>.py    (`run(cell, args, clock)`)
  per-layer       benchmark/layer_metrics/<name>.py (`read(context) -> float | None`)
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check_name(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"{name!r} is not a name (letters, digits, _ . -)")
    return name


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (a name may hold dots, so
    this loads by path, not by import)."""
    path = os.path.join(BENCH, kind, _check_name(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, _check_name(name) + ".json")
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=1)
def manifest() -> dict:
    """`BENCHMARK.json`, read once; callers do not change it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`: its own file, its configuration's sizes and
    the metrics `BENCHMARK.json` says it reports."""

    def __init__(self, name: str, rehearsal: bool = False):
        self.name = name
        self.spec = load_json("workloads", name)
        self.config_name = self.spec["config"]
        self.sizes = load_json("configs", self.config_name)
        self.rehearsal = rehearsal
        if rehearsal:
            # The CPU rehearsal's shrunk shapes: the configuration and the
            # cell each say how they shrink. A rehearsal never prints
            # `correct: true` (run.py), whatever it computes.
            self.sizes = {**self.sizes, **self.sizes.get("rehearsal", {})}
            self.spec = _merge(self.spec, self.spec.get("rehearsal", {}))
        self.chips = int(self.spec["chips"])
        entry = next((w for w in manifest()["workloads"]
                      if w["name"] == name), None)
        if entry is not None and (entry["config"] != self.config_name
                                  or int(entry["chips"]) != self.chips):
            raise ValueError(f"{name}: BENCHMARK.json and the cell's file "
                             "disagree on config or chips")

    def build(self, seed: int):
        return load_module("configs", self.config_name).build(
            self.sizes, seed, self.chips)

    def driver(self):
        return load_module("drivers", self.spec["driver"])

    def metric_names(self, group: str) -> list:
        """The names of `group` (`end_to_end` | `per_layer`) that
        `BENCHMARK.json` lists for this cell."""
        return [m["name"] for m in manifest()[group]
                if self.name in m.get("workloads", [self.name])]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if (isinstance(v, dict) and isinstance(
            base.get(k), dict)) else v
    return out


def read_layer_metrics(names, context) -> dict:
    """Each per-layer metric through its own reader. A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    units = {m["name"]: m["unit"] for m in manifest()["per_layer"]}
    out = {}
    for name in names:
        value = load_module("layer_metrics", name).read(context)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out
