"""Driver `fit_ref`: `fit`, then the program against the plain reference.

`fit.run` is called unchanged: the same window, the same count of samples,
the same checks of its own. After the window, from the state the timed
steps left (parameters, updater state, step count) and on a staged batch at
the timed sizes, the configuration's `reference_check`
(`build(...)["reference_check"]`) compares the program's own loss, logits
and gradients with the reference's, and then the change that one more call
of the compiled train step, the one the window timed, makes to the
parameters with the change the reference's own optimizer step makes from
that state; every miss goes into `problems`, and the numbers, each beside
its limit, into `info["reference_check"]`. The check is outside the window
and outside `setup_s`; `memory_peak_bytes` was read before it.

`check.fault` in a cell's file (no cell of `BENCHMARK.json` sets it) asks
the check for a deliberate fault, to show that its limits refuse one.
"""

from __future__ import annotations

import time


def run(cell, args, clock) -> dict:
    from benchmark.drivers import fit

    result = fit.run(cell, args, clock)
    t0 = time.perf_counter()
    check = result["context"]["built"]["reference_check"](
        fault=cell.spec.get("check", {}).get("fault"))
    numbers = dict(check["numbers"], seconds=time.perf_counter() - t0)
    result["info"]["reference_check"] = numbers
    result["info"]["layer_gauges"] = _layer_gauges()
    result["problems"] = list(result["problems"]) + list(check["problems"])
    result["correct"] = not result["problems"]
    return result


def _layer_gauges() -> dict:
    """The program's per-layer gauges as last published (`nn/fit_obs.py`:
    where the score was read, so at the window's last sync), for the
    reader of the info line; none where the program has none."""
    from deeplearning4j_tpu import observability as obs

    out = {}
    for name in ("dl4j_moe_pairs_held_share",
                 "dl4j_moe_expert_load_max_over_mean",
                 "dl4j_dsa_selected_keys_mean"):
        family = obs.metrics.get_family(name)
        if family is not None:
            out[name] = {c.labels.get("layer", ""): c.get()
                         for c in family.children()}
    return out
