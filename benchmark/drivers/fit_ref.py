"""Driver `fit_ref`: `fit`, then the program against the plain reference.

`fit.run` is called unchanged: the same window, the same count of samples,
the same checks of its own. After the window, from the state the timed
steps left (parameters, updater state, step count), the program is driven
on, untimed and through the window's own call (`trainer.fit(iterator)`,
the compiled step the window timed), until it has made exactly
`check.at_step` train steps since `init()`, the warm-up's and the window's
among them: a count in the cell's own file, the same on a parent and on a
change whatever their speed, because every reading of the check moves
with the steps taken. There, on a staged batch at the timed sizes, the
configuration's `reference_check` (`build(...)["reference_check"]`)
compares the program's own loss, logits and gradients with the
reference's, and then the change that one more call of the compiled train
step makes to the parameters with the change the reference's own optimizer
step makes from that state; every miss goes into `problems`, the numbers
into `info["reference_check"]`, and each number compared beside its limit
into the run's last line (`compared`). The extra steps and the check are
outside the window and outside `setup_s`; `memory_peak_bytes` was read
before them, and nothing compiles in them.

A window that has already passed `at_step` (a program much faster than the
one the count was chosen for) is checked where it ended, as every window
was before the count existed, and says so (`at_step_passed`): that alone
is no problem; raising the count is a `benchmark` PR's. A cell's file
without `check.at_step` is checked where its window ended.

`check.fault` in a cell's file (no cell of `BENCHMARK.json` sets it) asks
the check for a deliberate fault, to show that its limits refuse one.
"""

from __future__ import annotations

import time


def run(cell, args, clock) -> dict:
    from benchmark.drivers import fit
    from benchmark.harness import fit_check

    result = fit.run(cell, args, clock)
    built, spec = result["context"]["built"], cell.spec.get("check", {})
    gauges = _layer_gauges()        # as the window's last sync left them
    advanced, problems = advance_to(
        built, spec.get("at_step"),
        int(cell.spec["traffic"]["epochs_per_sync"]))
    t0 = time.perf_counter()
    check = built["reference_check"](fault=spec.get("fault"))
    numbers = dict(check["numbers"], **advanced,
                   seconds=time.perf_counter() - t0)
    result["info"]["reference_check"] = numbers
    result["info"]["layer_gauges"] = gauges
    result["problems"] = (list(result["problems"]) + problems
                          + list(check["problems"]))
    result["compared"] = dict(result["compared"],
                              **fit_check.compared(numbers))
    result["correct"] = not result["problems"]
    return result


def advance_to(built, at_step, epochs_per_sync: int):
    """Drive the program on from where the window left it, epoch by epoch
    through the window's own call, until `net.iteration` is `at_step`.
    Returns what `info["reference_check"]` says of it, and the problems: a
    compile on the way, or a count that whole epochs cannot reach. The
    score is not read on the way, so the program's gauges stay as the
    window's last sync left them; the device is waited for every
    `epochs_per_sync` epochs, as in the window."""
    import jax

    from benchmark.harness import counters

    net, trainer, iterator = built["net"], built["trainer"], built["iterator"]
    after_window = int(net.iteration)
    out = {"at_step": at_step, "steps_after_window": 0,
           "at_step_passed": at_step is not None and after_window > at_step,
           "advance_seconds": 0.0}
    if at_step is None or after_window >= at_step:
        return out, []
    t0, epochs = time.perf_counter(), 0
    with counters.CompileNames() as compiled:
        while int(net.iteration) < at_step:
            trainer.fit(iterator)
            epochs += 1
            if epochs % epochs_per_sync == 0:
                jax.block_until_ready(net.params_tree)
        jax.block_until_ready(net.params_tree)
    out["steps_after_window"] = int(net.iteration) - after_window
    out["advance_seconds"] = time.perf_counter() - t0
    problems = []
    if compiled.names:
        problems.append(f"compiled after the window: {compiled.names}")
    if int(net.iteration) != at_step:
        problems.append(f"at_step {at_step} is not reached by whole epochs "
                        f"from {after_window}: at {int(net.iteration)}")
    return out, problems


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
