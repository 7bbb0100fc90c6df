"""Driver `fit`: train through the engine's own `fit` until the window is over.

The window is a whole number of `epochs_per_sync` groups of epochs; each
group ends in `jax.block_until_ready(net.params_tree)` and a fetched loss,
so the clock never stops on work that is still queued. The rate is all the
samples of the window over all of its time.
"""

from __future__ import annotations

import time


def run(cell, args, clock) -> dict:
    import jax
    import numpy as np

    from benchmark.harness import counters, device, trace_reduce

    built = cell.build(args.seed)
    clock.mark("model_and_data")
    net, trainer, iterator = built["net"], built["trainer"], built["iterator"]
    traffic = cell.spec["traffic"]
    per_sync = int(traffic["epochs_per_sync"])
    leaves0 = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(net.params_tree)[:4]]

    def sync() -> float:
        jax.block_until_ready(net.params_tree)
        return float(net.score_value)

    # Warm-up: stages the data on the device, compiles (or loads) the step,
    # takes the first steps. One more group so that the window's first
    # group meets nothing for the first time.
    trainer.fit(iterator)
    losses = [sync()]
    clock.mark("first_fit")
    for _ in range(per_sync):
        trainer.fit(iterator)
    losses.append(sync())
    clock.mark("warm_group")

    readings = {
        "dispatch": lambda: counters.histogram_sum_count(
            "dl4j_step_dispatch_seconds"),
        "xla_compiles": lambda: counters.counter_total(
            "dl4j_xla_compiles_total"),
    }
    tracer = trace_reduce.for_window(args, traffic)

    setup_s = clock.setup_done()
    before = counters.Snapshot(readings)
    epochs, groups = 0, []
    with counters.CompileNames() as compiled:
        t0 = t_group = time.perf_counter()
        while True:
            for _ in range(per_sync):
                trainer.fit(iterator)
            epochs += per_sync
            losses.append(sync())
            t_now = time.perf_counter()
            groups.append(t_now - t_group)
            t_group = t_now
            tracer.tick(t_now - t0)
            if t_now - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
    tracer.finish()
    delta = counters.Snapshot(readings).delta(before)

    samples = epochs * built["samples_per_epoch"]
    steps = epochs * built["steps_per_epoch"]
    leaves1 = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(net.params_tree)[:4]]
    problems = []
    if not all(np.isfinite(l) for l in losses):
        problems.append(f"loss not finite: {losses[:3]} .. {losses[-3:]}")
    if not losses[-1] <= losses[0] * 1.05:
        problems.append(f"loss rose: {losses[0]} -> {losses[-1]}")
    if not any(not np.array_equal(a, b) for a, b in zip(leaves0, leaves1)):
        problems.append("parameters did not change")
    if not all(np.all(np.isfinite(l)) for l in leaves1):
        problems.append("parameters not finite")
    if compiled.names or delta["xla_compiles"]:
        problems.append(f"compiled inside the window: {compiled.names} "
                        f"({delta['xla_compiles']} XLA compiles)")

    # Each number compared, beside its limit, for the run's last line.
    compared = {
        "loss_last_over_first": [losses[-1] / losses[0] if losses[0]
                                 else None, 1.05],
        "compiles_in_window": [len(compiled.names) + delta["xla_compiles"],
                               0]}

    executables = net._get_jit("train_step").executables()
    groups_sorted = sorted(groups)
    return {
        "setup_s": setup_s,
        "window_s": window_s,
        "correct": not problems,
        "problems": problems,
        "compared": compared,
        "attempted": steps,
        "failed": 0,
        "end_to_end": {"fit_samples_per_s": samples / window_s},
        "memory_peak_bytes": device.memory_peak_bytes(
            cell.chips, device.program_footprint_bytes(executables)),
        "info": {"steps": steps, "samples": samples, "loss_first": losses[0],
                 "loss_last": losses[-1],
                 "sync_group_s": {"min": groups_sorted[0],
                                  "median": groups_sorted[len(groups) // 2],
                                  "max": groups_sorted[-1],
                                  "slowest_at": groups.index(groups_sorted[-1]),
                                  "n": len(groups)},
                 "tracer_overhead_s": tracer.overhead_s},
        "context": {
            "cell": cell, "built": built, "delta": delta,
            "window_s": window_s, "tracer": tracer,
            # The run's own rate, less the seconds the profiler took inside
            # the window to start and to write its trace: what `fit_mfu`
            # is computed from.
            "rate": samples / (window_s - tracer.overhead_s),
            "executables": executables,
        },
    }
