"""updater seam + kernels: share of the device's busy time under the train
step's own phases, in percent: the rows `step.grad_cast` (the gradients'
cast and unscaling), `step.update` (gradient normalisation, the updaters,
the parameter update, by layer) and `step.store` (the master -> stored
cast, the skip-step selects) of `harness/scope_table.py`'s table, each
instant once. Where the program has no such scope, nothing is read."""


def read(context):
    from benchmark.harness import scope_table

    table = scope_table.table(context)
    return None if table is None else table["updater_percent"]
