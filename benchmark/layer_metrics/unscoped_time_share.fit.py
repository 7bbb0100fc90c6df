"""engine: share of the device's busy time in operations that run under no
scope of the engine's, in percent: no `L.<vertex>` and no `step.<phase>` in
the `op_name` of the instruction, nor, where its own line has none, in that
of the instruction that consumes it or produces its first operand
(`harness/scope_table.py`, which also keeps the whole table beside the
trace). What is left are the sums autodiff issues between vertices, the
step's random-key split and what no walk places. Where the program has no
such scope at all, as before the PR that added them, nothing is read."""


def read(context):
    from benchmark.harness import scope_table

    table = scope_table.table(context)
    return None if table is None else table["unscoped_percent"]
