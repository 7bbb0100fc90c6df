"""kernels: share of the device's busy time in the routed experts, in
percent: the union of the intervals of the operations traced under the
scopes `moe.route` and `moe.experts` (forward and backward) and of the
operations the TPU compiler names `ragged-dot...` itself: its lowering of
`jax.lax.ragged_dot`, the experts' grouped products, which carries no
scope whatever the program issues it under (PERF.md section 3). An
operation found both ways is counted once."""

RAGGED_DOT = r"^ragged-dot"


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "moe.", named=RAGGED_DOT)
