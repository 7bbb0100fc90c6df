"""kernels: share of the device's busy time in the routed experts
(operations traced under the scopes `moe.route`, `moe.experts`, forward
and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "moe.")
